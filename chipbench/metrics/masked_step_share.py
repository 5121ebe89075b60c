"""Percent of the real clients' lane-steps in the traced slice that ran past
the client's own step count and were masked away: 1 -
``train.useful_steps`` / ``train.real_lane_steps``, counted by the trainer
where it picks the step bucket (``repro.obs`` session tally). Every masked
step still runs forward, backward and the optimizer."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:        # a program without the counters
        return None
    c = obs.session()
    if not c.get("train.real_lane_steps"):
        return None
    return 100.0 * (1.0 - c["train.useful_steps"]
                    / c["train.real_lane_steps"])

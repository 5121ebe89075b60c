"""Percent of the cohort program's lane-steps in the traced slice that ran
on padded lanes, the lanes of the cohort bucket past its real clients:
1 - ``train.real_lane_steps`` / ``train.lane_steps``, counted by the
trainer where it picks the bucket (``repro.obs`` session tally)."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:        # a program without the counters
        return None
    c = obs.session()
    if not c.get("train.lane_steps"):
        return None
    return 100.0 * (1.0 - c["train.real_lane_steps"] / c["train.lane_steps"])

"""The readers of the program's own spans and counters (``repro.obs``), on
hand-built trace reductions and session tallies: each reads what it names,
and reads nothing where the program counted or spanned nothing, as the
parent of the change that added them does."""
import sys
from types import SimpleNamespace

import pytest

import tinybench
from benchlib import registry
from benchlib.traces import Event, Trace

DEV = "/device:TPU:0"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: MNIST's traced slice, rounds 6-9: 4 dispatches of 30, 30, 47 and 76
#: clients on 32, 32, 64 and 128 lanes of 128 steps, 75 useful a client
MNIST = {"train.lane_steps": 256 * 128, "train.real_lane_steps": 183 * 128,
         "train.useful_steps": 183 * 75,
         "train.adam_bytes": 256 * 128 * 28 * 589_824}


def reader(name):
    return registry.load_module(tinybench.BENCH_DIR / "metrics"
                                / f"{name}.py").read


def ctx(host=(), ops=(), aggregations=4, lo=0, hi=1_000_000_000):
    tr = Trace(ops={DEV: list(ops)}, modules={DEV: []},
               host=[Event("chipbench.window", lo, hi - lo), *host])
    return SimpleNamespace(trace=tr, lo=lo, hi=hi, window_s=(hi - lo) / 1e9,
                           aggregations=aggregations, peak=PEAK)


@pytest.fixture
def tally(monkeypatch):
    """Sets what ``repro.obs.session()`` returns."""
    from repro import obs

    def set_(counts):
        monkeypatch.setattr(obs, "session", lambda: dict(counts))
    return set_


@pytest.mark.parametrize("name,want", [
    ("pad_lane_share", 100 * (1 - 183 / 256)),
    ("masked_step_share", 100 * (1 - 75 / 128)),
])
def test_share_readers(tally, name, want):
    tally(MNIST)
    assert reader(name)(ctx()) == pytest.approx(want)


def test_schedule_shares_round_as_predicted(tally):
    tally(MNIST)
    assert round(reader("pad_lane_share")(ctx()), 1) == 28.5
    assert round(reader("masked_step_share")(ctx()), 1) == 41.4


def test_adam_roofline(tally):
    tally(MNIST)
    ops = [Event("fused_adam.7", 0, 1.33e9 / 2), Event("fused_adam.7", 7e8,
                                                      1.33e9 / 2),
           Event("fusion.250", 0, 1e8), Event("fused_adam_like.1", 0, 5e8)]
    got = reader("adam_roofline")(ctx(ops=ops, hi=2_000_000_000))
    want = 100 * MNIST["train.adam_bytes"] / 819e9 / 1.33
    assert got == pytest.approx(want)
    assert 35 < got < 65


def _syncs():
    return [Event("fl.train", 100, 400),
            Event("fl.sync.cohort_losses", 200, 300),
            Event("fl.sync.eval", 450, 100),
            Event("fl.sync.eval", 500, 100),          # overlaps the one above
            Event("fl.sync.megastep", 2_000_000_000, 10),   # past the window
            Event("_services.py:740 _complete", 600, 50)]


def test_host_syncs_per_round():
    assert reader("host_syncs_per_round")(ctx(host=_syncs())) == 3 / 4


def test_host_self_ms_per_round():
    got = reader("host_self_ms_per_round")(ctx(host=_syncs()))
    assert got == pytest.approx((1e9 - 300 - 150) / 1e6 / 4)


@pytest.mark.parametrize("name", ["pad_lane_share", "masked_step_share",
                                  "adam_roofline"])
def test_counter_readers_read_nothing_when_nothing_was_counted(tally, name):
    tally({})
    ops = [Event("fused_adam.7", 0, 10)]
    assert reader(name)(ctx(ops=ops)) is None


@pytest.mark.parametrize("name", ["pad_lane_share", "masked_step_share",
                                  "adam_roofline"])
def test_counter_readers_without_the_program_module(monkeypatch, name):
    """A program without ``repro.obs`` (the parent of the change that
    added it): the reader returns None and does not raise."""
    import repro

    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs", raising=False)
    assert reader(name)(ctx(ops=[Event("fused_adam.7", 0, 10)])) is None


def test_adam_roofline_without_the_kernel(tally):
    """The kernel off the path (XLA Adam): no share."""
    tally(MNIST)
    assert reader("adam_roofline")(ctx(ops=[Event("fusion.1", 0, 10)])) \
        is None


@pytest.mark.parametrize("name", ["host_syncs_per_round",
                                  "host_self_ms_per_round"])
@pytest.mark.parametrize("case", ["no spans", "no aggregation"])
def test_span_readers_read_nothing(name, case):
    c = (ctx(host=[Event("_services.py:740 _complete", 600, 50)])
         if case == "no spans" else ctx(host=_syncs(), aggregations=0))
    assert reader(name)(c) is None

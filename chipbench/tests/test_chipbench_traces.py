"""The reduction from a profiler trace to busy time, idle share, module
and kernel time and idle gaps, on hand-made events and on a small trace
recorded on a TPU v5e (three runs each of the aggregation kernel and of a
jitted ``cohort_flat`` inside the ``chipbench.window`` span)."""
from pathlib import Path

import pytest

import tinybench  # noqa: F401  (puts the benchmark on sys.path)
from benchlib import registry, traces
from benchlib.traces import Event, Trace

RECORDED = Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"
AGG_KERNEL = registry.load_module(
    tinybench.BENCH_DIR / "metrics" / "agg_roofline.py").KERNEL


def _hand_made() -> Trace:
    dev = "/device:TPU:0"
    ops = [Event("fusion.1", 0, 10),
           Event("fusion.2", 5, 10),                         # overlaps
           Event("staleness_agg.1", 40, 20),
           Event("copy.3", 90, 30),                          # past hi
           Event("while.4", 0, 60)]                          # a loop
    mods = [Event("jit_cohort_flat(7)", 0, 15),
            Event("jit_staleness_agg(3)", 40, 20)]
    host = [Event("chipbench.window", 0, 100),
            Event("PjitFunction(cohort_flat)", 16, 20)]
    return Trace(ops={dev: ops}, modules={dev: mods}, host=host)


def test_union_clip_and_busy():
    tr = _hand_made()
    assert traces.union_ns(tr.ops["/device:TPU:0"]) == 60 + 30
    assert traces.busy_s(tr, 0, 100) == pytest.approx((60 + 10) / 1e9)
    assert traces.module_s(tr, "jit_cohort_flat", 0, 100) \
        == pytest.approx(15e-9)
    assert traces.op_s(tr, r"^staleness_agg(\.\d+)?$", 0, 100) \
        == pytest.approx(20e-9)
    assert traces.span(tr, "chipbench.window") == (0, 100)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    tr = _hand_made()
    tr.ops["/device:TPU:0"].pop()          # without the loop: real gaps
    gaps = traces.idle_gaps(tr, 0, 100)
    # gaps: 15-40 (25, covered by PjitFunction at its middle 27.5),
    # 60-90 (30, only the window span)
    assert gaps[0] == ["chipbench.window", pytest.approx(30e-9)]
    assert gaps[1] == ["PjitFunction(cohort_flat)", pytest.approx(25e-9)]


def test_top_ops_leave_out_loops():
    top = traces.top_ops(_hand_made(), 0, 100)
    assert top[0] == ["staleness_agg.1", pytest.approx(20e-9)]
    assert "while.4" not in [name for name, _ in top]


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_v5e_trace():
    tr = traces.load(str(RECORDED))
    assert tr.device_planes() == ["/device:TPU:0"]
    lo, hi = traces.span(tr, "chipbench.window")
    busy = traces.busy_s(tr, lo, hi)
    window = (hi - lo) / 1e9
    assert 0 < busy < window
    assert traces.module_s(tr, "jit_cohort_flat", lo, hi) > 0
    kernel = traces.op_s(tr, AGG_KERNEL, lo, hi)
    assert 0 < kernel < busy
    assert traces.top_ops(tr, lo, hi)
    assert traces.idle_gaps(tr, lo, hi)

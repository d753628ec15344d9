"""The trace reduction on a small synthetic trace."""
import pytest

from bench.trace import summarize

EVENTS = {
    "host": [("bench.window", 100, 1000), ("engine.step", 100, 400),
             ("engine.step", 500, 500), ("bench.submit", 1000, 50)],
    "ops:0": [("%fusion.1 = bf16[8]{0} fusion(%p.1)", 150, 100),
              ("%fusion.2 = bf16[8]{0} fusion(%p.2)", 200, 100),
              ("%fusion.1 = bf16[8]{0} fusion(%p.1)", 600, 200),
              ("%copy = bf16[8]{0} copy(%p.3)", 1020, 10),
              ("late", 1200, 50)],
    "modules:0": [("jit__unknown(3)", 140, 170),
                  ("jit__unknown(4)", 590, 220),
                  ("jit__unknown(4)", 1150, 20)],
}


def test_busy_union_and_idle_share():
    t = summarize(EVENTS)
    assert t.n_devices == 1
    assert t.window_s == pytest.approx(1000e-9)
    # [150, 300] (two ops overlapping), [600, 800], [1020, 1030]
    assert t.busy_s == pytest.approx(360e-9)
    assert 1 - t.busy_s / t.window_s == pytest.approx(0.64)


def test_device_time_per_step():
    """Program calls go to the step span that holds their midpoint; one
    past the window's last step counts for none."""
    t = summarize(EVENTS)
    assert t.step_device_s == pytest.approx([170e-9, 220e-9])


def test_ops_and_idle_gaps_by_host_span():
    t = summarize(EVENTS)
    assert t.op_seconds["%fusion.1"] == pytest.approx(300e-9)
    assert "late" not in t.op_seconds
    names = [n for n, _ in t.gaps]
    secs = [s for _, s in t.gaps]
    assert secs == pytest.approx([300e-9, 220e-9, 70e-9, 50e-9])
    assert names == ["engine.step", "engine.step", "none", "engine.step"]


def test_busy_averages_over_chips():
    ev = dict(EVENTS, **{"ops:1": [("fusion.9", 100, 1000)],
                         "modules:1": []})
    t = summarize(ev)
    assert t.n_devices == 2
    assert t.busy_s == pytest.approx((360e-9 + 1000e-9) / 2)


def test_a_trace_that_stops_inside_the_window_is_refused():
    """A step with no program call means the device record ended early,
    which would read as idle time and as fast steps."""
    with pytest.raises(ValueError, match="stopped recording"):
        summarize(dict(EVENTS, **{"modules:0": EVENTS["modules:0"][:1]}))


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(ValueError):
        summarize(dict(EVENTS, host=EVENTS["host"][1:]))

"""The engine's phase spans under a real CPU profiler trace, read by the
trace reduction."""
from pathlib import Path

import jax
import pytest

from bench.tests import _small
from bench.trace import STEP_SPAN, WINDOW_SPAN, read_xplane, summarize
from repro.config import RunConfig
from repro.models import init_model_params
from repro.serve import ServeEngine, telemetry


def test_engine_phase_spans_name_the_gaps_inside_a_step(tmp_path):
    """A CPU profiler trace of a tiny engine: ``read_xplane`` finds the
    engine's ``serve.*`` spans nested in the harness's ``engine.step``
    spans, and ``summarize`` names an idle gap inside a step by the phase
    the host was in.  The CPU has no device plane, so the device's record
    is made up around one phase."""
    _, m = _small.gqa()
    eng = ServeEngine(init_model_params(jax.random.PRNGKey(0), m), m,
                      RunConfig(remat=False, dtype="float32"), batch_slots=2,
                      max_len=64)
    eng.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        eng.submit([1, 2, 3, 4, 5], max_new=3)
        for _ in range(3):
            with jax.profiler.TraceAnnotation(STEP_SPAN):
                eng.step()
    jax.profiler.stop_trace()
    events = read_xplane(str(sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]))

    host = events["host"]
    steps = [(s, s + d) for n, s, d in host if n == STEP_SPAN]
    phases = [(n, s, s + d) for n, s, d in host if n in telemetry.PHASES]
    assert len(steps) == 3
    assert {n for n, _, _ in phases} == set(telemetry.PHASES)
    assert len(phases) == 3 * len(telemetry.PHASES)
    for _, a, b in phases:
        assert any(s <= a and b <= e for s, e in steps)

    # a phase span with no other host span inside it, and a device record
    # that is busy all through the window but for that span
    name, a, b = next(p for p in phases if not any(
        n != p[0] and p[1] <= s and s + d <= p[2] for n, s, d in host))
    w0, w1 = next((s, s + d) for n, s, d in host if n == WINDOW_SPAN)
    events["ops:0"] = [("%fusion.1 = f32[8]{0} fusion(%p.1)", w0, a - w0),
                       ("%fusion.2 = f32[8]{0} fusion(%p.2)", b, w1 - b)]
    events["modules:0"] = [("jit_decode_step(1)", (s + e) // 2, 1)
                           for s, e in steps]
    t = summarize(events)
    assert t.gaps[0] == (name, pytest.approx((b - a) / 1e9))

"""A whole run at a small size on the CPU, with the look for a chip skipped:
sound, it is correct; with the timed path broken underneath, or with the
float8 control in the program's place, ``correct`` comes out false.

The faults a serving cell on one chip can have: a step that returns its
state (the cache) unchanged; half of the batch left out, its slots given
their neighbours' logits; a token altered where it is produced.  (There is
no exchange between chips to leave out.)"""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import repro.serve.engine as engine_mod
from bench import harness
from bench.loadgen import LoadGen
from bench.tests import _small

BENCH = Path(__file__).resolve().parents[1]
#: The widest served-token gap allowed at this test's size, set as a cell's
#: limit is: the program read 0.004, 0.013 and 0.004 here (seeds 1-3), the
#: float8 control 0.336, 0.251 and 0.473, and the faults 3.4 or more.
LIMIT = 0.1


def stale_state(fn):
    def step(params, cache, batch, cfg, rc):
        return fn(params, cache, batch, cfg, rc)[0], cache
    return step


def half_batch(fn):
    def step(params, cache, batch, cfg, rc):
        logits, cache = fn(params, cache, batch, cfg, rc)
        return logits[jnp.arange(logits.shape[0]) // 2 * 2], cache
    return step


def altered_token(fn):
    def step(params, cache, batch, cfg, rc):
        logits, cache = fn(params, cache, batch, cfg, rc)
        return jnp.roll(logits, 1, axis=-1), cache
    return step


@pytest.fixture(scope="module")
def cell():
    cfg, m = _small.gqa(n_layers=2, d=128, heads=4, kv=2, d_ff=256,
                        vocab=512)
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    limits = {"limits": {"gap_sd": LIMIT, "bad_length": 0, "bad_token": 0,
                         "compiles": 0}}
    return (harness.Cell("small.chat", cfg, mix, 1, bench["end_to_end"],
                         limits), m)


def run(cell, seed):
    c, m = cell
    peak = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    return harness.run_cell(c, seed, 1.0, False, time.perf_counter(),
                            jax.devices()[0], peak, model_cfg=m)


def test_sound_run_is_correct(cell):
    out = run(cell, 2**31 + 11)
    assert out["correct"], out["checks"]
    assert list(out["checks"])[0] == "gap_sd"
    assert set(out["metrics"]) == {"out_tok_s", "ttft_p50_s", "itl_p95_s",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())


@pytest.mark.parametrize("fault", [stale_state, half_batch, altered_token])
def test_broken_step_is_not_correct(cell, fault, monkeypatch):
    monkeypatch.setattr(engine_mod, "decode_step",
                        fault(engine_mod.decode_step))
    monkeypatch.setattr(engine_mod, "prefill_step",
                        fault(engine_mod.prefill_step))
    out = run(cell, 2**31 + 12)
    assert not out["correct"]
    assert out["checks"]["gap_sd"]["value"] > out["checks"]["gap_sd"]["limit"]


def test_float8_control_is_not_correct(cell):
    """The reference in float8 e4m3 in the program's place: its first
    tokens read a gap over the limit in the float32 reference."""
    c, m = cell
    seed = 2**31 + 13
    weights, eng = harness.build(c, seed, m)
    window = harness.closed_loop(eng, LoadGen(c.traffic, seed, m.vocab), 4,
                                 0, 1.0)
    del eng
    readings, _ = harness.check(weights, c.config, c.traffic, window, seed,
                                m.vocab, control=True)
    assert harness.judge(readings, c.limits)[0]
    control = {**readings, "gap_sd": readings["control_gap_sd"]}
    assert not harness.judge(control, c.limits)[0]

"""Serve-SLO benchmark: a trace-driven load generator over the virtual-time
serving simulation (``repro.serve.scheduler.simulate_serve``).

Two pinned-seed arrival traces with mixed prompt/output lengths drive the
continuous-batching scheduler against the static (wave) baseline:

* **poisson** — memoryless arrivals at ~70% of the cluster's best decode
  service rate (steady load, the queueing-theory regime the ``serve-slo``
  calibration objective analyses);
* **bursty** — groups of near-simultaneous arrivals separated by long lulls
  (the regime where wave batching hurts most: short requests drain and
  their slots idle until the wave's longest request completes).

Everything is simulated in cycles-equivalent over a *pinned paper-default
operating point* (not the live PolicyTable — the gate must be hermetic
w.r.t. whatever calibration artifacts exist on the machine), so the whole
benchmark is exactly deterministic: the committed
``artifacts/BENCH_serve_slo.json`` is a golden artifact that
``benchmarks/bench_diff.py`` regenerates and compares bit-for-bit in CI.

Gates (smoke and full):

* continuous batching delivers >= :data:`MIN_CONTINUOUS_GAIN` x the static
  baseline's **throughput-at-SLO** on the bursty trace (tokens of requests
  that met their latency budget, per cycle);
* continuous batching *meets the p99 bound* (normalized p99 latency within
  :data:`SLO_P99_PER_TOKEN`) on both traces;
* continuous energy-per-token beats static on the bursty trace (padded
  slots burn energy; fewer idle slots = fewer wasted joules);
* straggler-aware dispatch flags exactly the injected slow host (no
  false-dead hosts) and beats rigid equal-share dispatch by
  >= :data:`MIN_STRAGGLER_GAIN` x on wall cycles;
* two runs of the same trace produce identical reports (determinism).

Writes ``artifacts/BENCH_serve_slo.json`` (``BENCH_serve_slo_smoke.json``
under ``--smoke``) with the cost model, the SLO, per-trace per-mode reports
and the headline gains.  Emits ``name,us_per_call,derived`` CSV rows like
every other section.

A second, live-engine section (``run_prefill`` / ``--prefill``) gates the
real chunked-prefill path: bit-exact tokens and cache vs the token-by-token
reference, >= :data:`MIN_PREFILL_TTFT_GAIN` x TTFT at prompt_len >= 64, and
a bounded chunk-bucket jit cache.  It writes
``artifacts/BENCH_serve_prefill.json``.
"""
import json
import os
import sys
import time

import numpy as np

from repro.core.policy import OperatingPoint
from repro.serve import telemetry
from repro.serve.scheduler import (AdmissionControl, HostDispatch, ServeSLO,
                                   StepCostModel, TraceRequest,
                                   simulate_serve)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(ROOT, "artifacts", "BENCH_serve_slo.json")
PREFILL_OUT_PATH = os.path.join(ROOT, "artifacts", "BENCH_serve_prefill.json")

#: the acceptance bar: continuous batching must beat wave batching by this
#: factor on bursty-trace throughput-at-SLO
MIN_CONTINUOUS_GAIN = 1.3
#: straggler-aware dispatch must beat rigid equal-share dispatch by this
#: factor on total cycles when one of four hosts runs 3x slow
MIN_STRAGGLER_GAIN = 1.5

#: the SLO: p99 normalized latency (cycles per work-token, queueing
#: included) and the per-request budget slack (absolute cycles)
SLO_P99_PER_TOKEN = 700.0
SLO_BASE_CYCLES = 800.0

N_SLOTS = 8
PREFILL_CHUNK = 8
#: mixed request shapes (drawn per request from the pinned seed)
PROMPT_LENS = (4, 8, 16)
MAX_NEWS = (4, 8, 16, 48)

FULL = dict(n_requests=160, seed=7, poisson_util=0.7,
            burst_size=16, burst_gap_steps=40)
SMOKE = dict(n_requests=48, seed=7, poisson_util=0.7,
             burst_size=12, burst_gap_steps=40)


def _cost_model() -> StepCostModel:
    """The pinned paper-default operating point's step costs (hermetic:
    never reads the live calibration artifacts)."""
    return StepCostModel.from_operating_point(OperatingPoint())


def _shapes(rng: np.random.RandomState, n: int):
    prompts = rng.choice(PROMPT_LENS, size=n)
    news = rng.choice(MAX_NEWS, size=n)
    return prompts, news


def poisson_trace(cost: StepCostModel, n: int, seed: int,
                  util: float) -> list:
    """Memoryless arrivals at ``util`` x the best decode service rate."""
    rng = np.random.RandomState(seed)
    prompts, news = _shapes(rng, n)
    step_cycles, _ = cost.step_cost(N_SLOTS, 0)
    token_rate = N_SLOTS / step_cycles              # tokens/cycle, all busy
    req_rate = util * token_rate / float(np.mean(MAX_NEWS))
    gaps = rng.exponential(1.0 / req_rate, size=n)
    arrivals = np.cumsum(gaps)
    return [TraceRequest(i, float(arrivals[i]), int(prompts[i]),
                         int(news[i])) for i in range(n)]


def bursty_trace(cost: StepCostModel, n: int, seed: int, burst_size: int,
                 burst_gap_steps: int) -> list:
    """Bursts of near-simultaneous arrivals separated by multi-wave lulls."""
    rng = np.random.RandomState(seed + 1)
    prompts, news = _shapes(rng, n)
    step_cycles, _ = cost.step_cost(N_SLOTS, 0)
    out, t = [], 0.0
    for i in range(n):
        if i and i % burst_size == 0:
            t += burst_gap_steps * step_cycles      # lull between bursts
        t += float(rng.exponential(0.2 * step_cycles))
        out.append(TraceRequest(i, t, int(prompts[i]), int(news[i])))
    return out


def _simulate(trace, cost, mode, dispatch=None):
    slo = ServeSLO(p99_cycles_per_token=SLO_P99_PER_TOKEN,
                   base_cycles=SLO_BASE_CYCLES)
    return simulate_serve(
        trace, N_SLOTS, cost, mode=mode, slo=slo,
        admission=AdmissionControl(max_pending=256),
        prefill_chunk=PREFILL_CHUNK, dispatch=dispatch)


def run(cfg=None, out_path=OUT_PATH):
    cfg = cfg or FULL
    t0 = time.time()
    cost = _cost_model()
    traces = {
        "poisson": poisson_trace(cost, cfg["n_requests"], cfg["seed"],
                                 cfg["poisson_util"]),
        "bursty": bursty_trace(cost, cfg["n_requests"], cfg["seed"],
                               cfg["burst_size"], cfg["burst_gap_steps"]),
    }
    rows, results = [], {}
    for name, trace in traces.items():
        results[name] = {}
        for mode in ("continuous", "static"):
            rep = _simulate(trace, cost, mode)
            if rep.n_unfinished:
                raise AssertionError(
                    f"{name}/{mode}: {rep.n_unfinished} admitted requests "
                    f"never completed (scheduler stuck or max_steps hit)")
            results[name][mode] = rep.to_dict()
            rows.append((f"serve_slo_{name}_{mode}_tput_at_slo", 0.0,
                         rep.slo["throughput_at_slo"]))
            rows.append((f"serve_slo_{name}_{mode}_p99", 0.0,
                         rep.p99_latency))

    # determinism: the whole pipeline must be replayable bit-for-bit
    again = _simulate(traces["bursty"], cost, "continuous").to_dict()
    if again != results["bursty"]["continuous"]:
        raise AssertionError("serve simulation is not deterministic: two "
                             "runs of the pinned bursty trace differ")

    # gate: continuous meets the p99 bound on both traces
    for name in traces:
        cont = results[name]["continuous"]
        if not cont["slo"]["p99_met"]:
            raise AssertionError(
                f"{name}: continuous batching missed the p99 bound "
                f"({cont['p99_latency']:.1f} > {SLO_P99_PER_TOKEN} "
                f"cyc/tok)")

    # gate: >=1.3x throughput-at-SLO over wave batching on the bursty trace
    gain = (results["bursty"]["continuous"]["slo"]["throughput_at_slo"]
            / max(results["bursty"]["static"]["slo"]["throughput_at_slo"],
                  1e-12))
    if gain < MIN_CONTINUOUS_GAIN:
        raise AssertionError(
            f"continuous batching gains only {gain:.2f}x throughput-at-SLO "
            f"over the static baseline on the bursty trace "
            f"(required {MIN_CONTINUOUS_GAIN}x)")
    rows.append(("serve_slo_bursty_tput_at_slo_gain", 0.0, gain))

    # gate: fewer idle padded slots = lower J/token
    e_cont = results["bursty"]["continuous"]["energy_per_token"]
    e_stat = results["bursty"]["static"]["energy_per_token"]
    if e_cont >= e_stat:
        raise AssertionError(
            f"continuous J/token {e_cont:.1f} did not beat static "
            f"{e_stat:.1f} on the bursty trace")
    rows.append(("serve_slo_bursty_energy_gain", 0.0, e_stat / e_cont))

    # gate: straggler-aware dispatch adapts (and declares nobody dead)
    slow_host = 3
    adaptive = HostDispatch(4, min_samples=8)
    adaptive.set_speed(slow_host, 3.0)
    rep_adapt = _simulate(traces["bursty"], cost, "continuous",
                          dispatch=adaptive)
    rigid = HostDispatch(4, min_samples=8, threshold=float("inf"))
    rigid.set_speed(slow_host, 3.0)
    rep_rigid = _simulate(traces["bursty"], cost, "continuous",
                          dispatch=rigid)
    if rep_adapt.straggler["flagged_hosts"] != [slow_host]:
        raise AssertionError(
            f"straggler dispatch flagged "
            f"{rep_adapt.straggler['flagged_hosts']}, expected "
            f"[{slow_host}]")
    if rep_adapt.straggler["dead_hosts"]:
        raise AssertionError(
            f"slow-but-beating hosts declared dead: "
            f"{rep_adapt.straggler['dead_hosts']}")
    straggler_gain = rep_rigid.total_cycles / rep_adapt.total_cycles
    if straggler_gain < MIN_STRAGGLER_GAIN:
        raise AssertionError(
            f"straggler-aware dispatch gains only {straggler_gain:.2f}x "
            f"over rigid dispatch (required {MIN_STRAGGLER_GAIN}x)")
    rows.append(("serve_slo_straggler_gain", 0.0, straggler_gain))

    report = {
        "cost_model": {
            "cycles_decode_token": cost.cycles_decode_token,
            "energy_decode_token": cost.energy_decode_token,
            "cycles_prefill_token": cost.cycles_prefill_token,
            "energy_prefill_token": cost.energy_prefill_token,
            "overhead_cycles": cost.overhead_cycles,
            "source": cost.source,
        },
        "slo": {"p99_cycles_per_token": SLO_P99_PER_TOKEN,
                "base_cycles": SLO_BASE_CYCLES},
        "config": {"n_slots": N_SLOTS, "prefill_chunk": PREFILL_CHUNK,
                   "prompt_lens": list(PROMPT_LENS),
                   "max_news": list(MAX_NEWS), **cfg},
        "results": results,
        "straggler": {"slow_host": slow_host, "slowdown": 3.0,
                      "adaptive": rep_adapt.straggler,
                      "adaptive_cycles": rep_adapt.total_cycles,
                      "rigid_cycles": rep_rigid.total_cycles,
                      "gain": straggler_gain},
        "headline": {"throughput_at_slo_gain_bursty": gain,
                     "min_required": MIN_CONTINUOUS_GAIN,
                     "p99_met": True,
                     "straggler_gain": straggler_gain},
    }
    us = (time.time() - t0) * 1e6 / max(len(rows), 1)
    rows = [(name, us, derived) for name, _z, derived in rows]

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    return rows


def main():
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived:.4f}")
    print(f"# wrote {OUT_PATH}")


def smoke():
    """Smaller trace, separate artifact — every gate still enforced."""
    out = os.path.join(ROOT, "artifacts", "BENCH_serve_slo_smoke.json")
    rows = run(cfg=SMOKE, out_path=out)
    if not rows:
        raise AssertionError("serve_slo smoke produced no rows")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.4f}")


# ---------------------------------------------------------------------------
# live-engine chunked-prefill gate
# ---------------------------------------------------------------------------
# This section runs the *real* jitted engine (not the virtual-time
# simulation): the chunked prefill path (`models.model.prefill_step` driven
# by `ServeEngine(prefill="chunked")`) against the token-by-token reference
# (`prefill="token"`) on the same params and prompt.  The engine stamps on
# the host clock, so the cycles-domain TTFT charges each step the engine
# logged from submit to first token (`repro.serve.telemetry`) at the pinned
# cost model, by the prompt tokens that step ingested.
#
# Gates:
# * generated tokens AND final cache rows are bit-exact between the two
#   paths (the chunk kernel scans the same decode_step body, so any diff is
#   a real bug, not float noise);
# * cycles-equivalent TTFT (deterministic: pinned cost model, fixed
#   prompt) improves >= MIN_PREFILL_TTFT_GAIN x;
# * measured wall-clock TTFT (median over trials, warm jits) improves
#   >= MIN_PREFILL_TTFT_GAIN x in full mode (a softer
#   MIN_PREFILL_TTFT_GAIN_SMOKE bar under --smoke: CI machines are noisy);
# * the chunk-bucket jit cache stays bounded: at most
#   log2(prefill_chunk) + 1 compiled prefill programs.

#: the acceptance bar from ROADMAP item 3's residual gap: chunked prefill
#: must at least halve TTFT at prompt_len >= 64
MIN_PREFILL_TTFT_GAIN = 2.0
#: smoke keeps a softer wall-clock bar (shared CI machines); the
#: deterministic cycles-domain gate stays at MIN_PREFILL_TTFT_GAIN
MIN_PREFILL_TTFT_GAIN_SMOKE = 1.2

PREFILL_FULL = dict(arch="phi3-mini-3.8b", prompt_len=64, max_new=8,
                    batch_slots=2, prefill_chunk=16, trials=5, seed=0)
PREFILL_SMOKE = dict(arch="phi3-mini-3.8b", prompt_len=64, max_new=4,
                     batch_slots=2, prefill_chunk=16, trials=3, seed=0)


def _prefill_engines(cfg):
    """Both engines (chunked + token reference) over shared params and the
    pinned paper-default operating point — hermetic w.r.t. live
    calibration artifacts, like the rest of this benchmark."""
    import jax
    from repro.config import RunConfig
    from repro.configs import get_reduced
    from repro.models import init_model_params
    from repro.serve import ServeEngine

    mcfg = get_reduced(cfg["arch"])
    rc = RunConfig(dtype="float32", param_dtype="float32", remat=False)
    params = init_model_params(jax.random.PRNGKey(cfg["seed"]), mcfg)
    rng = np.random.RandomState(cfg["seed"] + 1)
    prompt = [int(t) for t in rng.randint(0, mcfg.vocab,
                                          size=cfg["prompt_len"])]
    max_len = cfg["prompt_len"] + cfg["max_new"] + 8

    def mk(prefill):
        return ServeEngine(params, mcfg, rc,
                           batch_slots=cfg["batch_slots"], max_len=max_len,
                           operating_point=OperatingPoint(),
                           prefill=prefill,
                           prefill_chunk=cfg["prefill_chunk"])
    return mk, prompt


def _ttft_cycles(eng, prompt, max_new):
    """Serve one request to its first token and charge each step the engine
    logged from its submit on at the pinned cost model: a full-width
    decode pass, plus the prompt tokens of a chunked prefill step (a
    token-path step carries its prompt token in a decode column).  That is
    the request's cycles-equivalent TTFT.  The request is then drained;
    returns its rid and the TTFT."""
    cost = _cost_model()
    t_submit = time.perf_counter()
    rid = eng.submit(prompt, max_new=max_new)
    while not eng.requests[rid].generated:
        eng.step()
    cycles = sum(cost.step_cost(
        eng.sched.n_slots,
        rec.prompt_tokens if rec.kind == "prefill" else 0)[0]
        for rec in telemetry.steps(t_submit) if rec.kind != "empty")
    eng.run(max_steps=100_000)
    return rid, cycles


def _measure_ttft(eng, prompt, max_new, trials):
    """Warm run (compiles) + ``trials`` timed runs; returns the warm run's
    generated tokens, its cycles-domain TTFT, and per-trial wall TTFTs."""
    rid0, ttft_cycles = _ttft_cycles(eng, prompt, max_new)
    tokens = list(eng.finished[rid0].generated)
    walls = []
    for _ in range(trials):
        rid = eng.submit(prompt, max_new=max_new)
        t0 = time.time()
        while not eng.requests[rid].generated:
            eng.step()
        walls.append(time.time() - t0)
        eng.run(max_steps=100_000)           # drain before the next trial
    return tokens, ttft_cycles, walls


def run_prefill(cfg=None, out_path=PREFILL_OUT_PATH,
                min_wall_gain=MIN_PREFILL_TTFT_GAIN):
    import jax.numpy as jnp
    cfg = cfg or PREFILL_FULL
    t0 = time.time()
    mk, prompt = _prefill_engines(cfg)

    chunked = mk("chunked")
    token = mk("token")
    tok_c, cyc_c, walls_c = _measure_ttft(chunked, prompt, cfg["max_new"],
                                          cfg["trials"])
    tok_t, cyc_t, walls_t = _measure_ttft(token, prompt, cfg["max_new"],
                                          cfg["trials"])

    # gate: bit-exact generated tokens and final cache rows.  Only the
    # serving slot's rows are compared: free-slot rows are junk by design
    # (the unmasked token-by-token reference advances them every step, the
    # masked chunk path never touches them) and are zeroed before reuse.
    def _slot_rows(cache, i):
        return {k: (v if v.ndim == 0 else v[i] if v.ndim == 1 else v[:, i])
                for k, v in cache.items()}

    rows_c = _slot_rows(chunked.cache, 0)
    rows_t = _slot_rows(token.cache, 0)
    tokens_exact = tok_c == tok_t
    cache_exact = (set(rows_c) == set(rows_t) and all(
        bool(jnp.array_equal(rows_c[k], rows_t[k])) for k in rows_c))
    if not (tokens_exact and cache_exact):
        raise AssertionError(
            f"chunked prefill is not bit-exact with the token-by-token "
            f"path: tokens_exact={tokens_exact} cache_exact={cache_exact} "
            f"(chunked={tok_c} token={tok_t})")

    # gate: bounded chunk-bucket jit cache
    import math
    max_compiles = int(math.log2(cfg["prefill_chunk"])) + 1
    if chunked.prefill_compiles > max_compiles:
        raise AssertionError(
            f"chunk-bucket jit cache unbounded: {chunked.prefill_compiles} "
            f"compiles > log2({cfg['prefill_chunk']})+1 = {max_compiles}")

    # gate: deterministic cycles-domain TTFT gain (pinned cost model)
    cycles_gain = cyc_t / max(cyc_c, 1e-9)
    if cycles_gain < MIN_PREFILL_TTFT_GAIN:
        raise AssertionError(
            f"chunked prefill gains only {cycles_gain:.2f}x cycles-domain "
            f"TTFT at prompt_len={cfg['prompt_len']} "
            f"(required {MIN_PREFILL_TTFT_GAIN}x)")

    # gate: measured wall-clock TTFT gain (median over warm trials)
    wall_c = float(np.median(walls_c))
    wall_t = float(np.median(walls_t))
    wall_gain = wall_t / max(wall_c, 1e-12)
    if wall_gain < min_wall_gain:
        raise AssertionError(
            f"chunked prefill gains only {wall_gain:.2f}x wall-clock TTFT "
            f"at prompt_len={cfg['prompt_len']} (required {min_wall_gain}x)")

    report = {
        "config": dict(cfg),
        "ttft": {
            "cycles_chunked": cyc_c, "cycles_token": cyc_t,
            "wall_s_chunked": walls_c, "wall_s_token": walls_t,
            "wall_s_chunked_median": wall_c, "wall_s_token_median": wall_t,
        },
        "steps": {"chunked": chunked._n_steps, "token": token._n_steps},
        "prefill_compiles": chunked.prefill_compiles,
        "max_prefill_compiles": max_compiles,
        "headline": {
            "ttft_wall_gain": wall_gain,
            "ttft_cycles_gain": cycles_gain,
            "bit_exact": bool(tokens_exact and cache_exact),
            "min_required": MIN_PREFILL_TTFT_GAIN,
        },
    }
    rows = [
        ("serve_prefill_ttft_wall_gain", 0.0, wall_gain),
        ("serve_prefill_ttft_cycles_gain", 0.0, cycles_gain),
        ("serve_prefill_compiles", 0.0, float(chunked.prefill_compiles)),
    ]
    us = (time.time() - t0) * 1e6 / max(len(rows), 1)
    rows = [(name, us, derived) for name, _z, derived in rows]

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    return rows


def prefill_main():
    for name, us, derived in run_prefill():
        print(f"{name},{us:.1f},{derived:.4f}")
    print(f"# wrote {PREFILL_OUT_PATH}")


def prefill_smoke():
    """Smaller run, separate artifact; the wall-clock bar softens to
    MIN_PREFILL_TTFT_GAIN_SMOKE but bit-exactness, the cycles-domain gain
    and the bounded jit cache are still hard gates."""
    out = os.path.join(ROOT, "artifacts", "BENCH_serve_prefill_smoke.json")
    rows = run_prefill(cfg=PREFILL_SMOKE, out_path=out,
                       min_wall_gain=MIN_PREFILL_TTFT_GAIN_SMOKE)
    if not rows:
        raise AssertionError("serve_prefill smoke produced no rows")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.4f}")


if __name__ == "__main__":
    if "--prefill" in sys.argv[1:]:
        prefill_main()
    else:
        main()

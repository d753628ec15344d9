"""Batched serving: continuous batching over a reduced model, several
concurrent requests of different lengths, with chunked prefill.

The engine stamps every request on the host clock and logs each step's
phases (``repro.serve.telemetry``); the example prints the TTFT and
per-token latency percentiles and the mean time of each step phase.

  PYTHONPATH=src python examples/serve_batch.py --arch recurrentgemma-2b
"""
import argparse
import time

import jax

from repro.config import RunConfig
from repro.configs import ARCHS, get_reduced
from repro.models import init_model_params
from repro.serve import ServeEngine, telemetry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b",
                    choices=[a for a in ARCHS if a != "hubert-xlarge"])
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=10)
    ap.add_argument("--prefill", choices=("chunked", "token"),
                    default="chunked")
    args = ap.parse_args()

    cfg = get_reduced(args.arch)
    rc = RunConfig(dtype="float32", param_dtype="float32", remat=False)
    params = init_model_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, rc, batch_slots=3, max_len=128,
                      prefill=args.prefill)
    eng.warmup()

    # more requests than slots: later ones wait in the queue for a slot
    t0 = time.perf_counter()
    for i in range(args.requests):
        prompt = list(range(1 + i, 5 + 2 * i))
        eng.submit(prompt, max_new=args.max_new)

    done = eng.run()
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.generated) for r in done.values())
    print(f"{cfg.name}: {len(done)} requests, {n_tok} tokens, "
          f"{n_tok/dt:.1f} tok/s, {eng._n_steps} engine steps "
          f"({eng.prefill_compiles} prefill chunk programs), "
          f"prefill={args.prefill}")
    rep = eng.metrics()
    print(f"host clock: TTFT p50/p99 {1e3 * rep.p50_ttft:.1f}/"
          f"{1e3 * rep.p99_ttft:.1f} ms, latency p50/p99 "
          f"{1e3 * rep.p50_latency:.2f}/{1e3 * rep.p99_latency:.2f} "
          f"ms per output token")
    print("step log: " + telemetry.summary(telemetry.steps(t0)))
    for rid in sorted(done):
        r = done[rid]
        print(f"  req{rid} prompt[:4]={r.prompt[:4]} -> {r.generated}")


if __name__ == "__main__":
    main()

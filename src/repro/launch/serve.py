"""Serving launcher: batched decode over the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --reduced \\
      --requests 6 --max-new 12 --traffic high
"""
import argparse
import time

import jax
import jax.numpy as jnp

from ..config import ModelConfig, RunConfig
from ..configs import ARCHS, get_config, get_reduced
from ..core.policy import TRAFFIC_LEVELS
from ..models import init_model_params
from ..serve import ServeEngine, telemetry
from .compile_cache import enable_compile_cache


def build_engine(cfg: ModelConfig, seed: int = 0, **engine_kw) -> ServeEngine:
    """A :class:`ServeEngine` over seeded random weights for ``cfg``.  The
    weights are held in bf16 and the engine computes in bf16: a model at its
    published widths then fits one chip (phi3-mini-3.8b's weights take
    7.6 GB of a v5e's 16 GB in bf16, 15.3 GB in float32).  ``engine_kw``
    goes to :class:`ServeEngine` unchanged."""
    rc = RunConfig(dtype="bfloat16", param_dtype="bfloat16", remat=False)
    params = init_model_params(jax.random.PRNGKey(seed), cfg,
                               jnp.dtype(rc.param_dtype))
    return ServeEngine(params, cfg, rc, **engine_kw)


def main() -> None:
    ap = argparse.ArgumentParser(description="Serve an assigned architecture")
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=None,
                    help="decode batch slots (default: 4 per cluster core "
                         "of the calibrated 'serve' operating point)")
    ap.add_argument("--mode", choices=("continuous", "static"),
                    default="continuous",
                    help="slot refill discipline: continuous (refill per "
                         "step as sequences finish) or static (wave "
                         "batching, the measurable baseline)")
    ap.add_argument("--traffic", choices=sorted(TRAFFIC_LEVELS),
                    default=None,
                    help="pin the calibration artifact's per-traffic "
                         "serve-slo operating point (schema v5) for this "
                         "offered-load level")
    ap.add_argument("--prefill", choices=("chunked", "token"),
                    default="chunked",
                    help="prompt ingestion: chunked (jitted prefill_step, "
                         "C tokens per call) or token (one-token steps, "
                         "the measurable TTFT baseline)")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="max prompt tokens per prefilling slot per step")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if not cfg.causal:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")
    enable_compile_cache()
    eng = build_engine(cfg, args.seed, batch_slots=args.slots, max_len=256,
                       mode=args.mode, traffic=args.traffic,
                       prefill=args.prefill, prefill_chunk=args.prefill_chunk)
    op = eng.operating_point
    print(f"policy={op.policy.value} (source={op.source}, "
          f"cores={op.n_cores}, slots={len(eng.slots)}, mode={args.mode}, "
          f"prefill={args.prefill}, traffic={args.traffic or 'default'})")

    rng = jax.random.PRNGKey(args.seed + 1)
    rids = []
    for i in range(args.requests):
        rng, k = jax.random.split(rng)
        plen = 3 + int(jax.random.randint(k, (), 0, 6))
        prompt = [int(t) for t in
                  jax.random.randint(k, (plen,), 0, cfg.vocab)]
        rids.append((eng.submit(prompt, max_new=args.max_new), prompt))

    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.generated) for r in done.values())
    print(f"served {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.1f}s ({total_tokens/dt:.1f} tok/s)")
    rep = eng.metrics()
    print(f"host clock: TTFT p50/p99 {1e3 * rep.p50_ttft:.1f}/"
          f"{1e3 * rep.p99_ttft:.1f} ms, latency p50/p99 "
          f"{1e3 * rep.p50_latency:.2f}/{1e3 * rep.p99_latency:.2f} "
          f"ms per output token")
    print("step log: " + telemetry.summary(telemetry.steps(t0)))
    for rid, prompt in rids:
        r = done[rid]
        print(f"  req{rid}: prompt={prompt} -> {r.generated}")


if __name__ == "__main__":
    main()

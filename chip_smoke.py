"""Chip smoke run: phi3-mini-3.8b served on one TPU chip through ServeEngine.

    python3 chip_smoke.py

It needs a TPU and exits non-zero at once when JAX finds none; it never
falls back to the CPU.  One process drives one chip.

What it runs, through the entry points a user calls
(``repro.launch.serve.build_engine`` and :class:`repro.serve.ServeEngine`):

* phi3-mini-3.8b at its published widths (32 layers, d_model 3072, 32 heads,
  d_ff 8192, vocab 32064) with random weights from a seed, held and
  computed in bf16;
* 4 slots, ``max_len`` 1024 and chunked prefill of 8 tokens, the size the
  benchmark's ``phi3-mini.chat`` cell serves: a 1.61 GB cache beside the
  7.64 GB of weights (``prefill_step`` runs a chunk in one pass and holds
  no copy of the cache beyond its output, so it compiles at 8 x 1024 too);
* 8 requests with seeded prompts of 32-512 tokens and 32 new tokens each,
  so slots refill mid-run and prefill mixes with decode;
* a logits check: one request's prompt and generated tokens are
  teacher-forced through the engine's own jitted ``prefill_step`` and
  ``decode_step``, and the logits at every position are compared with
  ``repro.models.model.forward`` of the same weights in float32 at
  ``highest`` matmul precision.

It prints, each labelled with the device kind, the first-call seconds of
every program (compile included), the steady seconds per engine step, the
tokens served, the logits error with its tolerance, and the peak device
memory.  The last line is one JSON object naming the device.

There is no four-chip phase: no entry point of the repository runs on more
than one chip (``launch/train.py`` builds a 1 x 1 mesh, ``ServeEngine`` has
no mesh), so nothing exists across chips to check.
"""
from __future__ import annotations

import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import RunConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.policy import PolicyTable  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import build_engine  # noqa: E402
from repro.models.model import forward  # noqa: E402

ARCH = "phi3-mini-3.8b"
SLOTS, MAX_LEN, CHUNK = 4, 1024, 8
N_REQUESTS, MAX_NEW = 8, 32
PROMPT_LENS = (32, 512)
CHECK_PROMPT_LEN = 128
SEED = 0
MAX_STEPS = 2000
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: Largest relative RMS error of the engine's logits at any position, against
#: the float32 reference.  The engine computes in bf16 (8-bit mantissa, a
#: relative rounding of 2^-9 per operation), so its logits drift from the
#: float32 ones by a few percent through the layers; a cache position off by
#: one moves them by far more.
LOGITS_TOL = 0.05


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def teacher_forced_logits(eng, prompt, generated) -> np.ndarray:
    """Logits of the engine's own jitted programs at every position of
    ``prompt + generated[:-1]``, as rows ``(position, vocab)``.

    The prompt goes into every slot through ``prefill_step`` at the full
    chunk width.  Slot ``s`` of pass ``p`` opens with a chunk of
    ``p * slots + s + 1`` tokens, so the chunk ends, where ``prefill_step``
    returns logits, fall on every position across the passes.  The generated
    tokens then go through ``decode_step`` one at a time.

    The engine must be idle.  Its cache is dropped first: at the sizes this
    script runs, the step programs leave no room on the chip for a second
    cache."""
    spec = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        eng.cache)
    eng.cache = None

    def zeros():
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)

    n, B, C = len(prompt), eng.sched.n_slots, eng.prefill_chunk
    prefill = eng.prefill_fn(C)
    out = np.full((n + len(generated) - 1, eng.cfg.vocab), np.nan, np.float32)
    for p in range(-(-C // B)):
        cache = zeros()
        first = np.minimum(p * B + np.arange(B) + 1, C)
        cur = np.zeros(B, np.int32)
        while (cur < n).any():
            k = np.minimum(np.where(cur == 0, first, C), n - cur)
            tokens = np.zeros((B, C), np.int32)
            for s in range(B):
                tokens[s, :k[s]] = prompt[cur[s]:cur[s] + k[s]]
            logits, cache = prefill(
                eng.params, cache,
                {"tokens": jnp.asarray(tokens),
                 "n_tokens": jnp.asarray(k, jnp.int32)})
            logits = np.asarray(logits)
            for s in np.flatnonzero(k):
                out[cur[s] + k[s] - 1] = logits[s]
            cur = cur + k
    for j, tok in enumerate(generated[:-1]):
        logits, cache = eng.decode_fn(
            eng.params, cache, {"tokens": jnp.full((B, 1), tok, jnp.int32)})
        out[n + j] = np.asarray(logits)[0]
    del cache
    eng.cache = zeros()
    return out


def reference_logits(params, cfg, tokens) -> np.ndarray:
    """``forward`` of the same weights over ``tokens`` in float32, with
    float32 matmuls (a TPU otherwise runs them in bf16 passes)."""
    rc = RunConfig(dtype="float32", param_dtype="float32", remat=False)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(partial(forward, cfg=cfg, rc=rc))(
            params, {"tokens": jnp.asarray(tokens, jnp.int32)[None]})
    return np.asarray(logits[0], np.float32)


def logits_error(eng, prompt, generated) -> np.ndarray:
    """Relative RMS error of the engine's logits at each position of
    ``prompt + generated[:-1]`` against the float32 reference."""
    got = teacher_forced_logits(eng, prompt, generated)
    _require(not np.isnan(got).any(), "a position got no engine logits")
    want = reference_logits(eng.params, eng.cfg,
                            list(prompt) + list(generated[:-1]))
    return np.sqrt(np.mean((got - want) ** 2, axis=-1)
                   / np.mean(want ** 2, axis=-1))


def main() -> None:
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r}")
    kind, count = dev.device_kind, len(jax.devices())
    print(f"device: platform={dev.platform} kind={kind} count={count}",
          flush=True)

    def say(msg: str) -> None:
        print(f"[{kind}] {msg}", flush=True)

    cfg = get_config(ARCH)
    eng = build_engine(cfg, SEED, batch_slots=SLOTS, max_len=MAX_LEN,
                       prefill_chunk=CHUNK, policy_table=PolicyTable())
    n_params = sum(a.size for a in jax.tree.leaves(eng.params))
    say(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab}; "
        f"{n_params} parameters in bf16; {SLOTS} slots x max_len {MAX_LEN}, "
        f"prefill chunk {CHUNK}")

    compile_s = eng.warmup()
    for name, s in compile_s.items():
        say(f"first call (compile included) {name}: {s} s")

    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    lens[0] = CHECK_PROMPT_LEN
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in lens]
    rids = [eng.submit(p, max_new=MAX_NEW) for p in prompts]
    say(f"submitted {N_REQUESTS} requests, prompt lengths {lens.tolist()}, "
        f"max_new {MAX_NEW}")

    compiles = []

    def on_event(event, secs, **_):
        if event == COMPILE_EVENT:
            compiles.append(secs)

    def ingested():
        return sum(r.prefill_cursor for r in eng.sched.requests.values())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    step_s = {"prefill": [], "decode": []}     # steps with / without prompt
    for _ in range(MAX_STEPS):
        if not eng.sched.busy:
            break
        before = ingested()
        t0 = time.perf_counter()
        eng.step()
        jax.block_until_ready(eng.cache)
        phase = "prefill" if ingested() > before else "decode"
        step_s[phase].append(time.perf_counter() - t0)
    jax.monitoring.unregister_event_duration_listener(on_event)
    _require(not eng.sched.busy, f"still busy after {MAX_STEPS} steps")
    _require(not compiles, f"{len(compiles)} programs compiled inside the "
             f"served steps, {sum(compiles)} s")
    done = eng.finished
    _require(sorted(done) == sorted(rids), f"finished {sorted(done)}")
    for rid in rids:
        gen = done[rid].generated
        _require(len(gen) == MAX_NEW,
                 f"request {rid} got {len(gen)} tokens, not {MAX_NEW}")
        _require(all(0 <= t < cfg.vocab for t in gen),
                 f"request {rid} has a token outside [0, {cfg.vocab})")
    tokens = sum(len(done[r].generated) for r in rids)
    all_s = step_s["prefill"] + step_s["decode"]
    say(f"served {len(done)} requests, {tokens} tokens in {len(all_s)} "
        f"engine steps")
    say(f"steady s/engine step (to block_until_ready): mean "
        f"{float(np.mean(all_s))}, max {max(all_s)}")
    for phase, s in step_s.items():
        say(f"steady s/engine step, {len(s)} {phase} steps: mean "
            f"{float(np.mean(s))}, median {float(np.median(s))}, min "
            f"{min(s)}, max {max(s)}")

    err = logits_error(eng, prompts[0], done[rids[0]].generated)
    say(f"logits check: {len(err)} positions ({CHECK_PROMPT_LEN} prompt via "
        f"prefill_step, {MAX_NEW - 1} via decode_step) vs float32 forward: "
        f"max relative RMS error {float(err.max())}, mean "
        f"{float(err.mean())}, tolerance {LOGITS_TOL} (bf16 compute vs "
        f"float32 reference)")
    _require(bool(err.max() <= LOGITS_TOL),
             f"logits error {float(err.max())} > {LOGITS_TOL}")

    stats = dev.memory_stats()
    say(f"peak_bytes_in_use {stats['peak_bytes_in_use']} of bytes_limit "
        f"{stats['bytes_limit']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": count}}))


if __name__ == "__main__":
    main()

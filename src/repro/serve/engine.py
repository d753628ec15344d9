"""Batched serving engine: continuous batching over fixed decode slots,
with real chunked prefill on the live path.

Requests are admitted through the scheduler's arrival queue (bounded —
admission control sheds load past ``max_pending`` and refuses shapes that
cannot fit a slot); every engine step advances all active slots with a
single jitted call.  Slots refill *mid-run* the step after they drain — the
cache tracks a per-sequence position vector (``cache["len"]`` is ``(B,)``),
so one slot's readmission never disturbs its neighbours and never
resurrects stale KV rows (the freed slot's cache rows are zeroed before
reuse).  ``mode="static"`` keeps the old wave-batching behaviour as a
measurable baseline.

Prompt ingestion is chunked: any step with a prefilling slot runs the
jitted :func:`~repro.models.model.prefill_step`, feeding up to
``prefill_chunk`` prompt tokens per prefilling slot per call while
neighbouring slots mid-decode ride along in the same batch with a one-token
chunk — bit-exact with the token-by-token path by construction (the chunk
kernel scans the same ``decode_step`` body over its columns).  Chunk widths
are bucketed to powers of two so the jit cache holds at most
``log2(prefill_chunk) + 1`` programs (``prefill_compiles`` counts them, and
:meth:`ServeEngine.warmup` compiles them all before serving);
``prefill="token"`` keeps the old one-token-per-step ingestion as the
measurable TTFT baseline.

The engine runs on the host clock: every request lifecycle stamp is
``time.perf_counter()`` seconds.  Each phase of :meth:`ServeEngine.step` is
a profiler span, and each step appends one record to the process-wide step
log (:mod:`repro.serve.telemetry`).  The step programs are jitted under
their own names, so a device trace shows ``jit_decode_step`` and
``jit_prefill_step``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..config import ModelConfig, RunConfig, resolve_run_config
from ..core.policy import OperatingPoint, PolicyTable
from ..models.model import decode_step, init_cache, prefill_step
from . import telemetry
from .scheduler import (AdmissionControl, ContinuousScheduler, ServeReport,
                        ServeSLO, build_report)

Pytree = Any


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    generated: List[int] = field(default_factory=list)
    done: bool = False


def _jit_step(fn, cfg: ModelConfig, rc: RunConfig):
    """``fn(params, cache, batch, cfg=cfg, rc=rc)`` jitted under ``fn``'s
    own name, so that its program is ``jit_<name>`` in a device trace (a
    ``functools.partial`` would be ``jit__unknown``)."""
    def step(params, cache, batch):
        return fn(params, cache, batch, cfg=cfg, rc=rc)
    step.__name__ = step.__qualname__ = fn.__name__
    return jax.jit(step)


class ServeEngine:
    """Continuous-batching engine.

    The execution policy is resolved per workload at startup through
    :func:`repro.config.resolve_run_config`: an explicit ``operating_point``
    wins, a caller-pinned (non-default) ``rc.policy`` stays authoritative,
    and otherwise the calibration-backed
    :class:`~repro.core.policy.PolicyTable` (``policy_table`` or the
    process-wide default honouring ``REPRO_CALIBRATION_DIR``) supplies the
    ``"serve"`` workload's point, falling back to the paper's defaults when
    no artifact exists.  A ``traffic`` level ("low"/"medium"/"high") pins
    the artifact's per-traffic ``serve-slo`` point when the calibration
    carries one (schema v5).  The resolved policy is threaded into the
    engine's :class:`RunConfig` so every kernel the decode path reaches
    sees it; the resolution itself never touches the per-step hot path.

    Batch sizing is cluster-aware: with ``batch_slots=None`` the engine
    sizes its decode batch as ``SLOTS_PER_CORE * n_cores`` from the
    resolved operating point — an N-PE cluster sustains N concurrent
    per-core token streams, so the continuous batch scales with the
    calibrated cluster width instead of implicitly assuming one PE.  An
    explicit ``batch_slots`` always wins.

    Request lifecycle and accounting live in
    :class:`~repro.serve.scheduler.ContinuousScheduler`, stamped with
    ``time.perf_counter()``: a request arrives at :meth:`submit`, enters a
    slot at the start of the step that places it, and gets each token at
    the end of the step's ``serve.fetch`` phase.  :meth:`metrics` turns the
    stamps into p50/p99 latency and TTFT in seconds.
    """

    #: decode slots the batch allocates per cluster core (one PE's worth of
    #: concurrent streams at the paper's operating point)
    SLOTS_PER_CORE = 4

    PREFILL_MODES = ("chunked", "token")

    def __init__(self, params: Pytree, cfg: ModelConfig, rc: RunConfig,
                 batch_slots: Optional[int] = None, max_len: int = 256,
                 greedy: bool = True,
                 operating_point: Optional[OperatingPoint] = None,
                 policy_table: Optional[PolicyTable] = None,
                 mode: str = "continuous", max_pending: int = 64,
                 traffic: Optional[str] = None,
                 prefill: str = "chunked", prefill_chunk: int = 8):
        assert cfg.causal, "serving requires an autoregressive model"
        if prefill not in self.PREFILL_MODES:
            raise ValueError(f"prefill must be one of {self.PREFILL_MODES}, "
                             f"got {prefill!r}")
        assert prefill_chunk >= 1, prefill_chunk
        self.params = params
        rc, self.operating_point = resolve_run_config(
            rc, "serve", operating_point, policy_table, traffic=traffic)
        if batch_slots is None:
            batch_slots = self.SLOTS_PER_CORE * max(
                1, self.operating_point.n_cores)
        self.cfg, self.rc = cfg, rc
        self.traffic = traffic
        self.max_len = max_len
        self.greedy = greedy
        self.prefill = prefill
        self.prefill_chunk = prefill_chunk
        self.sched = ContinuousScheduler(
            batch_slots, mode=mode,
            admission=AdmissionControl(max_pending=max_pending,
                                       max_total_len=max_len))
        self.requests: Dict[int, Request] = {}
        self.cache = init_cache(cfg, batch_slots, max_len, jnp.dtype(rc.dtype))
        #: the jitted decode step (one token per slot)
        self.decode_fn = _jit_step(decode_step, cfg, rc)
        #: bucketed chunk-width jit cache: chunk width -> jitted prefill_step.
        #: Widths are powers of two, so at most log2(prefill_chunk)+1 programs
        #: ever compile; ``prefill_compiles`` counts them.
        self._prefill_jit: Dict[int, Any] = {}
        self.prefill_compiles = 0
        self._next_rid = 0
        self.finished: Dict[int, Request] = {}
        self._n_steps = 0

    @property
    def slots(self) -> List[Optional[Request]]:
        """Engine-side view of the decode batch: the live :class:`Request`
        per slot (``None`` for free slots)."""
        return [self.requests[s.rid] if s is not None else None
                for s in self.sched.slots]

    def submit(self, prompt: List[int], max_new: int = 16) -> int:
        """Queue a request; raises
        :class:`~repro.serve.scheduler.AdmissionError` when admission
        control sheds it (backpressure — the caller retries later).  The
        scheduler's admission control refuses empty prompts up front, so a
        ``[]`` prompt never reaches the batch-assembly hot path."""
        rid = self._next_rid
        self.sched.submit(rid, len(prompt), max_new,
                          now=time.perf_counter())
        self._next_rid += 1
        self.requests[rid] = Request(rid, list(prompt), max_new)
        return rid

    @staticmethod
    def _zero_slot(cache: Pytree, i: int) -> Pytree:
        """``cache`` with slot ``i``'s rows zeroed in every leaf: batch is
        axis 1 of every stacked leaf, axis 0 of the per-sequence ``len``
        vector.  This is what makes mid-run refill safe — the readmitted
        slot restarts at position 0 over zeroed KV/state rows while its
        neighbours keep decoding at their own positions."""
        return {k: (v if v.ndim == 0 else
                    v.at[i].set(0) if v.ndim == 1 else
                    v.at[:, i].set(0))
                for k, v in cache.items()}

    # -- chunked prefill machinery ----------------------------------------
    @staticmethod
    def _bucket(n: int) -> int:
        """Smallest power of two >= n: chunk widths quantize to buckets so
        the number of compiled prefill programs stays logarithmic."""
        return 1 << max(n - 1, 0).bit_length()

    def prefill_fn(self, width: int):
        """The jitted :func:`~repro.models.model.prefill_step` for chunk
        width ``width`` (compiled on its first call)."""
        fn = self._prefill_jit.get(width)
        if fn is None:
            fn = self._prefill_jit[width] = _jit_step(prefill_step,
                                                      self.cfg, self.rc)
            self.prefill_compiles += 1
        return fn

    def _chunk_batch(self, active):
        """A mixed-phase chunk batch: prefilling slots ingest up to
        ``prefill_chunk`` prompt tokens, decoding slots ride along with a
        one-token chunk, free slots stay masked out.  Returns the token and
        per-slot count arrays and the prompt tokens they ingest."""
        n = self.sched.n_slots
        need = 1
        for _, sreq in active:
            if sreq.phase == "prefill":
                need = max(need, min(self.prefill_chunk,
                                     sreq.prompt_len - sreq.prefill_cursor))
        tokens = np.zeros((n, self._bucket(need)), np.int32)
        counts = np.zeros((n,), np.int32)
        prompt_tokens = 0
        for i, sreq in active:
            req = self.requests[sreq.rid]
            cur = sreq.prefill_cursor
            if cur < len(req.prompt):
                k = min(self.prefill_chunk, len(req.prompt) - cur)
                tokens[i, :k] = req.prompt[cur:cur + k]
                counts[i] = k
                prompt_tokens += k
            else:
                tokens[i, 0] = (req.generated[-1] if req.generated
                                else req.prompt[-1])
                counts[i] = 1
        return tokens, counts, prompt_tokens

    def _token_batch(self, active):
        """A one-token batch (pure-decode steps, and the whole run when
        ``prefill="token"``): every active slot advances one token through
        the plain decode step."""
        tokens = np.zeros((self.sched.n_slots, 1), np.int32)
        counts = np.zeros((self.sched.n_slots,), np.int32)
        prompt_tokens = 0
        for i, sreq in active:
            req = self.requests[sreq.rid]
            cur = sreq.prefill_cursor
            if cur < len(req.prompt):
                tokens[i, 0] = req.prompt[cur]
                prompt_tokens += 1
            elif req.generated:
                tokens[i, 0] = req.generated[-1]
            else:
                tokens[i, 0] = req.prompt[-1]
            counts[i] = 1
        return tokens, counts, prompt_tokens

    def warmup(self) -> Dict[str, float]:
        """Compile every program :meth:`step` can dispatch — the decode
        step, with chunked prefill each chunk-width bucket, and the slot
        reset and argmax around them — before any request is served, so no
        compile lands inside a served step.  Returns each step program's
        first-call seconds, compile included.  The cache is left as it
        was: every output is dropped."""
        n = self.sched.n_slots
        calls = [("decode_step", self.decode_fn,
                  {"tokens": jnp.zeros((n, 1), jnp.int32)})]
        if self.prefill == "chunked":
            widths = {self._bucket(k) for k in range(1, self.prefill_chunk + 1)}
            calls += [(f"prefill_step[{w}]", self.prefill_fn(w),
                       {"tokens": jnp.zeros((n, w), jnp.int32),
                        "n_tokens": jnp.zeros((n,), jnp.int32)})
                      for w in sorted(widths)]
        seconds = {}
        for name, fn, batch in calls:
            t0 = time.perf_counter()
            # keep only the logits: a second live cache would raise the
            # device's peak memory above what serving needs
            logits = jax.block_until_ready(
                fn(self.params, self.cache, batch)[0])
            seconds[name] = time.perf_counter() - t0
        np.asarray(jnp.argmax(logits, axis=-1))
        jax.block_until_ready(self._zero_slot(self.cache, 0))
        return seconds

    def step(self) -> None:
        """Advance every active slot — one chunk of prompt tokens for
        prefilling slots, one decoded token for the rest — refilling freed
        slots from the arrival queue first (continuous batching).  Each
        phase is a profiler span, and the call appends one
        :class:`~repro.serve.telemetry.StepRecord` to the step log."""
        t0 = time.perf_counter()
        with TraceAnnotation(telemetry.REFILL):
            placed = self.sched.refill(t0)
            for i, _ in placed:
                self.cache = self._zero_slot(self.cache, i)
            active = self.sched.active()
        t_refill = time.perf_counter()
        if not active:
            telemetry.LOG.append(telemetry.StepRecord(
                t0, t_refill, t_refill, t_refill, t_refill, t_refill,
                t_refill, "empty", 0, 0, 0, len(placed)))
            return
        chunked = self.prefill == "chunked" and any(
            sreq.phase == "prefill" for _, sreq in active)
        with TraceAnnotation(telemetry.ASSEMBLE):
            if chunked:
                tokens, counts, prompt_tokens = self._chunk_batch(active)
                fn = self.prefill_fn(tokens.shape[1])
                batch = {"tokens": jnp.asarray(tokens),
                         "n_tokens": jnp.asarray(counts)}
            else:
                tokens, counts, prompt_tokens = self._token_batch(active)
                fn = self.decode_fn
                batch = {"tokens": jnp.asarray(tokens)}
        t_assemble = time.perf_counter()
        with TraceAnnotation(telemetry.DISPATCH):
            logits, self.cache = fn(self.params, self.cache, batch)
            nxt = jnp.argmax(logits, axis=-1)
        t_dispatch = time.perf_counter()
        with TraceAnnotation(telemetry.WAIT):
            jax.block_until_ready(nxt)
        t_wait = time.perf_counter()
        with TraceAnnotation(telemetry.FETCH):
            nxt = np.asarray(nxt)
        end = time.perf_counter()           # the step's tokens' stamp
        with TraceAnnotation(telemetry.COMMIT):
            for i, sreq in active:
                req = self.requests[sreq.rid]
                cur = sreq.prefill_cursor
                if cur < len(req.prompt):
                    k = int(counts[i])
                    self.sched.advance_prefill(sreq.rid, k, end)
                    if cur + k < len(req.prompt):
                        continue               # still ingesting the prompt
                    # the call that ingested the last prompt token emitted
                    # the first generated token — fall through to record it
                req.generated.append(int(nxt[i]))
                if self.sched.record_token(sreq.rid, end):
                    req.done = True
                    self.finished[req.rid] = req
            self._n_steps += 1
        telemetry.LOG.append(telemetry.StepRecord(
            t0, t_refill, t_assemble, t_dispatch, t_wait, end,
            time.perf_counter(), "prefill" if chunked else "decode",
            tokens.shape[1], prompt_tokens, len(active), len(placed)))

    def run(self, max_steps: int = 1000) -> Dict[int, Request]:
        steps = 0
        while self.sched.busy and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    def metrics(self, slo: Optional[ServeSLO] = None) -> ServeReport:
        """Per-request serving report (p50/p99 latency, TTFT, SLO
        attainment) from the scheduler's lifecycle stamps, which are
        ``time.perf_counter()`` seconds: latencies are seconds per output
        token, TTFTs seconds, throughput tokens per second from the first
        arrival to the last finish.  The engine measures no energy, so the
        energy fields read 0."""
        stamps = [t for r in self.sched.requests.values()
                  for t in (r.arrival, r.finish) if t is not None]
        span = max(stamps) - min(stamps) if stamps else 0.0
        return build_report(self.sched, span, 0.0, slo=slo,
                            cost_source="host_clock")

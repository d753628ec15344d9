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
measurable TTFT baseline.  Step accounting matches the virtual-time
``scheduler.simulate_serve``: every step charges the full batch width plus
the ingested prompt tokens at ``PREFILL_FRACTION`` through the
:class:`StepCostModel`, so the engine clock is in cycles-equivalent always.

When neither an explicit ``operating_point`` nor a ``traffic`` level is
given, the engine runs in *measured-traffic* mode: a
:class:`~repro.serve.scheduler.TrafficEstimator` watches the arrival
stream, and at refill boundaries the engine re-resolves the schema-v5
per-traffic ``serve-slo`` operating point for the measured level
(``traffic_history`` records every retarget).  An explicit ``traffic``
flag or operating point disables the estimator and pins the point.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import (ModelConfig, RunConfig, _DEFAULT_RC_POLICY,
                      resolve_run_config)
from ..core.policy import OperatingPoint, PolicyTable, default_table
from ..models.model import decode_step, init_cache, prefill_step
from .scheduler import (AdmissionControl, ContinuousScheduler, HostDispatch,
                        ServeReport, ServeSLO, StepCostModel,
                        TrafficEstimator, build_report)

Pytree = Any


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    generated: List[int] = field(default_factory=list)
    done: bool = False


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
    carries one (schema v5); with no pin the engine *measures* the level
    from the arrival stream and re-resolves at refill boundaries (the
    retarget swaps the operating point and its cost model — the executed
    numeric program is untouched, so generated tokens never depend on
    traffic).  The resolved policy is threaded into the engine's
    :class:`RunConfig` so every kernel the decode path reaches sees it; the
    resolution itself never touches the per-step hot path.

    Batch sizing is cluster-aware: with ``batch_slots=None`` the engine
    sizes its decode batch as ``SLOTS_PER_CORE * n_cores`` from the
    resolved operating point — an N-PE cluster sustains N concurrent
    per-core token streams, so the continuous batch scales with the
    calibrated cluster width instead of implicitly assuming one PE.  An
    explicit ``batch_slots`` always wins.

    Request lifecycle and accounting live in
    :class:`~repro.serve.scheduler.ContinuousScheduler`; :meth:`metrics`
    turns the recorded timestamps (cycles-equivalent — the engine clock is
    driven by the operating point's :class:`StepCostModel`) into p50/p99
    latency and energy-per-token.
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
                 cost_model: Optional[StepCostModel] = None,
                 dispatch: Optional[HostDispatch] = None,
                 prefill: str = "chunked", prefill_chunk: int = 8):
        assert cfg.causal, "serving requires an autoregressive model"
        if prefill not in self.PREFILL_MODES:
            raise ValueError(f"prefill must be one of {self.PREFILL_MODES}, "
                             f"got {prefill!r}")
        assert prefill_chunk >= 1, prefill_chunk
        self.params = params
        pinned = rc.policy if rc.policy is not _DEFAULT_RC_POLICY else None
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
        self._cost = cost_model or StepCostModel.from_operating_point(
            self.operating_point)
        self._explicit_cost = cost_model is not None
        # measured-traffic mode: no pinned point, no pinned level — estimate
        # offered load from arrivals and re-resolve at refill boundaries
        self._measured = operating_point is None and traffic is None
        self._pinned_policy = pinned
        self._table = (policy_table if policy_table is not None
                       else default_table())
        self.traffic_level: Optional[str] = traffic
        self.traffic_history: List[Dict[str, Any]] = []
        estimator = None
        if self._measured:
            step_cyc, _ = self._cost.step_cost(batch_slots, 0)
            estimator = TrafficEstimator(
                capacity_tokens_per_cycle=batch_slots / max(step_cyc, 1e-9))
        self.sched = ContinuousScheduler(
            batch_slots, mode=mode,
            admission=AdmissionControl(max_pending=max_pending,
                                       max_total_len=max_len),
            estimator=estimator)
        self.requests: Dict[int, Request] = {}
        self.cache = init_cache(cfg, batch_slots, max_len, jnp.dtype(rc.dtype))
        #: the jitted decode step (one token per slot)
        self.decode_fn = jax.jit(partial(decode_step, cfg=cfg, rc=rc))
        #: bucketed chunk-width jit cache: chunk width -> jitted prefill_step.
        #: Widths are powers of two, so at most log2(prefill_chunk)+1 programs
        #: ever compile; ``prefill_compiles`` counts them.
        self._prefill_jit: Dict[int, Any] = {}
        self.prefill_compiles = 0
        self._next_rid = 0
        self.finished: Dict[int, Request] = {}
        self._dispatch = dispatch
        self._n_steps = 0
        self._clock = 0.0       # cycles-equivalent (StepCostModel-driven)
        self._energy = 0.0

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
        self.sched.submit(rid, len(prompt), max_new, now=self._clock)
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
            fn = self._prefill_jit[width] = jax.jit(
                partial(prefill_step, cfg=self.cfg, rc=self.rc))
            self.prefill_compiles += 1
        return fn

    def _chunk_forward(self, active) -> Tuple[np.ndarray, np.ndarray, int]:
        """One mixed-phase chunk call: prefilling slots ingest up to
        ``prefill_chunk`` prompt tokens, decoding slots ride along with a
        one-token chunk, free slots stay masked out.  Returns the per-slot
        argmax tokens, the per-slot chunk counts, and the total prompt
        tokens ingested (the prefill component of this step's cost)."""
        n = self.sched.n_slots
        need = 1
        for _, sreq in active:
            if sreq.phase == "prefill":
                need = max(need, min(self.prefill_chunk,
                                     sreq.prompt_len - sreq.prefill_cursor))
        width = self._bucket(need)
        tokens = np.zeros((n, width), np.int32)
        counts = np.zeros((n,), np.int32)
        prefill_tokens = 0
        for i, sreq in active:
            req = self.requests[sreq.rid]
            cur = sreq.prefill_cursor
            if cur < len(req.prompt):
                k = min(self.prefill_chunk, len(req.prompt) - cur)
                tokens[i, :k] = req.prompt[cur:cur + k]
                counts[i] = k
                prefill_tokens += k
            else:
                tokens[i, 0] = (req.generated[-1] if req.generated
                                else req.prompt[-1])
                counts[i] = 1
        logits, self.cache = self.prefill_fn(width)(
            self.params, self.cache,
            {"tokens": jnp.asarray(tokens), "n_tokens": jnp.asarray(counts)})
        return np.asarray(jnp.argmax(logits, axis=-1)), counts, prefill_tokens

    def _token_forward(self, active) -> Tuple[np.ndarray, np.ndarray, int]:
        """One token-by-token step (pure-decode steps, and the whole run
        when ``prefill="token"``): every active slot advances one token
        through the plain jitted decode step."""
        tokens = np.zeros((self.sched.n_slots, 1), np.int32)
        counts = np.zeros((self.sched.n_slots,), np.int32)
        for i, sreq in active:
            req = self.requests[sreq.rid]
            cur = sreq.prefill_cursor
            if cur < len(req.prompt):
                tokens[i, 0] = req.prompt[cur]
            elif req.generated:
                tokens[i, 0] = req.generated[-1]
            else:
                tokens[i, 0] = req.prompt[-1]
            counts[i] = 1
        logits, self.cache = self.decode_fn(self.params, self.cache,
                                            {"tokens": jnp.asarray(tokens)})
        return np.asarray(jnp.argmax(logits, axis=-1)), counts, 0

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

    # -- measured-traffic retargeting --------------------------------------
    def _maybe_retarget_traffic(self) -> None:
        """In measured-traffic mode, re-resolve the per-traffic operating
        point when the estimator's level moved.  Called at refill
        boundaries only — never on the per-token hot path — and only swaps
        the accounting (operating point, cost model, estimator capacity):
        the compiled decode/prefill programs are left alone, so retargeting
        can never change which tokens get generated."""
        est = self.sched.estimator
        if not self._measured or est is None:
            return
        level = est.level()
        if level is None or level == self.traffic_level:
            return
        kw = ({"policy": self._pinned_policy}
              if self._pinned_policy is not None else {})
        op = self._table.resolve("serve", traffic=level, **kw)
        self.traffic_level = level
        self.operating_point = op
        if not self._explicit_cost:
            self._cost = StepCostModel.from_operating_point(op)
        step_cyc, _ = self._cost.step_cost(self.sched.n_slots, 0)
        est.capacity = self.sched.n_slots / max(step_cyc, 1e-9)
        self.traffic_history.append({
            "clock": self._clock, "level": level,
            "offered_load": est.offered_load(),
            "policy": op.policy.value, "source": op.source})

    def step(self) -> None:
        """Advance every active slot — one chunk of prompt tokens for
        prefilling slots, one decoded token for the rest — refilling freed
        slots from the arrival queue first (continuous batching)."""
        placed = self.sched.refill(self._clock)
        for i, _ in placed:
            self.cache = self._zero_slot(self.cache, i)
        if placed:
            self._maybe_retarget_traffic()
        active = self.sched.active()
        if not active:
            return
        if self.prefill == "chunked" and any(
                sreq.phase == "prefill" for _, sreq in active):
            nxt, counts, prefill_tokens = self._chunk_forward(active)
        else:
            nxt, counts, prefill_tokens = self._token_forward(active)
        cycles, joules = self._cost.step_cost(self.sched.n_slots,
                                              prefill_tokens)
        if self._dispatch is not None:
            cycles = self._dispatch.step(cycles, self._clock)
        end = self._clock + cycles
        self._energy += joules
        for i, sreq in active:
            req = self.requests[sreq.rid]
            cur = sreq.prefill_cursor
            if cur < len(req.prompt):
                k = int(counts[i])
                self.sched.advance_prefill(sreq.rid, k, end)
                if cur + k < len(req.prompt):
                    continue               # still ingesting the prompt
                # the call that ingested the last prompt token emitted the
                # first generated token — fall through to record it
            req.generated.append(int(nxt[i]))
            if self.sched.record_token(sreq.rid, end):
                req.done = True
                self.finished[req.rid] = req
        self._clock = end
        self._n_steps += 1

    def run(self, max_steps: int = 1000) -> Dict[int, Request]:
        steps = 0
        while self.sched.busy and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    def metrics(self, slo: Optional[ServeSLO] = None) -> ServeReport:
        """Per-request serving report (p50/p99 latency, TTFT, J/token,
        SLO attainment).  Timestamps are already in cycles-equivalent —
        every step is charged through the operating point's
        :class:`StepCostModel` as it executes (full batch width plus the
        step's prompt tokens at ``PREFILL_FRACTION``), the same accounting
        ``simulate_serve`` applies in virtual time."""
        return build_report(self.sched, self._clock, self._energy,
                            slo=slo, dispatch=self._dispatch,
                            cost_source=self._cost.source)

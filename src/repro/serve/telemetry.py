"""The serving engine's profiler span names and its host-clock step log.

Spans.  :meth:`~repro.serve.engine.ServeEngine.step` wraps each of its
phases in a ``jax.profiler.TraceAnnotation`` named by one of the constants
below.  They are TraceMe events on the profiler's timeline, beside the
device planes, so a trace shows which phase the host was in while the
device idled.  With no profiler running a span costs a TraceMe enter and
exit and records nothing.

Step log.  Every ``ServeEngine.step`` call, empty ones included, appends
one :class:`StepRecord` to :data:`LOG`, a bounded process-wide deque that
outlives the engine, as an operator's metrics registry does.  Its stamps
are ``time.perf_counter()`` seconds, the clock of the engine's request
lifecycle, so a reader filters by its own ``perf_counter`` readings with no
conversion (:func:`steps`).
"""
from __future__ import annotations

from collections import Counter, deque
from typing import Deque, Dict, List, NamedTuple

#: ``sched.refill`` and the cache-row reset of each slot it placed
REFILL = "serve.refill"
#: the numpy token and count arrays of the batch and their copy to the device
ASSEMBLE = "serve.assemble"
#: the jitted step program's call and the argmax's, which only enqueue work
DISPATCH = "serve.dispatch"
#: ``jax.block_until_ready`` on the argmax: the host waits on the device
WAIT = "serve.wait"
#: ``np.asarray`` of the ready argmax: the tokens' device-to-host copy
FETCH = "serve.fetch"
#: ``advance_prefill`` / ``record_token`` and the request bookkeeping
COMMIT = "serve.commit"
#: the phases of a step, in order
PHASES = (REFILL, ASSEMBLE, DISPATCH, WAIT, FETCH, COMMIT)

#: records :data:`LOG` keeps; the oldest fall out first
MAXLEN = 65536


class StepRecord(NamedTuple):
    """One ``ServeEngine.step`` call.  ``t0`` is its start; each phase field
    is that phase's end, so a phase lasts from the field before it to its
    own, and ``t1`` ends ``serve.commit`` and the step.  An ``empty`` step
    (no slot held a request after the refill) ends with ``serve.refill``:
    its later stamps all equal ``refill``."""
    t0: float
    refill: float
    assemble: float
    dispatch: float
    wait: float
    fetch: float
    t1: float
    kind: str            # "prefill" | "decode" | "empty": the program run
    width: int           # chunk columns of the program's token batch
    prompt_tokens: int   # prompt tokens ingested by the step
    live: int            # slots that held a request in the step
    placed: int          # slots the step's refill filled


LOG: Deque[StepRecord] = deque(maxlen=MAXLEN)


def phase_seconds(record: StepRecord) -> Dict[str, float]:
    """Each phase's seconds in ``record``, by span name; they add up to
    ``record.t1 - record.t0``."""
    return {name: b - a
            for name, a, b in zip(PHASES, record[:6], record[1:7])}


def phase_means(records: List[StepRecord]) -> Dict[str, float]:
    """Each phase's mean seconds over ``records``, by span name."""
    n = max(len(records), 1)
    per_step = [phase_seconds(r) for r in records]
    return {name: sum(p[name] for p in per_step) / n for name in PHASES}


def steps(t_from: float = float("-inf"),
          t_to: float = float("inf")) -> List[StepRecord]:
    """The logged steps that started at or after ``t_from`` and ended at
    or before ``t_to`` (``perf_counter`` seconds), oldest first."""
    return [r for r in LOG if r.t0 >= t_from and r.t1 <= t_to]


def summary(records: List[StepRecord]) -> str:
    """One line on ``records`` for an operator: the steps of each kind; the
    mean live slots of a busy step (occupancy) and the refills placed; a
    prefill step's mean chunk width and prompt tokens, which show whether
    slow steps are wide chunks; and each phase's mean milliseconds."""
    kinds = Counter(r.kind for r in records)
    busy = [r for r in records if r.kind != "empty"]
    pre = [r for r in records if r.kind == "prefill"]

    def mean(xs):
        return sum(xs) / max(len(xs), 1)
    return (f"{len(records)} steps ({kinds['prefill']} prefill, "
            f"{kinds['decode']} decode, {kinds['empty']} empty); "
            f"{mean([r.live for r in busy]):.2f} live slots per busy step, "
            f"{sum(r.placed for r in records)} refills; prefill step "
            f"width {mean([r.width for r in pre]):.1f}, "
            f"{mean([r.prompt_tokens for r in pre]):.1f} prompt tokens; "
            f"phase means ms: " + ", ".join(
                f"{k} {1e3 * v:.3f}"
                for k, v in phase_means(records).items()))

"""Reduction of a profiler trace (``.xplane.pb``) of one measured window.

The harness marks the window with a host span ``bench.window`` and each of
its calls into the engine with ``engine.step`` or ``bench.submit``.  From
the device planes (``/device:TPU:<n>``) this reads:

* busy time: the union of the intervals of the device's operations
  (line ``XLA Ops``) inside the window, averaged over the chips;
* each engine step's device time: the durations of the program calls
  (line ``XLA Modules``) whose midpoint lies in that ``engine.step`` span,
  averaged over the chips, in the order of the steps.  The engine's step
  programs are jitted ``functools.partial`` objects, which JAX names
  ``jit__unknown`` alike, so a call is told by the step that made it and
  not by its name;
* the operations that took most time, by the HLO instruction's name (the
  text before `` = ``), and the longest idle gaps, each named by the
  innermost host span that was open at the gap's midpoint (``none`` where
  the host was in no span)."""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
STEP_SPAN = "engine.step"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over chips
    n_devices: int
    step_device_s: List[float] = field(default_factory=list)
    op_seconds: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)


def op_name(event_name: str) -> str:
    """``%fusion.3`` of ``%fusion.3 = bf16[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0]


def busy_and_gaps(iv: np.ndarray, w0: float, w1: float
                  ) -> Tuple[float, np.ndarray]:
    """Length of the union of the intervals ``iv`` (n, 2), which lie inside
    the window ``[w0, w1]``, and the idle gaps of the window, as (m, 2)."""
    if len(iv) == 0:
        return 0.0, np.array([[w0, w1]], float)
    iv = iv[np.argsort(iv[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    first = np.ones(len(iv), bool)
    first[1:] = iv[1:, 0] > ends[:-1]
    piece_start = iv[first, 0]
    piece_end = ends[np.r_[np.flatnonzero(first)[1:] - 1, len(iv) - 1]]
    gaps = np.stack([np.r_[w0, piece_end], np.r_[piece_start, w1]], axis=1)
    return float((piece_end - piece_start).sum()), gaps[gaps[:, 1] > gaps[:, 0]]


def summarize(events: Dict[str, list], n_gaps: int = 10,
              n_ops: int = 10) -> TraceSummary:
    """Reduce plain event lists, each event ``(name, start_ns, dur_ns)``:
    ``host`` (every host span, ``bench.window`` among them) and, per chip,
    ``ops:<chip>`` and ``modules:<chip>``."""
    wins = [e for e in events["host"] if e[0] == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"{len(wins)} '{WINDOW_SPAN}' spans in the trace")
    w0, w1 = wins[0][1], wins[0][1] + wins[0][2]
    spans = np.array([(s, s + d, d) for n, s, d in events["host"]
                      if n != WINDOW_SPAN] or np.zeros((0, 3)), float)
    span_names = [n for n, _, _ in events["host"] if n != WINDOW_SPAN]
    chips = sorted(k.split(":", 1)[1] for k in events if k.startswith("ops:"))
    out = TraceSummary(window_s=(w1 - w0) / 1e9, busy_s=0.0,
                       n_devices=len(chips))
    steps = np.array(sorted((s, s + d) for n, s, d in events["host"]
                            if n == STEP_SPAN and w0 <= s and s + d <= w1),
                     float).reshape(-1, 2)
    step_s, op_s = np.zeros(len(steps)), defaultdict(float)
    gaps_all = []
    for chip in chips:
        iv = []
        for name, s, d in events[f"ops:{chip}"]:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                iv.append((a, b))
                op_s[op_name(name)] += (b - a) / 1e9
        busy, gaps = busy_and_gaps(np.array(iv, float).reshape(-1, 2),
                                   w0, w1)
        out.busy_s += busy / 1e9 / len(chips)
        gaps_all.append(gaps)
        for _, s, d in events.get(f"modules:{chip}", []):
            i = np.searchsorted(steps[:, 0], s + d / 2) - 1
            if i >= 0 and s + d / 2 <= steps[i, 1]:
                step_s[i] += d / 1e9 / len(chips)
    gaps = np.concatenate(gaps_all) if gaps_all else np.zeros((0, 2))
    longest = gaps[np.argsort(gaps[:, 0] - gaps[:, 1])][:n_gaps]
    for a, b in longest:
        mid = (a + b) / 2
        cover = np.flatnonzero((spans[:, 0] <= mid) & (spans[:, 1] >= mid)) \
            if len(spans) else []
        name = (span_names[cover[np.argmin(spans[cover, 2])]]
                if len(cover) else "none")
        out.gaps.append((name, (b - a) / 1e9))
    if chips and len(steps) and not step_s.all():
        raise ValueError(f"the device trace holds no program call for step "
                         f"{int(np.argmin(step_s > 0))} of {len(steps)}: "
                         f"the profiler stopped recording inside the window")
    out.step_device_s = step_s.tolist() if chips else []
    out.op_seconds = dict(sorted(op_s.items(), key=lambda kv: -kv[1])[:n_ops])
    return out


def read_xplane(path: str) -> Dict[str, list]:
    """Plain event lists of an ``.xplane.pb`` file, as
    :func:`summarize` takes them: the host spans of the thread that opened
    the window, and each chip's operations and program calls."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict[str, list] = {"host": []}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events]
                if any(e[0] == WINDOW_SPAN for e in events):
                    out["host"] += events
        elif _DEVICE_PLANE.match(plane.name):
            chip = plane.name.rsplit(":", 1)[1]
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    out[f"{key}:{chip}"] = [(e.name, e.start_ns,
                                             e.duration_ns)
                                            for e in line.events]
    return out

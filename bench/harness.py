"""The serving harness: one cell, one run, on the chip it is started on.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<name>.json``), a traffic mix
(``bench/traffic/<mix>.json``) and its limits (``bench/limits/<cell>.json``);
each metric is read by ``bench/metrics/<metric>.py``.  Nothing here names a
configuration, a mix or a metric, so a new one is a new file and a new entry.

A run:

1. makes the configuration's weights from the seed, on the device, in one
   jitted call (:mod:`bench.weights`), and hands them to a
   :class:`repro.serve.ServeEngine` with the configuration's slots and
   ``max_len`` and an empty :class:`repro.core.policy.PolicyTable`; no
   engine internal (chunk width, prefill mode) is set, so a change to the
   engine's defaults is measured on the same cells;
2. warms the engine's programs up (``ServeEngine.warmup``);
3. runs a closed loop with one client per slot: each client submits its
   next request of the mix (:mod:`bench.loadgen`) in the step after its
   last one finished.  Every token is stamped with ``time.perf_counter()``
   when ``ServeEngine.step()`` returns; the step has synced on the argmax
   by then.  A ramp lets as many requests finish as there are clients, so
   that the clients' requests are out of phase, and then the window opens.
   Set-up (``setup_s``) is process start to window open;
4. measures for ``seconds``: the window closes at the first step that ends
   past it.  Program compiles inside the window are counted;
5. with ``trace``, profiles a window of ``TRACE_SECONDS`` at most and
   reduces the trace (:mod:`bench.trace`);
6. reads the device's peak memory, frees the engine, and checks what the
   window served against the configuration's float32 reference
   (:mod:`bench.reference`)."""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from . import trace as trace_mod
from .loadgen import LoadGen
from .reference import reference_module, served_gaps
from .weights import make
from .workcount import WorkCounter

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: requests the check compares per run: the one with the most served tokens
#: and the rest drawn from the seed
CHECK_REQUESTS = 6
#: length of a traced run's window: the profiler keeps a bounded number of
#: device events, and at minicpm3-4b's rate of operations it stopped
#: recording after about 7 s of a 45 s window on a v5e
TRACE_SECONDS = 5.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, from the files its names point at
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    metrics: List[dict]               # end_to_end, or per_layer with trace
    limits: dict


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, trace: bool, root: Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    metrics = [m for m in bench["per_layer" if trace else "end_to_end"]
               if name in m.get("workloads", [name])]
    return Cell(name=name, config=_load_json(root / configs[w["config"]]["file"]),
                traffic=_load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                chips=w["chips"], metrics=metrics,
                limits=_load_json(BENCH / "limits" / f"{name}.json"))


def _field(obj, dotted: str):
    for name in dotted.split("."):
        obj = getattr(obj, name, None)
    return obj


def program_config(cfg: dict):
    """The registry's configuration that the file names, refused unless it
    holds what the file's reference asks of it (``program_fields``): the
    file holds the configuration as it is run."""
    from repro.configs import get_config
    m = get_config(cfg["registry"])
    want = reference_module(cfg).program_fields(cfg)
    got = {k: _field(m, k) for k in want}
    if got != want:
        raise SystemExit(f"registry {cfg['registry']!r} differs from the "
                         f"configuration file: {got} != {want}")
    return m


# ---------------------------------------------------------------------------
# the closed loop
# ---------------------------------------------------------------------------

@dataclass
class Req:
    prompt: List[int]
    max_new: int
    submit: float
    stamps: List[float] = field(default_factory=list)
    finish: Optional[float] = None
    served: List[int] = field(default_factory=list)


@dataclass
class Step:
    kind: str                          # "prefill" | "decode"
    slots: List[Tuple[int, int, int]]  # (cached p, new k, emitted) per seq
    live: int                          # live slots after the step


@dataclass
class Window:
    t_open: float
    t_close: float
    requests: Dict[int, Req]
    steps: List[Step]
    compiles: int
    trace: Optional[trace_mod.TraceSummary] = None


def _start_trace():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    path = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(path, profiler_options=opts)
    return path


def _stop_trace(path: str) -> trace_mod.TraceSummary:
    t = time.perf_counter()
    jax.profiler.stop_trace()
    try:
        files = sorted(Path(path).rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        log(f"trace: stopped in {time.perf_counter() - t} s, "
            f"{files[-1].stat().st_size} bytes")
        t = time.perf_counter()
        events = trace_mod.read_xplane(str(files[-1]))
        log(f"trace: read in {time.perf_counter() - t} s, "
            f"{sum(map(len, events.values()))} events")
        return trace_mod.summarize(events)
    finally:
        shutil.rmtree(path, ignore_errors=True)


def closed_loop(eng, gen: LoadGen, clients: int, ramp: int, seconds: float,
                trace: bool = False) -> Window:
    """Serve ``gen``'s requests from ``clients`` closed-loop clients; open
    the window once ``ramp`` requests have finished and close it at the
    first step that ends ``seconds`` after."""
    span = jax.profiler.TraceAnnotation if trace else (lambda _: nullcontext())
    sched, engine_reqs = eng.sched, eng.requests
    reqs: Dict[int, Req] = {}
    inflight: Dict[int, Req] = {}
    compiles: List[float] = []
    steps: List[Step] = []
    free, done, j = clients, 0, 0
    t_open = trace_dir = window_span = None

    def on_event(event, secs, **_):
        if event == COMPILE_EVENT:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        while True:
            if t_open is None and done >= ramp:
                if trace:
                    trace_dir = _start_trace()
                    window_span = span(trace_mod.WINDOW_SPAN)
                    window_span.__enter__()
                compiles.clear()
                t_open = time.perf_counter()
            if free:
                with span("bench.submit"):
                    for _ in range(free):
                        r = gen.request(j)
                        j += 1
                        t = time.perf_counter()
                        rid = eng.submit(r.prompt, max_new=r.max_new)
                        reqs[rid] = inflight[rid] = Req(r.prompt, r.max_new, t)
                free = 0
            before = [(rid, rec, sched.requests[rid].prefill_cursor,
                       len(engine_reqs[rid].generated))
                      for rid, rec in inflight.items()]
            with span(trace_mod.STEP_SPAN):
                eng.step()
            t = time.perf_counter()
            slots, prefill = [], False
            for rid, rec, cur, n_gen in before:
                er = engine_reqs[rid]
                emits = len(er.generated) - n_gen
                if cur < len(rec.prompt):
                    k = sched.requests[rid].prefill_cursor - cur
                    prefill |= k > 0
                else:
                    k = 1 if emits else 0
                if k:
                    slots.append((cur + max(n_gen - 1, 0), k, emits))
                rec.stamps += [t] * emits
                if er.done:
                    rec.finish, rec.served = t, list(er.generated)
                    del inflight[rid]
                    free += 1
                    done += 1
            if t_open is not None:
                steps.append(Step("prefill" if prefill else "decode", slots,
                                  len(sched.active())))
                if t - t_open >= seconds:
                    break
        n_compiles = len(compiles)
        summary = None
        if trace:
            window_span.__exit__(None, None, None)
            summary = _stop_trace(trace_dir)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    for rid, rec in inflight.items():
        rec.served = list(engine_reqs[rid].generated)
    return Window(t_open, t, reqs, steps, n_compiles, summary)


# ---------------------------------------------------------------------------
# a whole run
# ---------------------------------------------------------------------------

@dataclass
class Run:
    """What the metric readers read."""
    cell: Cell
    window: Window
    setup_s: float
    n_slots: int
    work: WorkCounter
    peak: dict

    @property
    def window_s(self) -> float:
        return self.window.t_close - self.window.t_open

    def in_window(self, t: float) -> bool:
        return self.window.t_open < t <= self.window.t_close

    def step_device_s(self, kind: str) -> Optional[List[float]]:
        """Device seconds of each of the window's ``kind`` steps, from the
        trace; None without a trace that holds one span per step."""
        tr = self.window.trace
        if tr is None or len(tr.step_device_s) != len(self.window.steps):
            return None
        return [d for d, s in zip(tr.step_device_s, self.window.steps)
                if s.kind == kind] or None

    def roofline(self, kind: str):
        """Share of the roofline, in %, of the window's ``kind`` steps: the
        least time their work needs at the chip's peaks over their device
        time, with the bound that set it."""
        dev = self.step_device_s(kind)
        if not dev:
            return None
        calls = [s for s in self.window.steps if s.kind == kind]
        work = [self.work.call(s.slots) for s in calls]
        least = sum(w.seconds(self.peak)[0] for w in work)
        tc = sum(w.flops for w in work) / self.peak["bf16_flops_per_s"]
        tb = sum(w.bytes for w in work) / self.peak["hbm_bytes_per_s"]
        return (100.0 * least / sum(dev),
                f"{'compute' if tc > tb else 'bytes'}-bound: {len(calls)} "
                f"steps need {tc} s of compute and {tb} s of bytes at peak, "
                f"and took {sum(dev)} s on the device")


def read_metric(name: str, run: Run):
    """Run ``bench/metrics/<name>.py``'s ``read``: a number, a
    ``(number, note)`` pair, or None where it finds nothing to read."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def check(weights, cfg: dict, traffic: dict, window: Window, seed: int,
          vocab: int, control: bool = False):
    """Readings of what the window served: the widest served-token gap over
    a sample of requests drawn from the seed, with the request that was
    served most tokens in it; with ``control`` the float8 control's gap
    too."""
    done = [rid for rid, r in window.requests.items()
            if r.finish is not None and window.t_open < r.finish
            <= window.t_close]
    live = [rid for rid, r in window.requests.items()
            if r.finish is None and r.served]
    pool = done if len(done) >= CHECK_REQUESTS else done + live
    if not pool:
        raise RuntimeError("the window served no token to check")
    longest = max(pool, key=lambda rid: len(window.requests[rid].served))
    rest = [rid for rid in pool if rid != longest]
    rng = np.random.default_rng([seed, 2])
    pick = [longest] + sorted(rng.choice(rest, min(CHECK_REQUESTS - 1,
                                                   len(rest)),
                                         replace=False).tolist())
    seqs = [{"prompt": window.requests[r].prompt,
             "served": window.requests[r].served} for r in pick]
    gaps = served_gaps(weights, cfg, seqs,
                       (CHECK_REQUESTS, traffic["max_total"]), control)
    in_win = [r for r in window.requests.values()
              if r.stamps and window.t_open < r.stamps[-1]]
    readings = {
        "gap_sd": float(max(g.max() for g in gaps["served"])),
        "bad_length": sum(len(window.requests[r].served)
                          != window.requests[r].max_new for r in done),
        "bad_token": sum(not all(0 <= t < vocab for t in r.served)
                         for r in in_win),
        "compiles": window.compiles,
    }
    if control:
        readings["control_gap_sd"] = float(max(g.max()
                                               for g in gaps["control"]))
    readings["checked_tokens"] = int(sum(len(g) for g in gaps["served"]))
    return readings, pick


def judge(readings: dict, limits: dict):
    """``correct``, and each number compared beside its limit: every reading
    named in the cell's limits file at or under its limit."""
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in limits["limits"].items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def build(cell: Cell, seed: int, model_cfg=None):
    """Weights from the seed and an engine over them, warmed up."""
    from repro.config import RunConfig
    from repro.core.policy import PolicyTable
    from repro.serve import ServeEngine
    cfg = cell.config
    model_cfg = model_cfg or program_config(cfg)
    t = time.perf_counter()
    weights = make(reference_module(cfg).layout(cfg), seed, cfg["dtype"])
    jax.block_until_ready(weights)
    log(f"weights: {time.perf_counter() - t} s")
    rc = RunConfig(dtype=cfg["dtype"], param_dtype=cfg["dtype"], remat=False)
    eng = ServeEngine(weights, model_cfg, rc,
                      batch_slots=cfg["serve"]["slots"],
                      max_len=cfg["serve"]["max_len"],
                      policy_table=PolicyTable())
    t = time.perf_counter()
    first = eng.warmup()
    log(f"warm-up: {time.perf_counter() - t} s, first calls {first}")
    return weights, eng


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             device, peak: dict, model_cfg=None) -> dict:
    """One run of ``cell``: the result's fields, each metric read."""
    weights, eng = build(cell, seed, model_cfg)
    slots = eng.sched.n_slots
    t = time.perf_counter()
    window = closed_loop(eng, LoadGen(cell.traffic, seed,
                                      cell.config["vocab_size"]),
                         slots, slots,
                         min(seconds, TRACE_SECONDS) if trace else seconds,
                         trace)
    setup_s = window.t_open - t0
    log(f"ramp: {window.t_open - t} s; set-up {setup_s} s")
    log(f"window: {window.t_close - window.t_open} s, {len(window.steps)} "
        f"steps, {window.compiles} compiles")
    stats = device.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    eng.cache = None
    del eng
    t = time.perf_counter()
    readings, picked = check(weights, cell.config, cell.traffic, window, seed,
                             cell.config["vocab_size"])
    log(f"check: {time.perf_counter() - t} s over requests {picked}, "
        f"{readings['checked_tokens']} served tokens")
    del weights
    run = Run(cell, window, setup_s, slots, WorkCounter(cell.config), peak)
    metrics, notes = {}, {}
    for m in cell.metrics:
        got = read_metric(m["name"], run)
        if isinstance(got, tuple):
            got, notes[m["name"]] = got
        if got is not None:
            metrics[m["name"]] = {"value": float(got), "unit": m["unit"]}
    submitted = [r for r in window.requests.values()
                 if window.t_open <= r.submit < window.t_close]
    correct, checks = judge(readings, cell.limits)
    out = {"correct": correct,
           "attempted": len(submitted), "failed": readings["bad_length"],
           "metrics": metrics,
           "device": {"platform": device.platform, "kind": device.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": mem_peak}}
    if window.trace is not None:
        tr = window.trace
        out["device"].update(busy_s=tr.busy_s, window_s=tr.window_s)
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in tr.op_seconds.items()],
            "idle_gaps": [[n, s] for n, s in tr.gaps]}
    if notes:
        out["notes"] = notes
    out["checks"] = checks
    return out

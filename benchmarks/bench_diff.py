"""Bench-drift gate: compare the committed ``artifacts/BENCH_*.json``
headline metrics against freshly recomputed values and fail CI when a code
change silently moves them.

Two classes of check, matched to how reproducible each metric is:

* **exact compares** — metrics that are pure functions of the committed
  code (virtual-time simulation, pinned seeds, no wall clock):

  - ``BENCH_serve_slo.json`` is regenerated end-to-end (full config, same
    pinned traces) and deep-compared field-for-field: cycles-equivalent
    totals, SLO attainment, straggler weights — everything.  Any diff means
    the serving semantics changed.
  - ``BENCH_cluster.json``'s strong-scaling points are recomputed via
    ``run_point`` and compared (cycles exactly, derived floats within
    :data:`REL_TOL`), including the headline 1->4-core speedup.

* **floor checks** — metrics that embed wall-clock throughput (sweep-engine
  points/sec ratios, the live engine's chunked-prefill TTFT gains in
  ``BENCH_serve_prefill.json``) cannot be exactly reproduced on a different
  machine, so the committed values are only checked against static floors:
  the gate catches a regression that slipped into a committed artifact, not
  machine noise.  ``BENCH_serve_prefill.json`` additionally must assert
  that the chunked path matches the token path (its
  ``headline.matches_token_path`` flag) and a bounded chunk-jit cache.

A per-metric delta table prints to stdout and, when ``$GITHUB_STEP_SUMMARY``
is set, is appended there so the drift is visible on the job page without
opening logs.  Any failed row exits non-zero.

A deliberate semantics change regenerates the exact-compare baselines::

    PYTHONPATH=src python -m benchmarks.bench_diff --update

(this rewrites ``BENCH_serve_slo.json`` and ``BENCH_cluster.json`` in
place; the artifact diff becomes part of the PR review).  The floor-checked
artifacts are refreshed by their own sections (``benchmarks.sweep_perf``,
``benchmarks.sweep_scale``, ``benchmarks.cluster_sweep_scale``).
"""
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(ROOT, "artifacts")

#: relative tolerance for recomputed floats: generous only against float
#: repr round-tripping — any real model change is far bigger
REL_TOL = 1e-9

#: static floors for wall-clock-dependent committed metrics:
#: (artifact, key path, floor, what the metric is)
FLOORS = (
    ("BENCH_sweep.json", ("speedup_event_cached",), 2.0,
     "event engine cached-sweep speedup over uncached cycle engine"),
    ("BENCH_sweep_scale.json", ("throughput", "speedup_cached"), 10.0,
     "batch engine cached 2880-pt sweep speedup"),
    ("BENCH_cluster_sweep_scale.json", ("throughput", "speedup_cached"),
     8.0, "batch cluster engine cached 1128-pt sweep speedup"),
)

#: strong-scaling point fields compared exactly vs within :data:`REL_TOL`
_EXACT_FIELDS = ("n_cores", "tcdm_banks", "cycles", "bank_stalls")
_FLOAT_FIELDS = ("throughput", "speedup", "ipc", "ipc_per_core",
                 "energy_per_sample")


def _load(name):
    path = os.path.join(ART, name)
    if not os.path.exists(path):
        raise AssertionError(
            f"committed baseline artifacts/{name} is missing; regenerate "
            f"it (see --update / the owning benchmark section) and commit")
    with open(path) as f:
        return json.load(f)


def _close(a, b):
    if isinstance(a, float) or isinstance(b, float):
        ref = max(abs(a), abs(b), 1.0)
        return abs(a - b) / ref <= REL_TOL
    return a == b


def _deep_diff(base, cur, path, problems):
    """Structural + value diff; floats within REL_TOL, all else exact."""
    if isinstance(base, dict) and isinstance(cur, dict):
        for k in sorted(set(base) | set(cur)):
            if k not in cur:
                problems.append(f"{path}.{k}: vanished from recomputation")
            elif k not in base:
                problems.append(f"{path}.{k}: new field not in baseline")
            else:
                _deep_diff(base[k], cur[k], f"{path}.{k}", problems)
    elif isinstance(base, list) and isinstance(cur, list):
        if len(base) != len(cur):
            problems.append(
                f"{path}: length {len(base)} -> {len(cur)}")
        else:
            for i, (b, c) in enumerate(zip(base, cur)):
                _deep_diff(b, c, f"{path}[{i}]", problems)
    elif isinstance(base, (int, float)) and isinstance(cur, (int, float)) \
            and not isinstance(base, bool) and not isinstance(cur, bool):
        if not _close(base, cur):
            problems.append(f"{path}: {base!r} -> {cur!r}")
    elif base != cur:
        problems.append(f"{path}: {base!r} -> {cur!r}")


def _row(metric, baseline, current, check, ok):
    delta = (current - baseline
             if isinstance(baseline, (int, float))
             and isinstance(current, (int, float)) else None)
    return {"metric": metric, "baseline": baseline, "current": current,
            "delta": delta, "check": check,
            "status": "ok" if ok else "FAIL"}


def check_serve_slo(rows, problems):
    """Full regeneration + bit-level (float-tolerant) compare."""
    from . import serve_slo
    committed = _load("BENCH_serve_slo.json")
    with tempfile.TemporaryDirectory() as td:
        tmp = os.path.join(td, "regen.json")
        serve_slo.run(cfg=serve_slo.FULL, out_path=tmp)
        with open(tmp) as f:
            regen = json.load(f)
    local = []
    _deep_diff(committed, regen, "serve_slo", local)
    problems.extend(local)
    for k in sorted(committed.get("headline", {})):
        b = committed["headline"][k]
        c = regen.get("headline", {}).get(k)
        rows.append(_row(f"serve_slo.headline.{k}", b, c,
                         f"exact (rtol {REL_TOL:g})", _close(b, c)))
    rows.append(_row("serve_slo.full_report_fields_drifted", 0,
                     len(local), "== 0", not local))


def check_cluster_strong(rows, problems):
    """Recompute every committed strong-scaling point via ``run_point``."""
    from repro.core import SweepPoint, run_point
    committed = _load("BENCH_cluster.json")
    strong = committed.get("strong_scaling", {})
    n_drift = 0
    for kernel in sorted(strong):
        n_samples = strong[kernel]["n_samples"]
        base_tp = None
        for i, pt in enumerate(strong[kernel]["points"]):
            rec = run_point(SweepPoint(
                kernel=kernel, policy="copiftv2", n_samples=n_samples,
                n_cores=pt["n_cores"], tcdm_banks=pt["tcdm_banks"]))
            if not rec.ok or not rec.equivalent:
                problems.append(
                    f"cluster.{kernel}.x{pt['n_cores']}: recompute failed "
                    f"({rec.status}: {rec.detail or 'diverged'})")
                continue
            if base_tp is None:
                base_tp = rec.throughput
            cur = {"n_cores": rec.n_cores, "tcdm_banks": rec.tcdm_banks,
                   "cycles": rec.cycles, "bank_stalls": rec.bank_stalls,
                   "throughput": rec.throughput,
                   "speedup": rec.throughput / base_tp,
                   "ipc": rec.ipc, "ipc_per_core": rec.ipc_per_core,
                   "energy_per_sample": rec.energy / rec.n_samples}
            for field in _EXACT_FIELDS + _FLOAT_FIELDS:
                exact = field in _EXACT_FIELDS
                same = (pt[field] == cur[field] if exact
                        else _close(pt[field], cur[field]))
                if not same:
                    n_drift += 1
                    problems.append(
                        f"cluster.{kernel}.x{pt['n_cores']}.{field}: "
                        f"{pt[field]!r} -> {cur[field]!r}")
            if i == 0 and pt["speedup"] != 1.0:
                problems.append(
                    f"cluster.{kernel}: first strong-scaling point is not "
                    f"the 1x baseline (speedup={pt['speedup']!r})")
    head = committed.get("headline", {})
    if head:
        kernel = head["kernel"]
        pts = {p["n_cores"]: p for p in strong[kernel]["points"]}
        c = round(pts[4]["speedup"], 4)
        rows.append(_row(f"cluster.headline.speedup_4c[{kernel}]",
                         head["speedup_4c"], c,
                         f"exact (rtol {REL_TOL:g})",
                         _close(head["speedup_4c"], c)))
    rows.append(_row("cluster.strong_scaling_fields_drifted", 0, n_drift,
                     "== 0", n_drift == 0))


def check_serve_prefill(rows, problems):
    """Committed live-engine chunked-prefill gate artifact.  Wall-clock and
    cycles TTFT gains are floor-checked against the embedded bar (the wall
    number is machine-dependent, so no exact compare); the flag that it
    matches the token path and the bounded chunk-jit-cache count must hold
    outright."""
    art = _load("BENCH_serve_prefill.json")
    head = art["headline"]
    bar = head["min_required"]
    for key in ("ttft_wall_gain", "ttft_cycles_gain"):
        ok = head[key] >= bar
        if not ok:
            problems.append(
                f"BENCH_serve_prefill.json:headline.{key} = {head[key]} "
                f"fell below the {bar} floor")
        rows.append(_row(f"serve_prefill.headline.{key}", bar, head[key],
                         f">= {bar}", ok))
    ok = head["matches_token_path"] is True
    if not ok:
        problems.append(
            "BENCH_serve_prefill.json: chunked prefill was committed "
            "without matching the token-by-token path")
    rows.append(_row("serve_prefill.headline.matches_token_path", True,
                     head["matches_token_path"], "== True", ok))
    compiles, bound = art["prefill_compiles"], art["max_prefill_compiles"]
    ok = compiles <= bound
    if not ok:
        problems.append(
            f"BENCH_serve_prefill.json: {compiles} prefill compiles "
            f"exceed the log2(chunk)+1 = {bound} bound")
    rows.append(_row("serve_prefill.prefill_compiles", bound, compiles,
                     f"<= {bound}", ok))


def check_floors(rows, problems):
    """Committed wall-clock ratios and gated gains stay above their bars."""
    floors = list(FLOORS)
    # the gated headline gains carry their own floor inside the artifact
    serve = _load("BENCH_serve_slo.json")["headline"]
    floors.append(("BENCH_serve_slo.json",
                   ("headline", "throughput_at_slo_gain_bursty"),
                   serve["min_required"],
                   "continuous vs wave batching throughput-at-SLO (bursty)"))
    cluster = _load("BENCH_cluster.json")["headline"]
    floors.append(("BENCH_cluster.json", ("headline", "speedup_4c"),
                   cluster["min_required"],
                   "1->4 core strong-scaling speedup"))
    for name, keys, floor, _what in floors:
        node = _load(name)
        for k in keys:
            node = node[k]
        ok = node >= floor
        if not ok:
            problems.append(
                f"{name}:{'.'.join(keys)} = {node} fell below the "
                f"{floor} floor")
        rows.append(_row(f"{name.removeprefix('BENCH_').removesuffix('.json')}"
                         f".{'.'.join(keys)}", floor, node, f">= {floor}",
                         ok))


def _fmt_cell(v):
    if isinstance(v, float):
        return f"{v:.4f}"
    return "" if v is None else str(v)


def render_table(rows):
    head = ("metric", "baseline", "current", "delta", "check", "status")
    lines = ["| " + " | ".join(head) + " |",
             "|" + "---|" * len(head)]
    for r in rows:
        lines.append("| " + " | ".join(_fmt_cell(r[k]) for k in head) + " |")
    return "\n".join(lines)


def _emit_summary(table, problems):
    summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if not summary:
        return
    with open(summary, "a") as f:
        f.write("## bench-drift gate\n\n")
        f.write(table + "\n\n")
        if problems:
            f.write(f"**{len(problems)} drift finding(s):**\n\n")
            for p in problems:
                f.write(f"- `{p}`\n")
        else:
            f.write("No drift: committed benchmark baselines match the "
                    "recomputation and every floor holds.\n")


def run():
    t0 = time.time()
    rows, problems = [], []
    for check in (check_serve_slo, check_cluster_strong,
                  check_serve_prefill, check_floors):
        try:
            check(rows, problems)
        except AssertionError as e:
            problems.append(str(e))
    table = render_table(rows)
    print(table)
    _emit_summary(table, problems)
    if problems:
        raise AssertionError(
            "committed benchmark baselines drifted:\n  "
            + "\n  ".join(problems)
            + "\nIf the change is deliberate, regenerate with: "
              "PYTHONPATH=src python -m benchmarks.bench_diff --update "
              "and include the artifact diff in the PR")
    us = (time.time() - t0) * 1e6
    return [("bench_diff_metrics_checked", us, float(len(rows))),
            ("bench_diff_drift_findings", us, 0.0)]


def update_baselines():
    """Regenerate the exact-compare golden artifacts in place."""
    from . import cluster_scaling, serve_slo
    serve_slo.run(cfg=serve_slo.FULL, out_path=serve_slo.OUT_PATH)
    print(f"wrote {serve_slo.OUT_PATH}")
    cluster_scaling.run(cfg=cluster_scaling.FULL,
                        out_path=cluster_scaling.OUT_PATH)
    print(f"wrote {cluster_scaling.OUT_PATH}")


def main():
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived:.4f}")


if __name__ == "__main__":
    if "--update" in sys.argv[1:]:
        update_baselines()
    else:
        main()

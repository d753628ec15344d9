"""Run one benchmark cell once, on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  It needs a
TPU with as many chips as the cell asks for, and exits non-zero with no
result when JAX finds fewer or another platform; it never falls back to the
CPU.  Progress and the numbers compared, each beside its limit, go to
standard error; the last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (with
``--trace 1`` also ``breakdown``), and ``checks`` last.  With ``--trace 0``
the metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics.

JAX's persistent compilation cache is the repository's (``.jax_cache`` in
the checkout, or ``JAX_COMPILATION_CACHE_DIR`` where that is set), so only
a cell's first run in a checkout compiles."""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> None:
    args = parse(argv)
    from bench import harness
    cell = harness.load_cell(args.workload, bool(args.trace))
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit(f"bench: {args.workload} needs {cell.chips} TPU "
                         f"chip(s); JAX found {len(devices)} "
                         f"{dev.platform!r} device(s)")
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if dev.device_kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind "
                         f"{dev.device_kind!r} in bench/peaks.json")
    harness.log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
                f"cell {args.workload}, seed {args.seed}, {args.seconds} s, "
                f"trace {args.trace}")
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T0, dev, peaks[dev.device_kind])
    for name, m in out["metrics"].items():
        harness.log(f"[{dev.device_kind}] {name}: {m['value']} {m['unit']}")
    for name, note in out.get("notes", {}).items():
        harness.log(f"[{dev.device_kind}] {name}: {note}")
    for name, c in out["checks"].items():
        harness.log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out))


if __name__ == "__main__":
    main()

"""Readings from which a cell's limits are set: the program's widest
served-token gap on many seeds, and the float8 control's on some of them,
all in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 101-112 \\
        --control-seeds 101-103 --seconds 20

Each seed makes its own weights and engine, serves the cell's closed loop
for ``--seconds`` from a cold start (no ramp: the readings need served
tokens, not a steady window), and checks them as a run does.  One JSON line
per seed goes to standard output, with ``correct`` as the run's own test
gives it and, for a control seed, ``control_correct``: the same test with
the control's gap in the program's place.  The benchmark's own runs never run the
control."""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def seeds(text: str):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    from bench import harness
    from bench.loadgen import LoadGen
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: needs a TPU")
    cell = harness.load_cell(args.workload, False)
    vocab = cell.config["vocab_size"]
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        t = time.perf_counter()
        weights, eng = harness.build(cell, seed)
        window = harness.closed_loop(
            eng, LoadGen(cell.traffic, seed, vocab), eng.sched.n_slots, 0,
            args.seconds)
        eng.cache = None
        del eng
        readings, picked = harness.check(
            weights, cell.config, cell.traffic, window, seed, vocab,
            control=seed in args.control_seeds)
        del weights
        readings["correct"] = harness.judge(readings, cell.limits)[0]
        if "control_gap_sd" in readings:
            # the control in the program's place, through the run's own test
            readings["control_correct"] = harness.judge(
                {**readings, "gap_sd": readings["control_gap_sd"]},
                cell.limits)[0]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "requests": picked, **readings,
                          "seconds": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()

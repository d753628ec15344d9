"""Benchmark entry point — one section per paper table/figure plus the
framework-level experiments.  Prints ``name,us_per_call,derived`` CSV.

``--smoke`` runs the CI-grade path: every section that defines a ``smoke()``
hook runs its tiny-grid variant.  Failures are never swallowed: every
section still runs (so one broken section cannot hide another), a
per-section ``PASS``/``FAIL`` summary prints at the end, and any failure
exits non-zero — the CI smoke job cannot go green on a silently broken
section.
"""
import argparse
import sys
import traceback


def _run_sections(sections) -> None:
    """Run every (title, callable) section, print a per-section pass/fail
    summary, and exit non-zero if anything raised."""
    failures = []
    statuses = []
    for title, fn in sections:
        print(f"# --- {title} ---")
        try:
            fn()
            statuses.append((title, "PASS", ""))
        except Exception as e:
            traceback.print_exc()
            failures.append(title)
            statuses.append((title, "FAIL", f" ({type(e).__name__}: {e})"))
    print("# --- summary ---")
    for title, verdict, detail in statuses:
        print(f"# {verdict}: {title}{detail}")
    if failures:
        sys.exit(f"benchmark sections failed: {failures}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grids, per-section pass/fail, non-zero exit "
                         "on any failure (CI gate)")
    args = ap.parse_args(argv)

    if args.smoke:
        from . import (calibration, cluster_pipeline, cluster_scaling,
                       cluster_sweep_scale, dse, fig3, front_diff,
                       serve_slo, sweep_perf, sweep_scale)
        _run_sections([
            ("fig3 smoke (machine model, small n)", fig3.smoke),
            ("dse smoke (tiny sweep grid + equivalence fuzz)", dse.smoke),
            ("sweep_perf smoke (event vs cycle engine throughput)",
             sweep_perf.smoke),
            ("sweep_scale smoke (batch engine parity + adaptive front "
             "cover)", sweep_scale.smoke),
            ("cluster_sweep_scale smoke (batch cluster engine parity on "
             "cluster/pipeline grids)", cluster_sweep_scale.smoke),
            ("calibration smoke (Pareto-selected vs hard-coded default)",
             calibration.smoke),
            ("cluster scaling smoke (weak/strong 1-4 cores + bank "
             "contention)", cluster_scaling.smoke),
            ("cluster pipeline smoke (producer/consumer pairs vs work "
             "partition on a bank-starved TCDM)", cluster_pipeline.smoke),
            ("front diff (committed Pareto-front drift gate)",
             front_diff.smoke),
            ("serve SLO smoke (continuous vs wave batching under "
             "trace-driven load)", serve_slo.smoke),
            ("serve prefill smoke (live chunked prefill vs token-by-token "
             "TTFT, matching tokens)", serve_slo.prefill_smoke),
        ])
        return

    from . import (calibration, cluster_pipeline, cluster_scaling,
                   cluster_sweep_scale, collective_policy, dse, fig3,
                   front_diff, kernel_bench, roofline_table, serve_slo,
                   sweep_perf, sweep_scale)
    _run_sections([
        ("fig3 (paper Fig.3a/b/c via the machine model)", fig3.main),
        ("dse (design-space sweep + Pareto fronts)", dse.main),
        ("sweep_perf (DSE points/sec, event vs cycle engine)",
         sweep_perf.main),
        ("sweep_scale (batch engine >=10x gate + adaptive front cover)",
         sweep_scale.main),
        ("cluster_sweep_scale (batch cluster engine >=8x gate on "
         "cluster/pipeline grids)", cluster_sweep_scale.main),
        ("calibration (Pareto-selected operating points vs defaults)",
         calibration.main),
        ("cluster scaling (weak/strong 1-8 cores + bank contention)",
         cluster_scaling.main),
        ("cluster pipeline (producer/consumer pairs vs work partition)",
         cluster_pipeline.main),
        ("front diff (committed Pareto-front drift gate)", front_diff.main),
        ("serve SLO (continuous vs wave batching under trace-driven load)",
         serve_slo.main),
        ("serve prefill (live chunked prefill >=2x TTFT gate, matching)",
         serve_slo.prefill_main),
        ("kernels (interpret-mode micro-bench)", kernel_bench.main),
        ("collective policy (bulk vs ring)", collective_policy.main),
        ("roofline (from dry-run artifacts)", roofline_table.main),
    ])


if __name__ == "__main__":
    main()

"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract).

  PYTHONPATH=src python -m benchmarks.run             # everything
  PYTHONPATH=src python -m benchmarks.run --only fig3 # one family
"""
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter: fig3/fig5/fig7/fig8/tab2/roofline")
    ap.add_argument("--fast", action="store_true",
                    help="smaller query counts (CI mode)")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (aggregation_bench, dir_bench, index_bench,
                            recsys_bench, roofline, sensitivity_bench)

    suites = [
        ("fig3_fig4_aggregation",
         lambda: aggregation_bench.run(n_queries=20 if args.fast else 60,
                                       trials=1 if args.fast else 2)),
        ("fig5_fig6_dir",
         lambda: dir_bench.run(n_queries=10 if args.fast else 30)),
        ("fig7_recsys",
         lambda: recsys_bench.run(n_test_users=15 if args.fast else 40)),
        ("fig8_fig9_sensitivity", sensitivity_bench.run),
        ("tab2_index", index_bench.run),
        ("roofline", roofline.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        print(f"# --- {name} ---")
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED: {e}", file=sys.stderr)
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()

"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Each suite records the JAX
backend it ran on; a timing taken on the CPU backend is a CPU timing, not a
TPU one.  Roofline numbers come from the dry-run artifacts (results/dryrun)
via ``repro.analysis.roofline``.

    PYTHONPATH=src python -m benchmarks.run [table2 table3 shrinking fig3
                                             eigdrop kernels]
"""
import sys


def main() -> None:
    from benchmarks import (disk_stream, eigdrop, fig3_stages, kernel_micro,
                            polish, shrinking, stage2_mesh, stage2_stream,
                            streaming, table2_solvers, table3_cv_grid,
                            trace_smoke)
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    suites = {
        "table2": table2_solvers.run,
        "table3": table3_cv_grid.run,
        "shrinking": shrinking.run,
        "fig3": fig3_stages.run,
        "eigdrop": eigdrop.run,
        "kernels": kernel_micro.run,
        "streaming": streaming.run,
        "stage2": stage2_stream.run,
        "stage2_mesh": stage2_mesh.run,
        "disk": disk_stream.run,
        "polish": polish.run,
        "trace_smoke": trace_smoke.run,
    }
    picked = sys.argv[1:] or list(suites)
    print("name,us_per_call,derived")
    for name in picked:
        suites[name]()


if __name__ == "__main__":
    main()

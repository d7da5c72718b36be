"""Benchmark harness: one module per paper table/figure.

Prints ``name,value,derived`` CSV lines.  ``--quick`` skips the slow
interpret-mode kernel timings.
"""
from __future__ import annotations

import sys


def main() -> None:
    quick = "--quick" in sys.argv
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig5_utilization, fig7_roofline,
                            table1_footprint, table2_energy)
    print("name,value,derived")
    fig5_utilization.run()
    fig7_roofline.run()
    table1_footprint.run()
    table2_energy.run()
    if not quick:
        from benchmarks import kernel_bench, roofline_report
        kernel_bench.run()
        roofline_report.run()


if __name__ == "__main__":
    main()

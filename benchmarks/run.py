"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints per-benchmark CSV blocks and a final ``name,us_per_call,derived``
summary line per benchmark (emitted by each module via csv_row).
--full restores the paper's 10,000-sample / full-r-sweep protocol.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    full = "--full" in sys.argv
    from . import (fig8_snr_vs_range, fig9_snr_vs_iters, fig10_variants,
                   fig11_fixed_vs_fp, table1_4_cost_model, table5_fixp_vs_fp,
                   table6_7_throughput)
    mods = [fig8_snr_vs_range, fig9_snr_vs_iters, fig10_variants,
            fig11_fixed_vs_fp, table1_4_cost_model, table5_fixp_vs_fp,
            table6_7_throughput]
    t0 = time.time()
    for mod in mods:
        name = mod.__name__.split(".")[-1]
        print(f"\n===== {name} =====", flush=True)
        t = time.time()
        mod.main(full=full)
        print(f"# {name}: {time.time()-t:.1f}s", flush=True)
    print(f"\n# total: {time.time()-t0:.1f}s")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()

"""Table 5 — fixed-point (32b) vs FP-HUB (single, N=26) implementation compare.

FPGA delay/LUT/power columns are replaced by the structural cost model of
table1_4 plus measured emulation throughput; the SNR columns (the
architectural argument for FP: dynamic range) are fully reproduced.
"""
from __future__ import annotations

from repro.core import GivensConfig, SINGLE

from .common import csv_row, gen_matrices, snr_cordic, snr_fixed
from .table1_4_cost_model import cost_model


def main(full=False):
    # cost model: FixP rotator = CORDIC core only (no converters)
    fx = cost_model(SINGLE, 32, 27, hub=False)
    fx_core_only = fx["core_bits"]
    hub = cost_model(SINGLE, 26, 24, hub=True)
    print("# table5: design,model_adder_bits,paper_luts")
    print(f"fixp32,{fx_core_only},1947")
    print(f"fp_hub_32_26,{hub['adder_bits']},2182")
    ratio = hub["adder_bits"] / fx_core_only
    print(f"# model FP/FixP area ratio {ratio:.2f} (paper: 1.12)")

    # dynamic-range sweep (the reason FP exists)
    print("# table5_snr: r,fixp32,hub_n26")
    wins = 0
    for r in (2, 6, 10, 14, 20, 30):
        A = gen_matrices(5000 + r, r)
        s_fx = snr_fixed(A, 32, 27, scale_exp=r)
        s_hub = snr_cordic(GivensConfig(hub=True), A, N=26, iters=24)
        print(f"{r},{s_fx:.2f},{s_hub:.2f}")
        wins += s_hub > s_fx
    csv_row("table5_fixp_vs_fp", 0.0,
            f"model_area_ratio={ratio:.2f};hub_wins_{wins}_of_6_r_points")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()

"""Fig. 11 — fixed-point (32b) vs floating-point (single, N=26) over r=1..40.

Paper's observations to reproduce:
  - FixP beats FP for small r (more effective fraction bits);
  - FP-HUB overtakes FixP around r ~ 8;
  - FixP SNR decays with r and collapses past r ~ 14; FP stays flat.
"""
from __future__ import annotations

from repro.core import GivensConfig

from .common import csv_row, gen_matrices, snr_cordic, snr_fixed, snr_reference


def main(full=False):
    rs = range(1, 41) if full else range(2, 41, 4)
    print("# fig11: r,variant,snr_db")
    crossover = None
    collapse = None
    for r in rs:
        A = gen_matrices(4000 + r, r)
        fx = snr_fixed(A, width=32, iters=27, scale_exp=r)
        ieee = snr_cordic(GivensConfig(hub=False), A, N=26, iters=23)
        hub = snr_cordic(GivensConfig(hub=True), A, N=26, iters=24)
        ref = snr_reference(A)
        for name, v in [("fixp32", fx), ("ieee_n26", ieee),
                        ("hub_n26", hub), ("matlab_qr_f32", ref)]:
            print(f"{r},{name},{v:.2f}")
        if crossover is None and hub > fx:
            crossover = r
        if collapse is None and fx < 40.0:
            collapse = r
    csv_row("fig11_fixed_vs_fp", 0.0,
            f"hub_overtakes_fixp_at_r={crossover};fixp_collapse_r={collapse}")
    return crossover, collapse


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import sys
    main(full="--full" in sys.argv)

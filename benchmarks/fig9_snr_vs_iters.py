"""Fig. 9 — SNR vs number of CORDIC micro-rotations for N = 25..30.

Paper's observations to reproduce:
  - conventional (IEEE) peaks at N-3 micro-rotations, then *degrades*;
  - HUB needs N-2 and does not degrade with more iterations;
  - HUB(N) tracks IEEE(N+1); N=29 and N=30 saturate at single-precision.
"""
from __future__ import annotations

import numpy as np

from repro.core import GivensConfig

from .common import R_SET, csv_row, gen_matrices, snr_cordic


def main(full=False):
    ns = range(25, 31)
    print("# fig9: variant,N,iters,mean_snr_db")
    As = {r: gen_matrices(2000 + r, r) for r in (R_SET if not full
                                                 else range(1, 21))}
    out = {}
    for hub in (False, True):
        cfg = GivensConfig(hub=hub)
        for n in ns:
            for it in range(n - 6, min(n + 2, 31)):
                snr = float(np.mean([snr_cordic(cfg, A, N=n, iters=it)
                                     for A in As.values()]))
                out[(hub, n, it)] = snr
                print(f"{'hub' if hub else 'ieee'},{n},{it},{snr:.2f}")
    # derived: argmax iteration count per (variant, N)
    peaks = {}
    for hub in (False, True):
        for n in ns:
            best = max((it for (h, nn, it) in out if h == hub and nn == n),
                       key=lambda it: out[(hub, n, it)])
            peaks[("hub" if hub else "ieee", n)] = best - n
    csv_row("fig9_snr_vs_iters", 0.0,
            ";".join(f"{k[0]}N{k[1]}peak=N{v:+d}" for k, v in peaks.items()))
    return out, peaks


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import sys
    main(full="--full" in sys.argv)

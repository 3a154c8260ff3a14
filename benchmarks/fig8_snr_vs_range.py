"""Fig. 8 — QRD SNR vs input dynamic range r, IEEE vs HUB, N = 25/27/29.

Paper's observations to reproduce:
  - SNR changes only slightly with r;
  - HUB(N) beats IEEE(N) at equal N (HUB needs one bit less for parity).
"""
from __future__ import annotations

from repro.core import GivensConfig

from .common import N_SAMPLES, csv_row, gen_matrices, snr_cordic, snr_reference


def main(full=False):
    rs = range(1, 21) if full else (1, 5, 10, 15, 20)
    ns = (25, 27, 29)
    print("# fig8: r,variant,N,iters,snr_db")
    rows = []
    for r in rs:
        A = gen_matrices(1000 + r, r)
        ref = snr_reference(A)
        rows.append(("fig8", r, "matlab_qr_f32", "-", "-", ref))
        for n in ns:
            for hub in (False, True):
                cfg = GivensConfig(hub=hub)
                it = n - 2 if hub else n - 3
                snr = snr_cordic(cfg, A, N=n, iters=it)
                rows.append(("fig8", r, "hub" if hub else "ieee", n, it, snr))
    for row in rows:
        print(",".join(str(x) for x in row))
    # summary assertions mirrored in tests: HUB >= IEEE at same N (mean)
    import numpy as np
    hub = np.mean([x[-1] for x in rows if x[2] == "hub"])
    ieee = np.mean([x[-1] for x in rows if x[2] == "ieee"])
    csv_row("fig8_snr_vs_range", 0.0,
            f"mean_hub={hub:.2f}dB;mean_ieee={ieee:.2f}dB;samples={N_SAMPLES}")
    return hub, ieee


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import sys
    main(full="--full" in sys.argv)

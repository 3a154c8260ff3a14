"""Fig. 10 — design-variant study vs N.

IEEE: input-converter rounding (IEEERound) vs truncation (IEEETrunc);
HUB:  full (unbiased + identity detection) / unbiased-only / detectI-only /
      basic (biased, no detection).

Paper's observations to reproduce:
  - IEEERound does NOT beat IEEETrunc (rounding the input alignment shift
    is wasted hardware);
  - identity detection is worth up to ~4 dB (the Q-accumulation rows carry
    exact 1.0s); unbiased extension only matters without detection.
"""
from __future__ import annotations

import numpy as np

from repro.core import GivensConfig

from .common import csv_row, gen_matrices, snr_cordic, R_SET

VARIANTS = {
    "IEEETrunc": GivensConfig(hub=False, input_rounding="trunc"),
    "IEEERound": GivensConfig(hub=False, input_rounding="rne"),
    "HUBFull": GivensConfig(hub=True, unbiased=True, detect_identity=True),
    "HUBunbias": GivensConfig(hub=True, unbiased=True, detect_identity=False),
    "HUBDetectI": GivensConfig(hub=True, unbiased=False, detect_identity=True),
    "HUBBasic": GivensConfig(hub=True, unbiased=False, detect_identity=False),
}


def main(full=False):
    ns = range(25, 31)
    rset = range(1, 21) if full else R_SET
    As = {r: gen_matrices(3000 + r, r) for r in rset}
    print("# fig10: variant,N,mean_snr_db")
    res = {}
    for name, cfg in VARIANTS.items():
        for n in ns:
            it = n - 2 if cfg.hub else n - 3
            snr = float(np.mean([snr_cordic(cfg, A, N=n, iters=it)
                                 for A in As.values()]))
            res[(name, n)] = snr
            print(f"{name},{n},{snr:.2f}")
    gain = np.mean([res[("HUBFull", n)] - res[("HUBBasic", n)] for n in ns])
    round_gain = np.mean([res[("IEEERound", n)] - res[("IEEETrunc", n)]
                          for n in ns])
    csv_row("fig10_variants", 0.0,
            f"detectI+unbias_gain={gain:.2f}dB;ieee_round_gain={round_gain:.2f}dB")
    return res


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import sys
    main(full="--full" in sys.argv)

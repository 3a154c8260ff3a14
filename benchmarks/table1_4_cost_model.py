"""Tables 1-4 — hardware cost model + measured emulation throughput.

No FPGA is in the loop (DESIGN.md §2), so the paper's LUT/delay/energy
numbers are reproduced through a *structural cost model* that counts the
adder bits and carry-chain depth of each architecture — the quantities that
drive LUTs and critical path on the Virtex-6:

  input conv (IEEE) : 2 exponent subs, 2 negate adders, [RNE adder + sticky]
  input conv (HUB)  : 2 exponent subs (negation is bit inversion)
  CORDIC core       : 2 adders x (N+2) bits x iters      (both variants)
  output conv (IEEE): 2 negate adders, 2 round incrementers, sticky trees,
                      exponent adjust (+ overflow increment)
  output conv (HUB) : exponent adjust only

Reported per format: model adder-bits + path-depth ratios (HUB/IEEE)
side-by-side with the paper's measured LUT and delay ratios, plus the
*measured throughput* of the bit-accurate JAX emulation and of the Pallas
kernel (interpret mode) for the N<=28 single-precision configs.
"""
from __future__ import annotations

import numpy as np

from repro.core import GivensConfig, GivensUnit, HALF, SINGLE, DOUBLE

from .common import csv_row, gen_matrices, timed

# paper Tables 1-2: (fmt, N_ieee, N_hub) -> (delay ratio, LUT ratio)
PAPER = {
    ("half", 14, 13): (0.76, 0.82),
    ("half", 16, 15): (0.74, 0.80),
    ("single", 26, 25): (0.71, 0.87),
    ("single", 28, 27): (0.73, 0.87),
    ("single", 30, 29): (0.77, 0.86),
    ("double", 55, 54): (0.67, 0.92),
    ("double", 57, 56): (0.62, 0.91),
    ("double", 59, 58): (0.67, 0.91),
}
FMTS = {"half": HALF, "single": SINGLE, "double": DOUBLE}


def cost_model(fmt, N, iters, hub, input_rne=False):
    e, m = fmt.exp_bits, fmt.man_bits
    w = N + 2
    lg = int(np.ceil(np.log2(w)))
    core = 2 * w * iters
    # both FP variants carry an input align shifter and two output
    # normalize shifters + leading-one detectors (mux bits)
    shifters = N * lg + 2 * (w * lg + w)
    if hub:
        in_conv = 2 * e + shifters            # negation is bit inversion
        out_conv = 2 * e
        path = w + e                          # one adder deep per stage
    else:
        in_conv = 2 * e + 2 * (m + 1) + (2 * N if input_rne else 0) + shifters
        sticky = 2 * (w - m)
        out_conv = 2 * w + 2 * m + sticky + 2 * e + 2
        path = w + m + e                      # negate->add->round chain
    return {"adder_bits": core + in_conv + out_conv,
            "core_bits": core, "conv_bits": in_conv + out_conv,
            "path_bits": path}


def measured_throughput(cfg: GivensConfig, batch=2048, e=8):
    unit = GivensUnit(cfg)
    A = gen_matrices(7, 4.0, n=batch)
    import jax, jax.numpy as jnp
    P = unit.encode(jnp.asarray(A))
    rows = P.reshape(batch * 2, -1)  # fake (x,y) rows of length e/2... use 4x4

    @jax.jit
    def rot(P):
        x = P[..., 0, :]
        y = P[..., 1, :]
        return unit.rotate_rows(x, y)

    sec = timed(rot, P)
    n_rot = batch  # one Givens rotation per matrix pair-slice
    return n_rot / sec


def main(full=False):
    print("# table1_3: fmt,N_ieee,N_hub,model_area_ratio,paper_lut_ratio,"
          "model_path_ratio,paper_delay_ratio")
    area_errs, delay_errs = [], []
    for (fname, n_ieee, n_hub), (d_ratio, l_ratio) in PAPER.items():
        fmt = FMTS[fname]
        it = n_ieee - 3  # same stage count for both (paper Sec. 5.2)
        ieee = cost_model(fmt, n_ieee, it, hub=False)
        hub = cost_model(fmt, n_hub, it, hub=True)
        mar = hub["adder_bits"] / ieee["adder_bits"]
        mpr = hub["path_bits"] / ieee["path_bits"]
        print(f"{fname},{n_ieee},{n_hub},{mar:.2f},{l_ratio},{mpr:.2f},{d_ratio}")
        area_errs.append(abs(mar - l_ratio))
        delay_errs.append(abs(mpr - d_ratio))

    # Table 4 analogue: relative model-area deltas
    base = cost_model(SINGLE, 26, 23, hub=False)
    plus_it = cost_model(SINGLE, 26, 24, hub=False)
    plus_n = cost_model(SINGLE, 27, 24, hub=False)
    print("# table4: change,model_area_increase_pct,paper_pct")
    print(f"+1_microrotation,{100*(plus_it['adder_bits']/base['adder_bits']-1):.1f},3.1")
    print(f"+1_N,{100*(plus_n['adder_bits']/base['adder_bits']-1):.1f},5.3")

    # measured emulation throughput (rotations/s), IEEE vs HUB
    t_ieee = measured_throughput(GivensConfig(hub=False, n=26))
    t_hub = measured_throughput(GivensConfig(hub=True, n=25))
    print(f"# measured emulation: ieee={t_ieee:.0f} rot/s, hub={t_hub:.0f} rot/s")
    csv_row("table1_4_cost_model", 1e6 / max(t_hub, 1),
            f"mean_area_model_err={np.mean(area_errs):.3f};"
            f"mean_delay_model_err={np.mean(delay_errs):.3f}")
    return area_errs, delay_errs


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()

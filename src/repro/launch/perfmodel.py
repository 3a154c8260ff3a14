"""Analytic QRD performance model: ops + HBM bytes per decomposition.

Why analytic: the interpret-mode kernels measure Python dispatch, not
hardware, and compiled-mode wall clocks mix achievable throughput with
achieved.  The *work* of a blocked Givens QRD, by contrast, is exact —
the rotation schedule, the per-rotation element counts, the CORDIC
iteration depth and the kernels' HBM-pass contract are all architectural
— so the two roofline terms are derived here from first principles and
measured rates are reported as a fraction of the resulting bound
(DESIGN.md §11).

Work accounting for one m x n QRD (e = n + m row elements with Q):

    rotations        len(givens_schedule(m, n))  — the Sameh–Kuck stages
                     reorder but never change this set
    elements/rot     2 * (e - col)               — both rows from `col`
    ops/element      iters * OPS_PER_MICROROTATION + OPS_GAIN
                     (+ OPS_CONVERT on the packed path: the converter
                     dataflow runs per element per rotation)
    word factor      1.0 for the int32 block-FP datapath, ~2x for the
                     int64 packed word (64-bit ALU emulation), ~3.5x for
                     the dual-int32 lane split (carry/shift cross terms)

HBM bytes: the kernel-resident paths stage the working tile into VMEM
once and write it back once (``qrd_blocked.HBM_PASSES_PER_QRD`` = 2
passes over ``m * e * itemsize``); the step-serial host loop
('cordic' backend) round-trips every rotation — ``2 * len(steps)``
passes.  Encode/decode round-trips of the float64 operand add two more
8-byte passes on every path.

`DeviceSpec` carries the peak elementwise-op rate and HBM bandwidth per
device kind; `roofline` turns (cost, spec) into the achievable QRD/s
bound and `roofline_fraction` scores a measured rate against it.
"""
from __future__ import annotations

import dataclasses

__all__ = ["DeviceSpec", "QRDCost", "DEVICE_SPECS", "device_spec",
           "qrd_cost", "panel_qrd_cost", "tsqr_qrd_cost",
           "roofline", "roofline_fraction",
           "OPS_PER_MICROROTATION", "OPS_GAIN", "OPS_CONVERT",
           "WORD_FACTOR"]

#: Integer ops per element per CORDIC micro-rotation: two shifted
#: adds/subtracts (x', y'), the direction select, and the sigma/flip
#: bookkeeping amortized across the row.
OPS_PER_MICROROTATION = 8.0

#: Gain compensation per element: the fixed-point multiply by 1/K
#: (two 16-bit partial products, shift, optional RNE round).
OPS_GAIN = 12.0

#: Packed-path converter dataflow per element per rotation: unpack,
#: exponent align, expand (hidden bit / HUB extension), renormalize,
#: saturate/pack — roughly 40 elementwise ops each way.
OPS_CONVERT = 80.0

#: Relative ALU cost of one "op" in each datapath's word representation.
WORD_FACTOR = {
    "int32": 1.0,      # blockfp: native 32-bit lanes
    "int64": 2.0,      # packed word on a 64-bit ALU (interpret / CPU)
    "lanes": 3.5,      # dual-int32 split: carries, two-case shifts, muls
}


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Peak rates the roofline divides by.

    ``peak_ops`` is the sustained *elementwise integer/vector* op rate
    (ops/s) — these kernels run shifts/adds/selects, not MXU matmuls, so
    the VPU-class number is the honest ceiling, not the headline FLOPs.
    ``hbm_bw`` is bytes/s of main-memory bandwidth.
    """

    name: str
    peak_ops: float
    hbm_bw: float


#: Keyed by `jax.devices()[0].device_kind` (lowercased prefix match).
DEVICE_SPECS = {
    # Generic host CPU: ~12 int32 lanes x ~4 GHz sustained vector ALU,
    # dual-channel DDR-class bandwidth.  Deliberately round numbers —
    # the CPU lane is interpret-mode anyway; fractions are directional.
    "cpu": DeviceSpec("cpu", peak_ops=4.8e10, hbm_bw=2.0e10),
    # TPU v5e: hbm_bw is the published 819 GB/s (Google Cloud, "TPU
    # v5e").  peak_ops is an UNSOURCED ESTIMATE of the int32 VPU rate
    # (8x128 lanes x ~940 MHz); no published figure backs it.
    "tpu v5 lite": DeviceSpec("tpu v5 lite", peak_ops=9.6e11, hbm_bw=8.19e11),
    "tpu v4": DeviceSpec("tpu v4", peak_ops=1.1e12, hbm_bw=1.2e12),
}

def device_spec(kind: str | None = None) -> DeviceSpec:
    """Resolve a `DeviceSpec` for a device kind (default: this process's
    first device).  A kind missing from `DEVICE_SPECS` is an error: a
    made-up peak would give a roofline share that means nothing."""
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    k = kind.lower()
    for prefix, spec in DEVICE_SPECS.items():
        if k.startswith(prefix):
            return spec
    raise KeyError(f"no peak figures for device kind {kind!r}; known: "
                   f"{', '.join(DEVICE_SPECS)}")


@dataclasses.dataclass(frozen=True)
class QRDCost:
    """Work of one QRD: elementwise ops and HBM bytes (per matrix)."""

    ops: float
    hbm_bytes: float

    @property
    def intensity(self) -> float:
        """Arithmetic intensity, ops per HBM byte."""
        return self.ops / self.hbm_bytes if self.hbm_bytes else float("inf")


def _datapath_terms(backend: str, iters: int, word: str | None):
    """Shared datapath constants: (weighted ops per rotated element,
    working-word itemsize in bytes) for the named backend."""
    packed = backend in ("cordic", "cordic_pallas")
    per_elem = iters * OPS_PER_MICROROTATION + OPS_GAIN
    if packed:
        per_elem += OPS_CONVERT
    if word is None:
        word = "int64" if (packed or backend == "fixed") else "int32"
    itemsize = 8 if (packed or backend == "fixed") else 4
    return per_elem * WORD_FACTOR[word], itemsize


def _active_elements(m: int, n: int, e: int) -> float:
    """Sum over the schedule of the elements both rows rotate.

    The column-major and Sameh–Kuck schedules perform the identical
    rotation set — (pivot, target, col) with 2·(e − col) live elements —
    so this is schedule-independent.
    """
    total = 0
    for col in range(min(m - 1, n)):
        total += (m - 1 - col) * 2 * (e - col)
    return float(total)


def qrd_cost(m: int, n: int, *, compute_q: bool = True, iters: int = 24,
             backend: str = "blockfp_pallas", schedule: str = "col",
             hbm_passes: float | None = None,
             word: str | None = None) -> QRDCost:
    """Analytic cost of one m x n QRD on the named datapath.

    Parameters
    ----------
    iters : int
        CORDIC micro-rotation depth (``GivensConfig.resolved_iters()``
        for the packed path, the ``iters`` knob for block-FP).
    backend : str
        ``'blockfp_pallas'`` (int32, no converter dataflow),
        ``'cordic_pallas'`` / ``'cordic'`` (packed word + converters),
        ``'fixed'`` (int64 word, no converters).
    hbm_passes : float, optional
        Override the kernel's HBM-pass contract; defaults from the
        backend (`repro.kernels.qrd_blocked.HBM_PASSES_PER_QRD` for the
        kernel-resident paths, ``2 * len(steps)`` for the host loop).
    word : str, optional
        Word representation override (`WORD_FACTOR` key); defaults from
        the backend (+ device: the packed path costs int64 emulation on
        CPU hosts and the lane split on 32-bit accelerators — callers
        who know pass it explicitly, the default stays 'int64').
    """
    e = n + (m if compute_q else 0)
    elems = _active_elements(m, n, e)
    rotations = sum(m - 1 - c for c in range(min(m - 1, n)))

    ops_per_elem, itemsize = _datapath_terms(backend, iters, word)
    ops = elems * ops_per_elem

    if hbm_passes is None:
        if backend == "cordic":          # host loop: round-trip per step
            hbm_passes = 2.0 * rotations
        else:                            # kernel-resident: in + out
            from repro.kernels.qrd_blocked import HBM_PASSES_PER_QRD
            hbm_passes = float(HBM_PASSES_PER_QRD)
    bytes_ = hbm_passes * m * e * itemsize
    bytes_ += 2.0 * m * e * 8            # float64 encode read + decode write
    return QRDCost(ops=ops, hbm_bytes=bytes_)


def panel_qrd_cost(m: int, n: int, *, compute_q: bool = True, iters: int = 24,
                   backend: str = "blockfp_pallas", panel_n: int = 8,
                   word: str | None = None) -> QRDCost:
    """Analytic cost of the tiled *panel* route (`repro.qrd.tiled`).

    The rotation set is identical to the flat schedule, but the
    dataflow differs on both roofline axes and the model must say so:

    * **ops** — every rotation spans the full ``panel_n``-wide factor
      tile (masked lanes still burn ALU slots) and the trailing region
      padded up to whole panel tiles, instead of exactly the live
      ``e − col`` suffix.
    * **bytes** — the factor tile and the trailing panels round-trip
      HBM *once per panel sweep*, so the matrix sees ≈ ``n / panel_n``
      passes where the flat kernel's contract is
      `repro.kernels.qrd_blocked.HBM_PASSES_PER_QRD` total.  This is
      the price of unbounded columns; the roofline fraction of a
      ``tiled:`` row is judged against this heavier bound, not the
      flat one.
    """
    e = n + (m if compute_q else 0)
    ops_per_elem, itemsize = _datapath_terms(backend, iters, word)
    elems = 0.0
    bytes_ = 0.0
    for c0 in range(0, min(n, m - 1), panel_n):
        nc = min(panel_n, n - c0)
        mr = m - c0
        tw = e - c0 - nc
        twp = -(-tw // panel_n) * panel_n if tw > 0 else 0
        rot = sum(mr - 1 - c for c in range(min(mr - 1, nc)))
        elems += rot * 2.0 * (nc + twp)
        bytes_ += 2.0 * mr * (nc + twp) * itemsize   # sweep in + out
    bytes_ += 2.0 * m * e * 8            # float64 encode read + decode write
    return QRDCost(ops=elems * ops_per_elem, hbm_bytes=bytes_)


def tsqr_qrd_cost(m: int, n: int, *, compute_q: bool = True, iters: int = 24,
                  backend: str = "blockfp_pallas", tile_m: int = 128,
                  panel_n: int = 8, word: str | None = None) -> QRDCost:
    """Analytic cost of the tiled *tsqr* route (`repro.qrd.tiled`).

    ``L = ceil(m / tile_m)`` leaf factorizations of ``(tile_m, n)`` plus
    ``L − 1`` tree-node factorizations of stacked ``(2n, n)`` R pairs,
    each costed on the panel model (the tiled driver runs every node
    through the panel kernels).  With Q the composition adds the float64
    einsum work — ``ceil(log2 L)`` levels of per-leaf ``(n, n)`` factor
    updates and the final ``(tile_m, n) @ (n, n)`` per leaf — plus one
    HBM round-trip of the leaf-Q stack (``L · tile_m · n`` float64
    elements held between the leaf launch and the composition).
    """
    L = -(-m // tile_m)
    leaf = panel_qrd_cost(tile_m, n, compute_q=compute_q, iters=iters,
                          backend=backend, panel_n=panel_n, word=word)
    node = panel_qrd_cost(2 * n, n, compute_q=compute_q, iters=iters,
                          backend=backend, panel_n=panel_n, word=word)
    ops = L * leaf.ops + (L - 1) * node.ops
    bytes_ = L * leaf.hbm_bytes + (L - 1) * node.hbm_bytes
    if compute_q:
        levels = max(1, (L - 1).bit_length())
        ops += levels * L * 2.0 * n ** 3         # per-level B updates
        ops += L * 2.0 * tile_m * n ** 2         # final Q_leaf @ B
        bytes_ += 2.0 * L * tile_m * n * 8       # leaf-Q stack round-trip
    return QRDCost(ops=ops, hbm_bytes=bytes_)


@dataclasses.dataclass(frozen=True)
class RooflinePoint:
    """The bound for one (cost, device) pair."""

    t_compute: float     # s per QRD at peak_ops
    t_memory: float      # s per QRD at hbm_bw

    @property
    def bound_s(self) -> float:
        return max(self.t_compute, self.t_memory)

    @property
    def bound_qrd_per_s(self) -> float:
        return 1.0 / self.bound_s

    @property
    def dominant(self) -> str:
        return "compute" if self.t_compute >= self.t_memory else "memory"


def roofline(cost: QRDCost, spec: DeviceSpec) -> RooflinePoint:
    """The achievable-rate bound: whichever of compute and memory is
    slower caps throughput (batched QRDs pipeline, so no latency term)."""
    return RooflinePoint(t_compute=cost.ops / spec.peak_ops,
                         t_memory=cost.hbm_bytes / spec.hbm_bw)


def roofline_fraction(measured_qrd_per_s: float, cost: QRDCost,
                      spec: DeviceSpec) -> float:
    """Measured rate as a fraction of the analytic bound.

    ~1.0 means the kernel saturates the modeled resource; interpret-mode
    rates land orders of magnitude below 1 (they measure the emulator,
    not the device) — which is exactly the honesty the column exists
    to enforce.
    """
    return measured_qrd_per_s / roofline(cost, spec).bound_qrd_per_s

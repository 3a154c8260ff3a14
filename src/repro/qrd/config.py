"""Unified QRD problem configuration (DESIGN.md §9).

`QRDConfig` consolidates every knob that used to be scattered across the
free functions — ``steps``/``stages`` schedule selection, the blockfp
``iters``/``hub``/``frac`` trio, the ``fixed_*`` baseline parameters —
plus an optional sharding ``mesh`` so the batch-sharded path
(`qr_blocked_sharded`) folds into plain ``engine(A)`` dispatch.

The config is a frozen dataclass: hashable (it participates in the
engine's jitted-callable cache key) except for ``mesh``, which is
excluded from equality/hash and keyed by identity instead (meshes are
runtime placement, not arithmetic).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any

import jax.numpy as jnp

from repro.core.formats import FloatFormat
from repro.core.givens import GivensConfig

__all__ = ["QRDConfig"]


@dataclasses.dataclass(frozen=True)
class QRDConfig:
    """Everything a QRD problem dispatch depends on.

    Parameters
    ----------
    backend : str
        A registered backend name (`repro.qrd.registry.available_backends`).
    schedule : str
        ``'col'`` (column-major) or ``'sameh_kuck'`` (parallel pairing);
        backends with the ``wavefront`` capability route ``'sameh_kuck'``
        onto the stage-parallel datapath (DESIGN.md §8).
    givens : GivensConfig
        Unit parameters for the cordic family; ``'blockfp_pallas'``
        derives its defaults (``hub``, iteration count) from it.
    iters, hub, frac : optional overrides for the block-FP kernel
        ``None`` resolves from ``givens`` (``resolved_iters()`` /
        ``givens.hub``); ``frac`` is the fraction-bit count F of the int32
        significands (F=24 keeps m ≲ 64 inside int32).
    fixed_width, fixed_iters, fixed_scale_exp : int
        Parameters of the ``'fixed'`` baseline rotator of [20].
    dtype : str or dtype-like
        Element dtype of the problem.  Real dtypes select the real
        datapath (output dtype for the float backends ``'jnp'`` /
        ``'givens_float'``; the bit-accurate backends always return
        float64).  Complex dtypes (``'complex64'`` / ``'complex128'``)
        select the **complex datapath** (DESIGN.md §10) on
        complex-capable backends — three-rotation Givens on (re, im)
        lane pairs; the bit-accurate backends then return complex128
        (precision still comes from ``givens.fmt``).  Normalized to the
        canonical dtype name string on construction; requesting a
        complex dtype on a backend without complex capability raises
        ``TypeError`` at validation.
    interpret : bool, optional
        Forwarded to the Pallas kernels; ``None`` auto-selects
        (interpret on CPU, Mosaic on TPU).
    tile_b : int, optional
        Batch tile of the blocked Pallas kernels (matrices per grid
        cell).  ``None`` consults the persisted autotune cache
        (`repro.kernels.autotune.lookup`) at dispatch time and lets the
        kernel choose on a cache miss (``TILE_B`` rows-first, up to 1024
        matrices batch-minor, DESIGN.md §5); an explicit value always
        wins, rounded up to whole lane rows of 128 where the shape runs
        batch-minor.
    table_layout : str, optional
        Stage-table memory layout of the wavefront kernels: ``'split'``
        (three separate (S, Pmax) operands) or ``'stacked'`` (one
        concatenated (3S, Pmax) operand — fewer kernel parameters, one
        contiguous DMA).  ``None`` resolves from the autotune cache like
        ``tile_b``.
    tiling : str, optional
        Route selection for the tiled QR layer (DESIGN.md §14):
        ``None``/``'auto'`` picks per-shape (flat for small single-tile
        operands, panel factorization for dense m up to the backend's
        ``max_shape``, TSQR tree reduction for tall-skinny / oversized
        m); ``'flat'`` forces the single-tile path (raises a shape error
        beyond ``max_shape`` instead of failing inside the kernel);
        ``'panel'`` / ``'tsqr'`` force the respective tiled route —
        requires the backend's ``supports_tiling`` capability.
    tile_m : int, optional
        Row-block height of the TSQR leaves (and the resident row count
        cap of the panel path).  ``None`` resolves from the autotune
        cache, falling back to the backend's ``max_shape`` rows; an
        explicit value always wins.
    panel_n : int, optional
        Column width of one panel in the panel/TSQR factorization.
        ``None`` resolves from the autotune cache, falling back to the
        built-in default (8); an explicit value always wins.
    mesh : jax.sharding.Mesh, optional
        When set, the engine places the operand's leading batch axis
        across the mesh's data axes before dispatch
        (`repro.launch.sharding.shard_qrd_batch`) — requires the
        backend's ``sharding`` capability.  Excluded from hash/equality.

    Use ``dataclasses.replace(cfg, ...)`` (or ``cfg.replace(...)``) to
    derive variants.
    """

    backend: str = "jnp"
    schedule: str = "col"
    givens: GivensConfig = dataclasses.field(default_factory=GivensConfig)
    iters: int | None = None
    hub: bool | None = None
    frac: int = 24
    fixed_width: int = 32
    fixed_iters: int = 27
    fixed_scale_exp: int = 0
    dtype: str = "float32"
    interpret: bool | None = None
    tile_b: int | None = None
    table_layout: str | None = None
    tiling: str | None = None
    tile_m: int | None = None
    panel_n: int | None = None
    mesh: Any = dataclasses.field(default=None, compare=False, repr=False)

    SCHEDULES = ("col", "sameh_kuck")
    TABLE_LAYOUTS = (None, "split", "stacked")
    TILINGS = (None, "auto", "flat", "panel", "tsqr")

    def __post_init__(self):
        # Normalize dtype-likes (jnp.complex64, np.dtype('float32'), ...) to
        # the canonical name so the frozen dataclass stays hashable and the
        # cache key is canonical.
        try:
            name = jnp.dtype(self.dtype).name
        except TypeError:
            raise TypeError(f"dtype must be a dtype or dtype name, got "
                            f"{self.dtype!r}") from None
        object.__setattr__(self, "dtype", name)

    def replace(self, **changes) -> "QRDConfig":
        return dataclasses.replace(self, **changes)

    def is_complex(self) -> bool:
        """Whether this config selects the complex datapath."""
        return jnp.dtype(self.dtype).kind == "c"

    # -- resolved block-FP parameters ----------------------------------------
    def blockfp_iters(self) -> int:
        return self.givens.resolved_iters() if self.iters is None else self.iters

    def blockfp_hub(self) -> bool:
        return self.givens.hub if self.hub is None else self.hub

    # -- declarative deployments: JSON round-trip ----------------------------
    def as_dict(self) -> dict:
        """JSON-ready dict of every *arithmetic* field.

        ``mesh`` is runtime placement, not arithmetic — it is excluded
        (exactly as it is excluded from hash/equality); reattach one on
        load with ``cfg.replace(mesh=mesh)``.  Nested `GivensConfig` /
        `FloatFormat` dataclasses recurse to plain dicts.
        """
        d = dataclasses.asdict(self)
        d.pop("mesh", None)
        return d

    def to_json(self, **json_kwargs) -> str:
        """Serialize to JSON (deterministic key order) — the declarative
        deployment format consumed by `repro.serve.presets` and
        ``launch/serve.py --config``."""
        json_kwargs.setdefault("sort_keys", True)
        return json.dumps(self.as_dict(), **json_kwargs)

    @classmethod
    def from_dict(cls, d: dict) -> "QRDConfig":
        """Inverse of `as_dict` (strict: unknown keys raise)."""
        d = dict(d)
        g = d.get("givens")
        if isinstance(g, dict):
            g = dict(g)
            fmt = g.get("fmt")
            if isinstance(fmt, dict):
                g["fmt"] = FloatFormat(**fmt)
            d["givens"] = GivensConfig(**g)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown QRDConfig field(s) {sorted(unknown)}; "
                             f"known: {sorted(known)}")
        return cls(**d)

    @classmethod
    def from_json(cls, s: str) -> "QRDConfig":
        """Inverse of `to_json`: ``QRDConfig.from_json(cfg.to_json()) == cfg``."""
        return cls.from_dict(json.loads(s))

    def cache_key(self):
        """Hashable key covering *everything* dispatch depends on.

        The frozen dataclass hash already covers the arithmetic fields;
        ``mesh`` (compare=False) is appended by identity so that engines
        re-used across meshes miss the cache instead of returning arrays
        with stale placement.
        """
        return (self, None if self.mesh is None else id(self.mesh))

    def check_compiled(self, caps, tiled: bool):
        """Refuse what does not compile on this host's accelerator.

        ``tiled`` says whether the request needs the tiled layer.  Raises
        ``NotImplementedError`` for a complex config on a TPU, and for an
        interpret-only feature of the backend (`BackendCapabilities.
        interpret_only`) when its kernels would be compiled.
        """
        from repro.kernels import ops
        if self.is_complex():
            ops.check_complex_compiles(f"dtype={self.dtype!r}")
        if ops.auto_interpret(self.interpret):
            return
        for feature, wanted, instead in (
                ("complex", self.is_complex(), "the 'cordic' backend"),
                ("tiling", tiled, "'blockfp_pallas' for tiled shapes")):
            if wanted and feature in caps.interpret_only:
                raise NotImplementedError(
                    f"backend {self.backend!r} has no compiled {feature} "
                    "datapath: its packed words are int64, which no Pallas "
                    f"compiler lowers (interpret mode only); use {instead}")

    def validate(self):
        """Early validation against the registry's capability metadata."""
        from . import registry
        spec = registry.get_backend(self.backend)  # raises w/ available set
        caps = spec.capabilities
        if self.schedule not in self.SCHEDULES:
            raise ValueError(f"unknown schedule {self.schedule!r}; "
                             f"expected one of {self.SCHEDULES}")
        if self.table_layout not in self.TABLE_LAYOUTS:
            raise ValueError(
                f"unknown table_layout {self.table_layout!r}; "
                f"expected one of {self.TABLE_LAYOUTS}")
        if self.tile_b is not None and self.tile_b < 1:
            raise ValueError(f"tile_b must be >= 1, got {self.tile_b}")
        if self.tiling not in self.TILINGS:
            raise ValueError(f"unknown tiling {self.tiling!r}; "
                             f"expected one of {self.TILINGS}")
        if self.tile_m is not None and self.tile_m < 2:
            raise ValueError(f"tile_m must be >= 2, got {self.tile_m}")
        if self.panel_n is not None and self.panel_n < 1:
            raise ValueError(f"panel_n must be >= 1, got {self.panel_n}")
        if (self.tiling in ("panel", "tsqr")
                and not caps.supports_tiling):
            tiled = [n for n, c in registry.list_backends().items()
                     if c.supports_tiling]
            raise ValueError(
                f"backend {self.backend!r} has no tiled datapath "
                f"(tiling={self.tiling!r}); tiling-capable backends: "
                f"{', '.join(tiled)}")
        if self.schedule not in caps.schedules:
            raise ValueError(
                f"backend {self.backend!r} does not support "
                f"schedule={self.schedule!r} (supported: {caps.schedules})")
        if jnp.dtype(self.dtype).kind not in "fc":
            raise TypeError(
                f"dtype {self.dtype!r} is not a floating or complex dtype; "
                "QRD backends operate on real or complex matrices")
        if self.is_complex() and not caps.supports_complex:
            raise TypeError(
                f"backend {self.backend!r} has no complex datapath "
                f"(dtype={self.dtype!r}); complex-capable backends: "
                f"{', '.join(registry.complex_capable_backends())}")
        self.check_compiled(caps, self.tiling in ("panel", "tsqr"))
        if self.mesh is not None and not caps.sharding:
            capable = [n for n, c in registry.list_backends().items()
                       if c.sharding]
            raise ValueError(
                f"backend {self.backend!r} has no sharding capability; "
                f"mesh dispatch is available on: {', '.join(capable)}")
        if caps.bit_exact:
            self.givens.validate()
        return spec

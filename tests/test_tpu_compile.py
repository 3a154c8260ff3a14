"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached: these tests catch what interpret mode hides —
64-bit values in a kernel, primitives Mosaic cannot lower, unaligned
blocks — with no chip.  Each compiles one kernel program at deployment
shape (a couple of seconds) and checks that Mosaic, not the interpreter,
produced it (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module-scoped fixture: only the worker
that runs this file loads the TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import qrd as q
from repro.core.givens import GivensConfig
from repro.kernels import ops
from repro.kernels import qrd_blocked as qb

B = 4096


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _flat(tables, dp, export=False, batch_minor=False):
    def fn(*tiles):
        return qb.qr_call(tiles, tables, dp, interpret=False, export=export,
                          batch_minor=batch_minor)
    return fn


def _tables(schedule, m=8, n=8):
    if schedule == "col":
        return ops.step_tables(q.givens_schedule(m, n), m)
    return ops.stage_tables(q.sameh_kuck_schedule(m, n), m)


def test_fused_rotator(one_chip):
    x = jax.ShapeDtypeStruct((B, 16), jnp.int32, sharding=one_chip)
    _compile(lambda a, b: ops.givens_rotate_rows_fused(a, b, interpret=False),
             x, x)


@pytest.mark.parametrize("schedule", ["col", "sameh_kuck"])
def test_blockfp_8x8(one_chip, schedule):
    assert qb.batch_minor(8, 16, B, qb.BlockFPPath.itemsize)
    X = jax.ShapeDtypeStruct((8, 16, B), jnp.int32, sharding=one_chip)
    _compile(_flat(_tables(schedule), qb.BlockFPPath(24, True),
                   batch_minor=True), X)


def test_lanes_col_8x8(one_chip):
    assert qb.batch_minor(8, 16, B, qb.LanePath.itemsize)
    half = jax.ShapeDtypeStruct((8, 16, B), jnp.int32, sharding=one_chip)
    _compile(_flat(_tables("col"), qb.LanePath(GivensConfig(hub=True)),
                   batch_minor=True), half, half)


@pytest.mark.parametrize("batch", [3276, 273], ids=["slot", "prb"])
@pytest.mark.parametrize("path", ["blockfp-col", "blockfp-sameh_kuck",
                                  "lanes-col"])
def test_batch_minor_cells(one_chip, path, batch):
    """The batch-minor kernel at the MIMO cells' shapes: 8×8 with Q, a
    slot's 3276 matrices (four cells of seven lane rows) and a PRB
    call's 273 (one cell of three), named for its layout."""
    datapath, schedule = path.split("-")
    dp = (qb.BlockFPPath(24, True) if datapath == "blockfp"
          else qb.LanePath(GivensConfig(hub=True)))
    assert qb.batch_minor(8, 16, batch, dp.itemsize)
    X = jax.ShapeDtypeStruct((8, 16, batch), jnp.int32, sharding=one_chip)
    fn = _flat(_tables(schedule), dp, batch_minor=True)
    text = jax.jit(fn).lower(*[X] * (2 if datapath == "lanes" else 1)
                             ).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"givens_qr_{dp.name}_batch_minor" in text


@pytest.mark.parametrize("m,e,batch", [(64, 128, 64),    # 64x64 panel, Q
                                       (128, 33, 256)],  # 4096x32 TSQR leaf
                         ids=["panel-64x64", "tsqr-leaf-4096x32"])
def test_blockfp_panel_kernels(one_chip, m, e, batch):
    """The first panel's factor (control words exported) and its replay
    over the trailing panels — the two kernels every panel sweep runs."""
    pw = qb.TILE_B
    assert not qb.batch_minor(m, e, batch, qb.BlockFPPath.itemsize)
    piv, tgt, col = ops.panel_steps(m, pw)
    dp = qb.BlockFPPath(24, True)
    S = piv.shape[0]
    G = -(-(e - pw) // pw)
    X = jax.ShapeDtypeStruct((m, batch, pw), jnp.int32, sharding=one_chip)
    _compile(_flat((piv[:, None], tgt[:, None], col[:, None]), dp, True), X)
    T = jax.ShapeDtypeStruct((G, m, batch, pw), jnp.int32, sharding=one_chip)
    W = jax.ShapeDtypeStruct((batch, S), jnp.int32, sharding=one_chip)
    _compile(lambda t, f, g: qb.replay_call(t, piv, tgt, (f, g), dp,
                                            interpret=False), T, W, W)

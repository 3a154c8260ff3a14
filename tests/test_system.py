"""End-to-end system behaviour: train a tiny model, checkpoint, preempt,
resume, verify bit-identical continuation and loss improvement; multi-device
paths (compressed cross-pod psum, sharded train step) run in a subprocess
with fake devices so this process keeps its single real CPU device."""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduce_config
from repro.data import SyntheticLM
from repro.models import init_params, train_loss
from repro.optim import adamw_init, adamw_update
from repro.runtime import PreemptionHandler

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _train_setup():
    cfg = reduce_config(get_config("qwen3-8b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    ds = SyntheticLM(vocab=cfg.vocab, seq=32, global_batch=8, seed=1)

    @jax.jit
    def step(params, opt, batch):
        (loss, _), g = jax.value_and_grad(
            lambda p: train_loss(cfg, p, batch), has_aux=True)(params)
        params, opt = adamw_update(g, opt, params, lr=3e-3)
        return params, opt, loss

    return cfg, params, ds, step


def test_train_loss_decreases():
    cfg, params, ds, step = _train_setup()
    opt = adamw_init(params)
    losses = []
    for s in range(30):
        params, opt, loss = step(params, opt, ds.batch(s))
        losses.append(float(loss))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2
    assert np.isfinite(losses).all()


def test_preempt_checkpoint_resume_is_bit_identical(tmp_path):
    """Kill at step 7, resume from the checkpoint, reach step 12; the
    resumed trajectory must equal the uninterrupted one exactly (stateless
    data addressing + full optimizer state in the checkpoint)."""
    cfg, params0, ds, step = _train_setup()

    # uninterrupted run to step 12
    p, o = params0, adamw_init(params0)
    for s in range(12):
        p, o, _ = step(p, o, ds.batch(s))
    ref = jax.tree.leaves(p)[0]

    # interrupted run: checkpoint every 5 steps, preempt after 7
    d = str(tmp_path / "ckpt")
    mgr = CheckpointManager(d, keep=2)
    handler = PreemptionHandler(install=False)
    state = {"params": params0, "opt": adamw_init(params0)}
    for s in range(12):
        state["params"], state["opt"], _ = step(state["params"], state["opt"],
                                                ds.batch(s))
        if (s + 1) % 5 == 0:
            mgr.save_async(s + 1, state, extra={"data_step": s + 1})
        if s == 6:
            handler.trigger()
        if handler.should_stop:
            break
    mgr.wait()

    # "new process": restore latest (step 5) and continue
    template = {"params": params0, "opt": adamw_init(params0)}
    step_at, state2, extra = mgr.restore_latest(template)
    assert step_at == 5 and extra["data_step"] == 5
    p2, o2 = state2["params"], state2["opt"]
    for s in range(extra["data_step"], 12):
        p2, o2, _ = step(p2, o2, ds.batch(s))
    got = jax.tree.leaves(p2)[0]
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_multidevice_subprocess_paths():
    """Sharded train step + int8 compressed cross-pod psum on 8 fake devices."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import repro  # enables x64
from repro.optim.compress import compressed_psum

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)

# --- compressed psum over the pod axis equals the exact mean (within int8 tol)
x = jnp.arange(16, dtype=jnp.float32).reshape(2, 8) / 7.0

def f(x):
    return compressed_psum({"g": x}, "pod")["g"]

out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod", None),
                            out_specs=P("pod", None), check_vma=False))(x)
expect = jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape)
err = float(jnp.max(jnp.abs(out - expect)))
amax = float(jnp.max(jnp.abs(x)))
assert err <= amax / 127.0 + 1e-6, err

# --- sharded tiny train step compiles and runs on the 3-axis mesh
from repro.configs import get_config, reduce_config
from repro.launch.steps import build_train
from repro.configs.registry import ShapeCell
from repro.models import init_params
from repro.optim import adamw_init

cfg = reduce_config(get_config("stablelm-1.6b"))
cell = ShapeCell("tiny", "train", 32, 4)
with mesh:
    fn, args = build_train(cfg, cell, mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    batch = {"tokens": jnp.zeros((4, 32), jnp.int32)}
    p2, o2, m = fn(params, opt, batch, jnp.asarray(0, jnp.int32))
    assert np.isfinite(float(m["loss"]))
print("SUBPROCESS_OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SUBPROCESS_OK" in res.stdout, res.stdout + res.stderr

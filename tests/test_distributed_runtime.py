"""Checkpoint/restore (incl. elastic), gradient compression, and the int8
collective building block."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import checkpoint as ck
from repro.distributed.grad_compress import DeltaEFCompressor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_checkpoint_roundtrip_and_retention(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.int32)}}
    for step in (1, 2, 3, 4):
        ck.save(str(tmp_path), step, tree, extras={"seed": 7}, keep=2)
    assert ck.latest_step(str(tmp_path)) == 4
    # retention pruned old checkpoints
    kept = sorted(p.name for p in tmp_path.iterdir()
                  if p.name.startswith("step_"))
    assert len(kept) == 2
    step, restored, extras = ck.restore(str(tmp_path), like=tree)
    assert step == 4 and extras == {"seed": 7}
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(restored["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))


def test_async_checkpointer(tmp_path):
    tree = {"w": jnp.full((4, 4), 3.0)}
    acp = ck.AsyncCheckpointer(str(tmp_path))
    acp.save(10, tree)
    acp.wait()
    step, restored, _ = ck.restore(str(tmp_path), like=tree)
    assert step == 10
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


def test_checkpoint_train_resume_bit_identical(tmp_path):
    """Train 4 steps; checkpoint at 2; resume; steps 3-4 must match exactly
    (deterministic pipeline + full state in checkpoint)."""
    from repro.configs.base import get
    from repro.data.pipeline import SyntheticLM
    from repro.models import params as P
    from repro.models.model import build_model
    from repro.training.optimizer import AdamW
    from repro.training.steps import make_train_step

    cfg = get("olmo-1b").smoke
    model = build_model(cfg)
    opt = AdamW()
    pipe = SyntheticLM(cfg, seq_len=32, global_batch=2)
    step_fn = jax.jit(make_train_step(model, opt, remat="none"))

    params = P.init(model.spec, jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    for i in range(2):
        params, opt_state, _ = step_fn(params, opt_state,
                                       pipe.batch_for_step(i))
    ck.save(str(tmp_path), 2, {"params": params, "opt": opt_state})
    # continue run A
    pa, oa = params, opt_state
    for i in range(2, 4):
        pa, oa, _ = step_fn(pa, oa, pipe.batch_for_step(i))
    # restore + continue run B
    _, restored, _ = ck.restore(str(tmp_path),
                                like={"params": params, "opt": opt_state})
    pb, ob = restored["params"], restored["opt"]
    for i in range(2, 4):
        pb, ob, _ = step_fn(pb, ob, pipe.batch_for_step(i))
    for a, b in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_grad_compress_error_feedback_converges():
    """Quantized-with-EF gradient descent must reach the optimum of a
    quadratic despite 8-bit gradients (the EF-SGD guarantee)."""
    comp = DeltaEFCompressor(qdtype=jnp.int8, refresh_interval=1000)
    w_true = jnp.asarray([1.5, -2.0, 0.5])
    w = jnp.zeros(3)
    ctx = comp.init({"w": w})
    lr = 0.2
    for _ in range(120):
        g = {"w": 2.0 * (w - w_true)}
        g, ctx = comp(g, ctx)
        w = w - lr * g["w"]
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_true), atol=1e-2)


def test_grad_compress_wire_bytes():
    comp = DeltaEFCompressor(qdtype=jnp.int8)
    params = {"w": jnp.zeros((1000,))}
    assert comp.wire_bytes(params, full=False) * 4 == comp.wire_bytes(
        params, full=True)


def test_compressed_psum_int8_on_wire():
    """compressed_psum must (a) approximate the true sum, (b) lower to an
    int8 all-reduce visible in the HLO."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.grad_compress import compressed_psum

from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("d",))
def body(x):
    return compressed_psum(x[0], "d", axis_size=4)[None]
f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("d"), out_specs=P("d"),
                          check_vma=False))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 256))
got = np.asarray(f(x))
want = np.asarray(jnp.sum(x, axis=0))
err = np.max(np.abs(got - want[None]))
assert err < np.max(np.abs(want)) * 0.05 + 0.05, err
txt = f.lower(x).compile().as_text()
lines = txt.splitlines()
# both wire phases carry s8 payloads of the data size
assert any("all-to-all" in l and "s8[" in l for l in lines), "no s8 a2a"
assert any("all-gather" in l and "s8[" in l for l in lines), "no s8 ag"
# and no f32 all-reduce of the full vector sneaks in
assert not any("all-reduce" in l and "f32[256" in l for l in lines)
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr
    assert "OK" in p.stdout


def test_elastic_restore_different_device_count(tmp_path):
    """Checkpoint written logically; restore targets a different mesh."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.configs.base import get
from repro.models.model import build_model
from repro.models import params as P
from repro.distributed import checkpoint as ck
from repro.distributed.elastic import elastic_restore, choose_lm_mesh

cfg = get("olmo-1b").smoke
model = build_model(cfg)
params = P.init(model.spec, jax.random.PRNGKey(0))
ck.save({str(tmp_path)!r}, 5, params)

# restore onto 8 devices (writer was 1 device)
step, restored, mesh, _ = elastic_restore(
    {str(tmp_path)!r}, model, n_devices=8, rules=None)
assert step == 5
assert mesh.devices.size == 8
for a, b in zip(jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
# degraded counts factorize sanely
assert choose_lm_mesh(512) == ((32, 16), ("data", "model"))
assert choose_lm_mesh(384) == ((24, 16), ("data", "model"))
assert choose_lm_mesh(100) == ((25, 4), ("data", "model"))
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr
    assert "OK" in p.stdout

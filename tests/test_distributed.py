"""Multi-device tests (8 fake CPU devices in a subprocess): sharding rules,
SketchDP compressed gradients, elastic checkpoint restore across meshes.
Plus single-device parity of the compressor's sketch path."""
import numpy as np
import jax.numpy as jnp
import pytest

from _subproc import run_with_devices


def test_sketch_gradient_pallas_routing_parity():
    """The compressor's default (fused ``backend="pallas"`` builders,
    DESIGN.md §13) must produce the same sketch as the legacy sort-based
    reference path it replaced: identical (idx, val), tau bit-equal for
    priority (an order statistic) and equal up to summation-order rounding
    for adaptive threshold."""
    from repro.distributed import sketch_gradient
    rng = np.random.default_rng(0)
    g = rng.standard_normal(1 << 14).astype(np.float32)
    g[rng.random(1 << 14) < 0.5] = 0
    for method, tau_exact in (("threshold", False), ("priority", True)):
        i_p, v_p, t_p = sketch_gradient(jnp.asarray(g), 256, 7,
                                        method=method)   # default: pallas
        i_r, v_r, t_r = sketch_gradient(jnp.asarray(g), 256, 7,
                                        method=method, backend="reference")
        np.testing.assert_array_equal(np.asarray(i_p), np.asarray(i_r))
        np.testing.assert_array_equal(np.asarray(v_p), np.asarray(v_r))
        if tau_exact:
            assert float(t_p) == float(t_r)
        else:
            np.testing.assert_allclose(float(t_p), float(t_r), rtol=1e-5)


def test_param_shardings_apply():
    run_with_devices("""
import jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.configs import get_config
from repro.models import init_params
from repro.distributed import param_shardings
from repro.launch.mesh import make_debug_mesh

mesh = make_debug_mesh(2, 4)
cfg = get_config("qwen2-moe-a2.7b").reduced()
params = init_params(cfg, jax.random.PRNGKey(0))
sh = param_shardings(cfg, mesh)
placed = jax.device_put(params, sh)
# experts dim must actually shard over the 4-way model axis
moe_w = placed["groups"]["p0"]["moe"]["w_gate"]
assert len(moe_w.addressable_shards) == 8
shard_shape = moe_w.addressable_shards[0].data.shape
assert shard_shape[1] == moe_w.shape[1] // 4, (shard_shape, moe_w.shape)
# loss still computes under the mesh
from repro.models import loss_fn
import numpy as np
rng = np.random.default_rng(0)
B, S = 4, 32
batch = {"tokens": jnp.array(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
         "labels": jnp.array(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
         "mask": jnp.ones((B, S), jnp.float32)}
loss, _ = jax.jit(lambda p, b: loss_fn(cfg, p, b))(placed, batch)
assert np.isfinite(float(loss))
print("OK")
""")


def test_sketchdp_exact_when_m_covers_params():
    """With m >= n_params the sketch keeps every coordinate, so the
    compressed mean gradient equals the dense mean gradient exactly."""
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import init_params, loss_fn
from repro.distributed import make_sketchdp_grad_fn, init_ef_state

mesh = jax.make_mesh((8,), ("data",))
cfg = get_config("gemma2-2b").reduced()
params = init_params(cfg, jax.random.PRNGKey(0))
n_params = sum(x.size for x in jax.tree.leaves(params))
rng = np.random.default_rng(0)
B, S = 8, 32
batch = {"tokens": jnp.array(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
         "labels": jnp.array(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
         "mask": jnp.ones((B, S), jnp.float32)}
lfn = lambda p, b: loss_fn(cfg, p, b)
grad_fn = make_sketchdp_grad_fn(mesh, lfn, m=n_params + 64, method="threshold")
ef = init_ef_state(mesh, params)
loss, grads, ef2 = jax.jit(grad_fn)(params, batch, ef,
                                    jnp.zeros((), jnp.int32))
# dense reference
(loss_ref, _), grads_ref = jax.value_and_grad(lfn, has_aux=True)(params, batch)
# identical up to scatter-add vs all-reduce accumulation order
for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=3e-3, atol=2e-4)
assert abs(float(loss) - float(loss_ref)) < 1e-4
# error feedback must be ~zero: everything was transmitted
assert float(jnp.max(jnp.abs(ef2))) < 1e-10
print("OK exact")
""")


def test_sketchdp_compressed_training_converges():
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import init_params, loss_fn
from repro.distributed import make_sketchdp_grad_fn, init_ef_state, compression_ratio
from repro.train import adamw
from repro.data import SyntheticLM

mesh = jax.make_mesh((8,), ("data",))
cfg = get_config("gemma2-2b").reduced()
params = init_params(cfg, jax.random.PRNGKey(0))
n_params = sum(x.size for x in jax.tree.leaves(params))
m = n_params // 20   # 20x compression
assert compression_ratio(params, m) > 2.0
lfn = lambda p, b: loss_fn(cfg, p, b)
grad_fn = make_sketchdp_grad_fn(mesh, lfn, m=m, method="threshold",
                                error_feedback=True)
opt = adamw(3e-3, weight_decay=0.0)
opt_state = opt.init(params)
ef = init_ef_state(mesh, params)
data = SyntheticLM(cfg.vocab_size, 32, 16, seed=5)
fixed = data.batch_at(0)   # overfit one batch: deterministic, fast signal

@jax.jit
def step(params, opt_state, ef, batch, i):
    loss, grads, ef = grad_fn(params, batch, ef, i)
    params, opt_state, _ = opt.update(grads, opt_state, params)
    return params, opt_state, ef, loss

losses = []
for i in range(120):
    params, opt_state, ef, loss = step(params, opt_state, ef, fixed,
                                       jnp.asarray(i, jnp.int32))
    losses.append(float(loss))
assert losses[-1] < losses[0] - 1.5, (losses[0], losses[-1])
print("OK converges", losses[0], losses[-1])
""", timeout=900)


def test_elastic_checkpoint_restore_smaller_mesh(tmp_path):
    """Save on an 8-device mesh, restore on 4 devices (elastic restart)."""
    code_save = f"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import Checkpointer

mesh = jax.make_mesh((8,), ("data",))
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
x = jax.device_put(x, NamedSharding(mesh, P("data", None)))
ck = Checkpointer(r"{tmp_path}", async_save=False)
ck.save(3, {{"x": x}})
print("saved", len(x.addressable_shards))
"""
    run_with_devices(code_save, n_devices=8)
    code_restore = f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import Checkpointer

mesh = jax.make_mesh((4,), ("data",))
ck = Checkpointer(r"{tmp_path}", async_save=False)
tree_like = {{"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}}
sh = {{"x": NamedSharding(mesh, P("data", None))}}
step, restored = ck.restore(tree_like, shardings=sh)
assert step == 3
x = restored["x"]
assert len(x.addressable_shards) == 4
np.testing.assert_array_equal(np.asarray(x),
                              np.arange(64, dtype=np.float32).reshape(8, 8))
print("restored OK")
"""
    run_with_devices(code_restore, n_devices=4)

"""Multi-device distribution tests, each in a subprocess with 8 host
devices (so the main test process keeps 1 device)."""
import pytest

from util import check, run_with_devices


@pytest.mark.slow
def test_mesh_and_param_sharding_apply():
    check(run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.distributed.sharding import param_pspecs, param_shardings

cfg = configs.get_config('llama3.2-3b').reduced()
mesh = make_mesh((2, 4), ('data', 'model'))
params = M.init_params(cfg, jax.random.PRNGKey(0))
sh = param_shardings(jax.eval_shape(lambda: params), mesh, cfg)
placed = jax.device_put(params, sh)
# every leaf addressable + sharded per spec
for leaf in jax.tree.leaves(placed):
    assert leaf.sharding.mesh.devices.size == 8
print('OK')
"""))


@pytest.mark.slow
def test_pjit_train_step_on_mesh():
    check(run_with_devices("""
import jax, jax.numpy as jnp
from repro import configs
from repro.launch.mesh import make_mesh
from repro.optim import OptConfig
from repro.train import steps as S

cfg = configs.get_config('yi-6b').reduced()
mesh = make_mesh((2, 4), ('data', 'model'))
state = S.init_train_state(cfg, jax.random.PRNGKey(0))
state_sds = jax.eval_shape(lambda: state)
batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                      cfg.vocab_size)}
batch_sds = jax.eval_shape(lambda: batch)
step = S.make_train_step(cfg, mesh, OptConfig(), accum=2)
in_sh, out_sh = S.train_step_shardings(cfg, mesh, state_sds, batch_sds)
state = jax.device_put(state, in_sh[0])
batch = jax.device_put(batch, in_sh[1])
jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
new_state, metrics = jitted(state, batch)
assert bool(jnp.isfinite(metrics['loss'])), metrics
# second step: shardings stable (no recompile-triggering mismatch)
new_state, metrics = jitted(new_state, batch)
print('OK loss', float(metrics['loss']))
"""))


@pytest.mark.slow
def test_pjit_vs_single_device_loss_parity():
    check(run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.launch.mesh import make_mesh
from repro.optim import OptConfig
from repro.train import steps as S

cfg = configs.get_config('llama3.2-3b').reduced()
batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                      cfg.vocab_size)}
# single-device reference
state0 = S.init_train_state(cfg, jax.random.PRNGKey(0))
_, m_ref = jax.jit(S.make_train_step(cfg, None, OptConfig()))(state0, batch)

# 2x4 mesh
mesh = make_mesh((2, 4), ('data', 'model'))
state = S.init_train_state(cfg, jax.random.PRNGKey(0))
in_sh, out_sh = S.train_step_shardings(
    cfg, mesh, jax.eval_shape(lambda: state),
    jax.eval_shape(lambda: batch))
state = jax.device_put(state, in_sh[0])
batchp = jax.device_put(batch, in_sh[1])
step = S.make_train_step(cfg, mesh, OptConfig())
_, m = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)(state, batchp)
np.testing.assert_allclose(float(m['loss']), float(m_ref['loss']),
                           rtol=1e-3)
print('OK parity', float(m['loss']), float(m_ref['loss']))
"""))


@pytest.mark.slow
def test_compressed_psum_multi_device():
    check(run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.distributed import compression as C

mesh = Mesh(np.array(jax.devices()).reshape(8), ('data',))
xs = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

out = jax.shard_map(lambda v: C.compressed_psum(v[0], 'data'), mesh=mesh,
                in_specs=P('data'), out_specs=P())(xs)
exact = xs.mean(0)
err = float(jnp.abs(out - exact).max())
amax = float(jnp.abs(xs).max())
assert err <= amax / 127.0 + 1e-6, (err, amax)
print('OK err', err)
"""))


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    check(run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.distributed.pipeline import pipeline_forward, stage_params

mesh = make_mesh((4,), ('pipe',))
n_layers, d = 8, 16
keys = jax.random.split(jax.random.PRNGKey(0), n_layers)
layers = [{'w': jax.random.normal(k, (d, d)) * 0.2} for k in keys]

def layer_fn(p, x):
    return jnp.tanh(x @ p['w'])

def stage_fn(sp, x):
    def body(h, p):
        return layer_fn(p, h), None
    h, _ = jax.lax.scan(body, x, sp)
    return h

staged = stage_params(layers, 4)
m, mb = 8, 4
x = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d))
out = pipeline_forward(stage_fn, staged, x, mesh=mesh)

# sequential reference
ref = x
for p in layers:
    ref = layer_fn(p, ref)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=1e-5, atol=1e-5)
print('OK pipeline')
"""))


@pytest.mark.slow
def test_decode_step_on_mesh():
    check(run_with_devices("""
import jax, jax.numpy as jnp
from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.train import steps as S

cfg = configs.get_config('recurrentgemma-9b').reduced()
mesh = make_mesh((2, 4), ('data', 'model'))
params = M.init_params(cfg, jax.random.PRNGKey(0))
cache = M.init_cache(cfg, 4, 64)
in_sh = S.decode_shardings(cfg, mesh, jax.eval_shape(lambda: params),
                           jax.eval_shape(lambda: cache), 4)
params = jax.device_put(params, in_sh[0])
cache = jax.device_put(cache, in_sh[1])
step = jax.jit(S.make_decode_step(cfg, mesh), in_shardings=in_sh)
logits, cache = step(params, cache, jnp.ones((4, 1), jnp.int32),
                     jnp.int32(3))
assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
print('OK decode on mesh')
"""))


@pytest.mark.slow
def test_act_sharding_parity_two_device_mesh():
    """Sharded forward (activation policy + param shardings on a 1x2
    mesh) must match the unsharded single-device forward bit-for-bit up
    to float tolerance — the policy only annotates placement."""
    pytest.importorskip("jax")
    check(run_with_devices("""
import jax, numpy as np
from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.distributed import act_sharding
from repro.distributed.sharding import make_activation_policy, \
    param_shardings

cfg = configs.get_config('llama3.2-3b').reduced()
params = M.init_params(cfg, jax.random.PRNGKey(0))
batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                      cfg.vocab_size)}
ref, _ = jax.jit(lambda p, b: M.forward(cfg, p, b))(params, batch)

mesh = make_mesh((1, 2), ('data', 'model'))
placed = jax.device_put(params,
                        param_shardings(jax.eval_shape(lambda: params),
                                        mesh, cfg))
with act_sharding.use_policy(make_activation_policy(mesh, cfg)):
    out, _ = jax.jit(lambda p, b: M.forward(cfg, p, b))(placed, batch)
np.testing.assert_allclose(np.asarray(ref, dtype=np.float32),
                           np.asarray(out, dtype=np.float32),
                           rtol=2e-3, atol=2e-5)
print('OK act-sharding parity')
""", n_devices=2))

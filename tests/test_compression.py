"""Gradient compression tests: quantization error bounds, error-feedback
convergence parity, compressed training step."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.distributed import compression as C
from repro.optim import OptConfig
from repro.train import steps as S


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (1024,)) * 3.0
    q, s = C.quantize(x)
    err = jnp.abs(C.dequantize(q, s) - x)
    assert float(err.max()) <= float(s) * 0.5 + 1e-6   # half-step bound


def test_quantize_zero_tensor():
    q, s = C.quantize(jnp.zeros((16,)))
    np.testing.assert_array_equal(C.dequantize(q, s), np.zeros(16))


def test_error_feedback_preserves_signal():
    """Sum of applied grads + residual == sum of true grads (no leakage)."""
    key = jax.random.PRNGKey(1)
    true = [jax.random.normal(jax.random.fold_in(key, i), (256,))
            for i in range(20)]
    err = {"g": jnp.zeros((256,))}
    applied_sum = jnp.zeros((256,))
    for g in true:
        out, err = C.ef_compress({"g": g}, err)
        applied_sum = applied_sum + out["g"]
    total_true = sum(true)
    np.testing.assert_allclose(np.asarray(applied_sum + err["g"]),
                               np.asarray(total_true), rtol=1e-4, atol=1e-4)


def test_ef_convergence_parity_quadratic():
    """SGD on a quadratic: int8+EF tracks the uncompressed trajectory."""
    A = jnp.diag(jnp.linspace(0.5, 2.0, 16))
    b = jnp.ones((16,))

    def grad(w):
        return A @ w - b

    w_ref = jnp.zeros((16,))
    w_c = jnp.zeros((16,))
    err = {"w": jnp.zeros((16,))}
    lr = 0.3
    for _ in range(200):
        w_ref = w_ref - lr * grad(w_ref)
        g, err = C.ef_compress({"w": grad(w_c)}, err)
        w_c = w_c - lr * g["w"]
    sol = jnp.linalg.solve(A, b)
    assert float(jnp.linalg.norm(w_ref - sol)) < 1e-3
    assert float(jnp.linalg.norm(w_c - sol)) < 1e-2


def test_compressed_train_step_learns():
    cfg = configs.get_config("llama3.2-3b").reduced()
    state = S.init_train_state(cfg, jax.random.PRNGKey(0), compress=True)
    assert state.ef_error is not None
    step = jax.jit(S.make_train_step(
        cfg, None, OptConfig(peak_lr=5e-3, warmup_steps=2, decay_steps=30),
        compress=True))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)}
    losses = []
    for _ in range(15):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 1.0


def test_compressed_psum_single_shard_identity():
    """With axis size 1, compressed_psum == plain quantize roundtrip."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (128,))

    f = jax.shard_map(lambda v: C.compressed_psum(v, "data"), mesh=mesh,
                  in_specs=P(), out_specs=P())
    out = f(x)
    q, s = C.quantize(x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(C.dequantize(q, s)),
                               rtol=1e-6, atol=1e-6)

"""Quickstart: the FTL pipeline end to end on the paper's benchmark.

1. build the fusion group (paper steps 1+3),
2. solve the joint tiling problem (steps 2+4),
3. compare fused vs layer-per-layer traffic (the paper's headline),
4. execute the fused plan with the Pallas kernel (interpret mode on CPU)
   and check it against the jnp oracle.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ftl, hw
from repro.kernels import ref
from repro.kernels.gemm_gelu import gemm_act

MB = 1 << 20


def main() -> None:
    # --- the paper's benchmark op: H = GeLU(X @ W1) ----------------------
    m, k, n = 3072, 768, 3072
    target = hw.TPU_V5E
    print(f"ViT-MLP GEMM+GeLU: X({m}x{k}) @ W1({k}x{n}) "
          f"on {target.describe()}\n")

    fused = ftl.solve(ftl.fusion.gemm_act(m=m, k=k, n=n, fuse=True),
                      target=target)
    unfused = [ftl.solve(g, target=target)
               for g in ftl.fusion.gemm_act(m=m, k=k, n=n, fuse=False)]

    print(fused.summary())
    print()
    cmp = ftl.compare(fused, unfused)
    print("fused vs layer-per-layer:", cmp.summary())
    print()

    # --- run the fused kernel the plan drives ----------------------------
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, k), jnp.float32) * 0.1
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32) * 0.05
    bm, bn = fused.tile("M"), fused.tile("F")
    bk = fused.tile("K")
    y = gemm_act(x, w, act="gelu", block_m=bm, block_n=bn, block_k=bk,
                 vmem_limit_bytes=target.vmem_limit_bytes,
                 interpret=jax.default_backend() != "tpu")
    y_ref = ref.gemm_act(x, w, act="gelu")
    err = float(jnp.abs(y - y_ref).max())
    print(f"pallas fused kernel vs oracle: max err {err:.2e}")
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)
    print("OK")


if __name__ == "__main__":
    main()

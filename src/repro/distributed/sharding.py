"""Parameter & activation sharding rules (FSDP × TP 2-D layout).

Layout on the production mesh (DESIGN.md §6):

  * ``model`` axis (16-way): tensor parallelism — attention heads, d_ff,
    vocab, expert dim (EP) where divisible.
  * ``data`` axis (16-way): FSDP — parameters sharded on the *other*
    matrix dim; GSPMD inserts all-gather on use, reduce-scatter on grads.
  * ``pod`` axis (multi-pod): pure data parallelism — params replicated
    across pods (cross-pod traffic is grad all-reduce only), batch sharded.

Dims are sharded **only when divisible** by the axis size (`_div`): e.g.
whisper's vocab 51865 stays replicated, Hk=1 MQA kv-heads never shard over
``model`` — the FTL *sharding constraint family* (DESIGN.md §2) expressed
at the framework level.

The rule engine is name-based over the parameter pytree paths produced by
``models.model.init_params`` — stacked layer params carry a leading
period-count dim which is never sharded.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes the batch is sharded over (pod included when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape.get(a, 1)     # absent axes don't shard
    return n


def _div(mesh: Mesh, dim: int, axes) -> Any:
    """``axes`` if ``dim`` divides evenly over them, else None (replicate).

    Singleton axis tuples collapse to the bare name — identical meaning to
    GSPMD, but keeps specs comparable to hand-written ``P("data", ...)``."""
    if dim % axis_size(mesh, axes) != 0:
        return None
    if isinstance(axes, tuple) and len(axes) == 1:
        return axes[0]
    return axes


def _path_names(path) -> tuple[str, ...]:
    names = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            names.append(str(k.key))
        elif isinstance(k, jax.tree_util.GetAttrKey):  # pragma: no cover
            names.append(k.name)
    return tuple(names)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

def _param_spec(names: tuple[str, ...], shape: tuple[int, ...],
                mesh: Mesh, cfg) -> P:
    """PartitionSpec for one parameter leaf.

    ``names``: pytree path, e.g. ('layers', 'pos0', 'attn', 'wq', 'w').
    Stacked leaves have a leading period dim (never sharded) — detected by
    the 'layers'/'enc_layers' prefix.
    """
    fsdp = "data"           # FSDP axis: params replicated across pods
    tp = "model"
    stacked = names[0] in ("layers", "enc_layers")
    lead: tuple = (None,) if stacked else ()
    body = shape[1:] if stacked else shape
    leaf = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    grand = names[-3] if len(names) >= 3 else ""

    def spec(*ax):
        return P(*lead, *ax)

    # ---- embeddings / head -------------------------------------------------
    if names[0] == "embed":
        return P(_div(mesh, shape[0], tp), None)            # (V, D)
    if names[0] == "lm_head":
        if leaf == "w":
            return P(_div(mesh, shape[0], fsdp), _div(mesh, shape[1], tp))
        return P(_div(mesh, shape[0], tp))                  # bias (V,)

    # ---- norms & scalars ---------------------------------------------------
    if parent in ("ln1", "ln2", "lnx", "norm", "head_norm", "final_norm",
                  "enc_norm") or names[-1] in ("xgate", "lam", "conv_b"):
        return spec(*([None] * len(body)))
    if leaf == "conv":                                       # (K, W) depthwise
        return spec(None, _div(mesh, body[-1], tp))

    # ---- MoE ----------------------------------------------------------------
    if grand == "moe" or parent == "moe":
        if parent == "router" or grand == "router":
            return spec(*([None] * len(body)))
        if leaf in ("w1", "wg", "w2") and len(body) == 3:    # (E, D, F)/(E, F, D)
            e = body[0]
            if e % axis_size(mesh, tp) == 0:                 # expert parallel
                return spec(tp, _div(mesh, body[1], fsdp), None)
            # TP inside each expert: shard d_ff (F); FSDP on d_model (D)
            if leaf == "w2":                                 # (E, F, D)
                return spec(None, tp, _div(mesh, body[2], fsdp))
            return spec(None, _div(mesh, body[1], fsdp), tp)

    # ---- generic 2-D matrices ----------------------------------------------
    if leaf == "w" and len(body) == 2:
        d_in, d_out = body
        # contraction-side matrices (wo, w2, down, out): TP on input dim
        if parent in ("wo", "w2", "down", "out"):
            return spec(_div(mesh, d_in, tp), _div(mesh, d_out, fsdp))
        return spec(_div(mesh, d_in, fsdp), _div(mesh, d_out, tp))
    if leaf == "w" and len(body) == 3:                       # blockdiag (H,dh,dh)
        return spec(None, None, _div(mesh, body[-1], tp))
    if leaf == "b":
        return spec(*([None] * (len(body) - 1)), _div(mesh, body[-1], tp))

    # fallback: replicate
    return spec(*([None] * len(body)))


def param_pspecs(params_shape: Params, mesh: Mesh, cfg) -> Params:
    """PartitionSpec pytree matching ``params_shape`` (ShapeDtypeStructs)."""

    def one(path, leaf):
        return _param_spec(_path_names(path), tuple(leaf.shape), mesh, cfg)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def param_shardings(params_shape: Params, mesh: Mesh, cfg) -> Params:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_pspecs(params_shape, mesh, cfg))


# ---------------------------------------------------------------------------
# activation policy (plugged into distributed.act_sharding.use_policy)
# ---------------------------------------------------------------------------

def make_activation_policy(mesh: Mesh, cfg):
    """Maps semantic activation kinds to sharding constraints."""
    dp = dp_axes(mesh)
    tp = "model"

    def policy(x: jax.Array, kind: str) -> jax.Array:
        sh = x.shape
        if kind == "residual":              # (B, S, D)
            spec = P(_div(mesh, sh[0], dp), None, None)
        elif kind == "ffn_hidden":          # (B, S, F)
            spec = P(_div(mesh, sh[0], dp), None, _div(mesh, sh[2], tp))
        elif kind == "logits":              # (B, S, V)
            spec = P(_div(mesh, sh[0], dp), None, _div(mesh, sh[2], tp))
        elif kind in ("heads_q", "heads_kv"):   # (B, H, S, Dh)
            spec = P(_div(mesh, sh[0], dp), _div(mesh, sh[1], tp), None, None)
        elif kind == "kv_cache":            # (B, S, Hk, Dh): seq over model
            spec = P(_div(mesh, sh[0], dp), _div(mesh, sh[1], tp), None, None)
        elif kind == "moe_buf":             # (E, C, D)
            spec = P(_div(mesh, sh[0], tp), _div(mesh, sh[1], dp), None)
        elif kind == "moe_hidden":          # (E, C, F)
            e_sharded = sh[0] % axis_size(mesh, tp) == 0
            spec = P(_div(mesh, sh[0], tp), _div(mesh, sh[1], dp),
                     None if e_sharded else _div(mesh, sh[2], tp))
        elif kind == "moe_gbuf":            # (G, E, C, D): dispatch buffer
            # G over dp ONLY — the scatter/gather stays shard-local; the
            # expert einsum consumes it against tp-sharded expert weights
            # with no resharding (each device computes its E-shard).
            spec = P(_div(mesh, sh[0], dp), None, None, None)
        elif kind == "moe_ghidden":         # (G, E, C, F)
            e_sharded = sh[1] % axis_size(mesh, tp) == 0
            spec = P(_div(mesh, sh[0], dp), _div(mesh, sh[1], tp), None,
                     None if e_sharded else _div(mesh, sh[3], tp))
        elif kind == "moe_gout":            # (G, E, C, D): expert outputs
            # gathered across the tp expert shards exactly once, here
            spec = P(_div(mesh, sh[0], dp), None, None, None)
        elif kind == "rec_state":           # (B, W)
            spec = P(_div(mesh, sh[0], dp), _div(mesh, sh[1], tp))
        else:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    policy.mesh = mesh      # act_sharding.mesh(): per-shard Pallas kernels
    return policy


# ---------------------------------------------------------------------------
# input / cache shardings
# ---------------------------------------------------------------------------

def batch_pspecs(batch_shape: dict, mesh: Mesh) -> dict:
    """tokens (B, S) and stub-frontend embeddings shard batch over dp."""
    dp = dp_axes(mesh)
    out = {}
    for k, v in batch_shape.items():
        spec = [None] * len(v.shape)
        spec[0] = _div(mesh, v.shape[0], dp)
        out[k] = P(*spec)
    return out


def cache_pspecs(cache_shape: Params, mesh: Mesh, cfg) -> Params:
    """Decode-state shardings.

    KV caches (stacked: (L, B, S, Hk, Dh)) shard batch over dp and the
    *sequence* dim over ``model`` — kv_heads (1..16) mostly cannot shard
    16-way, sequence always can (32 k / 512 k cells).  Recurrent states
    shard their feature dim over ``model``.
    """
    dp = dp_axes(mesh)
    tp = "model"

    def one(path, leaf):
        names = _path_names(path)
        sh = leaf.shape
        stacked = names[0] == "layers"
        lead: tuple = (None,) if stacked else ()
        body = sh[1:] if stacked else sh
        leafname = names[-1]
        if leafname in ("k", "v") and len(body) == 4:      # (B, S, Hk, Dh)
            return P(*lead, _div(mesh, body[0], dp), _div(mesh, body[1], tp),
                     None, None)
        if leafname == "C" and len(body) == 4:             # (B, H, Dh, Dh)
            return P(*lead, _div(mesh, body[0], dp), None, None,
                     _div(mesh, body[3], tp))
        if leafname in ("n",) and len(body) == 3:          # (B, H, Dh)
            return P(*lead, _div(mesh, body[0], dp), None,
                     _div(mesh, body[2], tp))
        if leafname == "conv" and len(body) == 3:          # (B, K-1, W)
            return P(*lead, _div(mesh, body[0], dp), None,
                     _div(mesh, body[2], tp))
        if len(body) == 2:                                 # (B, W) rec/slstm
            return P(*lead, _div(mesh, body[0], dp),
                     _div(mesh, body[1], tp))
        if len(body) == 1:                                 # (B,) scalars/m
            return P(*lead, _div(mesh, body[0], dp))
        return P(*lead, *([None] * len(body)))

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def to_shardings(pspecs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))

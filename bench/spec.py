"""A configuration file's sizes as one hashable record.

The reference, the weights and the FLOP and byte counts all read the
model from this record, built from ``bench/configs/<name>.json`` alone.
"""
from __future__ import annotations

import dataclasses

# the configuration files' activation names
ACTS = ("silu", "gelu_pytorch_tanh")
NORMS = ("rmsnorm", "layernorm")


@dataclasses.dataclass(frozen=True)
class Spec:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    act: str
    gated: bool
    attn_bias: bool           # q, k, v and output projections
    mlp_bias: bool
    norm: str
    eps: float
    theta: float
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, c: dict) -> "Spec":
        s = cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                ff=c["intermediate_size"], vocab=c["vocab_size"],
                act=c["hidden_act"], gated=c["mlp_gated"],
                attn_bias=c["attention_bias"], mlp_bias=c["mlp_bias"],
                norm=c["norm"], eps=c["norm_eps"], theta=c["rope_theta"],
                dtype=c["torch_dtype"])
        if s.act not in ACTS or s.norm not in NORMS:
            raise ValueError(f"{c['name']}: no reference for activation "
                             f"{s.act!r} with norm {s.norm!r}")
        if s.heads % s.kv_heads:
            raise ValueError(f"{c['name']}: {s.heads} query heads do not "
                             f"group over {s.kv_heads} key/value heads")
        if c.get("tie_word_embeddings"):
            raise ValueError(f"{c['name']}: tied heads are not modelled")
        return s

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    def layer_shapes(self) -> dict[str, tuple[int, ...]]:
        """Per-layer weight shapes (without the leading layer axis)."""
        d, f = self.d, self.ff
        shapes = {
            "ln1_scale": (d,), "ln2_scale": (d,),
            "wq": (d, self.q_width), "wk": (d, self.kv_width),
            "wv": (d, self.kv_width), "wo": (self.q_width, d),
            "w1": (d, f), "w2": (f, d),
        }
        if self.gated:
            shapes["wg"] = (d, f)
        if self.norm == "layernorm":
            shapes.update(ln1_bias=(d,), ln2_bias=(d,))
        if self.attn_bias:
            shapes.update(bq=(self.q_width,), bk=(self.kv_width,),
                          bv=(self.kv_width,), bo=(d,))
        if self.mlp_bias:
            shapes.update(b1=(f,), b2=(d,))
        return shapes

    def top_shapes(self) -> dict[str, tuple[int, ...]]:
        shapes = {"embed": (self.vocab, self.d), "final_scale": (self.d,),
                  "lm_head": (self.d, self.vocab)}
        if self.norm == "layernorm":
            shapes["final_bias"] = (self.d,)
        return shapes

    def n_params(self) -> int:
        per = sum(_prod(s) for s in self.layer_shapes().values())
        return self.layers * per + sum(_prod(s) for s in
                                       self.top_shapes().values())

    def kv_bytes_per_token(self) -> int:
        """Keys and values of one position over all layers, in bf16."""
        return self.layers * 2 * self.kv_width * 2


def _prod(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n

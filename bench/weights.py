"""Weights of a dense GQA decoder (Llama form), made from the seed.

The benchmark makes the weights itself, so that the reference can make the
same numbers again, leaf by leaf and layer by layer, without taking anything
from the program under test. Every leaf of one layer comes from its own key,
``fold_in(fold_in(fold_in(run_key, rung), leaf), layer)``: a whole stacked
tree in one jitted call and one layer alone give the same values.

Values are uniform with the variance of ``N(0, 1/fan_in)`` (table: unit
variance), rounded once to the served dtype. Norm gains are stored as
offsets from 1 (zeros), the parametrisation of the served model.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

# leaf order is part of the key derivation: append, never reorder
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wi", "wg", "wo_ffn")
GLOBAL_LEAVES = ("table", "unembed", "final_norm")


@dataclass(frozen=True)
class Arch:
    """Shapes of one rung (a dense GQA decoder)."""
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int            # ids served (the traffic draws below this)
    padded_vocab: int     # rows of the table and columns of the head
    num_layers: int
    rope_theta: float
    norm_eps: float

    @property
    def params_per_layer(self) -> int:
        D, F, H, KV, hd = (self.d_model, self.d_ff, self.num_heads,
                           self.num_kv_heads, self.head_dim)
        return 2 * D * H * hd + 2 * D * KV * hd + 3 * D * F + 2 * D

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one token over every layer, in bfloat16."""
        return 2 * 2 * self.num_layers * self.num_kv_heads * self.head_dim

    @property
    def matmul_params(self) -> int:
        """Parameters that each served token multiplies (layers + head)."""
        return self.num_layers * self.params_per_layer \
            + self.d_model * self.padded_vocab


def layer_shapes(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """leaf -> (shape, fan_in); fan_in 0 marks a norm gain (zeros)."""
    D, F, H, KV, hd = a.d_model, a.d_ff, a.num_heads, a.num_kv_heads, a.head_dim
    return {"ln1": ((D,), 0), "wq": ((D, H * hd), D), "wk": ((D, KV * hd), D),
            "wv": ((D, KV * hd), D), "wo": ((H * hd, D), H * hd),
            "ln2": ((D,), 0), "wi": ((D, F), D), "wg": ((D, F), D),
            "wo_ffn": ((F, D), F)}


def global_shapes(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    return {"table": ((a.padded_vocab, a.d_model), 1),
            "unembed": ((a.d_model, a.padded_vocab), a.d_model),
            "final_norm": ((a.d_model,), 0)}


def run_key(seed: int) -> jax.Array:
    """A key from a seed of any size (PRNGKey alone keeps 32 bits)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, shape, fan_in: int, dtype) -> jax.Array:
    if fan_in == 0:
        return jnp.zeros(shape, dtype)
    half_width = math.sqrt(3.0 / fan_in)   # uniform with variance 1/fan_in
    u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    return (u * half_width).astype(dtype)


def _leaf_key(key, rung: int, name: str, leaves) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(key, rung),
                              leaves.index(name))


def layer_leaf(key, a: Arch, rung: int, name: str, layer, dtype):
    """One layer's leaf (``layer`` may be traced)."""
    shape, fan_in = layer_shapes(a)[name]
    k = jax.random.fold_in(_leaf_key(key, rung, name, LAYER_LEAVES), layer)
    return _leaf(k, shape, fan_in, dtype)


def global_leaf(key, a: Arch, rung: int, name: str, dtype):
    shape, fan_in = global_shapes(a)[name]
    return _leaf(_leaf_key(key, rung, name, GLOBAL_LEAVES), shape, fan_in,
                 dtype)


def make_tree(key, a: Arch, rung: int, dtype) -> Dict:
    """Every leaf, stacked over layers, in the served layout. Jit it: the
    layers are made one at a time (``lax.map``), so no full-size temporary
    in float32 is ever held."""
    tree = {"embed": {"table": global_leaf(key, a, rung, "table", dtype),
                      "unembed": global_leaf(key, a, rung, "unembed", dtype)},
            "final_norm": global_leaf(key, a, rung, "final_norm", dtype)}

    def one_layer(i):
        return {n: layer_leaf(key, a, rung, n, i, dtype) for n in LAYER_LEAVES}

    st = jax.lax.map(one_layer, jnp.arange(a.num_layers))
    tree["layers"] = {"ln1": st["ln1"], "ln2": st["ln2"],
                      "attn": {n: st[n] for n in ("wq", "wk", "wv", "wo")},
                      "ffn": {"wi": st["wi"], "wg": st["wg"],
                              "wo": st["wo_ffn"]}}
    return tree

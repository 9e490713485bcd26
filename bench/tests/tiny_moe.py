"""A sparse-expert decoder (the program's ``moe`` family): the Llama form's
attention, and in place of its MLP a softmax router over ``num_experts``
SwiGLU experts, of which each token takes the top ``experts_per_token``,
their gates renormalised to sum to 1.

The architecture module of ``tiny_moe.json`` (see ``harness.arch_module``),
written for the tests: it shows that a second architecture needs only a
configuration file and its module. Its reference runs every expert and
gates the outputs, so it is for toy widths only.
"""
from __future__ import annotations

import functools
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import roofline
from reference import HI, Q_BLOCK, _gap, _head, _q8, _rms, _rope
from weights import _leaf, global_leaf, run_key  # noqa: F401  (the interface)

PAGED_KERNEL = "paged_flash_decode_bkhd"
KERNELS = {PAGED_KERNEL: "paged_decode"}
# leaf order is part of the key derivation: append, never reorder
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "router", "wi", "wg",
                "wo_ffn")
GLOBAL_LEAVES = ("table", "unembed", "final_norm")


@dataclass(frozen=True)
class Arch:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int                 # one expert's width
    num_experts: int
    experts_per_token: int
    vocab: int
    padded_vocab: int
    num_layers: int
    rope_theta: float
    norm_eps: float
    kv_itemsize: int

    @property
    def active_matmul_params(self) -> int:
        """Weights one token multiplies: attention, router, the experts it
        is sent to, norms, and the head."""
        D, F, H, KV, hd = (self.d_model, self.d_ff, self.num_heads,
                           self.num_kv_heads, self.head_dim)
        per_layer = (2 * D * H * hd + 2 * D * KV * hd + D * self.num_experts
                     + self.experts_per_token * 3 * D * F + 2 * D)
        return self.num_layers * per_layer + D * self.padded_vocab


def arch_of(cfg: Dict, num_layers: int) -> Arch:
    m = cfg["model"]
    return Arch(d_model=m["d_model"], num_heads=m["num_heads"],
                num_kv_heads=m["num_kv_heads"], head_dim=m["head_dim"],
                d_ff=m["d_ff"], num_experts=m["num_experts"],
                experts_per_token=m["experts_per_token"],
                vocab=m["vocab_size"], padded_vocab=cfg["padded_vocab"],
                num_layers=num_layers, rope_theta=m["rope_theta"],
                norm_eps=m["norm_eps"],
                kv_itemsize=jnp.dtype(m["dtype"]).itemsize)


def layer_shapes(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """leaf -> (shape, fan_in); fan_in 0 marks a norm gain (zeros)."""
    D, F, H, KV, hd, E = (a.d_model, a.d_ff, a.num_heads, a.num_kv_heads,
                          a.head_dim, a.num_experts)
    return {"ln1": ((D,), 0), "wq": ((D, H * hd), D), "wk": ((D, KV * hd), D),
            "wv": ((D, KV * hd), D), "wo": ((H * hd, D), H * hd),
            "ln2": ((D,), 0), "router": ((D, E), D), "wi": ((E, D, F), D),
            "wg": ((E, D, F), D), "wo_ffn": ((E, F, D), F)}


def layer_leaf(key, a: Arch, rung: int, name: str, layer, dtype):
    shape, fan_in = layer_shapes(a)[name]
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        key, rung), LAYER_LEAVES.index(name)), layer)
    return _leaf(k, shape, fan_in, dtype)


def make_tree(key, a: Arch, rung: int, dtype) -> Dict:
    tree = {"embed": {"table": global_leaf(key, a, rung, "table", dtype),
                      "unembed": global_leaf(key, a, rung, "unembed", dtype)},
            "final_norm": global_leaf(key, a, rung, "final_norm", dtype)}

    def one_layer(i):
        return {n: layer_leaf(key, a, rung, n, i, dtype) for n in LAYER_LEAVES}

    st = jax.lax.map(one_layer, jnp.arange(a.num_layers))
    tree["layers"] = {"ln1": st["ln1"], "ln2": st["ln2"],
                      "attn": {n: st[n] for n in ("wq", "wk", "wv", "wo")},
                      "ffn": {"router": st["router"], "wi": st["wi"],
                              "wg": st["wg"], "wo": st["wo_ffn"]}}
    return tree


def kv_bytes_per_token(a: Arch) -> int:
    return 2 * a.kv_itemsize * a.num_layers * a.num_kv_heads * a.head_dim


def step_work(a: Arch, step) -> Tuple[float, Dict[str, Tuple[float, float]]]:
    """A token multiplies the weights of the ``experts_per_token`` experts
    it is sent to, not all of them; attention as in the Llama form."""
    model = 0.0
    for lo, hi in step.useful:
        for p in range(lo, hi):
            model += roofline.token_flops(a.active_matmul_params,
                                          a.num_layers, a.num_heads,
                                          a.head_dim, p)
    flops = bytes_ = 0.0
    for c in range(step.calls):
        f, b = roofline.paged_decode_call([s + c + 1 for s in step.starts],
                                          a.num_heads, a.num_kv_heads,
                                          a.head_dim)
        flops += f * a.num_layers
        bytes_ += b * a.num_layers
    return model, {PAGED_KERNEL: (flops, bytes_)}


# ------------------------------------------------------------ the reference
def _mm(eq: str, x, w, mode: str):
    if mode == "fp8":
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.einsum(eq, x, w, precision=HI)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _layer(h, w, a: Arch, mode: str):
    """One decoder layer over one sequence h (S, D), every expert run."""
    S = h.shape[0]
    H, KV, hd = a.num_heads, a.num_kv_heads, a.head_dim
    pos = jnp.arange(S)
    x = _rms(h, w["ln1"], a.norm_eps)
    q = _rope(_mm("sd,de->se", x, w["wq"], mode).reshape(S, H, hd), pos,
              a.rope_theta)
    k = _rope(_mm("sd,de->se", x, w["wk"], mode).reshape(S, KV, hd), pos,
              a.rope_theta)
    v = _mm("sd,de->se", x, w["wv"], mode).reshape(S, KV, hd)
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, KV, H // KV, hd)

    def block(inp):
        qb, i = inp
        s = jnp.einsum("qkgh,tkh->kgqt", qb, k, precision=HI) / np.sqrt(hd)
        qi = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= qi[:, None], s, -jnp.inf)
        return jnp.einsum("kgqt,tkh->qkgh", jax.nn.softmax(s, -1), v,
                          precision=HI)

    o = jax.lax.map(block, (qg, jnp.arange(S // Q_BLOCK))).reshape(S, H * hd)
    h = h + _mm("se,ed->sd", o, w["wo"], mode)
    x = _rms(h, w["ln2"], a.norm_eps)
    probs = jax.nn.softmax(_mm("sd,de->se", x, w["router"], mode), -1)
    top, idx = jax.lax.top_k(probs, a.experts_per_token)
    gate = jnp.zeros_like(probs).at[jnp.arange(S)[:, None], idx].set(
        top / jnp.sum(top, -1, keepdims=True))
    m = jax.nn.silu(_mm("sd,edf->sef", x, w["wg"], mode)) \
        * _mm("sd,edf->sef", x, w["wi"], mode)
    y = _mm("sef,efd->sed", m, w["wo_ffn"], mode)
    return h + jnp.einsum("sed,se->sd", y, gate, precision=HI)


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def layer_weights(key, i, a: Arch, rung, dtype: str):
    return {n: layer_leaf(key, a, rung, n, i, dtype).astype(jnp.float32)
            for n in LAYER_LEAVES}


def logits(a: Arch, key, rung: int, dtype: str, seqs: Sequence[np.ndarray],
           rows: Sequence[np.ndarray], mode: str, max_len: int,
           max_rows: int) -> List[jax.Array]:
    """Logits at ``rows[i]`` of each sequence ``seqs[i]``, padded as the
    Llama form's reference pads them."""
    S = -(-max([max_len] + [len(s) for s in seqs]) // Q_BLOCK) * Q_BLOCK
    t0 = time.perf_counter()
    g = {n: global_leaf(key, a, rung, n, dtype).astype(jnp.float32)
         for n in GLOBAL_LEAVES}
    hs = []
    for s in seqs:
        toks = np.zeros((S,), np.int32)
        toks[:len(s)] = s
        hs.append(jnp.take(g["table"], jnp.asarray(toks), axis=0))
    for i in range(a.num_layers):
        w = layer_weights(key, jnp.int32(i), a, rung, dtype)
        hs = [_layer(h, w, a, mode) for h in hs]
    R = -(-max([max_rows] + [len(r) for r in rows]) // Q_BLOCK) * Q_BLOCK
    out = []
    for h, r in zip(hs, rows):
        pos = np.zeros((R,), np.int32)
        pos[:len(r)] = r
        out.append(_head(h, jnp.asarray(pos), g["final_norm"], g["unembed"],
                         a, mode))
    jax.block_until_ready(out)
    print(f"reference {mode}: {a.num_layers} layers x {len(seqs)} sequences "
          f"of {S}: {time.perf_counter() - t0:.3f} s", file=sys.stderr,
          flush=True)
    return out


def served_gaps(a: Arch, key, rung: int, dtype: str,
                samples: Sequence[Tuple[np.ndarray, np.ndarray]], *,
                max_len: int, max_rows: int, mode: str = "f32"
                ) -> Dict[str, float]:
    seqs, rows, toks = [], [], []
    for prompt, served in samples:
        seq = np.concatenate([prompt, served]).astype(np.int32)
        seqs.append(seq)
        rows.append(np.arange(len(prompt) - 1, len(seq) - 1))
        toks.append(np.asarray(served, np.int32))
    kw = dict(max_len=max_len, max_rows=max_rows)
    with jax.default_matmul_precision("highest"):
        ref = logits(a, key, rung, dtype, seqs, rows, "f32", **kw)
        out = {"gap": 0.0, "tokens": int(sum(len(t) for t in toks))}
        for lg, t in zip(ref, toks):
            pad = np.zeros((lg.shape[0],), np.int32)
            pad[:len(t)] = t
            out["gap"] = max(out["gap"], float(np.asarray(
                _gap(lg, jnp.asarray(pad)))[:len(t)].max()))
        if mode == "fp8":
            low = logits(a, key, rung, dtype, seqs, rows, "fp8", **kw)
            out["control_gap"] = max(float(np.asarray(
                _gap(lg, jnp.argmax(l8, -1)))[:len(t)].max())
                for lg, l8, t in zip(ref, low, toks))
    return out

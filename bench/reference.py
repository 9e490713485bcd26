"""The plain reference: a dense GQA decoder (Llama form) in float32.

Written from the published description, not from the program under test:
RMSNorm with a gain stored as an offset from 1, rotary embeddings on split
halves, grouped-query causal attention, a SwiGLU MLP (``down(silu(gate) *
up)``), an untied head. Weights come from ``weights.py`` from the seed, one
layer at a time, so the reference fits next to nothing else on the chip.
Every matmul runs at ``Precision.HIGHEST``.

``mode="fp8"`` is the control: the same network with the inputs of every
projection and of the head rounded to float8 (e4m3, one scale per weight
tensor and one per activation row), the precision below the served bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
import time
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from weights import (Arch, GLOBAL_LEAVES, LAYER_LEAVES, global_leaf,
                     layer_leaf)

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
Q_BLOCK = 256           # query rows per attention block


def _q8(x: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 with a max-abs scale over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode):
    if mode == "fp8":
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, pos, theta):
    """x (S, n, hd); rotate the two halves."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _layer(h, w, a: Arch, mode: str):
    """One decoder layer over one sequence h (S, D)."""
    S = h.shape[0]
    H, KV, hd = a.num_heads, a.num_kv_heads, a.head_dim
    pos = jnp.arange(S)
    x = _rms(h, w["ln1"], a.norm_eps)
    q = _rope(_mm(x, w["wq"], mode).reshape(S, H, hd), pos, a.rope_theta)
    k = _rope(_mm(x, w["wk"], mode).reshape(S, KV, hd), pos, a.rope_theta)
    v = _mm(x, w["wv"], mode).reshape(S, KV, hd)
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, KV, H // KV, hd)

    def block(inp):
        qb, i = inp
        s = jnp.einsum("qkgh,tkh->kgqt", qb, k, precision=HI) / np.sqrt(hd)
        qi = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= qi[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkh->qkgh", p, v, precision=HI)

    o = jax.lax.map(block, (qg, jnp.arange(S // Q_BLOCK))).reshape(S, H * hd)
    h = h + _mm(o, w["wo"], mode)
    x = _rms(h, w["ln2"], a.norm_eps)
    m = jax.nn.silu(_mm(x, w["wg"], mode)) * _mm(x, w["wi"], mode)
    return h + _mm(m, w["wo_ffn"], mode)


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def _layer_weights(key, i, a: Arch, rung, dtype: str):
    return {n: layer_leaf(key, a, rung, n, i, dtype).astype(jnp.float32)
            for n in LAYER_LEAVES}


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def _global_weights(key, a: Arch, rung, dtype: str):
    return {n: global_leaf(key, a, rung, n, dtype).astype(jnp.float32)
            for n in GLOBAL_LEAVES}


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _head(h, rows, g, unembed, a: Arch, mode: str):
    """Logits (R, vocab) at positions ``rows`` (R,) of a sequence h (S, D)."""
    x = _rms(h[rows], g, a.norm_eps)
    return _mm(x, unembed, mode)[:, :a.vocab]


@jax.jit
def _gap(lg, toks):
    """How far below the row's best each token's logit lies."""
    return jnp.max(lg, -1) - jnp.take_along_axis(lg, toks[:, None], -1)[:, 0]


def logits(a: Arch, key, rung: int, dtype: str, seqs: Sequence[np.ndarray],
           rows: Sequence[np.ndarray], mode: str = "f32",
           max_len: int = 0, max_rows: int = 0) -> List[jax.Array]:
    """Logits at ``rows[i]`` (positions) of each sequence ``seqs[i]``; the
    first ``len(rows[i])`` rows of each result are those positions.

    Sequences are padded at the end to ``max_len`` and rows to
    ``max_rows``, both rounded up to ``Q_BLOCK`` (causal attention keeps
    padding out of every real position), so each program has one shape per
    configuration and the persistent compile cache serves every later
    run."""
    S = -(-max([max_len] + [len(s) for s in seqs]) // Q_BLOCK) * Q_BLOCK
    layer_arch = dataclasses.replace(a, num_layers=0)   # one program a layer
    t0 = time.perf_counter()
    g = jax.block_until_ready(_global_weights(key, layer_arch, rung, dtype))
    hs = []
    for s in seqs:
        toks = np.zeros((S,), np.int32)
        toks[:len(s)] = s
        hs.append(jnp.take(g["table"], jnp.asarray(toks), axis=0))
    t1 = time.perf_counter()
    for i in range(a.num_layers):
        w = _layer_weights(key, jnp.int32(i), layer_arch, rung, dtype)
        hs = [_layer(h, w, layer_arch, mode) for h in hs]
        del w
    jax.block_until_ready(hs)
    t2 = time.perf_counter()
    # the served rows of every sequence, padded to one length
    R = -(-max([max_rows] + [len(r) for r in rows]) // Q_BLOCK) * Q_BLOCK
    out = []
    for h, r in zip(hs, rows):
        pos = np.zeros((R,), np.int32)
        pos[:len(r)] = r
        out.append(_head(h, jnp.asarray(pos), g["final_norm"], g["unembed"],
                         layer_arch, mode))
    jax.block_until_ready(out)
    print(f"reference {mode}: {a.num_layers} layers x {len(seqs)} sequences "
          f"of {S}: weights and embedding {t1 - t0:.3f} s, layers "
          f"{t2 - t1:.3f} s, head {time.perf_counter() - t2:.3f} s",
          file=sys.stderr, flush=True)
    return out


def served_gaps(a: Arch, key, rung: int, dtype: str,
                samples: Sequence[Tuple[np.ndarray, np.ndarray]],
                control: bool = False, max_len: int = 0,
                max_rows: int = 0) -> Dict[str, float]:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over every served token of ``samples`` (pairs of
    prompt and served tokens). With ``control``, also the same gap for the
    token that the float8 network puts first at each of those positions.
    """
    seqs, rows, toks = [], [], []
    for prompt, served in samples:
        seq = np.concatenate([prompt, served]).astype(np.int32)
        seqs.append(seq)
        # the logit at position p predicts the token at p + 1
        rows.append(np.arange(len(prompt) - 1, len(seq) - 1))
        toks.append(np.asarray(served, np.int32))
    kw = dict(max_len=max_len, max_rows=max_rows)
    with jax.default_matmul_precision("highest"):
        ref = logits(a, key, rung, dtype, seqs, rows, **kw)
        out = {"gap": 0.0, "tokens": int(sum(len(t) for t in toks))}
        for lg, t in zip(ref, toks):
            pad = np.zeros((lg.shape[0],), np.int32)
            pad[:len(t)] = t
            g = np.asarray(_gap(lg, jnp.asarray(pad)))[:len(t)]
            out["gap"] = max(out["gap"], float(g.max()))
        if control:
            low = logits(a, key, rung, dtype, seqs, rows, mode="fp8", **kw)
            out["control_gap"] = 0.0
            for lg, l8, t in zip(ref, low, toks):
                g = np.asarray(_gap(lg, jnp.argmax(l8, -1)))[:len(t)]
                out["control_gap"] = max(out["control_gap"], float(g.max()))
    return out

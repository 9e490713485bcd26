"""Language-model assembly for all assigned architecture families.

One ``LM`` class covers dense / moe / ssm / hybrid / vlm / audio configs:
layers are parameter-stacked and executed with ``lax.scan`` (95-layer models
compile fast), caches are stacked alongside. Whisper-style encoder-decoder is
handled with a separate encoder stack + cross-attention in the decoder blocks.

Public (pure, jittable) methods:
  init(rng)                       -> params
  apply(params, batch)            -> logits (teacher forcing)
  loss(params, batch)             -> (scalar, metrics)
  init_cache(batch_size, max_len) -> cache pytree
  prefill(params, batch)          -> (last-token logits, cache)
  decode_step(params, cache, tok) -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.sharding.context import constrain_batch
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssd as ssd_mod
from repro.models.layers import (apply_mlp, embed, init_embed, init_mlp,
                                 rms_norm, sinusoidal_positions,
                                 truncated_normal_init, unembed, vocab_mask)

AUDIO_FRAME_DIM = 80     # stub frontend: mel-frame embedding width
VISION_EMBED_DIM = 1024  # stub frontend: ViT patch embedding width


def _layer_windows(cfg: ModelConfig) -> np.ndarray:
    """Per-layer attention window (0 = full attention)."""
    L = cfg.num_layers
    w = np.full((L,), cfg.sliding_window, np.int32)
    if cfg.sliding_window and cfg.global_layer_every:
        w[::cfg.global_layer_every] = 0
    return w


def _uniform_window(cfg: ModelConfig):
    """Static per-layer window if all layers share one, else None."""
    w = _layer_windows(cfg)
    return int(w[0]) if (w == w[0]).all() else None


def _layer_slice(tree, i: int):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def _stack_layers(dicts):
    if not dicts or not dicts[0]:
        return {}
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *dicts)


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.compute_dtype = jnp.dtype(cfg.dtype)
        self._vmask = vocab_mask(cfg)

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def _init_layer(self, key) -> Dict:
        cfg = self.cfg
        pd = jnp.dtype(cfg.param_dtype)
        ks = jax.random.split(key, 8)
        p: Dict = {"ln1": jnp.zeros((cfg.d_model,), pd)}
        if cfg.family in ("dense", "moe", "vlm", "audio", "hybrid"):
            p["attn"] = attn.init_attention(ks[0], cfg)
        if cfg.family in ("ssm", "hybrid"):
            p["ssm"] = ssd_mod.init_ssm(ks[1], cfg)
        if cfg.family == "hybrid":
            p["mix_scale"] = jnp.zeros((2,), pd)  # learned attn/ssm fusion
        if cfg.family == "moe":
            p["ffn"] = moe_mod.init_moe(ks[2], cfg)
            p["ln2"] = jnp.zeros((cfg.d_model,), pd)
        elif cfg.family in ("dense", "vlm", "audio", "hybrid"):
            p["ffn"] = init_mlp(ks[3], cfg)
            p["ln2"] = jnp.zeros((cfg.d_model,), pd)
        if cfg.is_encoder_decoder:
            p["xattn"] = attn.init_attention(ks[4], cfg, cross=True)
            p["lnx"] = jnp.zeros((cfg.d_model,), pd)
        return p

    def _init_encoder_layer(self, key) -> Dict:
        cfg = self.cfg
        pd = jnp.dtype(cfg.param_dtype)
        k1, k2 = jax.random.split(key)
        return {
            "ln1": jnp.zeros((cfg.d_model,), pd),
            "attn": attn.init_attention(k1, cfg),
            "ln2": jnp.zeros((cfg.d_model,), pd),
            "ffn": init_mlp(k2, cfg),
        }

    def init(self, rng) -> Dict:
        cfg = self.cfg
        pd = jnp.dtype(cfg.param_dtype)
        keys = jax.random.split(rng, 8)
        params: Dict = {"embed": init_embed(keys[0], cfg),
                        "final_norm": jnp.zeros((cfg.d_model,), pd)}
        lkeys = jax.random.split(keys[1], cfg.num_layers)
        params["layers"] = jax.vmap(self._init_layer)(lkeys)
        if cfg.is_encoder_decoder:
            ekeys = jax.random.split(keys[2], cfg.enc_layers)
            params["enc_layers"] = jax.vmap(self._init_encoder_layer)(ekeys)
            params["enc_in"] = truncated_normal_init(
                keys[3], (AUDIO_FRAME_DIM, cfg.d_model), 1.0, pd)
            params["enc_norm"] = jnp.zeros((cfg.d_model,), pd)
        if cfg.frontend == "vision_patches":
            params["vis_proj"] = truncated_normal_init(
                keys[4], (VISION_EMBED_DIM, cfg.d_model), 1.0, pd)
        return params

    # ------------------------------------------------------------------
    # decoder block (full-sequence path: train / prefill)
    # ------------------------------------------------------------------
    def _block(self, lp: Dict, x: jax.Array, positions: jax.Array,
               window, enc: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        x = constrain_batch(x)   # keep batch sharded across layer boundaries
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        if cfg.family == "ssm":
            x = x + ssd_mod.ssm_forward(cfg, lp["ssm"], h)
        elif cfg.family == "hybrid":
            a = attn.attention_forward(cfg, lp["attn"], h, positions, window)
            s = ssd_mod.ssm_forward(cfg, lp["ssm"], h)
            sc = jax.nn.sigmoid(lp["mix_scale"].astype(jnp.float32))
            x = x + (sc[0] * a.astype(jnp.float32)
                     + sc[1] * s.astype(jnp.float32)).astype(x.dtype)
        else:
            x = x + attn.attention_forward(cfg, lp["attn"], h, positions, window)
        if cfg.is_encoder_decoder and enc is not None:
            hx = rms_norm(x, lp["lnx"], cfg.norm_eps)
            x = x + attn.cross_attention(cfg, lp["xattn"], hx, enc)
        if "ffn" in lp:
            h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
            if cfg.family == "moe":
                y, metrics = moe_mod.apply_moe(cfg, lp["ffn"], h2)
                aux = metrics["aux_loss"]
            else:
                y = apply_mlp(cfg, lp["ffn"], h2)
            x = x + y
        return x, aux

    def _run_layers(self, params: Dict, x: jax.Array, positions: jax.Array,
                    enc: Optional[jax.Array], train: bool) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        if not cfg.scan_layers:
            windows = _layer_windows(cfg)
            block = self._block
            if cfg.remat and train:
                block = jax.checkpoint(block, prevent_cse=False,
                                       static_argnums=(3,))
            aux_total = jnp.zeros((), jnp.float32)
            for i in range(cfg.num_layers):
                lp = _layer_slice(params["layers"], i)
                x, aux = block(lp, x, positions, int(windows[i]), enc)
                aux_total = aux_total + aux
            return x, aux_total / cfg.num_layers
        uw = _uniform_window(cfg)
        if uw is not None:
            # uniform window -> keep it static (enables the Pallas path)
            def body(carry, lp):
                y, aux = self._block(lp, carry, positions, uw, enc)
                return y, aux
            xs = params["layers"]
        else:
            def body(carry, inp):
                lp, w = inp
                y, aux = self._block(lp, carry, positions, w, enc)
                return y, aux
            xs = (params["layers"], jnp.asarray(_layer_windows(cfg)))

        if cfg.remat and train:
            body = jax.checkpoint(body, prevent_cse=False)
        x, auxes = jax.lax.scan(body, x, xs)
        return x, jnp.mean(auxes)

    # ------------------------------------------------------------------
    # encoder (whisper)
    # ------------------------------------------------------------------
    def encode(self, params: Dict, frames: jax.Array) -> jax.Array:
        cfg = self.cfg
        dt = self.compute_dtype
        x = jnp.einsum("btf,fd->btd", frames.astype(dt), params["enc_in"].astype(dt))
        pos = jnp.asarray(sinusoidal_positions(frames.shape[1], cfg.d_model))
        x = x + pos[None].astype(dt)

        def body(carry, lp):
            h = rms_norm(carry, lp["ln1"], cfg.norm_eps)
            carry = carry + attn.bidirectional_attention(cfg, lp["attn"], h)
            h2 = rms_norm(carry, lp["ln2"], cfg.norm_eps)
            carry = carry + apply_mlp(cfg, lp["ffn"], h2)
            return carry, None

        if not cfg.scan_layers:
            for i in range(cfg.enc_layers):
                x, _ = body(x, _layer_slice(params["enc_layers"], i))
        else:
            x, _ = jax.lax.scan(body, x, params["enc_layers"])
        return rms_norm(x, params["enc_norm"], cfg.norm_eps)

    # ------------------------------------------------------------------
    # embed input sequence (handles multimodal prefixes)
    # ------------------------------------------------------------------
    def _embed_inputs(self, params: Dict, batch: Dict) -> jax.Array:
        cfg = self.cfg
        dt = self.compute_dtype
        x = embed(cfg, params["embed"], batch["tokens"], dt)
        if cfg.frontend == "vision_patches" and "patch_embeds" in batch:
            vis = jnp.einsum("bpe,ed->bpd", batch["patch_embeds"].astype(dt),
                             params["vis_proj"].astype(dt))
            x = jnp.concatenate([vis, x], axis=1)
        if cfg.rope_theta <= 0 and not cfg.is_encoder_decoder:
            pos = jnp.asarray(sinusoidal_positions(x.shape[1], cfg.d_model))
            x = x + pos[None].astype(dt)
        if cfg.is_encoder_decoder:
            pos = jnp.asarray(sinusoidal_positions(x.shape[1], cfg.d_model))
            x = x + pos[None].astype(dt)
        return x

    # ------------------------------------------------------------------
    # full-sequence forward (train)
    # ------------------------------------------------------------------
    def apply(self, params: Dict, batch: Dict, train: bool = True
              ) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        enc = None
        if cfg.is_encoder_decoder:
            enc = self.encode(params, batch["frames"])
        x = constrain_batch(self._embed_inputs(params, batch))
        B, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x, aux = self._run_layers(params, x, positions, enc, train)
        x = rms_norm(constrain_batch(x), params["final_norm"], cfg.norm_eps)
        logits = unembed(cfg, params["embed"], x)
        logits = logits + jnp.asarray(self._vmask, logits.dtype)
        return logits, aux

    def loss(self, params: Dict, batch: Dict) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        logits, aux = self.apply(params, batch, train=True)
        labels = batch["labels"]
        n_prefix = logits.shape[1] - labels.shape[1]  # multimodal prefix tokens
        logits = logits[:, n_prefix:]
        mask = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(
            constrain_batch(logits).astype(jnp.float32), axis=-1)
        nll = constrain_batch(
            -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0])
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        total = loss + cfg.aux_loss_coef * aux
        return total, {"ce_loss": loss, "aux_loss": aux}

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def cache_capacity(self, max_len: int) -> int:
        cfg = self.cfg
        if cfg.sliding_window and cfg.family != "hybrid":
            return min(max_len, cfg.sliding_window)
        if cfg.family == "hybrid" and cfg.sliding_window:
            return min(max_len, cfg.sliding_window)
        return max_len

    def init_cache(self, batch_size: int, max_len: int) -> Dict:
        cfg = self.cfg
        dt = self.compute_dtype
        L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
        cache: Dict = {"pos": jnp.zeros((batch_size,), jnp.int32)}
        if cfg.family != "ssm":
            C = self.cache_capacity(max_len)
            # (B, KV, C, hd): the decode dot's native operand layout (§Perf C)
            cache["k"] = jnp.zeros((L, batch_size, KV, C, hd), dt)
            cache["v"] = jnp.zeros((L, batch_size, KV, C, hd), dt)
        if cfg.family in ("ssm", "hybrid"):
            ch = cfg.d_inner + 2 * cfg.ssm_state
            cache["conv"] = jnp.zeros((L, batch_size, cfg.conv_width - 1, ch), dt)
            cache["ssd"] = jnp.zeros(
                (L, batch_size, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                jnp.float32)
        if cfg.is_encoder_decoder:
            cache["enc"] = jnp.zeros((batch_size, cfg.enc_seq, cfg.d_model), dt)
        return cache

    # ------------------------------------------------------------------
    # paged KV cache (shared page pool + per-slot block tables)
    # ------------------------------------------------------------------
    def supports_paged_cache(self) -> bool:
        """Paged decode covers the pure-attention KV families. SSM/hybrid
        carry non-positional state, encoder-decoder adds a cross cache, and
        sliding windows imply the ring discipline — all stay dense."""
        cfg = self.cfg
        return (cfg.family in ("dense", "moe", "vlm")
                and not cfg.is_encoder_decoder and not cfg.sliding_window)

    def supports_chunked_prefill(self) -> bool:
        """Prefill continuation needs positional KV state and the non-ring
        slot==position cache discipline — the same predicate as the paged
        cache (SSM/hybrid recurrent state can't be rebuilt chunk-at-offset;
        sliding windows make cache slots ambiguous mid-prompt)."""
        return self.supports_paged_cache()

    def init_paged_cache(self, batch_size: int, pool_pages: int,
                         page_size: int, max_pages_per_seq: int) -> Dict:
        """Pool-shaped cache pytree: ``kp``/``vp`` are the shared page pool
        ``(L, KV, pool_pages, page_size, hd)``; ``pt`` is the per-slot block
        table (all rows initially the reserved trash page 0); ``pos`` the
        per-slot next position. Pool bookkeeping (which pages are free/owned)
        lives host-side in ``repro.models.attention.PagedKVCache``."""
        cfg = self.cfg
        assert self.supports_paged_cache(), \
            f"paged KV cache unsupported for config {cfg.name!r}"
        dt = self.compute_dtype
        L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
        return {
            "pos": jnp.zeros((batch_size,), jnp.int32),
            "kp": jnp.zeros((L, KV, pool_pages, page_size, hd), dt),
            "vp": jnp.zeros((L, KV, pool_pages, page_size, hd), dt),
            "pt": jnp.zeros((batch_size, max_pages_per_seq), jnp.int32),
        }

    def paged_admit(self, cache: Dict, prefill_cache: Dict,
                    cur_tok: jax.Array, first_tok: jax.Array,
                    page_ids: jax.Array, dest_slots: jax.Array
                    ) -> Tuple[Dict, jax.Array]:
        """Scatter ``b`` right-sized prefilled rows into the page pool.

        ``prefill_cache`` comes from ``prefill(..., max_len=prompt_len)`` —
        sized to the actual arriving batch and the prompt alone, never padded
        to slot capacity. ``page_ids`` (b, max_pages_per_seq) are the full
        block-table rows the pool manager allocated to each joiner;
        ``dest_slots`` (b,) the receiving batch slots. Rows of a partially
        filled admission bucket are dropped by pointing ``dest_slots`` (and
        their ``page_ids``) out of bounds — jnp scatter ``mode="drop"`` makes
        the masking free, so one compiled executable serves any joiner count
        within the bucket. Returns (new cache, new cur_tok)."""
        kp, vp, pt, pos = cache["kp"], cache["vp"], cache["pt"], cache["pos"]
        ps = kp.shape[3]
        k_new, v_new = prefill_cache["k"], prefill_cache["v"]  # (L,b,KV,S,hd)
        L, b, KV, S, hd = k_new.shape
        pp = -(-S // ps)                       # pages holding the prompt
        pad = pp * ps - S
        if pad:
            widths = ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
            k_new = jnp.pad(k_new, widths)
            v_new = jnp.pad(v_new, widths)
        # (L, KV, b, pp, ps, hd): match the pool gather shape of the scatter
        k_new = k_new.reshape(L, b, KV, pp, ps, hd).transpose(0, 2, 1, 3, 4, 5)
        v_new = v_new.reshape(L, b, KV, pp, ps, hd).transpose(0, 2, 1, 3, 4, 5)
        prompt_pages = page_ids[:, :pp]                       # (b, pp)
        kp = kp.at[:, :, prompt_pages].set(k_new, mode="drop")
        vp = vp.at[:, :, prompt_pages].set(v_new, mode="drop")
        pt = pt.at[dest_slots].set(page_ids, mode="drop")
        pos = pos.at[dest_slots].set(prefill_cache["pos"], mode="drop")
        tok = cur_tok.at[dest_slots].set(first_tok, mode="drop")
        out = dict(cache)
        out.update(kp=kp, vp=vp, pt=pt, pos=pos)
        return out, tok

    def paged_cow_copy(self, cache: Dict, src, dst) -> Dict:
        """Copy one pool page's K/V across every layer: the device half of
        admission-time copy-on-write (DESIGN.md §Prefix sharing). A new
        request whose prompt fully matches a published boundary block gets
        that block's K/V duplicated into its own fresh page ``dst`` instead
        of re-prefilling it, because its tail prefill / decode will write
        into the block and the shared source must stay immutable. ``src``/
        ``dst`` are traced scalars — one executable serves every copy."""
        out = dict(cache)
        out["kp"] = cache["kp"].at[:, :, dst].set(cache["kp"][:, :, src])
        out["vp"] = cache["vp"].at[:, :, dst].set(cache["vp"][:, :, src])
        return out

    def paged_retire(self, cache: Dict, slot: int) -> Dict:
        """Point a retiring slot's block-table row back at the trash page and
        reset its position, so the batch row decodes harmlessly until the
        next admission (its freed pages may be re-owned immediately)."""
        out = dict(cache)
        out["pt"] = cache["pt"].at[slot].set(0)
        out["pos"] = cache["pos"].at[slot].set(0)
        return out

    # ------------------------------------------------------------------
    # prefill continuation: one chunk of prompt tokens at an offset
    # ------------------------------------------------------------------
    def _sinusoid_pe(self, positions: jax.Array) -> jax.Array:
        """Sinusoidal rows for integer ``positions`` of any shape ->
        ``positions.shape + (d_model,)`` fp32."""
        cfg = self.cfg
        half = cfg.d_model // 2
        inv = 1.0 / (10_000.0 ** (jnp.arange(half) / half))
        ang = positions[..., None].astype(jnp.float32) * inv
        pe = jnp.zeros(positions.shape + (cfg.d_model,), jnp.float32)
        return pe.at[..., 0::2].set(jnp.sin(ang)).at[..., 1::2].set(jnp.cos(ang))

    def _finish_chunk(self, x: jax.Array, params: Dict, n_valid: jax.Array
                      ) -> jax.Array:
        """Final norm + unembed at each row's last valid chunk position ->
        logits (B, V) (garbage rows where n_valid == 0)."""
        cfg = self.cfg
        B, ck = x.shape[0], x.shape[1]
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            last = jnp.clip(n_valid - 1, 0, ck - 1)
            logits = unembed(cfg, params["embed"],
                             x[jnp.arange(B), last][:, None])
            return (logits + jnp.asarray(self._vmask, logits.dtype))[:, 0]

    def _chunk_trunk(self, params: Dict, cache: Dict, tokens: jax.Array,
                     start: jax.Array, n_valid: jax.Array, *, paged: bool
                     ) -> Tuple[jax.Array, Dict]:
        """Shared transformer trunk for chunked prefill continuation and
        multi-token verification: embed the (B, ck) chunk at per-row
        absolute ``start`` offsets, run every layer writing chunk K/V into
        the dense cache (``paged=False``) or the row's block-table pages
        (``paged=True``), and return (pre-final-norm activations (B, ck, D),
        new cache with ``pos`` advanced to ``start + n_valid`` on active
        rows). Rows with ``n_valid == 0`` are inert: no writes, no advance.
        """
        cfg = self.cfg
        assert self.supports_chunked_prefill(), \
            f"chunked prefill unsupported for config {cfg.name!r}"
        dt = self.compute_dtype
        with jax.named_scope("embed"):
            x = embed(cfg, params["embed"], tokens, dt)
            if cfg.rope_theta <= 0:
                positions = (start[:, None]
                             + jnp.arange(tokens.shape[1])[None, :])
                x = x + self._sinusoid_pe(positions).astype(dt)

        if paged:
            pt = cache["pt"]

            def body(carry, inp):
                lp, kp_l, vp_l = inp
                x_in = carry
                with jax.named_scope("attn_proj"):
                    h = rms_norm(x_in, lp["ln1"], cfg.norm_eps)
                a, kp_l, vp_l = attn.paged_chunk_prefill_attention(
                    cfg, lp["attn"], h, kp_l, vp_l, pt, start, n_valid)
                x_new = x_in + a
                with jax.named_scope("mlp"):
                    h2 = rms_norm(x_new, lp["ln2"], cfg.norm_eps)
                    if cfg.family == "moe":
                        y, _ = moe_mod.apply_moe(cfg, lp["ffn"], h2)
                    else:
                        y = apply_mlp(cfg, lp["ffn"], h2)
                    return x_new + y, {"kp": kp_l, "vp": vp_l}

            if not cfg.scan_layers:
                outs = []
                for i in range(cfg.num_layers):
                    x, out = body(x, (_layer_slice(params["layers"], i),
                                      cache["kp"][i], cache["vp"][i]))
                    outs.append(out)
                new_caches = _stack_layers(outs)
            else:
                x, new_caches = jax.lax.scan(
                    body, x, (params["layers"], cache["kp"], cache["vp"]))
        else:
            def body(carry, inp):
                lp, lc = inp
                x_in = carry
                h = rms_norm(x_in, lp["ln1"], cfg.norm_eps)
                a, kc, vc = attn.chunk_prefill_attention(
                    cfg, lp["attn"], h, lc["k"], lc["v"], start, n_valid)
                x_new = x_in + a
                h2 = rms_norm(x_new, lp["ln2"], cfg.norm_eps)
                if cfg.family == "moe":
                    y, _ = moe_mod.apply_moe(cfg, lp["ffn"], h2)
                else:
                    y = apply_mlp(cfg, lp["ffn"], h2)
                return x_new + y, {"k": kc, "v": vc}

            layer_caches = {k: cache[k] for k in ("k", "v")}
            if not cfg.scan_layers:
                outs = []
                for i in range(cfg.num_layers):
                    x, out = body(x, (_layer_slice(params["layers"], i),
                                      _layer_slice(layer_caches, i)))
                    outs.append(out)
                new_caches = _stack_layers(outs)
            else:
                x, new_caches = jax.lax.scan(body, x,
                                             (params["layers"], layer_caches))
        new_cache = dict(cache)
        new_cache.update(new_caches)
        new_cache["pos"] = jnp.where(n_valid > 0, start + n_valid,
                                     cache["pos"])
        return x, new_cache

    def prefill_chunk(self, params: Dict, cache: Dict, tokens: jax.Array,
                      start: jax.Array, n_valid: jax.Array
                      ) -> Tuple[jax.Array, Dict]:
        """Continue prompt prefill by one chunk against the dense cache.

        tokens: (B, ck) int32 — each prefilling row's next chunk of prompt
        tokens, right-padded; start: (B,) absolute position of tokens[:, 0];
        n_valid: (B,) count of real tokens this chunk (0 = row inert: no
        writes, no position advance). Chunk K/V is written at cache slots
        ``start..start+n_valid`` and every chunk query attends over the
        already-cached prefix plus the chunk itself — run over the whole
        prompt in chunks this reproduces ``prefill`` exactly (greedy-parity
        tested), but interleaves with decode ticks instead of blocking them.

        Returns (logits at each row's last valid token (B, V), new cache);
        ``cache["pos"]`` advances to ``start + n_valid`` on active rows.
        """
        x, new_cache = self._chunk_trunk(params, cache, tokens, start,
                                         n_valid, paged=False)
        return self._finish_chunk(x, params, n_valid), new_cache

    def prefill_chunk_paged(self, params: Dict, cache: Dict,
                            tokens: jax.Array, start: jax.Array,
                            n_valid: jax.Array) -> Tuple[jax.Array, Dict]:
        """``prefill_chunk`` against the paged pool: chunk K/V lands in each
        row's block-table pages (the pages were allocated at admission);
        attention masks to the written prefix per query. Same contract and
        return shape as the dense form."""
        x, new_cache = self._chunk_trunk(params, cache, tokens, start,
                                         n_valid, paged=True)
        return self._finish_chunk(x, params, n_valid), new_cache

    def _verify_finish(self, x: jax.Array, params: Dict) -> jax.Array:
        """Final norm + unembed at EVERY chunk position -> greedy argmax
        (B, ck) int32. Verification needs the target model's prediction at
        each proposed position, not just the row's last valid one."""
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(cfg, params["embed"], x)
        logits = logits + jnp.asarray(self._vmask, logits.dtype)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def verify_chunk(self, params: Dict, cache: Dict, tokens: jax.Array,
                     start: jax.Array, n_valid: jax.Array
                     ) -> Tuple[jax.Array, Dict]:
        """Score a (B, k+1) proposed-token slice at per-row offsets in one
        call (speculative-decoding verify; DESIGN.md §Speculative decoding).

        ``tokens[:, 0]`` is each row's last committed token and the rest are
        draft proposals; position j's argmax is what target-only greedy
        decoding would emit after consuming ``tokens[:, :j+1]``. The chunk's
        K/V is written into the cache exactly like a prefill continuation —
        rejected positions are discarded afterwards by position rewind
        (``rollback``), which the causal validity masks make safe: stale
        slots beyond ``pos`` are never attended.

        Returns (per-position greedy argmax (B, ck) int32, new cache)."""
        x, new_cache = self._chunk_trunk(params, cache, tokens, start,
                                         n_valid, paged=False)
        return self._verify_finish(x, params), new_cache

    def verify_chunk_paged(self, params: Dict, cache: Dict,
                           tokens: jax.Array, start: jax.Array,
                           n_valid: jax.Array) -> Tuple[jax.Array, Dict]:
        """``verify_chunk`` against the paged pool; same contract."""
        x, new_cache = self._chunk_trunk(params, cache, tokens, start,
                                         n_valid, paged=True)
        return self._verify_finish(x, params), new_cache

    # ------------------------------------------------------------------
    # prefill: run the full prompt, build the cache
    # ------------------------------------------------------------------
    def prefill(self, params: Dict, batch: Dict, max_len: Optional[int] = None
                ) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        enc = None
        if cfg.is_encoder_decoder:
            enc = self.encode(params, batch["frames"])
        x = self._embed_inputs(params, batch)
        B, S = x.shape[0], x.shape[1]
        C = self.cache_capacity(max_len or S)
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        uw = _uniform_window(cfg)
        dt = self.compute_dtype
        KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim

        def body(carry, inp):
            if uw is not None:
                lp, w = inp, uw
            else:
                lp, w = inp
            x_in = carry
            out = {}
            h = rms_norm(x_in, lp["ln1"], cfg.norm_eps)
            if cfg.family == "ssm":
                y, (conv_st, ssd_st) = ssd_mod.ssm_forward(cfg, lp["ssm"], h,
                                                           return_cache=True)
                x_new = x_in + y
                out.update(conv=conv_st, ssd=ssd_st)
            elif cfg.family == "hybrid":
                a = attn.attention_forward(cfg, lp["attn"], h, positions, w)
                s, (conv_st, ssd_st) = ssd_mod.ssm_forward(cfg, lp["ssm"], h,
                                                           return_cache=True)
                sc = jax.nn.sigmoid(lp["mix_scale"].astype(jnp.float32))
                x_new = x_in + (sc[0] * a.astype(jnp.float32)
                                + sc[1] * s.astype(jnp.float32)).astype(x_in.dtype)
                out.update(conv=conv_st, ssd=ssd_st)
            else:
                x_new = x_in + attn.attention_forward(cfg, lp["attn"], h,
                                                      positions, w)
            if cfg.family != "ssm":
                # recompute K/V once for the cache (cheap relative to attn)
                hh = rms_norm(x_in, lp["ln1"], cfg.norm_eps)
                k = jnp.einsum("bsd,de->bse", hh, lp["attn"]["wk"].astype(dt))
                v = jnp.einsum("bsd,de->bse", hh, lp["attn"]["wv"].astype(dt))
                k = k.reshape(B, S, KV, hd)
                v = v.reshape(B, S, KV, hd)
                k = attn.apply_rope(k, positions, cfg.rope_theta)
                k = k.transpose(0, 2, 1, 3)        # (B, KV, S, hd)
                v = v.transpose(0, 2, 1, 3)
                kc = jnp.zeros((B, KV, C, hd), dt)
                vc = jnp.zeros((B, KV, C, hd), dt)
                if S >= C:
                    # keep last C positions, ring-aligned: slot = pos % C
                    tail_k, tail_v = k[:, :, S - C:], v[:, :, S - C:]
                    roll = (S - C) % C
                    slots = (jnp.arange(C) + roll) % C
                    kc = kc.at[:, :, slots].set(tail_k)
                    vc = vc.at[:, :, slots].set(tail_v)
                else:
                    kc = kc.at[:, :, :S].set(k)
                    vc = vc.at[:, :, :S].set(v)
                out.update(k=kc, v=vc)
            if cfg.is_encoder_decoder and enc is not None:
                hx = rms_norm(x_new, lp["lnx"], cfg.norm_eps)
                x_new = x_new + attn.cross_attention(cfg, lp["xattn"], hx, enc)
            if "ffn" in lp:
                h2 = rms_norm(x_new, lp["ln2"], cfg.norm_eps)
                if cfg.family == "moe":
                    y, _ = moe_mod.apply_moe(cfg, lp["ffn"], h2)
                else:
                    y = apply_mlp(cfg, lp["ffn"], h2)
                x_new = x_new + y
            return x_new, out

        if not cfg.scan_layers:
            windows = _layer_windows(cfg)
            outs = []
            for i in range(cfg.num_layers):
                lp = _layer_slice(params["layers"], i)
                inp = lp if uw is not None else (lp, jnp.asarray(windows[i]))
                x, out = body(x, inp)
                outs.append(out)
            layer_caches = _stack_layers(outs)
        else:
            xs = params["layers"] if uw is not None else (
                params["layers"], jnp.asarray(_layer_windows(cfg)))
            x, layer_caches = jax.lax.scan(body, x, xs)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(cfg, params["embed"], x[:, -1:])
        logits = logits + jnp.asarray(self._vmask, logits.dtype)

        cache: Dict = {"pos": jnp.full((B,), S, jnp.int32)}
        cache.update(layer_caches)
        if cfg.is_encoder_decoder:
            cache["enc"] = enc
        return logits[:, 0], cache

    # ------------------------------------------------------------------
    # one-token decode against the cache
    # ------------------------------------------------------------------
    def decode_step(self, params: Dict, cache: Dict, tokens: jax.Array
                    ) -> Tuple[jax.Array, Dict]:
        """tokens: (B,) int32 -> (logits (B, V), updated cache)."""
        cfg = self.cfg
        dt = self.compute_dtype
        pos = cache["pos"]
        x = embed(cfg, params["embed"], tokens[:, None], dt)
        if cfg.rope_theta <= 0 or cfg.is_encoder_decoder:
            # gather the true sinusoidal row for each position
            x = x + self._sinusoid_pe(pos)[:, None, :].astype(dt)
        enc = cache.get("enc")
        windows = jnp.asarray(_layer_windows(cfg))

        def body(carry, inp):
            lp, w, lc = inp
            x_in = carry
            new_lc = {}
            h = rms_norm(x_in, lp["ln1"], cfg.norm_eps)
            if cfg.family == "ssm":
                y, conv_st, ssd_st = ssd_mod.ssm_decode(cfg, lp["ssm"], h,
                                                        lc["conv"], lc["ssd"])
                x_new = x_in + y
                new_lc.update(conv=conv_st, ssd=ssd_st)
            elif cfg.family == "hybrid":
                a, kc, vc = attn.decode_attention(cfg, lp["attn"], h, lc["k"],
                                                  lc["v"], pos, w)
                s, conv_st, ssd_st = ssd_mod.ssm_decode(cfg, lp["ssm"], h,
                                                        lc["conv"], lc["ssd"])
                sc = jax.nn.sigmoid(lp["mix_scale"].astype(jnp.float32))
                x_new = x_in + (sc[0] * a.astype(jnp.float32)
                                + sc[1] * s.astype(jnp.float32)).astype(x_in.dtype)
                new_lc.update(k=kc, v=vc, conv=conv_st, ssd=ssd_st)
            else:
                a, kc, vc = attn.decode_attention(cfg, lp["attn"], h, lc["k"],
                                                  lc["v"], pos, w)
                x_new = x_in + a
                new_lc.update(k=kc, v=vc)
            if cfg.is_encoder_decoder and enc is not None:
                hx = rms_norm(x_new, lp["lnx"], cfg.norm_eps)
                x_new = x_new + attn.cross_attention(cfg, lp["xattn"], hx, enc)
            if "ffn" in lp:
                h2 = rms_norm(x_new, lp["ln2"], cfg.norm_eps)
                if cfg.family == "moe":
                    y, _ = moe_mod.apply_moe(cfg, lp["ffn"], h2)
                else:
                    y = apply_mlp(cfg, lp["ffn"], h2)
                x_new = x_new + y
            return x_new, new_lc

        layer_caches = {k: cache[k] for k in ("k", "v", "conv", "ssd")
                        if k in cache}
        if not cfg.scan_layers:
            wnp = _layer_windows(cfg)
            outs = []
            for i in range(cfg.num_layers):
                inp = (_layer_slice(params["layers"], i), jnp.asarray(wnp[i]),
                       _layer_slice(layer_caches, i))
                x, out = body(x, inp)
                outs.append(out)
            new_caches = _stack_layers(outs)
        else:
            x, new_caches = jax.lax.scan(body, x, (params["layers"], windows,
                                                   layer_caches))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(cfg, params["embed"], x)
        logits = logits + jnp.asarray(self._vmask, logits.dtype)
        new_cache = dict(cache)
        new_cache.update(new_caches)
        new_cache["pos"] = pos + 1
        return logits[:, 0], new_cache

    # ------------------------------------------------------------------
    # one-token decode against the paged pool
    # ------------------------------------------------------------------
    def decode_step_paged(self, params: Dict, cache: Dict, tokens: jax.Array,
                          *, n_pages: int) -> Tuple[jax.Array, Dict]:
        """tokens: (B,) int32 -> (logits (B, V), updated cache).

        Paged counterpart of ``decode_step``: per-layer attention runs
        against the shared page pool through each slot's block table, bounded
        by the static ``n_pages`` (the caller's live-page bucket). Per-layer
        pool leaves ride through the layer scan exactly like the dense k/v
        leaves; ``pt``/``pos`` are shared across layers (a token lands at the
        same page offset in every layer's pool)."""
        cfg = self.cfg
        assert self.supports_paged_cache(), cfg.name
        dt = self.compute_dtype
        pos = cache["pos"]
        with jax.named_scope("embed"):
            x = embed(cfg, params["embed"], tokens[:, None], dt)
            if cfg.rope_theta <= 0:
                x = x + self._sinusoid_pe(pos)[:, None, :].astype(dt)
        pt = cache["pt"]

        def body(carry, inp):
            lp, kp_l, vp_l = inp
            x_in = carry
            with jax.named_scope("attn_proj"):
                h = rms_norm(x_in, lp["ln1"], cfg.norm_eps)
            a, kp_l, vp_l = attn.paged_decode_attention(
                cfg, lp["attn"], h, kp_l, vp_l, pt, pos, n_pages=n_pages)
            x_new = x_in + a
            with jax.named_scope("mlp"):
                h2 = rms_norm(x_new, lp["ln2"], cfg.norm_eps)
                if cfg.family == "moe":
                    y, _ = moe_mod.apply_moe(cfg, lp["ffn"], h2)
                else:
                    y = apply_mlp(cfg, lp["ffn"], h2)
                x_new = x_new + y
            return x_new, {"kp": kp_l, "vp": vp_l}

        if not cfg.scan_layers:
            outs = []
            for i in range(cfg.num_layers):
                inp = (_layer_slice(params["layers"], i),
                       cache["kp"][i], cache["vp"][i])
                x, out = body(x, inp)
                outs.append(out)
            new_caches = _stack_layers(outs)
        else:
            x, new_caches = jax.lax.scan(
                body, x, (params["layers"], cache["kp"], cache["vp"]))
        with jax.named_scope("head"):
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            logits = unembed(cfg, params["embed"], x)
            logits = logits + jnp.asarray(self._vmask, logits.dtype)
        new_cache = dict(cache)
        new_cache.update(new_caches)
        new_cache["pos"] = pos + 1
        return logits[:, 0], new_cache


@functools.lru_cache(maxsize=64)
def _cached_lm(cfg: ModelConfig) -> LM:
    return LM(cfg)


def build_model(cfg: ModelConfig) -> LM:
    return _cached_lm(cfg)

"""GQA attention: train/prefill (full-sequence causal, optional sliding window)
and single-token decode against a KV cache — dense ring-buffered per-slot
caches or the paged pool (``PagedKVCache`` + ``paged_decode_attention``).

Two execution paths throughout:
  * pure-jnp einsum path (always available; oracle for the kernels)
  * Pallas path (``cfg.use_pallas``) via ``repro.kernels.ops``
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, truncated_normal_init


def init_attention(key, cfg: ModelConfig, cross: bool = False) -> Dict:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    return {
        "wq": truncated_normal_init(ks[0], (D, H * hd), 1.0, pd),
        "wk": truncated_normal_init(ks[1], (D, KV * hd), 1.0, pd),
        "wv": truncated_normal_init(ks[2], (D, KV * hd), 1.0, pd),
        "wo": truncated_normal_init(ks[3], (H * hd, D), 1.0, pd),
    }


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    return x.reshape(x.shape[:-1] + (n, hd))


def causal_mask_bias(q_len: int, kv_len: int, q_offset: int, window) -> jax.Array:
    """(q_len, kv_len) additive bias; window==0 means full causal.

    ``window`` may be a Python int or a traced scalar (per-layer windows in
    hybrid models scanned over layers).
    """
    qi = jnp.arange(q_len)[:, None] + q_offset
    kj = jnp.arange(kv_len)[None, :]
    ok = kj <= qi
    win = jnp.asarray(window)
    ok &= (kj > qi - win) | (win <= 0)
    return jnp.where(ok, 0.0, -1e9).astype(jnp.float32)


def gqa_attend(q: jax.Array, k: jax.Array, v: jax.Array, bias: Optional[jax.Array],
               softcap: float = 0.0) -> jax.Array:
    """q: (B,S,H,hd)  k,v: (B,T,KV,hd)  bias: (S,T) or (B,S,T) additive.

    Operands stay in their native dtype (bf16 in production) with fp32
    accumulation via ``preferred_element_type`` — avoids materializing fp32
    copies of the K/V cache every step (§Perf hillclimb C: −45% decode HBM
    traffic). Softmax runs in fp32; probabilities are cast back to the value
    dtype for the PV matmul (flash-attention convention). For fp32 inputs the
    math is bit-identical to the previous all-fp32 form.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                        preferred_element_type=jnp.float32) / np.sqrt(hd)
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    if bias is not None:
        if bias.ndim == 2:
            scores = scores + bias[None, None, None, :, :]
        else:
            scores = scores + bias[:, None, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, hd).astype(q.dtype)


# Above this sequence length the jnp path switches to the q-block flash form
# (never materializes the (S, S) score matrix). The Pallas kernel is used when
# cfg.use_pallas regardless.
FLASH_JNP_THRESHOLD = 2048
FLASH_JNP_BQ = 512


def flash_attend_qblocks(q: jax.Array, k: jax.Array, v: jax.Array, window,
                         softcap: float = 0.0, bq: int = FLASH_JNP_BQ,
                         q_offset: int = 0) -> jax.Array:
    """Blockwise causal attention in pure jnp: lax.scan over query blocks,
    each block attending to the full K/V with a mask. Memory is O(bq·S) per
    block instead of O(S²). (The scanned body is cost-corrected analytically
    in the dry-run roofline — see repro.analysis.roofline.)"""
    B, S, H, hd = q.shape
    pad = (-S) % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = q.shape[1] // bq
    qb = q.reshape(B, nq, bq, H, hd).transpose(1, 0, 2, 3, 4)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def block(carry, inp):
        # rematerialized in the backward pass: the (bq, S) score/prob blocks
        # are recomputed instead of stored (flash-attention backward)
        qi, idx = inp
        bias = causal_mask_bias(bq, S, idx * bq + q_offset, window)
        out = gqa_attend(qi, k, v, bias, softcap)
        return carry, out

    _, outs = jax.lax.scan(block, None, (qb, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * bq, H, hd)
    from repro.sharding.context import constrain_batch
    return constrain_batch(out[:, :S])


def attention_forward(cfg: ModelConfig, p: Dict, x: jax.Array, positions: jax.Array,
                      window: Optional[int] = None) -> jax.Array:
    """Full-sequence causal self-attention (training / prefill)."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = x.dtype
    q = _split_heads(jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt)), H, hd)
    k = _split_heads(jnp.einsum("bsd,de->bse", x, p["wk"].astype(dt)), KV, hd)
    v = _split_heads(jnp.einsum("bsd,de->bse", x, p["wv"].astype(dt)), KV, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    w = cfg.sliding_window if window is None else window
    S = x.shape[1]
    if cfg.use_pallas and isinstance(w, int):
        from repro.kernels import ops as kops
        out = kops.flash_prefill(q, k, v, window=w, softcap=cfg.attn_logit_softcap)
    elif S > FLASH_JNP_THRESHOLD:
        out = flash_attend_qblocks(q, k, v, w, cfg.attn_logit_softcap)
    else:
        bias = causal_mask_bias(S, S, 0, w)
        out = gqa_attend(q, k, v, bias, cfg.attn_logit_softcap)
    out = out.reshape(x.shape[0], x.shape[1], H * hd)
    return jnp.einsum("bse,ed->bsd", out, p["wo"].astype(dt))


def bidirectional_attention(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    """Encoder self-attention (no mask, no rope — whisper-style)."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = x.dtype
    q = _split_heads(jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt)), H, hd)
    k = _split_heads(jnp.einsum("bsd,de->bse", x, p["wk"].astype(dt)), KV, hd)
    v = _split_heads(jnp.einsum("bsd,de->bse", x, p["wv"].astype(dt)), KV, hd)
    out = gqa_attend(q, k, v, None)
    return jnp.einsum("bse,ed->bsd", out.reshape(x.shape[0], x.shape[1], H * hd),
                      p["wo"].astype(dt))


def cross_attention(cfg: ModelConfig, p: Dict, x: jax.Array, enc: jax.Array) -> jax.Array:
    """Decoder cross-attention over encoder outputs."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = x.dtype
    q = _split_heads(jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt)), H, hd)
    k = _split_heads(jnp.einsum("btd,de->bte", enc, p["wk"].astype(dt)), KV, hd)
    v = _split_heads(jnp.einsum("btd,de->bte", enc, p["wv"].astype(dt)), KV, hd)
    out = gqa_attend(q, k, v, None)
    return jnp.einsum("bse,ed->bsd", out.reshape(x.shape[0], x.shape[1], H * hd),
                      p["wo"].astype(dt))


# ---------------------------------------------------------------------------
# Decode against a KV cache (one new token)
# ---------------------------------------------------------------------------

def decode_attention(cfg: ModelConfig, p: Dict, x: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, pos: jax.Array, window: Optional[int] = None,
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, 1, D); k/v_cache: (B, KV, C, hd) where C = cache capacity.

    Cache layout is (B, KV, C, hd) — the exact operand layout of the decode
    attention dot, so no per-step relayout/transpose copy is paid (§Perf
    hillclimb C iteration 2: the (B, C, KV, hd) layout showed transpose
    buffers in the lowered IR every step).

    ``pos``: (B,) int32 absolute position of the new token. When the cache
    capacity C is smaller than the max position (sliding window) the cache is
    a ring buffer indexed by ``pos % C``.

    Returns (attn_out (B,1,D), new_k_cache, new_v_cache).
    """
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = x.dtype
    B, C = k_cache.shape[0], k_cache.shape[2]
    q = _split_heads(jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt)), H, hd)
    k = _split_heads(jnp.einsum("bsd,de->bse", x, p["wk"].astype(dt)), KV, hd)
    v = _split_heads(jnp.einsum("bsd,de->bse", x, p["wv"].astype(dt)), KV, hd)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)

    slot = (pos % C).astype(jnp.int32)                       # (B,)
    batch_idx = jnp.arange(B)
    k_cache = k_cache.astype(dt).at[batch_idx, :, slot].set(k[:, 0])
    v_cache = v_cache.astype(dt).at[batch_idx, :, slot].set(v[:, 0])

    w = cfg.sliding_window if window is None else window
    # validity of each cache slot: the absolute position stored in slot j is
    # the largest value p <= pos with p % C == j; valid iff pos - p < min(C, pos+1)
    j = jnp.arange(C)[None, :]
    stored_pos = pos[:, None] - ((pos[:, None] - j) % C)     # (B, C) abs positions
    ok = stored_pos >= 0
    ok &= stored_pos >= jnp.maximum(pos[:, None] - C + 1, 0)
    if w is not None and not (isinstance(w, int) and w == 0):
        win = jnp.asarray(w)
        ok &= (stored_pos > pos[:, None] - win) | (win <= 0)
    bias = jnp.where(ok, 0.0, -1e9).astype(jnp.float32)      # (B, C)

    qg = q.reshape(B, KV, H // KV, hd)                       # (B,KV,G,hd)
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        out = kops.flash_decode_bkchd(qg, k_cache, v_cache, bias,
                                      softcap=cfg.attn_logit_softcap)
    else:
        scores = jnp.einsum("bkgh,bkth->bkgt", qg, k_cache,
                            preferred_element_type=jnp.float32) / np.sqrt(hd)
        if cfg.attn_logit_softcap:
            c = cfg.attn_logit_softcap
            scores = jnp.tanh(scores / c) * c
        scores = scores + bias[:, None, None, :]
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgt,bkth->bkgh", probs.astype(v_cache.dtype),
                         v_cache, preferred_element_type=jnp.float32)
        out = out.astype(dt)
    out = out.reshape(B, 1, H * hd)
    attn = jnp.einsum("bse,ed->bsd", out, p["wo"].astype(dt))
    return attn, k_cache, v_cache


# ---------------------------------------------------------------------------
# Paged KV cache: pool bookkeeping + decode against block-table pages
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PrefixPlan:
    """How one admission maps onto the prefix index (``PagedKVCache.
    prefix_plan``): ``shared`` pages are mapped read-only by reference
    (refcount bumped at ``alloc``); ``cow_src`` is the page to copy into the
    admission's first fresh page when the boundary block fully matched but
    the request will write into it (the copy-on-write resolved at admission
    — see DESIGN.md §Prefix sharing); ``tail_start`` is the first sequence
    position the request must still prefill itself."""
    shared: Tuple[int, ...]
    cow_src: Optional[int]
    tail_start: int


class PagedKVCache:
    """Host-side bookkeeping for one replica's shared KV page pool.

    The device arrays (the ``(L, KV, P, page_size, hd)`` pool leaves and the
    per-slot block table) live in the engine's cache pytree; this object
    tracks which pool pages are free and which slot maps which pages, so
    admission can be gated on *memory-true* capacity and retirement returns
    pages for reuse.

    Page 0 is reserved as the **trash page**: block-table rows of free slots
    point at it, so decode-step writes from dead batch rows land somewhere
    harmless instead of corrupting a live sequence's pages. ``alloc`` never
    hands it out and ``usable_pages`` excludes it.

    **Prefix sharing** (DESIGN.md §Prefix sharing): pages carry refcounts,
    and a prefix index maps the rolling hash of each ``page_size``-token
    prompt block chain to the live page holding that block's K/V. A new
    request's admission asks ``prefix_plan`` which existing pages cover its
    prompt: fully-covered blocks below every position the request will write
    are mapped read-only (``alloc(..., shared=...)`` bumps their refcount);
    a fully-matched boundary block that the request *will* write into is
    copied into a fresh page (copy-on-write, resolved at admission — after
    admission a request only ever appends at ``pos // page_size``, so shared
    pages are never written). ``free`` decrements refcounts; when the last
    holder lets go a *published* page parks on the LRU **retained tier**
    with its index entry intact (so identical prompts keep hitting across
    quiet gaps) while unpublished pages return to the free list. Retained
    pages are reclaimed — index entries invalidated — only when ``alloc``
    actually needs them, oldest first. Index entries are published by the
    owner once the block's K/V is fully written (``publish_prefix``),
    never before, so a sharer can never gather unwritten pages.

    **Speculative rollback** (DESIGN.md §Speculative decoding):
    ``rollback(slot, new_len)`` validates a position rewind that discards
    rejected draft tokens' KV — no pages move (slots hold their budget
    all-or-nothing), it asserts the rewind stays inside the slot's budget
    and never rejects positions covered by a published prefix block.

    Invariants (property-tested in ``tests/test_kernels_paged.py`` and the
    stateful harness in ``tests/test_paged_prefix.py``): every usable page
    is either free or refcounted ≥ 1 by the slots mapping it; ``alloc`` is
    all-or-nothing; double-``alloc`` on a live slot and ``free`` of a
    never-admitted slot are errors, not silent corruption; index entries
    always point at live pages. See ``assert_invariants``.
    """

    TRASH_PAGE = 0

    def __init__(self, total_pages: int, page_size: int, metrics=None):
        assert total_pages >= 2, "need at least one usable page + trash"
        assert page_size >= 1
        self.total_pages = total_pages
        self.page_size = page_size
        # registry hook (repro.obs): pool telemetry counters are mirrored
        # into the engine-wide registry at the increment site, so
        # kv_pool_stats / benchmarks read them there even after this pool's
        # backend retires. Defaults to the shared no-op registry.
        if metrics is None:
            from repro.obs.registry import NULL_REGISTRY
            metrics = NULL_REGISTRY
        self.metrics = metrics
        # LIFO free list: recently freed pages are reused first (their pool
        # rows are warm in cache)
        self._free: List[int] = list(range(total_pages - 1, 0, -1))
        self._owned: Dict[int, List[int]] = {}     # slot -> mapped page ids
        self._ref: Dict[int, int] = {}             # page -> slots mapping it
        self._index: Dict[bytes, int] = {}         # block-chain digest -> page
        self._page_key: Dict[int, bytes] = {}      # published page -> digest
        # retained-prefix tier (DESIGN.md §Prefix sharing): refcount-0
        # *published* pages park here LRU-ordered (oldest first) with their
        # index entries intact, so a later identical prompt still hits even
        # after every sharer retired. Reclaimed (index invalidated) only
        # when alloc actually needs the pages.
        self._retained: List[int] = []
        # sharing telemetry (surfaced via kv_pool_stats()/summarize and the
        # prefix_sharing bench): lookups/hits at admission, fresh pages
        # actually allocated vs the worst-case budget callers reserved
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.fresh_pages_allocated = 0
        self.shared_page_maps = 0

    @property
    def usable_pages(self) -> int:
        return self.total_pages - 1                # page 0 is the trash page

    @property
    def free_pages(self) -> int:
        """Pages alloc can satisfy a fresh request from: the free list plus
        the retained tier (retained pages are reclaimed on demand)."""
        return len(self._free) + len(self._retained)

    @property
    def used_pages(self) -> int:
        """Pages mapped by live slots (excludes free and retained)."""
        return self.usable_pages - self.free_pages

    @property
    def retained_pages(self) -> int:
        """Refcount-0 prefix pages kept live for future hits."""
        return len(self._retained)

    @property
    def shared_pages(self) -> int:
        """Pages currently mapped by more than one slot."""
        return sum(1 for c in self._ref.values() if c > 1)

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_hits / max(self.prefix_lookups, 1)

    @property
    def occupancy(self) -> float:
        """Fraction of usable pool pages currently mapped by live slots."""
        return self.used_pages / max(self.usable_pages, 1)

    def pages_needed(self, tokens: int) -> int:
        return -(-max(tokens, 0) // self.page_size)

    def can_alloc(self, n: int) -> bool:
        return n <= self.free_pages

    def _reclaim(self, n: int, keep: Sequence[int] = ()) -> int:
        """Evict up to ``n`` retained pages (LRU: oldest first) back to the
        free list, invalidating their index entries. Pages in ``keep`` (about
        to be revived as shared references by the caller) are skipped.
        Returns the number actually reclaimed."""
        got = 0
        survivors = []
        for pg in self._retained:
            if got < n and pg not in keep:
                key = self._page_key.pop(pg, None)
                if key is not None:
                    del self._index[key]
                self._free.append(pg)
                got += 1
            else:
                survivors.append(pg)
        self._retained = survivors
        if got:
            self.metrics.inc("kv.retained_reclaimed", got)
        return got

    def alloc(self, slot: int, n: int, shared: Sequence[int] = (),
              protect: Sequence[int] = ()) -> Optional[List[int]]:
        """Give ``slot`` ``n`` fresh pages plus read-only references to the
        ``shared`` pages (their refcount is bumped; a retained page is
        revived — pulled off the LRU list with its index entry intact);
        None if the free list plus reclaimable retained pages can't satisfy
        the whole fresh request (all-or-nothing — a partial grant would
        admit a sequence the pool cannot finish). ``protect`` pages (the
        admission plan's CoW source, which the caller is about to *read*
        but not map) are exempt from retained-tier reclaim for this call —
        without it a refcount-0 CoW source could be reclaimed into this
        very allocation's fresh set and copied after its contents died.
        Returns the fresh pages only; the slot's full positional mapping
        is ``list(shared) + returned``."""
        if slot in self._owned:
            raise ValueError(f"slot {slot} already owns pages (double alloc)")
        for pg in (*shared, *protect):             # validate before mutating
            if pg == self.TRASH_PAGE or (pg not in self._ref
                                         and pg not in self._retained):
                raise ValueError(f"cannot share dead page {pg}")
        keep = set(shared) | set(protect)
        if n > len(self._free):
            need = n - len(self._free)
            reclaimable = sum(1 for pg in self._retained if pg not in keep)
            if reclaimable < need:                 # check before evicting:
                return None                        # a refused alloc must not
            self._reclaim(need, keep=keep)         # cost any retained entry
        fresh = [self._free.pop() for _ in range(n)]
        for pg in fresh:
            self._ref[pg] = 1
        for pg in shared:
            if pg in self._ref:
                self._ref[pg] += 1
            else:                                  # revive a retained page
                self._retained.remove(pg)
                self._ref[pg] = 1
                self.metrics.inc("kv.retained_revived")
        self.fresh_pages_allocated += n
        self.shared_page_maps += len(shared)
        self.metrics.inc("kv.pages_allocated", n)
        if shared:
            self.metrics.inc("kv.shared_page_maps", len(shared))
        self._owned[slot] = list(shared) + fresh
        return list(fresh)

    def free(self, slot: int) -> List[int]:
        """Drop ``slot``'s page references. When a page's refcount hits
        zero it either parks on the retained tier (published prefix pages:
        index entry kept so future identical prompts still hit) or returns
        to the free list (unpublished pages: index entry never existed);
        pages still shared by other slots stay live. Returns the pages
        whose refcount actually dropped to zero. Freeing a never-admitted
        slot is an error (it means the caller lost track of the slot
        lifecycle — the bug class the poisoned-page tests guard against)."""
        if slot not in self._owned:
            raise ValueError(f"slot {slot} owns no pages "
                             f"(double free or never admitted)")
        released = []
        for pg in self._owned.pop(slot):
            if pg == self.TRASH_PAGE or pg in self._free:
                raise ValueError(f"double free of page {pg}")
            self._ref[pg] -= 1
            if self._ref[pg] == 0:
                del self._ref[pg]
                if pg in self._page_key:           # published: retain (MRU
                    self._retained.append(pg)      # at the tail)
                    self.metrics.inc("kv.pages_retained")
                else:
                    self._free.append(pg)
                released.append(pg)
        return released

    def rollback(self, slot: int, new_len: int) -> None:
        """Discard ``slot``'s KV tail beyond ``new_len`` tokens — the
        speculative-decoding reject path (DESIGN.md §Speculative decoding).

        Pages are slot-granular and all-or-nothing here: a slot keeps its
        full page budget for its whole residency, so rewinding the write
        position never frees a page — in particular a CoW page shared from
        this slot can never be yanked from under a sharer by a rollback.
        The device-side masks (``paged_decode_attention`` lengths,
        ``paged_chunk_prefill_attention`` positions) already ignore slots
        beyond ``pos``, so the host side only has to *validate* the rewind:

        * the slot is live and ``new_len`` fits its page budget;
        * no published prefix-index entry covers a rejected position — the
          index only ever covers fully-written prompt blocks published at
          prefill completion, and drafts append strictly after the prompt,
          so a violation means the engine rolled back into committed state.
        """
        pages = self._owned.get(slot)
        if pages is None:
            raise ValueError(f"rollback of slot {slot} that owns no pages")
        if new_len < 0 or self.pages_needed(new_len) > len(pages):
            raise ValueError(f"rollback of slot {slot} to {new_len} tokens "
                             f"outside its {len(pages)}-page budget")
        for i, pg in enumerate(pages):
            if pg in self._page_key and (i + 1) * self.page_size > new_len:
                raise ValueError(
                    f"rollback of slot {slot} to {new_len} would reject "
                    f"positions covered by published block {i} (page {pg})")
        self.metrics.inc("kv.rollbacks")

    def owned(self, slot: int) -> List[int]:
        return list(self._owned.get(slot, []))

    # -------------------------------------------------- prefix index (sharing)
    def _block_digests(self, tokens) -> List[bytes]:
        """Rolling digest per complete ``page_size``-token block: digest i
        covers tokens ``[0, (i+1)·page_size)``, so a chain match means the
        whole prefix matches, not just one block."""
        import hashlib
        toks = np.asarray(tokens, np.int64)
        h = hashlib.sha256()
        out = []
        for i in range(len(toks) // self.page_size):
            h.update(toks[i * self.page_size:(i + 1) * self.page_size]
                     .tobytes())
            out.append(h.digest())
        return out

    def lookup_prefix(self, tokens, count: bool = True) -> List[int]:
        """Longest chain of fully-matched prompt blocks -> their live page
        ids (index entries are invalidated at release, so every returned
        page is live). ``count=False`` re-checks a plan without skewing the
        hit-rate telemetry."""
        pages = []
        for d in self._block_digests(tokens):
            pg = self._index.get(d)
            if pg is None:
                break
            pages.append(pg)
        if count:
            self.prefix_lookups += 1
            self.prefix_hits += bool(pages)
            self.metrics.inc("kv.prefix_lookups")
            if pages:
                self.metrics.inc("kv.prefix_hits")
        return pages

    def prefix_plan(self, tokens, count: bool = True) -> PrefixPlan:
        """Resolve how a sequence maps onto the index. All writes a request
        performs after admission sit at positions ``>= len(tokens) - 1``
        (the tail prefill re-feeds at least the final token to regenerate
        its logits; decode appends after it), so matched blocks strictly
        below that position are shared read-only. A fully-matched *boundary*
        block containing position ``len(tokens) - 1`` cannot be shared — the
        re-fed final token writes into it — so it is CoW-copied into the
        admission's first fresh page and only that one token is re-fed."""
        pages = self.lookup_prefix(tokens, count=count)
        last_write = max(len(tokens) - 1, 0)
        ro = min(len(pages), last_write // self.page_size)
        cow = pages[ro] if len(pages) > ro else None
        tail = last_write if cow is not None else ro * self.page_size
        return PrefixPlan(shared=tuple(pages[:ro]), cow_src=cow,
                          tail_start=tail)

    def publish_prefix(self, slot: int, tokens) -> int:
        """Register ``slot``'s fully-written prompt blocks in the index
        (called by the owner once prefill completes — never earlier, so a
        sharer cannot map pages whose K/V is still being written). Blocks
        whose chain is already indexed (the shared prefix itself, or a CoW
        copy whose source is published) are skipped. Returns #entries
        added."""
        pages = self._owned.get(slot)
        if pages is None:
            raise ValueError(f"slot {slot} owns no pages to publish")
        added = 0
        for i, d in enumerate(self._block_digests(tokens)):
            if i >= len(pages):
                break
            pg = pages[i]
            if d in self._index or pg in self._page_key:
                continue
            self._index[d] = pg
            self._page_key[pg] = d
            added += 1
        return added

    def assert_invariants(self) -> None:
        """Pool-wide consistency (the stateful harness calls this after
        every step): refcount conservation, free/live partition, no
        double-grants, index liveness."""
        mapped = [p for pages in self._owned.values() for p in pages]
        # refcount conservation: total refcounts == total slot->page maps,
        # and each page's refcount equals the number of slots mapping it
        assert sum(self._ref.values()) == len(mapped)
        counts: Dict[int, int] = {}
        for p in mapped:
            counts[p] = counts.get(p, 0) + 1
        assert counts == self._ref
        # free list, live pages, and the retained tier partition the usable
        # pool; no duplicates anywhere
        assert len(self._free) == len(set(self._free))
        assert len(self._retained) == len(set(self._retained))
        assert self.TRASH_PAGE not in self._free
        assert self.TRASH_PAGE not in self._ref
        assert self.TRASH_PAGE not in self._retained
        live = set(self._ref)
        retained = set(self._retained)
        assert not (live & set(self._free))
        assert not (retained & set(self._free))
        assert not (retained & live)
        assert len(live) + len(self._free) + len(self._retained) \
            == self.usable_pages
        # every retained page is published (that's why it was retained)
        for pg in self._retained:
            assert pg in self._page_key
        # the prefix index only ever points at live or retained pages,
        # bidirectionally
        for key, pg in self._index.items():
            assert pg in live or pg in retained
            assert self._page_key.get(pg) == key
        assert len(self._page_key) == len(self._index)


def paged_decode_attention(cfg: ModelConfig, p: Dict, x: jax.Array,
                           k_pages: jax.Array, v_pages: jax.Array,
                           page_table: jax.Array, pos: jax.Array, *,
                           n_pages: int,
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against the paged pool (one layer's pool leaves).

    x: (B, 1, D); k/v_pages: (KV, P, page_size, hd) — the shared pool;
    page_table: (B, max_pages) int32 page ids per slot; pos: (B,) int32
    absolute position of the new token. ``n_pages`` is the static live-page
    bound the caller bucketed the batch to: attention reads only the first
    ``n_pages`` table columns, so per-step cost is proportional to the live
    context of the batch, not the pool/slot capacity.

    The new token's K/V is written to page ``page_table[b, pos // ps]`` at
    offset ``pos % ps`` — free slots' table rows point at the reserved trash
    page, so their (garbage) writes are harmless. No sliding-window/ring
    support: the paged discipline allocates capacity for the whole sequence
    (the engine asserts this at cache init).

    Returns (attn_out (B,1,D), new_k_pages, new_v_pages).
    """
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = x.dtype
    B = x.shape[0]
    ps = k_pages.shape[2]
    max_pages = page_table.shape[1]
    with jax.named_scope("attn_proj"):
        q = _split_heads(jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt)),
                         H, hd)
        k = _split_heads(jnp.einsum("bsd,de->bse", x, p["wk"].astype(dt)),
                         KV, hd)
        v = _split_heads(jnp.einsum("bsd,de->bse", x, p["wv"].astype(dt)),
                         KV, hd)
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)

    # scatter the new token into its page (clip keeps long-dead rows inside
    # the table; their row is all trash-page anyway)
    with jax.named_scope("kv_write"):
        page_col = jnp.minimum(pos // ps, max_pages - 1)
        page = page_table[jnp.arange(B), page_col]           # (B,)
        off = pos % ps
        k_pages = k_pages.astype(dt).at[:, page, off].set(
            k[:, 0].transpose(1, 0, 2))                      # value (KV,B,hd)
        v_pages = v_pages.astype(dt).at[:, page, off].set(
            v[:, 0].transpose(1, 0, 2))

    with jax.named_scope("attn_kernel"):
        lengths = pos + 1
        tables = page_table[:, :n_pages]
        qg = q.reshape(B, KV, H // KV, hd)                   # (B,KV,G,hd)
        if cfg.use_pallas:
            from repro.kernels import ops as kops
            out = kops.paged_flash_decode(qg, k_pages, v_pages, tables,
                                          lengths,
                                          softcap=cfg.attn_logit_softcap)
        else:
            kg = jnp.moveaxis(k_pages[:, tables], 1, 0)  # (B,KV,np,ps,hd)
            vg = jnp.moveaxis(v_pages[:, tables], 1, 0)
            kg = kg.reshape(B, KV, n_pages * ps, hd)
            vg = vg.reshape(B, KV, n_pages * ps, hd)
            scores = jnp.einsum("bkgh,bkth->bkgt", qg, kg,
                                preferred_element_type=jnp.float32
                                ) / np.sqrt(hd)
            if cfg.attn_logit_softcap:
                c = cfg.attn_logit_softcap
                scores = jnp.tanh(scores / c) * c
            valid = jnp.arange(n_pages * ps)[None, :] < lengths[:, None]
            scores = jnp.where(valid[:, None, None], scores, -1e9)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bkgt,bkth->bkgh", probs.astype(vg.dtype), vg,
                             preferred_element_type=jnp.float32)
            out = out.astype(dt)
    with jax.named_scope("attn_out"):
        out = out.reshape(B, 1, H * hd)
        attn = jnp.einsum("bse,ed->bsd", out, p["wo"].astype(dt))
    return attn, k_pages, v_pages


# ---------------------------------------------------------------------------
# Prefill continuation: one chunk of prompt tokens against the cached prefix
# ---------------------------------------------------------------------------

def _chunk_qkv(cfg: ModelConfig, p: Dict, x: jax.Array, positions: jax.Array):
    """Shared chunk front half: project q/k/v for a (B, ck) chunk and rope
    them at per-row absolute ``positions`` (B, ck)."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = x.dtype
    q = _split_heads(jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt)), H, hd)
    k = _split_heads(jnp.einsum("bsd,de->bse", x, p["wk"].astype(dt)), KV, hd)
    v = _split_heads(jnp.einsum("bsd,de->bse", x, p["wv"].astype(dt)), KV, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _chunk_attend(cfg: ModelConfig, q: jax.Array, kg: jax.Array, vg: jax.Array,
                  bias: jax.Array) -> jax.Array:
    """Chunk queries over a gathered/stored cache: q (B,ck,H,hd),
    kg/vg (B,KV,T,hd), bias (B,ck,T) additive -> (B,ck,H,hd). Pure-jnp
    oracle for the per-token Pallas route (fp32 accumulation, softmax in
    fp32 — the ``gqa_attend`` conventions)."""
    B, ck, H, hd = q.shape
    KV = kg.shape[1]
    qg = q.reshape(B, ck, KV, H // KV, hd)
    scores = jnp.einsum("bjkgh,bkth->bkgjt", qg, kg,
                        preferred_element_type=jnp.float32) / np.sqrt(hd)
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        scores = jnp.tanh(scores / c) * c
    scores = scores + bias[:, None, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgjt,bkth->bjkgh", probs.astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, ck, H, hd).astype(q.dtype)


def chunk_prefill_attention(cfg: ModelConfig, p: Dict, x: jax.Array,
                            k_cache: jax.Array, v_cache: jax.Array,
                            start: jax.Array, n_valid: jax.Array,
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill-continuation attention for the dense discipline.

    x: (B, ck, D) — the next ``ck`` prompt tokens of each row (right-padded;
    ``n_valid`` (B,) counts the real ones, 0 = row not prefilling);
    k/v_cache: (B, KV, C, hd); start: (B,) absolute position of x[:, 0].
    The chunk's K/V is scattered at positions ``start..start+n_valid`` (the
    non-ring dense cache: slot index == absolute position — the engine
    asserts no sliding window before enabling chunked prefill), then every
    chunk query attends causally over the cache: key slot ``t`` is valid iff
    ``t <= start + j`` — exactly the already-written prefix plus the chunk
    itself, the same stale-entry masking the decode step relies on.

    With ``cfg.use_pallas`` attention routes through the flash decode kernel
    once per chunk token (the chunk is small and static), reusing its
    cached-prefix bias masking; otherwise a blockwise jnp einsum.

    Returns (attn_out (B, ck, D), new_k_cache, new_v_cache).
    """
    H, hd = cfg.num_heads, cfg.resolved_head_dim
    dt = x.dtype
    B, ck = x.shape[0], x.shape[1]
    C = k_cache.shape[2]
    offs = jnp.arange(ck)
    positions = start[:, None] + offs[None, :]               # (B, ck)
    q, k, v = _chunk_qkv(cfg, p, x, positions)
    dest = jnp.where(offs[None, :] < n_valid[:, None], positions, C)
    batch_idx = jnp.arange(B)[:, None]
    k_cache = k_cache.astype(dt).at[batch_idx, :, dest].set(k, mode="drop")
    v_cache = v_cache.astype(dt).at[batch_idx, :, dest].set(v, mode="drop")

    # causal over absolute positions == cache slots; padded queries (j >=
    # n_valid) read stale-but-finite entries and their output is discarded
    valid = jnp.arange(C)[None, None, :] <= positions[:, :, None]
    bias = jnp.where(valid, 0.0, -1e9).astype(jnp.float32)   # (B, ck, C)
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        KV = k_cache.shape[1]
        qg = q.reshape(B, ck, KV, H // KV, hd)
        outs = [kops.flash_decode_bkchd(qg[:, j], k_cache, v_cache, bias[:, j],
                                        softcap=cfg.attn_logit_softcap)
                for j in range(ck)]
        out = jnp.stack(outs, axis=1).reshape(B, ck, H, hd)
    else:
        out = _chunk_attend(cfg, q, k_cache, v_cache, bias)
    out = out.reshape(B, ck, H * hd)
    attn = jnp.einsum("bse,ed->bsd", out, p["wo"].astype(dt))
    return attn, k_cache, v_cache


def paged_chunk_prefill_attention(cfg: ModelConfig, p: Dict, x: jax.Array,
                                  k_pages: jax.Array, v_pages: jax.Array,
                                  page_table: jax.Array, start: jax.Array,
                                  n_valid: jax.Array,
                                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill-continuation attention for the paged discipline (one layer's
    pool leaves — the ``paged_decode_attention`` counterpart of
    ``chunk_prefill_attention``).

    The chunk's K/V lands at page ``page_table[b, pos // ps]`` offset
    ``pos % ps`` for each valid position (invalid rows/tail are pointed out
    of bounds and dropped); attention runs over the row's full block table
    (chunks are rare next to decode ticks, so no live-page bucketing) with
    per-query length masking ``t <= start + j``. Pallas path: the paged
    flash decode kernel per chunk token with per-token lengths.

    Returns (attn_out (B, ck, D), new_k_pages, new_v_pages).
    """
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = x.dtype
    B, ck = x.shape[0], x.shape[1]
    ps = k_pages.shape[2]
    max_pages = page_table.shape[1]
    T = max_pages * ps
    offs = jnp.arange(ck)
    positions = start[:, None] + offs[None, :]               # (B, ck)
    with jax.named_scope("attn_proj"):
        q, k, v = _chunk_qkv(cfg, p, x, positions)

    with jax.named_scope("kv_write"):
        page_col = jnp.minimum(positions // ps, max_pages - 1)
        page = page_table[jnp.arange(B)[:, None], page_col]  # (B, ck)
        page = jnp.where(offs[None, :] < n_valid[:, None], page,
                         k_pages.shape[1])                   # OOB: dropped
        off = positions % ps
        k_pages = k_pages.astype(dt).at[:, page, off].set(
            k.transpose(2, 0, 1, 3), mode="drop")            # (KV,B,ck,hd)
        v_pages = v_pages.astype(dt).at[:, page, off].set(
            v.transpose(2, 0, 1, 3), mode="drop")

    with jax.named_scope("attn_kernel"):
        qg = q.reshape(B, ck, KV, H // KV, hd)
        if cfg.use_pallas:
            from repro.kernels import ops as kops
            lengths = jnp.clip(positions + 1, 1, T)          # (B, ck)
            outs = [kops.paged_flash_decode(qg[:, j], k_pages, v_pages,
                                            page_table, lengths[:, j],
                                            softcap=cfg.attn_logit_softcap)
                    for j in range(ck)]
            out = jnp.stack(outs, axis=1).reshape(B, ck, H, hd)
        else:
            kg = jnp.moveaxis(k_pages[:, page_table], 1, 0)  # (B,KV,mp,ps,hd)
            vg = jnp.moveaxis(v_pages[:, page_table], 1, 0)
            kg = kg.reshape(B, KV, T, hd)
            vg = vg.reshape(B, KV, T, hd)
            valid = jnp.arange(T)[None, None, :] <= positions[:, :, None]
            bias = jnp.where(valid, 0.0, -1e9).astype(jnp.float32)
            out = _chunk_attend(cfg, q, kg, vg, bias)
    with jax.named_scope("attn_out"):
        out = out.reshape(B, ck, H * hd)
        attn = jnp.einsum("bse,ed->bsd", out, p["wo"].astype(dt))
    return attn, k_pages, v_pages

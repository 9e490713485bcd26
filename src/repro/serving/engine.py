"""In-process JAX serving engine — the real-execution counterpart of the
discrete-event simulator. Implements the shared ``ClusterAPI``/``ServingAPI``
(see ``repro.serving.api``) so the same InfAdapter controller drives either.

Two execution modes per ``VariantBackend``:

  * ``"continuous"`` (default) — continuous batching over a persistent
    slot-based batch: the KV cache is allocated once at ``(max_batch, C)``
    and lives across requests; new requests join free slots at any decode
    step and finished sequences retire immediately, so a long generation
    never head-of-line-blocks a short one. The decode loop is jitted ONCE as
    a ``jax.lax.scan`` over ``decode_chunk`` steps — no per-token Python
    dispatch. Slot admission scatters a freshly prefilled cache into the
    resident batch cache with a single jitted masked-gather (no recompiles:
    every shape is fixed at warm-up).
  * ``"pump"`` — the legacy micro-batching path (per-chunk Python decode
    loop), kept as the baseline that ``benchmarks/bench_engine.py`` measures
    continuous batching against.

Two KV disciplines (``kv_cache=``): ``"dense"`` materializes the per-slot
``(max_batch, prompt_len + max_new)`` ring cache; ``"paged"`` replaces it
with a shared per-replica page pool (``PagedVariantBackend``): prefill is
right-sized to the actual arriving batch, decode attention is bounded by the
live context's page count instead of capacity, and pages are allocated at
admission / freed at retirement so admission respects memory-true capacity
(DESIGN.md §Paged KV cache).

Admission control: the engine keeps a bounded queue *per variant*
(backpressure — ``submit`` returns False and counts a rejection when the
queue is full), so ``backlog(t)`` reports true queue depth to the
queue-aware controller mode.

Scheduling (``scheduler=``, DESIGN.md §Scheduling): the order in which
queued requests claim slots — and how prefill interleaves with decode — is a
pluggable ``SchedulerAPI`` policy (``repro.serving.sched``): ``"fifo"``
(default, the legacy tick byte-for-byte), ``"edf"`` (earliest-deadline-first
admission over ``Request.deadline``), and ``"chunked"`` (EDF + chunked
prefill: prompts prefill in ``prefill_chunk``-token chunks interleaved with
decode chunks, so no resident decode step waits longer than one chunk —
no head-of-line blocking behind long prompts). ``preemption=`` optionally
retires deadline-hopeless in-service requests so feasible waiters run:
``"requeue"`` resumes them later via prefill continuation with every
generated token preserved; ``"drop"`` completes them early as ``dropped``.

Variant loading (init + jit warm-up of prefill, the decode chunk, and the
slot-admission scatter) happens on first use — that IS the readiness time
rt_m on this backend, measured rather than assumed.

Replica sharding (``nodes=``): the engine mounts the shared
``repro.cluster.ReplicaFabric`` and an allocation of n units materializes as
multiple ``VariantBackend`` *instances* per variant ("variant#i" replicas),
each with its own slots, KV cache, and bounded admission queue, placed on
nodes by the configured policy. ``submit`` routes two-level: the caller's
dispatcher picks the variant (solver-quota WRR), the engine's ``RoutingAPI``
picks the replica (power-of-two-choices least-outstanding by default).
``inject_fault`` supports node crashes (in-flight and queued requests of
killed replicas are re-submitted to survivors — retry semantics, latency
keeps the original arrival) and replica slow-downs (decode stretched by the
slow factor). The legacy single-backend-per-variant layout is untouched when
``nodes`` is omitted.

Every backend commits its params and cache to one device — a fabric
replica to device ``n mod jax.device_count()``, n being its node's index,
device 0 otherwise — and its jits run where those inputs live. The tests
run it at toy widths on the CPU; ``chip_smoke.py`` serves the published
tinyllama-1.1b widths on a TPU. Set ``use_pallas=True`` to route attention
through the Pallas kernels (interpret mode off-TPU; see DESIGN.md).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import (Callable, Deque, Dict, List, Mapping, Optional, Sequence,
                    Set, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.faults import FaultEvent
from repro.cluster.placement import Node
from repro.cluster.replicas import ReplicaFabric
from repro.cluster.router import ReplicaView, make_router
from repro.configs.base import ModelConfig
from repro.models.attention import PagedKVCache
from repro.models.model import build_model
from repro.obs import Observability, TickRecord
from repro.obs import trace as ev
from repro.obs.slo import slo_class_key
from repro.serving.api import Request, summarize_requests
from repro.serving.sched import make_scheduler, migration_target

__all__ = ["Request", "VariantBackend", "PagedVariantBackend",
           "DraftPair", "InProcessServingEngine"]

# Batch axis of each cache leaf (k/v/conv/ssd carry a leading layer axis).
_CACHE_BATCH_AXIS = {"pos": 0, "k": 1, "v": 1, "conv": 1, "ssd": 1, "enc": 0}


@dataclass
class _PrefillJob:
    """Host-side progress of one slot's chunked prefill (DESIGN.md
    §Scheduling): ``seq`` is everything that must be in the cache before
    decode resumes — the prompt for a fresh request, prompt + all-but-last
    generated token for a preempted one (``resume_tok`` is that last token,
    fed to decode instead of the prefill argmax; ``gen`` seeds
    ``slot_tokens`` so no generated token is lost or duplicated)."""
    req: Request
    seq: np.ndarray               # tokens to prefill (int64)
    pos: int = 0                  # next seq index to feed
    resume_tok: Optional[int] = None
    gen: List[int] = field(default_factory=list)


class _Span:
    """One traced host phase of an engine tick: its start and end on
    ``time.perf_counter`` go to ``spans[phase]`` (``TickRecord.spans``),
    inside a ``jax.profiler.TraceAnnotation`` named ``engine.<phase>`` that
    puts the same phase on the profiler's clock. Built only when the
    engine traces. The stamps sit inside the annotation: annotating a phase
    costs its parent's time (the tick's self time, for a top-level phase),
    never its own."""
    __slots__ = ("spans", "phase", "ann", "t0")

    def __init__(self, spans: Dict[str, Tuple[float, float]], phase: str,
                 backend: Optional[str] = None):
        self.spans, self.phase = spans, phase
        name = "engine." + phase
        self.ann = (jax.profiler.TraceAnnotation(name) if backend is None
                    else jax.profiler.TraceAnnotation(name, backend=backend))

    def __enter__(self) -> None:
        self.ann.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        self.spans[self.phase] = (self.t0, time.perf_counter())
        self.ann.__exit__(*exc)


def _span_ms(spans: Dict[str, Tuple[float, float]], phase: str) -> float:
    """A phase's duration in ms; NaN when the phase did not run."""
    s = spans.get(phase)
    return (s[1] - s[0]) * 1e3 if s else float("nan")


@dataclass
class _PendingExec:
    """One dispatched-but-uncommitted exec phase (DESIGN.md §Async tick
    loop). ``toks`` is the un-synced device output — the decode chunk's
    ``(chunk, B)`` token matrix or the fused tick's ``(B,)`` ``cur_tok``
    snapshot; neither is in any jit's donation set, so holding the
    reference across the next dispatch is safe while the donated KV cache
    is updated in place underneath it. Everything value-*independent*
    (slot_remaining, positions, prefill progress) was already applied at
    dispatch time; ``commit_exec`` applies the value-*dependent* remainder
    (token appends, ``_finish``, slot retirement) one tick later, guarded
    by the ``(request identity, slot_gen)`` pair so a slot preempted or
    rebound inside the gap never absorbs stale tokens."""
    kind: str                                  # "decode" | "fused" | "spec"
    toks: object                               # un-synced device array
    dispatched_at: float                       # perf_counter at dispatch start
    t_dispatch: float                          # timeline clock at dispatch
    # (slot, req, slot_gen, take, finishing) — decode rows to append
    decode_items: List[Tuple] = field(default_factory=list)
    # (slot, req, slot_gen, resume_tok, gen_before, finishing) — rows whose
    # chunked prefill completed at dispatch; their first token is the fused
    # argmax (or the preserved resume token) read at commit
    fused_completions: List[Tuple] = field(default_factory=list)
    # (slot, req, slot_gen, base, round_no) — speculative rounds; ``toks``
    # is the packed (B, 2k+1) [drafts | verifier argmax] matrix and the
    # commit replays the device's acceptance rule on it (DraftPair.commit)
    spec_items: List[Tuple] = field(default_factory=list)


class VariantBackend:
    """One loaded model variant: params + jitted prefill/decode + slot state.

    The KV discipline is pluggable: this base class materializes the dense
    per-slot ring cache at ``(max_batch, prompt_len + max_new)``;
    ``PagedVariantBackend`` replaces it with the shared page pool (see
    DESIGN.md §Paged KV cache). The slot lifecycle, queueing, and retirement
    logic are shared — subclasses override ``_build_state`` (cache + jit
    warm-up, measured as readiness), ``_run_decode_chunk``, admission, and
    the ``_retire_slot`` hook."""

    def __init__(self, name: str, cfg: ModelConfig, accuracy: float,
                 max_batch: int = 8, prompt_len: int = 32, max_new: int = 16,
                 seed: int = 0, decode_chunk: int = 4,
                 use_pallas: bool = False, chunked: bool = False,
                 prefill_chunk_tokens: int = 16, preemption: str = "none",
                 prefix_sharing: bool = False,
                 cache_headroom: int = 0, build_chunked: bool = False,
                 clock: Callable[[], float] = time.time,
                 obs: Optional[Observability] = None,
                 device: Optional[jax.Device] = None):
        self.name = name
        # observability bundle (metrics registry + tracer) — the engine hands
        # its own down so all backends publish into one registry; hot paths
        # use the cached instrument refs, never the bundle
        self.obs = obs if obs is not None else Observability.disabled()
        self.metrics = self.obs.metrics
        self.tracer = self.obs.tracer
        self.windows = self.obs.windows
        # the running tick's spans while the engine traces it (see _Span):
        # _jit_exec and the commit's read add their own phases here
        self._spans: Optional[Dict[str, Tuple[float, float]]] = None
        if use_pallas and not cfg.use_pallas:
            cfg = cfg.replace(use_pallas=True)
        self.cfg = cfg
        self.accuracy = accuracy
        self.max_batch = max_batch
        self.prompt_len = prompt_len
        self.max_new = max_new
        self.decode_chunk = max(1, min(decode_chunk, max_new))
        self.clock = clock       # every service/completion stamp uses this
        # chunked-prefill machinery is built when the scheduler interleaves
        # prefill chunks with decode, when preemption is on (resume = a
        # prefill continuation over prompt + preserved tokens), or when
        # prefix sharing is on (a shared-prefix admission prefills only the
        # novel tail — a continuation starting mid-sequence); right-sized
        # admission (true prompt length, not padded) only under the chunked
        # scheduler itself — resume under monolithic admission must rebuild
        # the padded cache it preempted (see admit_chunked)
        self.preemption = preemption
        self.prefix_sharing = prefix_sharing   # honored by paged backends
        self.right_sized = chunked
        self.chunked = chunked or preemption != "none" or prefix_sharing
        self.prefill_chunk_tokens = max(1, prefill_chunk_tokens)
        # cache_headroom: extra token capacity past prompt_len + max_new.
        # Speculative drafters need it — a draft scan writes up to k
        # positions past the last committed token, and on the dense ring a
        # write past capacity would wrap onto the row's own prompt. The
        # request budget (``_budget``) is NOT widened: headroom is
        # scratch space, never servable tokens.
        self.cache_headroom = max(0, cache_headroom)
        self.model = build_model(cfg)
        if self.chunked:
            assert self.model.supports_chunked_prefill(), \
                (f"scheduler needs prefill continuation, unsupported for "
                 f"config {cfg.name!r} (needs a pure-attention family "
                 f"without sliding window)")
        elif build_chunked and self.model.supports_chunked_prefill():
            # opportunistic: the engine wants the continuation machinery
            # (async-tick admission pipelining) but nothing *requires* it —
            # right_sized stays False, so admission still prefills the
            # zero-padded prompt and outputs bit-match the monolithic path
            self.chunked = True
        # speculative decoding: the engine attaches a DraftPair here when
        # this backend is the verifier of a drafter:verifier binding
        self._spec_pair: Optional["DraftPair"] = None
        self.units = 1
        self.slot_cap: Optional[int] = None   # units -> concurrency (enforced
        # only when the engine runs with enforce_units; see free_slots)
        self.slow_factor = 1.0   # straggler fault: decode stretched by this
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.slot_remaining = np.zeros((max_batch,), np.int64)
        self.slot_tokens: List[List[int]] = [[] for _ in range(max_batch)]
        # async tick loop (DESIGN.md §Async tick loop): the engine parks the
        # dispatched-but-uncommitted exec here between ticks; slot_gen is a
        # per-slot bind counter so a commit can detect preempt/rebind inside
        # the gap; _uncommitted_done marks slots finished by count at
        # dispatch whose tokens have not been read back yet (excluded from
        # further dispatch and from preemption, still occupying their slot
        # so admission headroom lags exactly one tick)
        self._pending: Optional[_PendingExec] = None
        self.slot_gen = [0] * max_batch
        self._uncommitted_done: Set[int] = set()
        # host mirror of each bound row's device position (the paged backend
        # buckets on it; chunked fused ticks feed it as the continuation
        # offset) — maintained through admit/chunk/decode for bound rows
        self.slot_pos = np.zeros((max_batch,), np.int64)
        self._prefilling: Dict[int, _PrefillJob] = {}   # slot -> progress
        # prompt tokens this backend actually prefilled (monolithic admits
        # + continuation chunks) — the prefix_sharing bench's reduction
        # metric compares this between sharing on/off on the same workload
        self.prefill_tokens_total = 0
        # params and cache are committed to this replica's device; every jit
        # then runs where its committed inputs live (a jax.default_device
        # scope would key the warmed executables to that scope instead)
        self.device = device if device is not None else jax.devices()[0]
        t0 = time.time()
        self.params = jax.device_put(self.model.init(jax.random.PRNGKey(seed)),
                                     self.device)
        self._build_state()                  # cache + jit warm-up = readiness
        if self.chunked:
            self._build_chunk_state()        # prefill-continuation jits too
        # warm-up only enqueued device work: readiness ends when it finishes
        jax.block_until_ready((self.cache, self.cur_tok))
        self.readiness_s = time.time() - t0

    def _build_state(self) -> None:
        """Dense KV discipline: one resident ``(max_batch, C)`` cache.

        The resident cache is **donated** to every jitted consumer (decode,
        decode chunk, admission merge): the engine always replaces
        ``self.cache`` with the call's result, so XLA may update the KV
        buffers in place instead of copying the whole capacity-sized cache
        every step (§Paged KV cache perf notes — the copy, not the math, was
        the dominant per-step cost at large C on CPU)."""
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(
                p, b, max_len=(self.prompt_len + self.max_new
                               + self.cache_headroom)))
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(1,))
        self._decode_chunk = jax.jit(self._decode_chunk_fn,
                                     donate_argnums=(1,))
        self._admit_merge = jax.jit(self._admit_merge_fn, donate_argnums=(0,))

        # --- persistent slot state (continuous batching) ---
        # Warm-up compiles every jitted entry point (part of readiness).
        # Donated caches are chained call-to-call — a donated buffer is dead
        # after the call, so each step feeds the previous step's output.
        toks = jnp.zeros((self.max_batch, self.prompt_len), jnp.int32)
        zeros_tok = jnp.zeros((self.max_batch,), jnp.int32)
        logits, cache = self._prefill(self.params, {"tokens": toks})
        self.cur_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        _, cache = self._decode(self.params, cache, zeros_tok)
        _, cache, _ = self._decode_chunk(self.params, cache, self.cur_tok)
        _, fresh = self._prefill(self.params, {"tokens": toks})
        self.cache, self.cur_tok = self._admit_merge(
            cache, fresh, self.cur_tok, self.cur_tok,
            jnp.zeros((self.max_batch,), jnp.int32),
            jnp.zeros((self.max_batch,), bool))
        self.slot_req = [None] * self.max_batch          # warm-up left no state

    def _build_chunk_state(self) -> None:
        """Chunked-prefill machinery (built only when the scheduler or
        preemption needs it): ONE prefill-continuation jit, donated and
        warmed as part of readiness. It serves fused ticks — mid-prefill
        rows consume a chunk of prompt tokens while decoding rows consume
        their single current token (decode IS a 1-token continuation), so a
        tick never pays a prefill call *and* a decode call."""
        self._prefill_chunk = jax.jit(self._prefill_chunk_fn,
                                      donate_argnums=(1,))
        B, ck = self.max_batch, self.prefill_chunk_tokens
        zeros = jnp.zeros((B,), jnp.int32)
        self.cur_tok, self.cache = self._prefill_chunk(
            self.params, self.cache, self.cur_tok,
            jnp.zeros((B, ck), jnp.int32), zeros, zeros,
            jnp.zeros((B,), bool), jnp.zeros((B,), bool))

    # ------------------------------------------------------------- jitted fns
    def _chunk_scan(self, cache, tok, step_fn):
        """``decode_chunk`` greedy steps of ``step_fn(cache, tok)`` as one
        traced scan. Returns (next feed token (B,), cache, emitted tokens
        (chunk, B)). A chunk of 1 skips the scan: the scan carry
        double-buffers the whole cache per iteration, which donation cannot
        elide."""
        def body(carry, _):
            t, c = carry
            logits, c = step_fn(c, t)
            with jax.named_scope("sample"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, c), nxt

        if self.decode_chunk == 1:
            (tok, cache), toks = body((tok, cache), None)
            return tok, cache, toks[None]
        (tok, cache), toks = jax.lax.scan(
            body, (tok, cache), None, length=self.decode_chunk)
        return tok, cache, toks

    def _decode_chunk_fn(self, params, cache, tok):
        return self._chunk_scan(
            cache, tok, lambda c, t: self.model.decode_step(params, c, t))

    def _model_prefill_chunk(self, params, cache, tokens, start, n_valid):
        """KV-discipline hook: the paged backend swaps in the pool form."""
        return self.model.prefill_chunk(params, cache, tokens, start, n_valid)

    def _prefill_chunk_fn(self, params, cache, cur_tok, tokens, start,
                          n_valid, set_mask, feed_mask):
        """One prefill-continuation chunk for every mid-prefill row, plus the
        first greedy token for rows whose prompt completes here
        (``set_mask``) — one executable regardless of which rows are
        prefilling. ``feed_mask`` rows (plain decodes riding the fused
        tick) take their input token from the device-side ``cur_tok``
        instead of the host matrix: bitwise the same value as the host's
        ``slot_tokens[s][-1]`` feed, but available without a D2H sync —
        what lets the async tick dispatch a fused step before the previous
        tick's tokens have been read back."""
        tokens = tokens.at[:, 0].set(
            jnp.where(feed_mask, cur_tok.astype(tokens.dtype), tokens[:, 0]))
        logits, cache = self._model_prefill_chunk(params, cache, tokens,
                                                  start, n_valid)
        with jax.named_scope("sample"):
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jnp.where(set_mask, tok, cur_tok), cache

    @staticmethod
    def _admit_merge_fn(cache, new_cache, cur_tok, new_tok, src, mask):
        """Scatter prefilled rows into the resident batch cache.

        ``src[i]`` is the row of ``new_cache`` destined for slot ``i``;
        ``mask[i]`` selects which slots actually receive it. Fixed shapes —
        compiles once regardless of how many requests join."""
        out = {}
        for key, old in cache.items():
            ax = _CACHE_BATCH_AXIS[key]
            nv = jnp.take(new_cache[key], src, axis=ax)
            m = mask.reshape((1,) * ax + (-1,) + (1,) * (old.ndim - ax - 1))
            out[key] = jnp.where(m, nv, old)
        tok = jnp.where(mask, jnp.take(new_tok, src), cur_tok)
        return out, tok

    # -------------------------------------------------------- pump-mode path
    def generate(self, prompts: np.ndarray, max_new: int) -> np.ndarray:
        """Legacy pump path: per-token Python decode loop over a micro-batch.

        prompts: (b, prompt_len), padded to max_batch internally."""
        b = prompts.shape[0]
        pad = self.max_batch - b
        # one H2D copy of the unpadded prompts, padded on device — the old
        # np.pad-then-asarray form materialized the padded matrix on host
        # first (a second full copy per admission)
        toks = jnp.pad(jnp.asarray(prompts), ((0, pad), (0, 0)))
        logits, cache = self._prefill(self.params, {"tokens": toks})
        outs = []
        tok = jnp.argmax(logits, axis=-1)
        for _ in range(max_new):
            outs.append(tok)
            logits, cache = self._decode(self.params, cache, tok)
            tok = jnp.argmax(logits, axis=-1)
        out = jnp.stack(outs, axis=1)
        return np.asarray(out[:b])

    # ------------------------------------------------- continuous-batch path
    @property
    def free_slots(self) -> List[int]:
        """Slots open for admission. With ``slot_cap`` set (the engine's
        ``enforce_units`` mode), allocation units bound live concurrency the
        same way the profiler's allocation sweep does — so measured th(n)
        describes the serving behaviour at allocation n, not just the
        profiling run."""
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        if self.slot_cap is not None:
            allow = min(self.slot_cap, self.max_batch) - self.active_slots
            return free[:max(allow, 0)]
        return free

    @property
    def active_slots(self) -> int:
        return sum(1 for r in self.slot_req if r is not None)

    def _admit_prefill(self, reqs: List[Request], rows: int):
        """Shared admission front half: stamp service start (everything
        before is queue wait), build the (rows, prompt_len) prompt matrix,
        prefill, take the first greedy token. Returns (first tokens (rows,)
        device, same as np, prefill cache)."""
        t_service = self.clock()
        for r in reqs:                   # service (= prefill + decode) begins
            r.service_start = t_service
            self.tracer.request_event(r, ev.ADMITTED, t_service,
                                      backend=self.name, mode="monolithic")
        prompts = np.zeros((rows, self.prompt_len), np.int64)
        for j, r in enumerate(reqs):
            prompts[j, :len(r.tokens)] = r.tokens[:self.prompt_len]
        self._count_prefill_tokens(len(reqs) * self.prompt_len)
        logits, new_cache = self._prefill(self.params,
                                          {"tokens": jnp.asarray(prompts)})
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return first, np.asarray(first), new_cache

    def _count_prefill_tokens(self, n: int) -> None:
        """The ONE increment site for prompt tokens this backend prefilled
        (monolithic admits + continuation chunks): the legacy attribute and
        the registry counter move together and can never drift apart."""
        self.prefill_tokens_total += n
        self.metrics.inc("engine.prefill_tokens_total", n)

    def _budget(self, r: Request) -> int:
        """A request's token budget is ``min(r.max_new, self.max_new)`` —
        the cache is provisioned for prompt_len + max_new tokens, so longer
        asks are truncated (``r.output`` carries the served length; the
        request object itself is never mutated)."""
        return min(r.max_new, self.max_new)

    def _bind_slot(self, r: Request, slot: int, tok0: int) -> None:
        self.slot_gen[slot] += 1
        self.slot_req[slot] = r
        self.slot_remaining[slot] = self._budget(r) - 1
        self.slot_tokens[slot] = [tok0]
        self.slot_pos[slot] = self.prompt_len     # device pos after prefill
        if self._spec_pair is not None:
            # monolithic admission prefilled the zero-padded prompt, so the
            # drafter must mirror exactly that sequence
            self._spec_pair.on_fresh(slot, self._effective_seq(r))

    def admit(self, reqs: List[Request], now: float) -> List[Request]:
        """Prefill ``reqs`` (≤ free slots) and join them to the batch.
        Requests whose budget is 1 complete at admission (their token is
        the prefill argmax). Returns requests finished here."""
        free = self.free_slots
        assert len(reqs) <= len(free)
        if not reqs:
            return []
        first, first_np, new_cache = self._admit_prefill(reqs, self.max_batch)
        src = np.zeros((self.max_batch,), np.int32)
        mask = np.zeros((self.max_batch,), bool)
        finished = []
        for j, r in enumerate(reqs):
            slot = free[j]
            src[slot], mask[slot] = j, True
            tok0 = int(first_np[j])
            if self._budget(r) <= 1:
                self._finish(r, [tok0], now)
                finished.append(r)
                continue
            self._bind_slot(r, slot, tok0)
        self.cache, self.cur_tok = self._admit_merge(
            self.cache, new_cache, self.cur_tok, first,
            jnp.asarray(src), jnp.asarray(mask))
        if self.tracer.on:    # monolithic prefill finishes inside the admit
            for r in reqs:
                if r not in finished:
                    self.tracer.event(r.rid, ev.PREFILL_COMPLETE, now,
                                      backend=self.name)
        return finished

    # ----------------------------------------------- chunked-prefill path
    def admit_chunked(self, reqs: List[Request], now: float) -> List[Request]:
        """Chunked admission: bind a slot and queue the prompt for prefill
        continuation — no device work here; the prefill advances one chunk
        per fused tick, interleaved with decode. A preempted request's
        preserved tokens extend the prefill sequence (see ``_PrefillJob``).
        Returns [] — nothing finishes at bind time.

        Prefill is **right-sized to the actual prompt** when the scheduler
        is chunked: a 16-token prompt costs one chunk, not a padded
        ``prompt_len`` prefill (the monolithic path always pads). When this
        machinery serves only preemption resume under monolithic admission,
        the sequence IS zero-padded to ``prompt_len`` so the rebuilt cache
        bit-matches the original padded prefill and resumed greedy tokens
        cannot diverge."""
        free = self.free_slots
        assert len(reqs) <= len(free)
        t_service = self.clock()
        for j, r in enumerate(reqs):
            slot = free[j]
            if r.service_start <= 0.0:   # resume keeps the original stamp
                r.service_start = t_service
            seq = self._effective_seq(r)
            resume_tok: Optional[int] = None
            gen: List[int] = []
            if r.resume_tokens:
                gen = [int(t) for t in r.resume_tokens[:-1]]
                resume_tok = int(r.resume_tokens[-1])
                seq = np.concatenate([seq, np.asarray(gen, np.int64)])
            self.slot_gen[slot] += 1
            self.slot_req[slot] = r
            self.slot_remaining[slot] = 0      # set when prefill completes
            self.slot_tokens[slot] = []
            self.slot_pos[slot] = 0
            self._prefilling[slot] = _PrefillJob(req=r, seq=seq,
                                                 resume_tok=resume_tok,
                                                 gen=gen)
            self.tracer.request_event(
                r, ev.RESUME if resume_tok is not None else ev.ADMITTED,
                t_service, backend=self.name, slot=slot, seq_len=len(seq))
            self._bind_chunked_slot(slot)      # paged: allocate pages now
        return []

    def _effective_seq(self, r: Request) -> np.ndarray:
        """The sequence chunked admission must put in the cache for ``r``'s
        prompt: right-sized to the true prompt under the chunked scheduler,
        zero-padded to ``prompt_len`` otherwise (monolithic parity — see
        ``admit_chunked``). Prefix-index hashes are computed over exactly
        this sequence, so sharing matches whatever discipline admits."""
        toks = np.asarray(r.tokens[:self.prompt_len], np.int64)
        if self.right_sized:
            return toks if len(toks) else np.zeros((1,), np.int64)
        seq = np.zeros((self.prompt_len,), np.int64)
        seq[:len(toks)] = toks
        return seq

    def _bind_chunked_slot(self, slot: int) -> None:
        """KV-discipline hook at chunked bind time (dense: nothing to do —
        the resident cache rows are permanent)."""

    def _prefill_complete(self, slot: int, job: _PrefillJob) -> None:
        """KV-discipline hook when a slot's chunked prefill finishes (paged
        backends with prefix sharing publish the slot's fully-written prompt
        blocks to the prefix index here — never earlier, so a sharer cannot
        map pages whose K/V is still being written)."""

    def fused_chunk_step(self, now: float) -> List[Request]:
        """One fused tick, sync form: dispatch then commit back-to-back —
        exactly the legacy fused tick. The async engine calls the two
        halves a tick apart instead (``dispatch_exec``/``commit_exec``)."""
        return self.commit_exec(self.dispatch_fused(now), now)

    def dispatch_fused(self, now: float) -> _PendingExec:
        """Dispatch one fused tick (Sarathi-style stall-free batching):
        every mid-prefill row advances by one prompt chunk while every
        decoding row advances by exactly one token — a decode step IS a
        one-token prefill continuation (feed the current token at the
        current position, take the argmax of its logits) — all in a single
        jitted call. A resident decode therefore never waits on more than
        one chunk of someone else's prompt, and a tick never pays a prefill
        call *and* a decode call.

        Only value-independent bookkeeping happens here: prefill progress,
        position mirrors, remaining-budget counts, the prefill-complete
        transition (including the prefix-index publish — device-stream
        ordering guarantees the published pages are written before any
        later-dispatched sharer reads them). Token *values* — which never
        influence any of the above under greedy decoding — are applied by
        ``commit_exec`` from the returned pending record."""
        B, ck = self.max_batch, self.prefill_chunk_tokens
        tokens = np.zeros((B, ck), np.int64)
        start = np.zeros((B,), np.int32)
        n_valid = np.zeros((B,), np.int32)
        set_mask = np.zeros((B,), bool)
        feed_mask = np.zeros((B,), bool)
        for slot, job in self._prefilling.items():
            nv = min(len(job.seq) - job.pos, ck)
            tokens[slot, :nv] = job.seq[job.pos:job.pos + nv]
            start[slot] = job.pos
            n_valid[slot] = nv
            # fresh rows completing here take the chunk's argmax as their
            # first generated token; resumed rows already know theirs
            set_mask[slot] = (job.pos + nv >= len(job.seq)
                              and job.resume_tok is None)
        # speculative rows are advanced only by DraftPair rounds — a fused
        # tick (someone else's prefill) must not single-step them, so they
        # stall for the tick exactly like zombies (their spec state stays
        # consistent; the pair resumes them on the next spec dispatch)
        spec_rows = (self._spec_pair.owned()
                     if self._spec_pair is not None else ())
        decode_rows = [s for s, r in enumerate(self.slot_req)
                       if r is not None and s not in self._prefilling
                       and s not in self._uncommitted_done
                       and s not in spec_rows]
        for s in decode_rows:
            feed_mask[s] = True          # device-side cur_tok feed (see
            start[s] = self.slot_pos[s]  # _prefill_chunk_fn) — no D2H dep
            n_valid[s] = 1
            set_mask[s] = True                       # argmax = next token
        t_disp = time.perf_counter()
        self.cur_tok, self.cache = self._jit_exec(
            self._prefill_chunk,
            self.params, self.cache, self.cur_tok, jnp.asarray(tokens),
            jnp.asarray(start), jnp.asarray(n_valid), jnp.asarray(set_mask),
            jnp.asarray(feed_mask))
        # cur_tok is NOT donated: this snapshot stays valid across the next
        # dispatch even though the donated cache is updated in place
        pend = _PendingExec(kind="fused", toks=self.cur_tok,
                            dispatched_at=t_disp, t_dispatch=now)
        resume_sets: List[Tuple[int, int]] = []
        tron = self.tracer.on
        for slot, job in list(self._prefilling.items()):
            nv = int(n_valid[slot])
            job.pos += nv
            self._count_prefill_tokens(nv)
            self.slot_pos[slot] = job.pos
            if tron:
                self.tracer.event(job.req.rid, ev.PREFILL_CHUNK, now,
                                  backend=self.name, pos=job.pos, n=nv)
            if job.pos < len(job.seq):
                continue
            del self._prefilling[slot]
            self._prefill_complete(slot, job)
            r = job.req
            if tron:
                self.tracer.event(r.rid, ev.PREFILL_COMPLETE, now,
                                  backend=self.name)
            if job.resume_tok is not None:
                resume_sets.append((slot, job.resume_tok))
            gen_n = len(job.gen) + 1     # count-based: known at dispatch
            fin = gen_n >= self._budget(r)
            if fin:
                self.slot_remaining[slot] = 0
                self._uncommitted_done.add(slot)
            else:
                self.slot_remaining[slot] = self._budget(r) - gen_n
                if self._spec_pair is not None:
                    # the row starts decoding next tick — hand it to the
                    # drafter pair (job.seq is exactly what this backend
                    # prefilled, so the drafter mirrors it bit-for-bit)
                    self._spec_pair.on_fresh(slot, job.seq)
            pend.fused_completions.append(
                (slot, r, self.slot_gen[slot], job.resume_tok,
                 list(job.gen), fin))
        for s in decode_rows:
            self.slot_pos[s] += 1
            self.slot_remaining[s] -= 1
            fin = self.slot_remaining[s] <= 0
            if fin:
                self._uncommitted_done.add(s)
            pend.decode_items.append(
                (s, self.slot_req[s], self.slot_gen[s], 1, fin))
        if resume_sets:    # resumed rows decode from their preserved token
            self.cur_tok = self.cur_tok.at[
                jnp.asarray([s for s, _ in resume_sets])].set(
                jnp.asarray([t for _, t in resume_sets], jnp.int32))
        return pend

    def preempt(self, r: Request, now: float) -> str:
        """Retire ``r`` early (scheduler-selected victim): its slot — and
        pages, for paged backends — is freed, the tokens it generated are
        preserved on ``r.resume_tokens``. Returns "requeued" (caller puts it
        back on the queue; it later resumes exactly where it stopped) or
        "dropped" (completed now with partial output, ``dropped=True``)."""
        slot = next(s for s, q in enumerate(self.slot_req) if q is r)
        job = self._prefilling.pop(slot, None)
        if job is not None:              # mid-prefill: preserved tokens are
            gen = job.gen + ([] if job.resume_tok is None
                             else [job.resume_tok])   # whatever it resumed with
        else:
            gen = list(self.slot_tokens[slot])
        self.slot_req[slot] = None
        self.slot_tokens[slot] = []
        self.slot_remaining[slot] = 0
        self._uncommitted_done.discard(slot)
        self._retire_slot(slot)
        if self._spec_pair is not None:
            self._spec_pair.on_release(slot)
        r.preemptions += 1
        r.resume_tokens = gen
        self.metrics.inc("requests.preempted")
        self.tracer.request_event(r, ev.PREEMPT, now, backend=self.name,
                                  slot=slot, generated=len(gen),
                                  action=self.preemption)
        if self.preemption == "drop":
            r.output = np.asarray(gen, np.int64)
            r.completion = self.clock()
            r.accuracy = self.accuracy
            r.dropped = True
            self._obs_complete(r, dropped=True)
            return "dropped"
        return "requeued"

    def decode_step_batch(self, now: float) -> List[Request]:
        """One jitted chunk of decode steps, sync form: dispatch then commit
        back-to-back (the async engine splits them a tick apart). Never
        called with rows mid-prefill — those ticks are fused
        (``fused_chunk_step``); the plain decode path stays the fast,
        bucket-aware one."""
        if self._spec_pair is not None and self._spec_pair.has_work():
            return self.commit_exec(self._spec_pair.dispatch(now), now)
        if self.active_slots == 0:
            return []
        return self.commit_exec(self.dispatch_decode(now), now)

    def dispatch_decode(self, now: float) -> Optional[_PendingExec]:
        """Dispatch one decode chunk without waiting for its tokens.
        Value-independent bookkeeping (positions, remaining counts,
        count-based completion detection) happens here; the returned
        pending record carries the un-synced ``(chunk, B)`` token array for
        ``commit_exec``. Returns None when every bound slot is a
        finished-but-uncommitted zombie — nothing left to run."""
        assert not self._prefilling, "mid-prefill rows need the fused tick"
        items = []
        for slot, r in enumerate(self.slot_req):
            if r is None or slot in self._uncommitted_done:
                continue
            take = min(int(self.slot_remaining[slot]), self.decode_chunk)
            items.append([slot, r, self.slot_gen[slot], take, False])
        if not items:
            return None
        t_disp = time.perf_counter()
        toks = self._dispatch_chunk()        # un-synced device (chunk, B)
        for it in items:
            slot, take = it[0], it[3]
            self.slot_remaining[slot] -= take
            if self.slot_remaining[slot] <= 0:
                it[4] = True
                self._uncommitted_done.add(slot)
        return _PendingExec(kind="decode", toks=toks, dispatched_at=t_disp,
                            t_dispatch=now,
                            decode_items=[tuple(it) for it in items])

    def dispatch_exec(self, now: float) -> Tuple[str, Optional[_PendingExec]]:
        """Async exec phase: enqueue this tick's jitted work and return
        (tick kind, pending record) — the record is committed on the NEXT
        tick, after that tick's own dispatch, so the D2H read and
        bookkeeping hide behind in-flight device compute."""
        if self._prefilling:
            return "fused", self.dispatch_fused(now)
        if self._spec_pair is not None and self._spec_pair.has_work():
            return "spec", self._spec_pair.dispatch(now)
        pend = self.dispatch_decode(now) if self.active_slots else None
        return ("decode" if pend is not None else "idle"), pend

    def commit_exec(self, pending: Optional[_PendingExec],
                    now: float) -> List[Request]:
        """Apply a dispatched exec's value-dependent bookkeeping: ONE
        batched D2H read for the whole tick (tokens of every slot arrive in
        a single ``np.asarray`` — commit lag never multiplies small
        per-slot transfers), then token appends, completion stamping, and
        slot retirement. A ``(request identity, slot_gen)`` mismatch means
        the slot was preempted or rebound inside the dispatch→commit gap;
        its stale tokens are discarded — greedy decoding regenerates the
        identical values on resume. Returns requests finished here."""
        if pending is None:
            return []
        if pending.kind == "spec":
            return self._spec_pair.commit(pending, now)
        toks = self._read_tokens(pending.toks)
        if self.slow_factor > 1.0 and pending.kind == "decode":
            # injected straggler: effective chunk time scales by slow_factor
            time.sleep((time.perf_counter() - pending.dispatched_at)
                       * (self.slow_factor - 1.0))
        finished: List[Request] = []
        for slot, r, gen_id, resume_tok, gen_before, fin \
                in pending.fused_completions:
            if self.slot_req[slot] is not r or self.slot_gen[slot] != gen_id:
                continue
            tok0 = resume_tok if resume_tok is not None else int(toks[slot])
            gen = gen_before + [tok0]
            if fin:
                self._finish(r, gen, now)
                finished.append(r)
                self._release_slot(slot)
            else:
                self.slot_tokens[slot] = gen
        for slot, r, gen_id, take, fin in pending.decode_items:
            if self.slot_req[slot] is not r or self.slot_gen[slot] != gen_id:
                continue
            if pending.kind == "fused":
                self.slot_tokens[slot].append(int(toks[slot]))
            else:
                self.slot_tokens[slot].extend(
                    int(t) for t in toks[:take, slot])
            if fin:
                self._finish(r, self.slot_tokens[slot], now)
                finished.append(r)
                self._release_slot(slot)
        return finished

    def _release_slot(self, slot: int) -> None:
        self.slot_req[slot] = None
        self.slot_tokens[slot] = []
        self._uncommitted_done.discard(slot)
        self._retire_slot(slot)
        if self._spec_pair is not None:
            self._spec_pair.on_release(slot)

    def flush_pending(self, now: float) -> List[Request]:
        """Commit the in-flight tick, if any (async shutdown/fault path)."""
        pend, self._pending = self._pending, None
        return self.commit_exec(pend, now)

    def _jit_exec(self, call, *args):
        """Run one exec-phase jitted call. In a traced tick the call is the
        ``launch`` span: the time the runtime takes to accept the step —
        the call returns before the device runs it, unless the launch
        itself waits (on a donated buffer still in use, say)."""
        if self._spans is None:
            return call(*args)
        with _Span(self._spans, "launch", self.name):
            return call(*args)

    def _read_tokens(self, toks) -> np.ndarray:
        """The commit's one device-to-host read, which blocks until the
        exec that produces ``toks`` has run. In a traced tick it is the
        ``commit_wait`` span."""
        if self._spans is None:
            return np.asarray(toks)
        with _Span(self._spans, "commit_wait", self.name):
            return np.asarray(toks)

    def _dispatch_chunk(self):
        """Enqueue one decode chunk; returns the UN-SYNCED device token
        array (chunk, B) — the chunk outputs are not in the donation set,
        so the caller may hold them across the next dispatch."""
        self.cur_tok, self.cache, toks = self._jit_exec(
            self._decode_chunk, self.params, self.cache, self.cur_tok)
        self.slot_pos += self.decode_chunk   # device advanced every row
        return toks

    def _retire_slot(self, slot: int) -> None:
        """Hook called when a slot's request completes (paged backends free
        the slot's pool pages here); the dense cache needs no cleanup —
        stale entries are masked by the validity bias."""

    def _finish(self, r: Request, tokens: List[int], now: float) -> None:
        r.output = np.asarray(tokens[:min(r.max_new, self.max_new)], np.int64)
        r.completion = self.clock()
        r.accuracy = self.accuracy
        self._obs_complete(r)

    def _obs_complete(self, r: Request, dropped: bool = False) -> None:
        """Completion-side metrics + terminal span event — one site for
        normal finishes, preemption drops, and the legacy pump path, so the
        registry's request totals always agree with ``self.done``.

        Goodput counts a request when it wasn't dropped and met its own
        ``slo_ms`` (requests without a per-request SLO count as good — the
        registry can't know the summary-time global SLO).

        With rolling windows on (``Observability(windows=True)``) the same
        outcomes also land in the windowed instruments under the SAME
        names, keyed at ``r.completion`` (the backend's one clock), plus
        the per-SLO-class ``slo.class.<key>.good|bad`` counters the
        burn-rate monitor reads — the DES ``_record`` sink mirrors this
        exactly (parity-tested)."""
        m = self.metrics
        lat = r.latency_ms
        good = not dropped and (r.slo_ms <= 0 or lat <= r.slo_ms)
        m.inc("requests.completed")
        m.observe("request.latency_ms", lat)
        m.observe("request.queue_wait_ms", r.queue_wait_ms)
        m.observe("request.service_ms", r.service_ms)
        if dropped:
            m.inc("requests.dropped")
        elif good:
            m.inc("requests.goodput_ok")
        w = self.windows
        if w.on:
            tc = r.completion
            w.inc("requests.completed", tc)
            w.observe("request.latency_ms", tc, lat)
            cls = slo_class_key(r.slo_ms)
            if dropped:
                w.inc("requests.dropped", tc)
            elif good:
                w.inc("requests.goodput_ok", tc)
            w.inc(f"slo.class.{cls}.{'good' if good else 'bad'}", tc)
        self.tracer.request_event(r, ev.DROP if dropped else ev.COMPLETE,
                                  r.completion, backend=self.name,
                                  latency_ms=lat)

    def release(self) -> None:
        """Free this backend's device buffers now. A backend sits in
        reference cycles through its jitted closures, so once retired its
        params and cache would otherwise hold HBM until the cyclic garbage
        collector runs — long enough for the next load to run out."""
        self.params = self.cache = self.cur_tok = None
        if self._spec_pair is not None:
            self._spec_pair.d.release()

    def drain_slots(self, now: float) -> List[Request]:
        """Run prefill/decode until every in-flight sequence completes
        (connection draining before retirement — create-then-remove).
        Commits any in-flight async tick first, then loops synchronously."""
        done: List[Request] = list(self.flush_pending(now))
        steps = 0
        max_steps = self.max_new // self.decode_chunk + 2
        if self.chunked:   # fused ticks: 1 decode token while chunks finish
            max_steps += -(-(self.prompt_len + self.max_new)
                           // self.prefill_chunk_tokens) + self.max_new + 2
        if self._spec_pair is not None:
            max_steps += self.max_new + 2   # worst case: 1 accepted/round
        while self.active_slots and steps < max_steps:
            if self._prefilling:
                done.extend(self.fused_chunk_step(now))
            else:
                done.extend(self.decode_step_batch(now))
            steps += 1
        return done


def _bucket_ladder(lo: int, hi: int) -> List[int]:
    """Doubling ladder of static sizes in [lo, hi], always ending at hi —
    the compile-once buckets for right-sized prefill batches and live-page
    bounds (log₂ many executables instead of one per dynamic size)."""
    sizes = []
    n = max(1, lo)
    while n < hi:
        sizes.append(n)
        n *= 2
    sizes.append(hi)
    return sizes


class PagedVariantBackend(VariantBackend):
    """``VariantBackend`` with a paged KV pool instead of the dense ring.

    Three cost levers over the dense discipline (DESIGN.md §Paged KV cache):

      * **Right-sized prefill** — admission prefills a batch bucketed to the
        actual number of joiners (1, 2, 4, …), never padded to ``max_batch``,
        and only to ``prompt_len`` capacity (decode tokens live in pages, so
        the prefill cache never over-allocates for them).
      * **Length-aware decode** — each decode chunk runs at the smallest
        live-page bucket covering the longest live sequence; attention cost
        is proportional to live context, not ``prompt_len + max_new``
        capacity. With ``use_pallas`` the ``paged_flash_decode`` kernel
        additionally skips pages per row.
      * **Memory-true capacity** — pages are allocated at admission (whole
        sequence budget, all-or-nothing) and freed at retirement;
        ``free_slots`` admits only what the pool can hold, so
        ``enforce_units`` and the profiler observe real memory capacity.
    """

    def __init__(self, name: str, cfg: ModelConfig, accuracy: float,
                 page_size: int = 16, pool_pages: Optional[int] = None,
                 **kw):
        self.page_size = page_size
        self._pool_pages_arg = pool_pages
        super().__init__(name, cfg, accuracy, **kw)

    def _build_state(self) -> None:
        model, ps = self.model, self.page_size
        # pages covering one slot's whole budget (prompt + decode tokens,
        # plus any scratch headroom — speculative drafters write drafts
        # past the last committed position before they are accepted)
        self.pages_per_slot = -(-(self.prompt_len + self.max_new
                                  + self.cache_headroom) // ps)
        pool_pages = self._pool_pages_arg or (
            self.max_batch * self.pages_per_slot + 1)   # +1: trash page 0
        self.pool = PagedKVCache(pool_pages, ps, metrics=self.metrics)
        self.cache, self.cur_tok = jax.device_put(
            (model.init_paged_cache(self.max_batch, pool_pages, ps,
                                    self.pages_per_slot),
             jnp.zeros((self.max_batch,), jnp.int32)), self.device)
        self.batch_buckets = _bucket_ladder(1, self.max_batch)
        first_pages = self.pool.pages_needed(self.prompt_len + self.decode_chunk)
        self.page_buckets = _bucket_ladder(first_pages, self.pages_per_slot)

        # The pool is donated to the admission scatter and the decode chunk
        # (the engine always replaces ``self.cache`` with the result), so
        # page writes happen in place — a paged cache that copied the whole
        # pool per touch would scale with capacity again
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, max_len=self.prompt_len))
        self._paged_admit = jax.jit(model.paged_admit, donate_argnums=(0,))
        self._decode_chunk_p = jax.jit(self._paged_chunk_fn,
                                       static_argnums=(3,),
                                       donate_argnums=(1,))

        # warm-up every (batch bucket, page bucket) executable — all are
        # part of this backend's measured readiness rt_m (donated caches are
        # chained call-to-call; see the dense warm-up)
        for bb in self.batch_buckets:
            toks = jnp.zeros((bb, self.prompt_len), jnp.int32)
            logits, pref = self._prefill(self.params, {"tokens": toks})
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            self.cache, self.cur_tok = self._paged_admit(
                self.cache, pref, self.cur_tok, first,
                jnp.full((bb, self.pages_per_slot), self.pool.total_pages,
                         jnp.int32),                     # OOB page ids: drop
                jnp.full((bb,), self.max_batch, jnp.int32))  # OOB slots: drop
        for nb in self.page_buckets:
            self.cur_tok, self.cache, _ = self._decode_chunk_p(
                self.params, self.cache, self.cur_tok, nb)
        # prefix sharing: the admission-time CoW page copy (one executable —
        # src/dst are traced scalars) and the per-request plans stashed
        # between the admit-time lookup and the slot bind (same tick)
        self._admit_plans: Dict[int, "object"] = {}
        if self.prefix_sharing:
            self._cow_copy = jax.jit(self.model.paged_cow_copy,
                                     donate_argnums=(0,))
            self.cache = self._cow_copy(self.cache, 0, 0)   # warm: trash->trash

    # chunked machinery: the base ``_build_chunk_state`` works unchanged —
    # ``_model_prefill_chunk`` below is the only paged-specific piece (the
    # pool-form continuation attends the row's whole block table: one
    # executable; fused ticks are already bounded by the chunk size)

    # ------------------------------------------------------------- jitted fns
    def _paged_chunk_fn(self, params, cache, tok, n_pages: int):
        """``decode_chunk`` paged decode steps as one traced scan at the
        static live-page bucket ``n_pages`` (shares ``_chunk_scan`` with the
        dense path)."""
        return self._chunk_scan(
            cache, tok,
            lambda c, t: self.model.decode_step_paged(params, c, t,
                                                      n_pages=n_pages))

    def _model_prefill_chunk(self, params, cache, tokens, start, n_valid):
        return self.model.prefill_chunk_paged(params, cache, tokens, start,
                                              n_valid)

    # ------------------------------------------------- continuous-batch path
    @property
    def free_slots(self) -> List[int]:
        """Slots open for admission = free batch rows ∩ slot_cap (see base)
        ∩ what the page pool can actually hold — memory-true capacity."""
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        if self.slot_cap is not None:
            allow = min(self.slot_cap, self.max_batch) - self.active_slots
            free = free[:max(allow, 0)]
        return free[:self.pool.free_pages // self.pages_per_slot]

    @property
    def kv_pool_occupancy(self) -> float:
        return self.pool.occupancy

    def admit(self, reqs: List[Request], now: float) -> List[Request]:
        """Right-sized admission: prefill only the actual joiners (bucketed),
        allocate each a full page budget, scatter the prefilled KV into its
        pages. With prefix sharing on, joiners whose prompt hits the prefix
        index are peeled off onto the continuation path instead — their
        indexed prefix is mapped by reference at bind and only the novel
        tail is prefilled (the monolithic batch prefill would recompute the
        whole prompt)."""
        if not self.prefix_sharing:
            return self._admit_monolithic(reqs, now)
        hits, misses = [], []
        for r in reqs:
            plan = self.pool.prefix_plan(self._effective_seq(r)) \
                if self._budget(r) > 1 else None   # budget-1: no pages at all
            if plan is not None and (plan.shared or plan.cow_src is not None):
                self._admit_plans[id(r)] = plan
                hits.append(r)
            else:
                misses.append(r)
        finished = self._admit_monolithic(misses, now)
        if hits:                     # binds slots; nothing finishes at bind
            self.admit_chunked(hits, now)
        return finished

    def _admit_monolithic(self, reqs: List[Request],
                          now: float) -> List[Request]:
        free = self.free_slots
        assert len(reqs) <= len(free)
        if not reqs:
            return []
        bb = next(b for b in self.batch_buckets if b >= len(reqs))
        first, first_np, pref = self._admit_prefill(reqs, bb)
        # OOB defaults: rows not joining a slot are dropped by the scatter
        page_ids = np.full((bb, self.pages_per_slot), self.pool.total_pages,
                           np.int32)
        dest = np.full((bb,), self.max_batch, np.int32)
        finished = []
        for j, r in enumerate(reqs):
            slot = free[j]
            tok0 = int(first_np[j])
            if self._budget(r) <= 1:     # completes at admission: no pages
                self._finish(r, [tok0], now)
                finished.append(r)
                continue
            pages = self.pool.alloc(slot, self.pages_per_slot)
            assert pages is not None     # free_slots gated on the pool
            page_ids[j] = pages
            dest[j] = slot
            self._bind_slot(r, slot, tok0)   # slot_pos mirror set there
        self.cache, self.cur_tok = self._paged_admit(
            self.cache, pref, self.cur_tok, first,
            jnp.asarray(page_ids), jnp.asarray(dest))
        if self.prefix_sharing:
            # the scatter above wrote every bound row's full prompt K/V, so
            # those blocks are publishable to the prefix index immediately
            for j, r in enumerate(reqs):
                if int(dest[j]) < self.max_batch:
                    self.pool.publish_prefix(int(dest[j]),
                                             self._effective_seq(r))
        return finished

    def _bind_chunked_slot(self, slot: int) -> None:
        """Chunked admission owns the slot's full page budget up front (the
        all-or-nothing discipline of ``admit``; ``free_slots`` already gated
        the bind on pool capacity — worst-case, so sharing savings are
        realized here, never promised in advance).

        With prefix sharing, the plan's matched blocks are mapped by
        reference (refcount bump) and only the remainder is allocated
        fresh; a fully-matched boundary block is copied on write into the
        first fresh page so the re-fed final prompt token's K/V write
        cannot touch the shared original. The prefill job then starts at
        ``plan.tail_start`` instead of 0 — shared tokens are never
        recomputed."""
        job = self._prefilling[slot]
        stored = self._admit_plans.pop(id(job.req), None)
        plan = None
        if self.prefix_sharing:
            # plan against the *current* index: with the retained tier, a
            # plan computed at admit() peel time can go stale within the
            # same tick (an earlier bind or monolithic alloc may reclaim a
            # planned refcount-0 page). Lookups are cheap; the hit-rate
            # telemetry was already counted once at plan time (resume
            # lookups stay out of it).
            plan = self.pool.prefix_plan(
                self._effective_seq(job.req),
                count=stored is None and job.resume_tok is None)
        shared = tuple(plan.shared) if plan is not None else ()
        cow = plan.cow_src if plan is not None else None
        # protect the CoW source from retained-tier reclaim within this very
        # alloc — the device copy below reads it after the pages are granted
        fresh = self.pool.alloc(slot, self.pages_per_slot - len(shared),
                                shared=shared,
                                protect=() if cow is None else (cow,))
        if fresh is None:
            # retained-tier squeeze: the plan's keep-set blocked reclaim of
            # the last pages. Drop the plan and take the full budget fresh —
            # free_slots gated the bind on free_pages, which is sufficient
            # once nothing is protected.
            plan, shared, cow = None, (), None
            fresh = self.pool.alloc(slot, self.pages_per_slot)
        assert fresh is not None
        self.cache["pt"] = self.cache["pt"].at[slot].set(
            jnp.asarray(list(shared) + list(fresh), jnp.int32))
        if plan is not None and plan.tail_start > 0:
            if plan.cow_src is not None:
                self.cache = self._cow_copy(self.cache, plan.cow_src,
                                            fresh[0])
                self.metrics.inc("kv.cow_copies")
            job.pos = plan.tail_start
            self.slot_pos[slot] = plan.tail_start
            self.tracer.request_event(job.req, ev.COW_BIND, self.clock(),
                                      backend=self.name, slot=slot,
                                      shared_pages=len(shared),
                                      tail_start=plan.tail_start,
                                      cow=plan.cow_src is not None)

    def _prefill_complete(self, slot: int, job: "_PrefillJob") -> None:
        """Publish the slot's fully-written prompt blocks to the prefix
        index — only now, so a sharer can never map pages whose K/V is
        still being written by an in-flight continuation. Resume jobs
        publish just the prompt portion of the rebuilt sequence (generated
        tokens live past the prompt and their final page keeps being
        appended to)."""
        if not self.prefix_sharing:
            return
        prompt = job.seq[:len(job.seq) - len(job.gen)]
        self.pool.publish_prefix(slot, prompt)

    def _dispatch_chunk(self):
        # bucket on rows that still generate: finished-but-uncommitted
        # zombies keep decoding harmlessly (their writes clamp into the
        # slot's own last page, as sync tail chunks always have) but must
        # not inflate the live-page bound
        live = [self.slot_pos[s] for s, r in enumerate(self.slot_req)
                if r is not None and s not in self._uncommitted_done]
        need = self.pool.pages_needed(int(max(live)) + self.decode_chunk)
        need = min(need, self.pages_per_slot)
        nb = next(b for b in self.page_buckets if b >= need)
        self.cur_tok, self.cache, toks = self._jit_exec(
            self._decode_chunk_p, self.params, self.cache, self.cur_tok, nb)
        self.slot_pos += self.decode_chunk   # device advanced every row
        return toks

    def _retire_slot(self, slot: int) -> None:
        """Free the slot's pages and point its table row back at the trash
        page so the dead batch row keeps decoding harmlessly."""
        self.pool.free(slot)
        self.cache = self.model.paged_retire(self.cache, slot)
        self.slot_pos[slot] = 0

    # -------------------------------------------------------- pump-mode path
    def generate(self, prompts: np.ndarray, max_new: int) -> np.ndarray:
        raise NotImplementedError(
            "paged KV backends serve in continuous mode only")


class DraftPair:
    """Speculative decoding binding (DESIGN.md §Speculative decoding): a
    cheap *drafter* backend proposes ``k`` tokens per round for every
    decoding slot of its *verifier* backend; the verifier scores all k+1
    positions (the pending token + the k drafts) in ONE prefill-continuation
    call (``model.verify_chunk``), the longest agreeing draft prefix plus
    the verifier's own bonus token commits, and the rest rolls back by pure
    position rewind.

    **Greedy parity.** The bonus token is always the verifier's own argmax
    given the committed prefix, and a draft commits only where it equals
    that argmax — inductively the committed stream is bitwise identical to
    target-only greedy decoding, whatever the drafter proposes.

    **Overlap.** Acceptance of round t is computed on DEVICE at round
    t+1's dispatch (``_accept_fn`` over the previous round's un-synced
    draft/argmax arrays), so under ``async_tick=True`` the draft+verify of
    round t+1 dispatches before round t's tokens are read back — draft of
    chunk t+1 overlaps verify of chunk t. The commit replays the same
    integer acceptance rule on the packed ``(B, 2k+1)`` matrix host-side
    one tick later; exact equality keeps both sides identical.

    **Rollback.** Both caches rewind ``pos`` to the committed length.
    Chunk and decode attention mask every slot past the query position and
    overwrite a slot before attending it, so rejected-draft K/V is
    unreachable the moment the position retreats — no page is freed
    (budgets are all-or-nothing), CoW pages keep their sharers, and
    ``PagedKVCache.rollback`` audits that published prefix entries never
    cover rejected positions.

    **Per-slot host state** (``_mode``): ``"fresh"`` — drafter mirror
    prefilled this dispatch, the pending token lives in the verifier's
    device ``cur_tok``; ``"device"`` — a dispatched round's acceptance has
    not been committed yet, the device derives base/pending itself;
    ``"host"`` — the round committed before the next spec dispatch (sync
    ticks, or async ticks interleaved with fused prefill ticks), so the
    host feeds base/pending/resync explicitly. ``base[slot]`` always holds
    the round-start base of the arrays in ``_prev`` until a dispatch
    consumes their acceptance, then catches up at commit."""

    def __init__(self, verifier: VariantBackend, drafter: VariantBackend,
                 k: int):
        assert k >= 1
        assert drafter.max_batch == verifier.max_batch
        assert drafter.prompt_len == verifier.prompt_len
        assert drafter.max_new == verifier.max_new
        assert drafter.decode_chunk == k, \
            "the drafter's warmed decode scan IS the k-token draft"
        assert drafter.chunked, "drafter needs the continuation machinery " \
            "(mirror prefill + the full-accept resync)"
        self.v, self.d, self.k = verifier, drafter, k
        self.paged = isinstance(verifier, PagedVariantBackend)
        assert self.paged == isinstance(drafter, PagedVariantBackend)
        self.metrics = verifier.metrics
        self.windows = verifier.windows
        B = verifier.max_batch
        self.base = np.zeros((B,), np.int64)       # round-start verifier pos
        self.end = np.zeros((B,), np.int64)        # base at completion
        self.pend_tok = np.zeros((B,), np.int64)   # host-fed pending token
        self.resync_host = np.zeros((B,), bool)    # host-fed full-accept flag
        self._slot_round = np.zeros((B,), np.int64)
        self._round_no = 0
        self._mode: Dict[int, str] = {}
        self.fresh: Dict[int, np.ndarray] = {}     # slot -> mirror sequence
        self._d_bound: Set[int] = set()
        self._prev = None            # (drafts (B,k), argmax (B,k+1)) device
        # per-slot acceptance telemetry (k-adaptation reads these)
        self.slot_rounds = np.zeros((B,), np.int64)
        self.slot_accepted = np.zeros((B,), np.int64)
        self.slot_proposed = np.zeros((B,), np.int64)
        self._accept = jax.jit(self._accept_fn)
        vfn = (verifier.model.verify_chunk_paged if self.paged
               else verifier.model.verify_chunk)
        self._verify = jax.jit(lambda p, c, t, s, nv: vfn(p, c, t, s, nv),
                               donate_argnums=(1,))
        # warm-up: the verify executable (n_valid=0 writes nothing) and the
        # drafter's width-1 continuation (the full-accept resync shape)
        zi = jnp.zeros((B,), jnp.int32)
        fz = jnp.zeros((B,), bool)
        _, self.v.cache = self._verify(
            verifier.params, verifier.cache,
            jnp.zeros((B, k + 1), jnp.int32), zi, zi)
        self.d.cur_tok, self.d.cache = drafter._prefill_chunk(
            drafter.params, drafter.cache, drafter.cur_tok,
            jnp.zeros((B, 1), jnp.int32), zi, zi, fz, fz)
        verifier._spec_pair = self

    # ------------------------------------------------------------ slot hooks
    def on_fresh(self, slot: int, seq: np.ndarray) -> None:
        """The verifier bound ``slot`` to a decoding request whose cache
        holds exactly ``seq`` (+ the pending first token in ``cur_tok``)."""
        self.fresh[slot] = np.asarray(seq, np.int64)
        self._mode.pop(slot, None)

    def on_release(self, slot: int) -> None:
        """The verifier released ``slot`` (finish or preemption): drop the
        spec state and free the drafter's mirror resources. Any in-flight
        round's stale items are discarded by the commit guard."""
        self._mode.pop(slot, None)
        self.fresh.pop(slot, None)
        if slot in self._d_bound:
            self._d_bound.discard(slot)
            self.d._retire_slot(slot)

    def owned(self):
        return self._mode.keys() | self.fresh.keys()

    def has_work(self) -> bool:
        return bool(self._mode or self.fresh)

    # ------------------------------------------------------------- jitted fns
    def _accept_fn(self, drafts, pred, base_in, end, dev_m, fresh_m,
                   host_tok, host_resync, cur_v):
        """Acceptance of the previous round + inputs of the next, one
        traced call. ``dev_m`` rows derive base/pending from the previous
        round's arrays; ``fresh_m`` rows take the verifier's device
        ``cur_tok`` as pending at their bootstrap base; remaining live rows
        are host-fed (their round already committed). ``n_valid`` is capped
        by the tokens still owed (``end - base``), so a finished row's
        in-flight zombie round verifies nothing and writes nothing."""
        k = self.k
        nv_prev = jnp.clip(end - base_in, 0, k + 1)
        agree = ((drafts == pred[:, :k])
                 & (jnp.arange(k)[None, :] < (nv_prev - 1)[:, None]))
        a = jnp.sum(jnp.cumprod(agree.astype(jnp.int32), axis=1), axis=1)
        bonus = jnp.take_along_axis(pred, a[:, None], axis=1)[:, 0]
        base_new = jnp.where(dev_m, base_in + a + 1, base_in) \
            .astype(jnp.int32)
        pending = jnp.where(dev_m, bonus,
                            jnp.where(fresh_m, cur_v, host_tok)) \
            .astype(jnp.int32)
        resync = (dev_m & (a == k)) | (~dev_m & ~fresh_m & host_resync)
        nv_next = jnp.clip(end - base_new, 0, k + 1).astype(jnp.int32)
        return base_new, pending, nv_next, resync

    # ---------------------------------------------------------- round halves
    def _bootstrap_fresh(self) -> None:
        """Mirror-prefill every newly bound slot's committed sequence into
        the drafter's cache (batched continuation chunks — handles resumed
        rows whose sequence exceeds ``prompt_len``), and seed the host
        state. The pending token itself is copied device-side from the
        verifier's ``cur_tok`` at dispatch — it may only exist on device
        (a chunked completion whose commit has not run yet)."""
        v, d = self.v, self.d
        B, ck = v.max_batch, d.prefill_chunk_tokens
        maxlen = 0
        for slot, seq in sorted(self.fresh.items()):
            base0 = int(v.slot_pos[slot])
            assert base0 == len(seq), (base0, len(seq))
            self.base[slot] = base0
            self.end[slot] = base0 + int(v.slot_remaining[slot])
            self.pend_tok[slot] = 0
            self.resync_host[slot] = False
            self._mode[slot] = "fresh"
            maxlen = max(maxlen, len(seq))
            if self.paged and slot not in self._d_bound:
                pages = d.pool.alloc(slot, d.pages_per_slot)
                assert pages is not None, "drafter pool covers max_batch"
                d.cache["pt"] = d.cache["pt"].at[slot].set(
                    jnp.asarray(pages, jnp.int32))
            self._d_bound.add(slot)
        fz = jnp.zeros((B,), bool)
        for off in range(0, maxlen, ck):
            tokens = np.zeros((B, ck), np.int64)
            st = np.zeros((B,), np.int32)
            nv = np.zeros((B,), np.int32)
            for slot, seq in self.fresh.items():
                n = min(len(seq) - off, ck)
                if n <= 0:
                    continue
                tokens[slot, :n] = seq[off:off + n]
                st[slot] = off
                nv[slot] = n
            d.cur_tok, d.cache = d._prefill_chunk(
                d.params, d.cache, d.cur_tok, jnp.asarray(tokens),
                jnp.asarray(st), jnp.asarray(nv), fz, fz)
        self.fresh.clear()

    def dispatch(self, now: float) -> Optional[_PendingExec]:
        """One speculative round for every owned slot: consume the previous
        round's acceptance (device), rewind both caches, resync the drafter
        on full accepts, draft k tokens on the cheap model, verify all k+1
        positions on the target — five device calls, no D2H."""
        v, d, k = self.v, self.d, self.k
        B = v.max_batch
        if self.fresh:
            self._bootstrap_fresh()
        live = sorted(self._mode)
        if not live:
            return None
        t_disp = time.perf_counter()
        self._round_no += 1
        rnd = self._round_no
        live_np = np.zeros((B,), bool)
        dev_np = np.zeros((B,), bool)
        fresh_np = np.zeros((B,), bool)
        items = []
        for s in live:
            live_np[s] = True
            dev_np[s] = self._mode[s] == "device"
            fresh_np[s] = self._mode[s] == "fresh"
            items.append((s, v.slot_req[s], v.slot_gen[s],
                          int(self.base[s]), rnd))
            self._slot_round[s] = rnd
            self._mode[s] = "device"
        if self._prev is None:
            pd = jnp.zeros((B, k), jnp.int32)
            pp = jnp.zeros((B, k + 1), jnp.int32)
        else:
            pd, pp = self._prev
        base_new, pending, nv_next, resync = self._accept(
            pd, pp, jnp.asarray(self.base), jnp.asarray(self.end),
            jnp.asarray(dev_np), jnp.asarray(fresh_np),
            jnp.asarray(self.pend_tok), jnp.asarray(self.resync_host),
            v.cur_tok)
        live_j = jnp.asarray(live_np)
        # rollback + advance: pure position rewind on both caches — chunk
        # and decode attention mask every slot past the query position and
        # overwrite before attending, so rejected-draft K/V is dead
        v.cache["pos"] = jnp.where(live_j, base_new, v.cache["pos"])
        d.cache["pos"] = jnp.where(live_j, base_new, d.cache["pos"])
        if self.paged:
            for s in live:     # pool-side audit: rewind never uncovers a
                v.pool.rollback(s, int(self.base[s]) + 1)   # published page
        if self._prev is not None:
            # full-accept resync: the k-th draft committed but its K/V was
            # never written (the scan emits it as output only) — feed it
            # through a width-1 continuation at base_new - 1
            fz = jnp.zeros((B,), bool)
            d.cur_tok, d.cache = d._prefill_chunk(
                d.params, d.cache, d.cur_tok, pd[:, -1:], base_new - 1,
                resync.astype(jnp.int32), fz, fz)
        d.cur_tok = jnp.where(live_j, pending, d.cur_tok)
        if self.paged:
            mx = max(int(self.base[s]) for s in live)
            cap = d.prompt_len + d.max_new + d.cache_headroom
            need = d.pool.pages_needed(min(mx + 2 * k + 2, cap))
            nb = next(b for b in d.page_buckets
                      if b >= min(need, d.pages_per_slot))
            d.cur_tok, d.cache, dtoks = d._decode_chunk_p(
                d.params, d.cache, d.cur_tok, nb)
        else:
            d.cur_tok, d.cache, dtoks = d._decode_chunk(
                d.params, d.cache, d.cur_tok)
        drafts = jnp.transpose(dtoks).astype(jnp.int32)      # (B, k)
        vt = jnp.concatenate([pending[:, None], drafts], axis=1)
        pred, v.cache = v._jit_exec(self._verify, v.params, v.cache, vt,
                                    base_new, nv_next)
        self._prev = (drafts, pred)
        self.metrics.inc("spec.batch_rounds")
        return _PendingExec(kind="spec",
                            toks=jnp.concatenate([drafts, pred], axis=1),
                            dispatched_at=t_disp, t_dispatch=now,
                            spec_items=items)

    def commit(self, pending: _PendingExec, now: float) -> List[Request]:
        """Replay the round's acceptance host-side from the packed
        ``(B, 2k+1)`` matrix — ONE D2H read — and apply the value-dependent
        bookkeeping: token appends, acceptance telemetry, completion. A
        ``(request identity, slot_gen)`` mismatch means the slot was
        preempted or rebound inside the dispatch→commit gap; its stale
        tokens are discarded and regenerated identically on resume."""
        v, k = self.v, self.k
        m, w = self.metrics, self.windows
        pack = v._read_tokens(pending.toks)
        drafts, pred = pack[:, :k], pack[:, k:]
        finished: List[Request] = []
        for slot, r, gen_id, _base_disp, rnd in pending.spec_items:
            if v.slot_req[slot] is not r or v.slot_gen[slot] != gen_id:
                continue
            # The round-start base is read LIVE from ``self.base``, not
            # from the dispatch-time snapshot: under async overlap the
            # dispatch of round r+1 runs before the commit of round r has
            # advanced the host base, so the snapshot can be one round
            # stale. Commits drain strictly FIFO and each advances
            # ``self.base`` by exactly a+1, so at commit(r) the host base
            # is always round r's true start offset.
            base_t = int(self.base[slot])
            nv = int(min(self.end[slot] - base_t, k + 1))
            if nv <= 0:
                continue          # zombie round of an already-finished row
            a = 0
            while a < nv - 1 and int(drafts[slot, a]) == int(pred[slot, a]):
                a += 1
            v.slot_tokens[slot].extend(
                [int(t) for t in drafts[slot, :a]] + [int(pred[slot, a])])
            new_base = base_t + a + 1
            self.base[slot] = new_base
            v.slot_pos[slot] = new_base
            v.slot_remaining[slot] = self.end[slot] - new_base
            self.slot_rounds[slot] += 1
            self.slot_accepted[slot] += a
            self.slot_proposed[slot] += nv - 1
            m.inc("spec.rounds")
            m.inc("spec.committed_tokens", a + 1)
            m.inc("spec.drafts_accepted", a)
            m.inc("spec.drafts_proposed", nv - 1)
            if w.on:
                w.observe("spec.tokens_per_step", now, a + 1)
                if nv > 1:
                    w.observe("spec.accept_rate", now, a / (nv - 1))
            if self._slot_round[slot] == rnd:
                # no newer round in flight (sync ticks, or async ticks
                # interleaved with fused prefill): the next dispatch takes
                # base/pending/resync from the host side
                self._mode[slot] = "host"
                self.pend_tok[slot] = int(pred[slot, a])
                self.resync_host[slot] = a == k
            # else: a newer round already consumed this acceptance on
            # device — self.base just caught up to that round's base
            if new_base >= self.end[slot]:
                v._finish(r, v.slot_tokens[slot], now)
                finished.append(r)
                v._release_slot(slot)     # -> on_release drops spec state
        return finished

    def acceptance_stats(self) -> Dict:
        rounds = int(self.slot_rounds.sum())
        acc = int(self.slot_accepted.sum())
        prop = int(self.slot_proposed.sum())
        return {"rounds": rounds, "drafts_accepted": acc,
                "drafts_proposed": prop,
                "accept_rate": acc / max(prop, 1),
                "tokens_per_step": (acc + rounds) / max(rounds, 1)}


class InProcessServingEngine:
    """``ServingAPI`` on real models (continuous batching or legacy pump).

    Parameters mirror the paper's serving setup: ``variants`` maps name ->
    (ModelConfig, accuracy%); ``apply_allocation`` loads/retires variants
    with measured readiness; per-variant admission queues are bounded at
    ``queue_cap`` requests (backpressure).
    """

    def __init__(self, variants: Mapping[str, Tuple[ModelConfig, float]],
                 max_batch: int = 8, prompt_len: int = 32,
                 mode: str = "continuous", max_new: int = 16,
                 decode_chunk: int = 4, queue_cap: int = 256,
                 use_pallas: bool = False, enforce_units: bool = False,
                 nodes: Optional[Sequence[Node]] = None,
                 placement="first-fit", router="p2c", replica_size: int = 1,
                 kv_cache: str = "dense", kv_page_size: int = 16,
                 kv_pool_pages: Optional[int] = None,
                 kv_prefix_sharing: bool = False,
                 scheduler="fifo", prefill_chunk: int = 16,
                 preemption: str = "none",
                 clock: Callable[[], float] = time.time,
                 trace: bool = False,
                 obs: Optional[Observability] = None,
                 async_tick: bool = False,
                 speculative: Optional[str] = None,
                 spec_k: int = 4):
        assert mode in ("continuous", "pump"), mode
        assert not async_tick or mode == "continuous", \
            "async_tick needs the continuous engine (the pump path is " \
            "a blocking per-batch loop)"
        assert kv_cache in ("dense", "paged"), kv_cache
        assert kv_cache == "dense" or mode == "continuous", \
            "paged KV backends serve in continuous mode only"
        assert preemption in ("none", "requeue", "drop", "migrate"), \
            preemption
        assert not (kv_prefix_sharing and kv_cache != "paged"), \
            "kv_prefix_sharing requires kv_cache='paged' (the prefix index " \
            "maps shared blocks onto pool pages)"
        # scheduling discipline between each backend's queue and its slots
        # (DESIGN.md §Scheduling): "fifo" = the legacy behavior; "edf" =
        # deadline-order admission; "chunked" = EDF + chunked prefill.
        # preemption= retires deadline-hopeless residents for feasible
        # waiters ("requeue" resumes them later with tokens preserved,
        # "drop" completes them early as dropped).
        self.sched = make_scheduler(scheduler)
        self.prefill_chunk = prefill_chunk
        self.preemption = preemption
        self.clock = clock   # every arrival/service/completion stamp source
        # observability: metrics are on by default (registry bumps cost what
        # the old ad-hoc counters cost); span/tick tracing is opt-in via
        # trace=True. One bundle serves the engine and every backend it
        # creates, so all replicas publish into one registry and one trace
        # timeline (stamped from self.clock — the engine's one clock).
        self.obs = obs if obs is not None else Observability(trace=trace)
        self.metrics = self.obs.metrics
        self.tracer = self.obs.tracer
        self.windows = self.obs.windows
        # async tick loop (DESIGN.md §Async tick loop): each tick dispatches
        # its exec FIRST, then commits the PREVIOUS tick's — the D2H read
        # and bookkeeping of tick t hide behind tick t+1's device compute.
        # Greedy outputs are bitwise identical to the sync default; only
        # completion/retirement bookkeeping lags by exactly one tick.
        self.async_tick = bool(async_tick)
        assert mode == "continuous" or (
            not self.sched.chunked and preemption == "none"), \
            "chunked scheduling/preemption need the continuous engine"
        # speculative decoding on the variant ladder: "drafter:verifier"
        # names two loaded variants; every backend of the verifier variant
        # gets a dedicated drafter instance bound as a DraftPair
        self.spec_drafter = self.spec_verifier = None
        self.spec_k = int(spec_k)
        if speculative is not None:
            assert mode == "continuous", \
                "speculative decoding needs the continuous engine"
            drafter, _, verifier = speculative.partition(":")
            assert drafter and verifier and drafter != verifier, \
                f"speculative= wants 'drafter:verifier', got {speculative!r}"
            assert drafter in variants and verifier in variants, \
                f"speculative variants must be loaded: {speculative!r}"
            assert 1 <= self.spec_k <= max_new, \
                "spec_k must fit inside the decode budget"
            self.spec_drafter, self.spec_verifier = drafter, verifier
        self.variant_defs = dict(variants)       # name -> (cfg, accuracy)
        self.max_batch = max_batch
        self.prompt_len = prompt_len
        self.mode = mode
        self.max_new = max_new
        self.decode_chunk = decode_chunk
        self.queue_cap = queue_cap
        self.use_pallas = use_pallas
        # KV discipline of every backend this engine creates: "dense" is the
        # per-slot ring cache; "paged" the shared page pool (page_size tokens
        # per page, pool sized kv_pool_pages or full slot parity by default)
        self.kv_cache = kv_cache
        self.kv_page_size = kv_page_size
        self.kv_pool_pages = kv_pool_pages
        self.kv_prefix_sharing = kv_prefix_sharing
        # enforce_units: an allocation of n units caps the variant at n
        # concurrent slots — the same units -> concurrency mapping the
        # profiling subsystem measures th(n) under, so measured profiles
        # describe live capacity exactly (off by default: PR-1 semantics,
        # where units are cost bookkeeping and batching always uses the
        # full slot budget)
        self.enforce_units = enforce_units
        self.backends: Dict[str, VariantBackend] = {}
        self.units: Dict[str, int] = {}
        self.queues: Dict[str, Deque[Request]] = {}
        self.done: List[Request] = []
        self.rejected: int = 0
        self.cost_log: List[Tuple[float, int]] = []
        # replica sharding (cluster fabric): backends keyed by replica rid
        # ("variant#i") instead of variant name; ``nodes=None`` keeps the
        # legacy one-backend-per-variant layout byte-for-byte.
        self.fabric: Optional[ReplicaFabric] = None
        self.router = None
        if nodes is not None:
            # loading is synchronous on this engine (construction blocks for
            # the measured jit warm-up), so fabric readiness is immediate
            self.fabric = ReplicaFabric(nodes, policy=placement,
                                        replica_size=replica_size,
                                        rt_fn=lambda m: 0.0)
            self.router = make_router(router, metrics=self.metrics)

    def _make_backend(self, variant: str,
                      device: Optional[jax.Device] = None) -> VariantBackend:
        cfg, acc = self.variant_defs[variant]
        kw = dict(max_batch=self.max_batch, prompt_len=self.prompt_len,
                  max_new=self.max_new, decode_chunk=self.decode_chunk,
                  use_pallas=self.use_pallas, chunked=self.sched.chunked,
                  prefill_chunk_tokens=self.prefill_chunk,
                  preemption=self.preemption, clock=self.clock,
                  obs=self.obs, device=device,
                  # async-tick admission pipelining: build the continuation
                  # machinery so monolithic admission can route through the
                  # dispatch/commit pipeline (chunked admission of the same
                  # zero-padded prompt — bitwise-identical outputs)
                  build_chunked=self.async_tick)
        if self.kv_cache == "paged":
            b = PagedVariantBackend(variant, cfg, acc,
                                    page_size=self.kv_page_size,
                                    pool_pages=self.kv_pool_pages,
                                    prefix_sharing=self.kv_prefix_sharing,
                                    **kw)
        else:
            b = VariantBackend(variant, cfg, acc, **kw)
        if variant == self.spec_verifier:
            self._attach_drafter(b)
        return b

    def _attach_drafter(self, verifier: VariantBackend) -> None:
        """Materialize a dedicated drafter backend for one verifier replica
        and bind them as a ``DraftPair``. The drafter is hidden from
        routing/queues — it exists purely as the verifier's proposal
        engine, with its own KV (pool) sized for scratch headroom: drafts
        are written up to k positions past the last committed token before
        acceptance, plus one in-flight zombie round under async commit."""
        dcfg, dacc = self.variant_defs[self.spec_drafter]
        kw = dict(max_batch=self.max_batch, prompt_len=self.prompt_len,
                  max_new=self.max_new, decode_chunk=self.spec_k,
                  use_pallas=self.use_pallas, chunked=True,
                  prefill_chunk_tokens=self.prefill_chunk,
                  preemption="none", clock=self.clock, obs=self.obs,
                  cache_headroom=self.spec_k + 2, device=verifier.device)
        if self.kv_cache == "paged":
            d = PagedVariantBackend(self.spec_drafter, dcfg, dacc,
                                    page_size=self.kv_page_size, **kw)
        else:
            d = VariantBackend(self.spec_drafter, dcfg, dacc, **kw)
        DraftPair(verifier, d, self.spec_k)

    # ------------------------------------------------------------ ClusterAPI
    def apply_allocation(self, t: float, units: Mapping[str, int]) -> None:
        target = {m: n for m, n in units.items() if n > 0}
        if self.fabric is not None:
            self._apply_fabric(t, target)
            return
        for m, n in target.items():
            if m not in self.backends:
                self.backends[m] = self._make_backend(m)
                self.queues.setdefault(m, deque())
            self.backends[m].units = n
            self.backends[m].slot_cap = n if self.enforce_units else None
        for m in list(self.backends):
            if m not in target:
                b = self.backends.pop(m)
                # connection draining: finish in-flight work; waiting requests
                # stay queued and are rebalanced onto survivors at the next
                # tick — an accepted request is never dropped by a switch
                self.done.extend(b.drain_slots(t))
                b.release()
        self._rebalance_queues()
        self.units = dict(target)
        self.cost_log.append((t, sum(target.values())))

    def _apply_fabric(self, t: float, target: Mapping[str, int]) -> None:
        """Replica-granular create-then-remove: the fabric diffs the target
        replica multiset, new replicas become whole ``VariantBackend``
        instances (ready on construction — the warm-up blocks here, which IS
        rt_m), surplus replicas drain their slots and requeue waiters."""
        tr = self.fabric.apply(t, target)
        node_ids = list(self.fabric.nodes)
        for rep in tr.created:
            # one device per node: node i's replicas live on device i mod n
            dev = jax.devices()[node_ids.index(rep.node_id)
                                % jax.device_count()]
            b = self._make_backend(rep.variant, dev)
            b.units = rep.units
            b.slot_cap = min(rep.units, self.max_batch) \
                if self.enforce_units else None
            b.slow_factor = rep.slow_factor
            rep.handle = b
            self.backends[rep.rid] = b
            self.queues.setdefault(rep.rid, deque())
        for rep in self.fabric.purge(t):     # switch_t == t: loads blocked
            b = self.backends.pop(rep.rid, None)
            if b is not None and not rep.crashed:
                self.done.extend(b.drain_slots(t))
                b.release()
        self._rebalance_queues()
        self.units = dict(target)
        self.cost_log.append((t, self.fabric.provisioned_units()))

    def _rebalance_queues(self) -> None:
        """Move requests queued on retired backends to the least-loaded live
        ones. Accepted work is never dropped, so a switch may transiently
        push a survivor's queue past ``queue_cap``; only *new* submissions
        are bounded (backpressure). If an allocation empties the cluster,
        orphans stay queued (visible via ``backlog``/``summarize['pending']``)
        and are served once the next allocation loads a variant."""
        if not self.backends:
            return                       # keep orphans until a variant loads
        dead = [m for m in self.queues if m not in self.backends]
        for m in dead:
            for r in self.queues.pop(m):
                tgt = min(self.backends,
                          key=lambda n: len(self.queues.setdefault(n, deque())))
                r.backend = tgt
                self.queues.setdefault(tgt, deque()).append(r)

    def loaded_variants(self, t: float) -> Set[str]:
        if self.fabric is not None:
            return set(self.fabric.variants_ready(t))
        return set(self.backends)

    def backlog(self, t: float) -> float:
        """Queued-but-not-in-service depth (requests waiting for a slot) —
        the shared ``ClusterAPI.backlog`` semantics; in-slot requests are in
        service and excluded."""
        return float(sum(len(q) for q in self.queues.values()))

    def capacity_factor(self, t: float) -> float:
        """Fraction of the target allocation actually live (1.0 without a
        fabric). Lets reactive controllers see crashes immediately."""
        return self.fabric.capacity_factor(t) if self.fabric is not None else 1.0

    def mark_warm(self, variants: Optional[Sequence[str]] = None,
                  t: float = 0.0) -> None:
        """Harness parity with the simulator: engine backends are ready the
        moment construction returns, so warm start is a no-op."""

    def in_flight(self) -> int:
        return sum(b.active_slots for b in self.backends.values())

    def flush_pending(self, now: float) -> int:
        """Commit every backend's in-flight async tick (no-op in sync mode
        or when nothing is pending). ``drain``/``drain_slots`` flush on
        their own; faults and external shutdown paths call this so
        bookkeeping never trails the last dispatch. Returns #completed."""
        n0 = len(self.done)
        for b in self.backends.values():
            self.done.extend(b.flush_pending(now))
        return len(self.done) - n0

    def kv_pool_stats(self) -> Optional[Dict]:
        """Aggregate page-pool usage across paged backends (None when the
        engine runs dense KV caches) — the memory-true capacity gauge that
        admission already enforces per backend via ``free_slots``.

        Occupancy-style levels are read off the live pools and published as
        registry gauges; the cumulative counters (prefix lookups/hits, fresh
        pages) are read from the registry, where the pools themselves
        already increment them — so this surface, the benchmarks, and the
        JSONL dump all report the same numbers from the same source. (When
        the registry is disabled the pools' own attribute counters are the
        fallback — live backends only, retired pools' history is gone.)"""
        pools = [b.pool for b in self.backends.values()
                 if isinstance(b, PagedVariantBackend)]
        if not pools:
            return None
        m = self.metrics
        used = sum(p.used_pages for p in pools)
        usable = sum(p.usable_pages for p in pools)
        shared = sum(p.shared_pages for p in pools)
        retained = sum(p.retained_pages for p in pools)
        occupancy = used / max(usable, 1)
        m.set("kv.used_pages", used)
        m.set("kv.usable_pages", usable)
        m.set("kv.shared_pages", shared)
        m.set("kv.retained_pages", retained)
        m.set("kv.occupancy", occupancy)
        if m.enabled:
            lookups = int(m.value("kv.prefix_lookups"))
            hits = int(m.value("kv.prefix_hits"))
            fresh = int(m.value("kv.pages_allocated"))
        else:
            lookups = sum(p.prefix_lookups for p in pools)
            hits = sum(p.prefix_hits for p in pools)
            fresh = sum(p.fresh_pages_allocated for p in pools)
        return {"used_pages": used, "usable_pages": usable,
                "occupancy": occupancy, "shared_pages": shared,
                "retained_pages": retained,
                "prefix_lookups": lookups, "prefix_hits": hits,
                "prefix_hit_rate": hits / max(lookups, 1),
                "fresh_pages_allocated": fresh}

    # ----------------------------------------------------------------- faults
    def inject_fault(self, now: float, event: FaultEvent) -> None:
        """Apply one ``repro.cluster.faults`` event (fabric mode only)."""
        if self.fabric is None:
            raise RuntimeError("fault injection requires the replica fabric "
                               "(construct the engine with nodes=)")
        if event.kind == "node_crash":
            self._crash_node(now, event.target)
        elif event.kind == "node_recover":
            self.fabric.recover_node(now, event.target)
        elif event.kind in ("replica_slowdown", "replica_restore"):
            factor = event.factor if event.kind == "replica_slowdown" else 1.0
            if self.fabric.slow_replica(now, event.target, factor):
                rep = self.fabric.replicas[event.target]
                if rep.handle is not None:
                    rep.handle.slow_factor = rep.slow_factor
        if self.obs.flight is not None:   # snapshot the run-up to the fault
            self.obs.flight.trigger(f"fault_{event.kind}", now,
                                    extra={"target": event.target,
                                           "factor": event.factor})

    def _crash_node(self, now: float, node_id: str) -> None:
        """Kill every replica on the node NOW (no drain): their in-flight
        and queued requests are re-submitted to survivors — retry semantics;
        latency keeps the original arrival stamp, so the failure's SLO cost
        is measured, not hidden."""
        # commit in-flight async ticks first: a request whose last tokens
        # are already committed on a SURVIVOR must not be re-submitted, and
        # the killed replicas' zombies re-enter the queue as full retries
        self.flush_pending(now)
        killed = self.fabric.crash_node(now, node_id)
        orphans: List[Tuple[str, Request]] = []
        for rep in killed:
            b = self.backends.pop(rep.rid, None)
            orphans.extend((rep.variant, r)
                           for r in self.queues.pop(rep.rid, deque()))
            if b is not None:
                orphans.extend((rep.variant, r)
                               for r in b.slot_req if r is not None)
                b.release()
        self.fabric.purge(now)
        for variant, r in orphans:
            r.service_start = 0.0        # retry starts from the queue again
            # retry keeps the dispatcher's variant choice: surviving replicas
            # of the same variant absorb first; _route_replica spills to the
            # whole cluster only if none are left. Full/empty: counts rejected
            self.submit(r, variant)

    # ---------------------------------------------------------------- serving
    def submit(self, req: Request, backend: Optional[str]) -> bool:
        """Enqueue on an admission queue. Legacy: ``backend`` names the
        variant's single backend. Fabric mode: two-level routing — the
        caller's dispatcher already picked the variant; the ``RoutingAPI``
        picks the replica among it (power-of-two-choices least-outstanding
        by default). Returns False — backpressure — when the queue is full."""
        if not self.backends:
            self.rejected += 1
            self.metrics.inc("requests.rejected")
            if self.windows.on:
                self.windows.inc("requests.rejected", self.clock())
            self.tracer.request_event(req, ev.REJECTED, self.clock(),
                                      reason="no_backend")
            return False
        if self.fabric is not None:
            name = self._route_replica(req, backend)
        else:
            name = backend if backend in self.backends else \
                min(self.queues, key=lambda m: len(self.queues[m])) \
                if self.queues else min(self.backends)
        q = self.queues.setdefault(name, deque())
        if len(q) >= self.queue_cap:
            self.rejected += 1
            self.metrics.inc("requests.rejected")
            if self.windows.on:
                self.windows.inc("requests.rejected", self.clock())
            self.tracer.request_event(req, ev.REJECTED, self.clock(),
                                      backend=name, reason="queue_full")
            return False
        req.backend = name
        q.append(req)
        self.metrics.inc("requests.submitted")
        if self.windows.on:
            self.windows.inc("requests.submitted", self.clock())
        # stamped at clock(), not req.arrival: a crash retry re-queues with
        # its original arrival preserved, and span times must stay monotone
        self.tracer.request_event(req, ev.QUEUED, self.clock(), backend=name,
                                  arrival=req.arrival)
        return True

    def _route_replica(self, req: Request, variant: Optional[str]) -> str:
        """Level 2 of two-level routing: pick the replica rid. Outstanding =
        queued + in-slot requests, normalized by replica units so bigger
        replicas absorb proportionally more."""
        rids = [rid for rid, b in self.backends.items()
                if variant is not None and b.name == variant]
        if not rids:                     # unknown/retired variant: all live
            rids = list(self.backends)
        views = [ReplicaView(
            rid,
            len(self.queues.get(rid, ())) + self.backends[rid].active_slots,
            self.backends[rid].units) for rid in rids]
        return self.router.pick(views)

    def step(self, now: float) -> int:
        """ONE engine tick (continuous mode): each backend admits waiting
        requests into free slots, then runs one jitted decode chunk.
        Non-blocking — the real-time loops in ``examples/`` and
        ``benchmarks/bench_engine.py`` call this between arrival batches."""
        if self.mode != "continuous":
            return self._pump_legacy(now)
        return self._tick(now)

    def pump(self, now: float) -> int:
        """Serve everything currently queued; returns #completed.

        Blocking convenience wrapper: in continuous mode it ticks until the
        queues and slots are empty; in pump mode it drains every queue in
        micro-batches (the legacy path)."""
        if self.mode == "continuous":
            return self.drain(now)
        return self._pump_legacy(now)

    def _tick(self, now: float) -> int:
        """One scheduler-driven engine tick per backend, in four phases:
        preempt (optional) → admit (scheduler-ordered) → dispatch → commit.
        The sync default commits the exec it just dispatched; the async
        tick commits the previous tick's. With the default FIFO scheduler
        and no preemption this is exactly the legacy admit+decode tick.

        With tracing on, the tick and each backend's phases are ``_Span``s:
        start and end on ``time.perf_counter`` in one ``TickRecord`` per
        backend (``spans``, with the legacy per-phase costs derived from
        them), and ``engine.<phase>`` profiler annotations. Tracing off
        runs none of it: one branch per phase."""
        if not self.tracer.on:
            return self._tick_backends(now, None)
        recs: List[TickRecord] = []
        tick: Dict[str, Tuple[float, float]] = {}
        with _Span(tick, "tick"):
            n = self._tick_backends(now, recs)
        for rec in recs:
            rec.spans["tick"] = tick["tick"]
            self.tracer.tick(rec)
        return n

    def _tick_backends(self, now: float,
                       recs: Optional[List[TickRecord]]) -> int:
        """Tick every backend; with ``recs`` (tracing on) time each phase
        and append one ``TickRecord`` per backend."""
        self._rebalance_queues()
        done_before = len(self.done)
        for name, b in self.backends.items():
            q = self.queues.get(name, deque())
            if recs is None:
                self._preempt(name, b, q, now)
                self._admit(b, q, now)
                _, pend = self._dispatch(b, now)
                self.done.extend(b.commit_exec(pend, now))
                continue
            bdone = len(self.done)
            sp = b._spans = {}
            with _Span(sp, "preempt", name):
                n_preempted = self._preempt(name, b, q, now)
            with _Span(sp, "admit", name):
                n_admitted = self._admit(b, q, now)
            with _Span(sp, "dispatch", name):
                kind, pend = self._dispatch(b, now)
            if pend is not None:
                with _Span(sp, "commit", name):
                    self.done.extend(b.commit_exec(pend, now))
            b._spans = None
            nan = float("nan")
            commit_ms = gap_ms = wait_ms = hidden_ms = nan
            if not self.async_tick:      # exec = dispatch + its own commit
                exec_ms = (sp.get("commit", sp["dispatch"])[1]
                           - sp["dispatch"][0]) * 1e3
            else:
                exec_ms = _span_ms(sp, "dispatch")
                if pend is not None:
                    commit_ms = _span_ms(sp, "commit")
                    wait_ms = _span_ms(sp, "commit_wait")
                    if "commit_wait" in sp:
                        gap_ms = (sp["commit_wait"][0]
                                  - pend.dispatched_at) * 1e3
                    # host work done this tick while t-1 was still in
                    # flight on the device (preempt + admit + dispatch)
                    hidden_ms = (sp["dispatch"][1] - sp["preempt"][0]) * 1e3
            occ = (b.kv_pool_occupancy
                   if isinstance(b, PagedVariantBackend) else nan)
            recs.append(TickRecord(
                backend=name, t=now, kind=kind,
                preempt_ms=_span_ms(sp, "preempt"),
                admit_ms=_span_ms(sp, "admit"), exec_ms=exec_ms,
                active=b.active_slots,
                prefilling=len(b._prefilling), queued=len(q),
                admitted=n_admitted, preempted=n_preempted,
                completed=len(self.done) - bdone, pool_occupancy=occ,
                commit_ms=commit_ms, commit_gap_ms=gap_ms,
                commit_wait_ms=wait_ms, hidden_host_ms=hidden_ms, spans=sp))
        return len(self.done) - done_before

    def _preempt(self, name: str, b: VariantBackend, q: Deque[Request],
                 now: float) -> int:
        """Preemption phase: retire the scheduler's victims (requeued,
        migrated or dropped). Returns how many were preempted."""
        if self.preemption == "none" or not q:
            return 0
        # finished-but-uncommitted zombie slots are not preemptable: their
        # request is already complete by count, only its token read-back
        # lags (async commit lag)
        resident = [r for s, r in enumerate(b.slot_req)
                    if r is not None and s not in b._uncommitted_done]
        victims = self.sched.select_victims(resident, list(q), now,
                                            len(b.free_slots))
        for v in victims:
            if b.preempt(v, now) == "dropped":
                self.done.append(v)
                continue            # resumes later, tokens preserved
            tq = q
            if self.preemption == "migrate":
                # cross-variant migration: resume on a cheaper variant via
                # chunked prefill continuation — the accuracy-for-latency
                # escape hatch under deadline pressure (stays put when
                # nothing is cheaper)
                tgt = migration_target(name, self.backends, self.queues)
                if tgt is not None:
                    v.backend = tgt
                    tq = self.queues.setdefault(tgt, deque())
                    self.metrics.inc("requests.migrated")
                    if self.windows.on:
                        self.windows.inc("requests.migrated", now)
            tq.append(v)
        return len(victims)

    def _admit(self, b: VariantBackend, q: Deque[Request], now: float) -> int:
        """Admission phase: move scheduler-ordered waiters into free slots.
        Returns how many joined."""
        free_n = len(b.free_slots)
        if not (q and free_n):
            return 0
        ordered = self.sched.order(list(q), now)
        joiners, rest = ordered[:free_n], ordered[free_n:]
        q.clear()
        q.extend(rest)
        if self.sched.chunked:
            self.done.extend(b.admit_chunked(joiners, now))
        elif self.async_tick and b.chunked:
            # async-tick headroom: monolithic admission would prefill
            # synchronously inside the tick; chunked admission of the same
            # zero-padded prompt (right_sized stays off) defers the prefill
            # into the dispatch/commit pipeline with identical outputs
            self.done.extend(b.admit_chunked(joiners, now))
        else:
            # resumed requests need prefill continuation even under
            # monolithic admission (preemption builds the machinery)
            fresh = [r for r in joiners if not r.resume_tokens]
            self.done.extend(b.admit(fresh, now))
            resumed = [r for r in joiners if r.resume_tokens]
            if resumed:
                self.done.extend(b.admit_chunked(resumed, now))
        return len(joiners)

    def _dispatch(self, b: VariantBackend, now: float
                  ) -> Tuple[str, Optional[_PendingExec]]:
        """Dispatch phase: enqueue this tick's exec. Returns (tick kind, the
        exec this tick commits): the async tick parks its own exec and
        commits the previous tick's — whose read and bookkeeping then hide
        behind this tick's device compute — the sync tick commits its own."""
        if not self.async_tick:
            return b.dispatch_exec(now)
        prev, b._pending = b._pending, None
        kind, b._pending = b.dispatch_exec(now)
        return kind, prev

    def drain(self, now: float, max_ticks: int = 10_000) -> int:
        """Tick until every queue and slot is empty."""
        if self.mode != "continuous":
            return self._pump_legacy(now)
        served = 0
        for _ in range(max_ticks):
            if not self.backends or (self.backlog(now) == 0
                                     and self.in_flight() == 0):
                break
            served += self._tick(now)
        return served

    def _pump_legacy(self, now: float) -> int:
        self._rebalance_queues()
        served = 0
        for name in list(self.queues):
            q = self.queues[name]
            if not q or name not in self.backends:
                continue
            b = self.backends[name]
            reqs = list(q)
            q.clear()
            for i in range(0, len(reqs), b.max_batch):
                chunk = reqs[i:i + b.max_batch]
                t_service = self.clock()
                for r in chunk:
                    r.service_start = t_service
                prompts = np.stack([
                    np.pad(r.tokens[:self.prompt_len],
                           (0, max(0, self.prompt_len - len(r.tokens))))
                    for r in chunk])
                gen = min(max(r.max_new for r in chunk), self.max_new)
                out = b.generate(prompts, max_new=gen)
                tdone = self.clock()
                for j, r in enumerate(chunk):
                    r.output = out[j, :min(r.max_new, self.max_new)]
                    r.completion = tdone
                    r.accuracy = b.accuracy
                    b._obs_complete(r)
                    self.done.append(r)
                    served += 1
        return served

    # ---------------------------------------------------------------- metrics
    def summarize(self, slo_ms: float, best_accuracy: float) -> Dict:
        out = summarize_requests(
            [r.arrival for r in self.done],
            [r.latency_ms for r in self.done],
            [r.accuracy for r in self.done],
            slo_ms=slo_ms, best_accuracy=best_accuracy,
            cost_samples=self.cost_log,
            queue_ms=[r.queue_wait_ms for r in self.done],
            service_ms=[r.service_ms for r in self.done],
            slo_list_ms=[r.slo_ms for r in self.done],
            dropped=[r.dropped for r in self.done])
        if out:
            out["rejected"] = self.rejected
            # accepted but not yet served (queued + in flight) — nonzero when
            # summarizing mid-run or after an allocation emptied the cluster
            out["pending"] = int(sum(len(q) for q in self.queues.values())
                                 + self.in_flight())
            pool = self.kv_pool_stats()
            if pool is not None:
                out["kv_pool_occupancy"] = pool["occupancy"]
                out["kv_shared_pages"] = pool["shared_pages"]
                out["kv_prefix_hit_rate"] = pool["prefix_hit_rate"]
            pairs = [b._spec_pair for b in self.backends.values()
                     if b._spec_pair is not None]
            if pairs:
                rounds = sum(int(p.slot_rounds.sum()) for p in pairs)
                acc = sum(int(p.slot_accepted.sum()) for p in pairs)
                prop = sum(int(p.slot_proposed.sum()) for p in pairs)
                out["spec_accept_rate"] = acc / max(prop, 1)
                out["spec_tokens_per_step"] = \
                    (acc + rounds) / max(rounds, 1)
        return out

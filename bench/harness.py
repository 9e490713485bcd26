"""One run of one cell: set-up, the measured window, the check.

The harness's contract with the program under test (``src/repro``):
``InProcessServingEngine(...)``, ``apply_allocation``, ``submit``, ``step``,
``flush_pending``, ``in_flight``, ``kv_pool_stats``, ``done`` and
``tracer.ticks`` (``TickRecord``); on each backend ``slot_req``,
``slot_tokens``, ``slot_pos``, ``decode_chunk``, ``params``,
``device``, ``model.init`` (for the layout the weights must have) and
``release``; the stamps of ``Request``. Everything else (traffic, weights,
reference, trace reduction, peaks) lives in this directory; what belongs to
one architecture lives in its module (``arch_module``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

import roofline  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

WARM_RID = 1 << 40        # request ids of the warm-up, apart from the window's
LLAMA = "bench/arch/llama.py"   # of a configuration that names no "arch"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> Tuple[Dict, Dict, Dict]:
    """(workload entry, configuration file, traffic mix) by name."""
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    wl = next((w for w in bm["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{[w['name'] for w in bm['workloads']]}")
    cf = next(c for c in bm["configs"] if c["name"] == wl["config"])
    cfg = load_json(os.path.join(root, cf["file"]))
    mix = traffic.load(os.path.join(BENCH, "traffic", wl["traffic"] + ".json"))
    metrics = {"end_to_end": [m for m in bm["end_to_end"]
                              if workload in m.get("workloads", [workload])],
               "per_layer": [m for m in bm["per_layer"]
                             if workload in m.get("workloads", [workload])]}
    wl = dict(wl, metrics=metrics)
    return wl, cfg, mix


def require_chips(n: int):
    """The devices JAX finds; no accelerator, or too few chips, ends the
    run with a non-zero exit and no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform is "
                         f"{devs[0].platform!r}); there is no CPU fallback")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX sees "
                         f"{len(devs)}")
    return devs


_ARCH: Dict[str, object] = {}


def arch_module(cfg: Dict):
    """The architecture module of a configuration: the Python file that its
    ``"arch"`` names, relative to the repository root, else the Llama form.
    Loaded once a path. It provides:

    - ``arch_of(cfg, num_layers)``: one rung's shapes, hashable (jit takes
      it as a static argument);
    - ``run_key(seed)``: the key that every weight of a run comes from;
    - ``make_tree(key, arch, rung, dtype)``: the weights in the served
      layout, made one layer at a time under jit (arguments 1-3 static);
    - ``served_gaps(arch, key, rung, dtype, samples, *, max_len, max_rows,
      mode)``: the float32 reference's widest gap below its best logit over
      the served tokens (``gap``, ``tokens``); ``mode="fp8"`` adds the
      control's (``control_gap``);
    - ``kv_bytes_per_token(arch)``: the cache one token holds, all layers;
    - ``KERNELS``: the op kinds to time in a trace, each mapped to the name
      its readings take in the trace facts (``<name>_s``, ``_calls``,
      ``_flops``, ``_bytes``);
    - ``step_work(arch, step)``: for one backend's ``StepRows`` in a traced
      step, the model FLOPs of its useful tokens and, per op kind of
      ``KERNELS``, that kernel's (FLOPs, bytes).
    """
    path = os.path.abspath(os.path.join(ROOT, cfg.get("arch", LLAMA)))
    if path not in _ARCH:
        name = "arch_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT))
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod       # dataclasses look their module up
        spec.loader.exec_module(mod)
        _ARCH[path] = mod
    return _ARCH[path]


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads, and their time."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        from jax import monitoring
        self.n = {self.COMPILE: 0, self.LOAD: 0}
        self.s = {self.COMPILE: 0.0, self.LOAD: 0.0}
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event in self.n:
            self.n[event] += 1
            self.s[event] += secs

    def snapshot(self) -> Tuple[int, float, int, float]:
        return (self.n[self.COMPILE], self.s[self.COMPILE],
                self.n[self.LOAD], self.s[self.LOAD])


@dataclass
class Stamp:
    """What a streaming client of one request sees, on the host clock."""
    due: float
    rung: str
    prompt_len: int
    budget: int
    refused: bool = False
    first: Optional[float] = None
    done: Optional[float] = None
    n_out: int = 0
    req: object = None


@dataclass
class StepSnap:
    """Host view after one engine step, kept while the profiler runs."""
    t0: float
    t1: float
    ticks: List                     # this step's TickRecords
    rows: Dict[str, List]           # backend -> [(rid or None, slot_pos)]


@dataclass
class StepRows:
    """One backend in one traced step, as the host saw it."""
    kind: str                       # the tick's kind: "fused" or "decode"
    calls: int                      # paged attention calls a layer
    starts: List[int]               # each bound row's position before it
    useful: List[Tuple[int, int]]   # positions [lo, hi) that serve a request
    ticks: List                     # this backend's TickRecords of the step


@dataclass
class Outcome:
    stamps: Dict[int, Stamp]
    t0: float
    t_end: float                    # the window's close
    t_drained: float
    committed_in_window: int
    lateness_s: List[float]
    ticks: List = field(default_factory=list)
    kv: Tuple[Optional[Dict], Optional[Dict]] = (None, None)
    compiles_in_window: int = 0
    snaps: List[StepSnap] = field(default_factory=list)
    trace_dir: Optional[str] = None
    host_spans: List[Tuple[float, float, str]] = field(default_factory=list)
    step_s: List[Tuple[float, float, int]] = field(default_factory=list)
    gc_s: List[Tuple[int, float]] = field(default_factory=list)
    in_use_bytes: int = 0           # device memory in use at the close
    kv_live_bytes: int = 0          # K and V the bound rows hold at the close


class Cell:
    """The engine of one configuration, with the benchmark's weights."""

    def __init__(self, cfg: Dict, seed: int, trace: bool,
                 clock: Callable[[], float] = time.perf_counter):
        import jax
        from repro.configs.base import get_config
        from repro.serving.engine import InProcessServingEngine
        self.cfg, self.seed, self.clock = cfg, seed, clock
        self.counter = CompileCounter()
        self.split: Dict[str, float] = {}
        self.form = arch_module(cfg)       # the architecture module
        variants, self.arch, self.rung_index = {}, {}, {}
        base = get_config(cfg["registry"])
        repl = dict(cfg["model"])
        repl.pop("padded_vocab", None)
        for i, r in enumerate(cfg["rungs"]):
            variants[r["name"]] = (base.replace(num_layers=r["num_layers"],
                                                **repl), r["accuracy"])
            self.arch[r["name"]] = self.form.arch_of(cfg, r["num_layers"])
            self.rung_index[r["name"]] = i
            self.split[r["name"]] = r["share"]
        eng = cfg["engine"]
        self.max_batch = eng["max_batch"]
        self.prompt_len = eng["prompt_len"]
        self.max_new = eng["max_new"]
        self.split_s: Dict[str, float] = {}
        self.peaks: Dict[str, int] = {}

        t = time.perf_counter()
        c0 = self.counter.snapshot()
        self.engine = InProcessServingEngine(variants, clock=clock,
                                             trace=trace, **eng)
        # deepest rung first: its load, the largest, meets an empty chip
        order = sorted(cfg["rungs"], key=lambda r: -r["num_layers"])
        self.engine.apply_allocation(0.0, {r["name"]: 1 for r in order})
        c1 = self.counter.snapshot()
        self.split_s["engine_load"] = time.perf_counter() - t
        self.split_s["compile"] = c1[1] - c0[1]
        self.split_s["cache_load"] = c1[3] - c0[3]
        self.n_compiled, self.n_loaded = c1[0] - c0[0], c1[2] - c0[2]
        self._peak("engine_load")

        t = time.perf_counter()
        self._load_weights(jax)
        self.split_s["weights"] = time.perf_counter() - t
        self._peak("weights")

    def _peak(self, stage: str) -> None:
        import jax
        st = jax.devices()[0].memory_stats() or {}
        self.peaks[stage] = int(st.get("peak_bytes_in_use", 0))

    def _load_weights(self, jax) -> None:
        """Put the benchmark's weights in place of the engine's own: free
        the engine's first, then make each rung's on its device in one
        jitted call, in the served dtype."""
        dtype = self.cfg["model"]["param_dtype"]
        key = self.form.run_key(self.seed)
        for name, b in self.engine.backends.items():
            a, idx = self.arch[name], self.rung_index[name]
            made = jax.jit(self.form.make_tree, static_argnums=(1, 2, 3))
            want = jax.eval_shape(made, key, a, idx, dtype)
            have = jax.eval_shape(b.model.init, jax.random.PRNGKey(0))
            tu = jax.tree_util
            same = tu.tree_structure(want) == tu.tree_structure(have) and all(
                x.shape == y.shape and x.dtype == y.dtype
                for x, y in zip(tu.tree_leaves(want), tu.tree_leaves(have)))
            if not same:
                raise SystemExit(f"bench: the served layout of {name} is not "
                                 f"the one {self.form.__file__} makes")
            b.params = None
            gc.collect()
            with jax.default_device(b.device):
                p = made(key, a, idx, dtype)
            b.params = jax.device_put(p, b.device)
        jax.block_until_ready([b.params for b in self.engine.backends.values()])

    # ---------------------------------------------------------------- warm-up
    def warm_up(self, vocab: int) -> None:
        """Serve a few short requests on every rung, two of them sharing a
        prefix: the window's host paths (prefix hit, copy on write,
        retirement) then find every program compiled."""
        from repro.serving.api import Request
        t = time.perf_counter()
        c0 = self.counter.snapshot()
        rng = np.random.default_rng(12345)
        eng = self.engine
        head = rng.integers(0, vocab, 48).astype(np.int32)
        rid = WARM_RID
        for wave in range(2):
            for name in eng.backends:
                for j in range(2):
                    tail = rng.integers(0, vocab, 8 + 8 * j).astype(np.int32)
                    eng.submit(Request(rid=rid, tokens=np.concatenate(
                        [head, tail]), max_new=6, arrival=self.clock()), name)
                    rid += 1
            for _ in range(400):
                if not (eng.in_flight() or eng.backlog(self.clock())):
                    break
                eng.step(self.clock())
            eng.flush_pending(self.clock())
        served = sum(1 for r in eng.done if r.output is not None
                     and len(r.output) == 6)
        if served != 4 * len(eng.backends):
            raise SystemExit(f"bench: warm-up served {served} of "
                             f"{4 * len(eng.backends)} requests")
        eng.done.clear()
        c1 = self.counter.snapshot()
        self.split_s["warm_up"] = time.perf_counter() - t
        self._peak("warm_up")
        self.split_s["compile"] += c1[1] - c0[1]
        self.split_s["cache_load"] += c1[3] - c0[3]
        self.n_compiled += c1[0] - c0[0]
        self.n_loaded += c1[2] - c0[2]

    # ----------------------------------------------------------------- window
    def _snapshot(self, t0: float, t1: float, n_ticks: int) -> StepSnap:
        eng = self.engine
        rows = {name: [(None if r is None else r.rid, int(b.slot_pos[s]))
                       for s, r in enumerate(b.slot_req)]
                for name, b in eng.backends.items()}
        return StepSnap(t0, t1, list(eng.tracer.ticks[n_ticks:]), rows)

    def run_window(self, plan: List[traffic.Planned], seconds: float,
                   drain_s: float, trace_s: float,
                   fill: bool = False) -> Outcome:
        """Offer ``plan`` open loop for ``seconds``; then, up to ``drain_s``
        more, serve what was due in the window. With ``trace_s`` the JAX
        profiler records the last ``trace_s`` seconds of the window.

        With ``fill`` (a backlog mix), the requests due at 0 are submitted
        and served until every one in a slot has its first token before the
        window opens: the window then measures the batch as it decodes,
        and filling it is set-up."""
        import jax
        from repro.serving.api import Request
        eng, clock = self.engine, self.clock
        pending = deque(sorted(plan, key=lambda p: p.due_s))
        stamps: Dict[int, Stamp] = {}
        live: Dict[int, Stamp] = {}
        lateness: List[float] = []
        snaps: List[StepSnap] = []
        host_spans: List[Tuple[float, float, str]] = []
        tracing = False
        trace_dir = None
        n_done = 0
        kv0 = eng.kv_pool_stats()
        n_ticks0 = len(eng.tracer.ticks)
        c0 = self.counter.snapshot()

        def observe(t: float) -> None:
            nonlocal n_done
            for b in eng.backends.values():
                for s, r in enumerate(b.slot_req):
                    st = live.get(r.rid) if r is not None else None
                    if st is not None and st.first is None \
                            and len(b.slot_tokens[s]):
                        st.first = t
            for r in eng.done[n_done:]:
                st = live.pop(r.rid, None)
                if st is not None:
                    st.first = st.first if st.first is not None else t
                    st.done = t
                    st.n_out = len(r.output)
            n_done = len(eng.done)

        def committed() -> int:
            n = sum(st.n_out for st in stamps.values() if st.done is not None)
            for b in eng.backends.values():
                n += sum(len(b.slot_tokens[s]) for s, r in enumerate(b.slot_req)
                         if r is not None and r.rid in live)
            return n

        t0 = clock()
        n_before = 0
        if fill:
            while pending and pending[0].due_s <= 0.0:
                p = pending.popleft()
                st = Stamp(due=t0, rung=p.rung, prompt_len=len(p.prompt),
                           budget=min(p.max_new, self.max_new))
                st.req = Request(rid=p.rid, tokens=p.prompt,
                                 max_new=p.max_new, arrival=t0)
                stamps[p.rid] = st
                if eng.submit(st.req, p.rung):
                    live[p.rid] = st
                else:
                    st.refused = True
            give_up = clock() + 600.0
            while clock() < give_up and (
                    any(r is not None and not len(b.slot_tokens[s])
                        for b in eng.backends.values()
                        for s, r in enumerate(b.slot_req))
                    or not any(r is not None for b in eng.backends.values()
                               for r in b.slot_req)):
                eng.step(clock())
                observe(clock())
            n_before = committed()
            n_ticks0 = len(eng.tracer.ticks)
            kv0 = eng.kv_pool_stats()
            t0 = clock()
        t_end = t0 + seconds
        t_trace = t_end - trace_s if trace_s > 0 else math.inf
        # (start - t0, wall, the longest row's position after it) a step
        step_s: List[Tuple[float, float, int]] = []
        gc_s: List[Tuple[int, float]] = []       # (generation, wall) a pause
        gc_t = [0.0]

        def on_gc(phase: str, info: Dict) -> None:
            if phase == "start":
                gc_t[0] = time.perf_counter()
            else:
                gc_s.append((info["generation"],
                             time.perf_counter() - gc_t[0]))
        gc.callbacks.append(on_gc)
        while True:
            now = clock()
            if now >= t_end:
                break
            if not tracing and now >= t_trace:
                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(trace_dir)
                tracing = True
            ts = now
            while pending and t0 + pending[0].due_s <= now:
                p = pending.popleft()
                due = t0 + p.due_s
                st = Stamp(due=due, rung=p.rung, prompt_len=len(p.prompt),
                           budget=min(p.max_new, self.max_new))
                r = Request(rid=p.rid, tokens=p.prompt, max_new=p.max_new,
                            arrival=due)
                st.req = r
                stamps[p.rid] = st
                lateness.append(now - due)
                if eng.submit(r, p.rung):
                    live[p.rid] = st
                else:
                    st.refused = True
            if tracing:
                host_spans.append((ts, clock(), "bench.submit"))
            if eng.in_flight() or eng.backlog(now) > 0:
                s0 = clock()
                nt = len(eng.tracer.ticks)
                if tracing:
                    with jax.profiler.TraceAnnotation("bench.step"):
                        eng.step(s0)
                else:
                    eng.step(s0)
                s1 = clock()
                step_s.append((s0 - t0, s1 - s0, max(
                    int(b.slot_pos.max()) for b in eng.backends.values())))
                observe(s1)
                if tracing:
                    host_spans.append((s1, clock(), "bench.observe"))
                    snaps.append(self._snapshot(s0, s1, nt))
            else:
                nxt = min(t0 + pending[0].due_s if pending else t_end, t_end)
                if not tracing:
                    nxt = min(nxt, t_trace)
                s0 = clock()
                time.sleep(max(0.0, nxt - s0))
                if tracing:
                    host_spans.append((s0, clock(), "bench.wait_arrival"))
        t_close = clock()
        gc.callbacks.remove(on_gc)
        n_window = committed() - n_before
        in_use = int((jax.devices()[0].memory_stats() or {}).get(
            "bytes_in_use", 0))
        live_kv = sum(int(b.slot_pos[s])
                      * self.form.kv_bytes_per_token(self.arch[n])
                      for n, b in eng.backends.items()
                      for s, r in enumerate(b.slot_req) if r is not None)
        c1 = self.counter.snapshot()
        if tracing:
            jax.profiler.stop_trace()
        kv1 = eng.kv_pool_stats()
        ticks = list(eng.tracer.ticks[n_ticks0:])
        # serve what was due in the window, up to the cap
        t_cap = t_close + drain_s
        while live and clock() < t_cap:
            eng.step(clock())
            observe(clock())
        eng.flush_pending(clock())
        observe(clock())
        return Outcome(stamps=stamps, t0=t0, t_end=t_close,
                       t_drained=clock(), committed_in_window=n_window,
                       lateness_s=lateness, ticks=ticks, kv=(kv0, kv1),
                       compiles_in_window=(c1[0] - c0[0]) + (c1[2] - c0[2]),
                       snaps=snaps, trace_dir=trace_dir,
                       host_spans=host_spans, step_s=step_s, gc_s=gc_s,
                       in_use_bytes=in_use,
                       kv_live_bytes=live_kv)

    # ------------------------------------------------------------ the check
    def samples(self, out: Outcome, k: int, seed: int
                ) -> Dict[str, List[Tuple[np.ndarray, np.ndarray]]]:
        """Per rung, ``k`` requests that served tokens: the one with most
        served tokens, and the rest drawn from the seed. In-flight requests
        count with the tokens committed so far."""
        served: Dict[str, List[Tuple[int, np.ndarray, np.ndarray]]] = {}
        slot_of = {}
        for b in self.engine.backends.values():
            for s, r in enumerate(b.slot_req):
                if r is not None:
                    slot_of[r.rid] = np.asarray(b.slot_tokens[s], np.int32)
        for rid, st in sorted(out.stamps.items()):
            r = st.req
            toks = (np.asarray(r.output, np.int32) if st.done is not None
                    else slot_of.get(rid))
            if toks is None or len(toks) == 0:
                continue
            served.setdefault(st.rung, []).append(
                (rid, np.asarray(r.tokens, np.int32), toks))
        rng = np.random.default_rng(np.random.SeedSequence(
            [seed & 0xFFFFFFFF, seed >> 32, 0xC4EC]))
        picked = {}
        for rung, lst in served.items():
            lst.sort(key=lambda x: (-len(x[2]), x[0]))
            rest = lst[1:]
            idx = rng.permutation(len(rest))[:k - 1]
            picked[rung] = [(p, t) for _, p, t in [lst[0]] +
                            [rest[i] for i in sorted(idx)]]
        return picked

    def release(self) -> None:
        """Free the engine's device state before the reference runs."""
        for b in self.engine.backends.values():
            b.release()
        self.engine.backends.clear()
        gc.collect()


# ------------------------------------------------------------ traced window
def work_in_trace(cell: Cell, out: Outcome) -> Dict:
    """What the device was asked to do in the traced steps, from the host's
    view of every row before and after each step: the model FLOPs of the
    tokens that serve a request, and each timed kernel's FLOPs and bytes,
    as the architecture module counts them (``step_work``).

    A fused tick calls paged attention once per chunk token and a decode
    tick once per decode step, each call over every bound row. A row bound
    in this step is taken to have run a whole chunk (its start is not on
    the host)."""
    eng = cell.engine
    mflops = tokens = 0.0
    kernels: Dict[str, Dict[str, float]] = {}
    for prev, cur in zip(out.snaps, out.snaps[1:]):
        kinds = {t.backend: t.kind for t in cur.ticks}
        for name, rows in cur.rows.items():
            kind = kinds.get(name, "idle")
            if kind == "idle" or name not in prev.rows:
                continue
            calls = (eng.prefill_chunk if kind == "fused"
                     else eng.backends[name].decode_chunk)
            starts, useful = [], []
            for s, (rid, pos) in enumerate(rows):
                if rid is None:
                    continue
                prid, ppos = prev.rows[name][s]
                start = ppos if prid == rid else max(0, pos - calls)
                starts.append(start)
                st = out.stamps.get(rid)
                if st is not None:   # useful positions: the prompt and the
                    last = st.prompt_len + st.budget - 1   # fed-back tokens
                    useful.append((start, min(pos, last)))
                    tokens += max(0, min(pos, last) - start)
            step = StepRows(kind, calls, starts, useful,
                            [t for t in cur.ticks if t.backend == name])
            f, per_kernel = cell.form.step_work(cell.arch[name], step)
            mflops += f
            for k, (kf, kb) in per_kernel.items():
                acc = kernels.setdefault(k, {"flops": 0.0, "bytes": 0.0})
                acc["flops"] += kf
                acc["bytes"] += kb
    return {"model_flops": mflops, "model_tokens": tokens,
            "kernels": kernels}


def _tick_spans(snaps: List[StepSnap]) -> List[Tuple[float, float, str]]:
    """The engine's host phases in each traced step, rebuilt on the host
    clock from the ``TickRecord``s (backends tick one after the other)."""
    spans = []
    for sn in snaps:
        spans.append((sn.t0, sn.t1, "engine.step"))
        t = sn.t0
        for tr in sn.ticks:
            for ph, ms in (("preempt", tr.preempt_ms), ("admit", tr.admit_ms),
                           ("dispatch", tr.exec_ms),
                           ("commit", tr.commit_ms)):
                if ms == ms and ms > 0:        # NaN: phase did not run
                    spans.append((t, t + ms * 1e-3, f"{tr.backend}.{ph}"))
                    t += ms * 1e-3
    return spans


def reduce_trace(cell: Cell, out: Outcome, device_kind: str) -> Dict:
    """Busy and idle time, kernel time, work and the breakdown, over the
    traced steps (from the start of the second to the end of the last)."""
    if out.trace_dir is None:
        return {}
    tr = trace_reduce.load(trace_reduce.find_xplane(out.trace_dir))
    shutil.rmtree(out.trace_dir, ignore_errors=True)
    steps = [e for e in tr.host if e.name == "bench.step"]
    snaps = out.snaps
    if len(steps) != len(snaps) or len(snaps) < 3 or not tr.device_ops:
        log(f"bench: trace unusable ({len(steps)} step spans, {len(snaps)} "
            f"steps, {len(tr.device_ops)} device planes)")
        return {}
    # host clock -> trace clock, from the step spans
    offset = float(np.median([e.start_ns - s.t0 * 1e9
                              for e, s in zip(steps, snaps)]))
    lo, hi = snaps[1].t0 * 1e9 + offset, snaps[-1].t1 * 1e9 + offset
    spans = [(s * 1e9 + offset, e * 1e9 + offset, n)
             for s, e, n in out.host_spans + _tick_spans(snaps)]
    planes = sorted(tr.device_ops)
    busy = [trace_reduce.busy_ns(tr.device_ops[p], lo, hi) for p in planes]
    ops = tr.device_ops[planes[0]]
    idle = trace_reduce.gaps(ops, lo, hi)
    work = work_in_trace(cell, out)
    red = dict(model_flops=work["model_flops"],
               model_tokens=work["model_tokens"], kernels={},
               window_s=(hi - lo) * 1e-9, busy_s=float(np.mean(busy)) * 1e-9,
               peaks=roofline.peaks(device_kind),
               device_ops=trace_reduce.top_ops(ops, lo, hi),
               idle_gaps=trace_reduce.longest_gaps(idle, spans))
    for kind, short in cell.form.KERNELS.items():
        ns, n = trace_reduce.op_time_ns(
            ops, lo, hi, lambda x, k=kind: trace_reduce.op_kind(x) == k)
        w = work["kernels"].get(kind, {"flops": 0.0, "bytes": 0.0})
        got = dict(s=ns * 1e-9, calls=n, **w)
        red["kernels"][kind] = got
        red.update({f"{short}_{x}": v for x, v in got.items()})
    return red

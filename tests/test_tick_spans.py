"""Engine tick spans (DESIGN.md §Observability) and the model step's named
regions.

With tracing on, every backend's ``TickRecord`` carries the host phases of
its tick as ``spans`` — (start, end) on ``time.perf_counter`` — and the
engine wraps each phase in a ``jax.profiler.TraceAnnotation`` named
``engine.<phase>``. The contract under test:

* spans nest as ``obs.trace.TICK_PHASES`` says (``launch`` in ``dispatch``,
  ``commit_wait`` in ``commit``, every phase in the engine ``tick``),
  siblings do not overlap, and the tick's self time is never negative;
* tracing off records no span and builds no annotation;
* the annotations open and close in phase order, per backend;
* the compiled paged programs name every region of the model step in
  their HLO ``op_name`` metadata, for the device trace to group by.
"""
import numpy as np
import pytest

from conftest import PROMPT_LEN, VOCAB, tiny_engine

from repro.obs.trace import TICK_PHASES

REGIONS = ("embed", "attn_proj", "kv_write", "attn_kernel", "attn_out",
           "mlp", "head", "sample")
SIBLINGS = ("preempt", "admit", "dispatch", "commit")


def _serve(n_variants=1, **kw):
    """A staggered workload on a paged, chunked engine to completion, so
    the run holds fused, decode and idle ticks."""
    from repro.serving.api import Request
    t = [0.0]
    eng = tiny_engine(n_variants, kv_cache="paged", scheduler="chunked",
                      clock=lambda: t[0], **kw)
    eng.apply_allocation(0.0, {v: 1 for v in eng.variant_defs})
    rng = np.random.default_rng(3)
    names = sorted(eng.variant_defs)
    for i in range(6):
        eng.submit(Request(rid=i, tokens=rng.integers(0, VOCAB, PROMPT_LEN),
                           max_new=int(rng.integers(2, 7)), arrival=t[0]),
                   names[i % len(names)])
        eng.step(t[0])
        t[0] += 0.05
    for _ in range(200):
        if not eng.backlog(t[0]) and not eng.in_flight():
            break
        eng.step(t[0])
        t[0] += 0.05
    eng.step(t[0])                       # one idle tick
    return eng


def _inside(inner, outer):
    return outer[0] <= inner[0] <= inner[1] <= outer[1]


@pytest.mark.parametrize("async_tick", [True, False])
def test_traced_tick_spans_nest_and_self_time_is_not_negative(async_tick):
    eng = _serve(trace=True, async_tick=async_tick)
    recs = eng.tracer.ticks
    assert {r.kind for r in recs} >= {"fused", "decode", "idle"}
    assert any("commit" in r.spans for r in recs)
    for r in recs:
        sp = r.spans
        assert set(sp) <= set(TICK_PHASES)
        assert {"tick", "preempt", "admit", "dispatch"} <= set(sp)
        if r.kind != "idle":
            assert "launch" in sp
            # the sync tick commits its own exec, the async one the last
            assert async_tick or "commit" in sp
        if "commit" in sp:
            assert "commit_wait" in sp
        for phase, (s, e) in sp.items():
            assert s <= e
            parent = TICK_PHASES[phase]
            if parent is not None:
                assert _inside(sp[phase], sp[parent]), (phase, r)
        ran = sorted(sp[p] for p in SIBLINGS if p in sp)
        assert all(a[1] <= b[0] for a, b in zip(ran, ran[1:])), ran
        # the phase costs the harness reads are the spans' durations
        for phase in ("preempt", "admit"):
            s, e = sp[phase]
            assert getattr(r, f"{phase}_ms") == pytest.approx((e - s) * 1e3)
        if async_tick and "commit" in sp:
            s, e = sp["commit_wait"]
            assert r.commit_wait_ms == pytest.approx((e - s) * 1e3)
        tick = sp["tick"]
        busy = sum(e - s for s, e in ran)
        assert tick[1] - tick[0] - busy >= 0.0


def test_tracing_off_records_no_span_and_builds_no_annotation(monkeypatch):
    import jax
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    eng = _serve(trace=False, async_tick=True)
    assert len(eng.done) == 6
    assert built == [] and eng.tracer.ticks == []
    assert all(b._spans is None for b in eng.backends.values())
    _serve(trace=True, async_tick=True)     # the same patch sees tracing on
    assert "engine.tick" in built and "engine.launch" in built


def test_annotations_open_and_close_in_phase_order(monkeypatch):
    import jax
    log = []

    class Recording:
        def __init__(self, name, **kw):
            self.key = (name, kw.get("backend"))

        def __enter__(self):
            log.append(("enter",) + self.key)

        def __exit__(self, *exc):
            log.append(("exit",) + self.key)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    eng = _serve(n_variants=2, trace=True, async_tick=True)
    backends = list(eng.backends)
    assert len(backends) == 2
    # proper nesting: every exit closes the innermost open annotation
    stack = []
    for op, name, b in log:
        if op == "enter":
            stack.append((name, b))
        else:
            assert stack.pop() == (name, b)
    assert not stack
    # per engine tick: one tick around each backend's phases in order
    order = ["engine.preempt", "engine.admit", "engine.dispatch",
             "engine.launch", "engine.commit", "engine.commit_wait"]
    ticks, cur = [], None
    for op, name, b in log:
        if op != "enter":
            continue
        if name == "engine.tick":
            cur = []
            ticks.append(cur)
        else:
            cur.append((name, b))
    assert len(ticks) == len(eng.tracer.ticks) // len(backends)
    for tick in ticks:
        assert [b for _, b in tick] == sorted(
            [b for _, b in tick], key=backends.index)
        for b in backends:
            names = [n for n, bb in tick if bb == b]
            assert names[:3] == order[:3]
            assert names == [n for n in order if n in names]


@pytest.mark.parametrize("program", ["decode_chunk", "fused_chunk"])
def test_paged_programs_name_every_region(program):
    import jax.numpy as jnp
    eng = tiny_engine(kv_cache="paged", scheduler="chunked", use_pallas=True)
    eng.apply_allocation(0.0, {"small": 1})
    b = eng.backends["small"]
    if program == "decode_chunk":
        low = b._decode_chunk_p.lower(b.params, b.cache, b.cur_tok,
                                      b.page_buckets[0])
    else:
        B, ck = b.max_batch, b.prefill_chunk_tokens
        zi, zb = jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool)
        low = b._prefill_chunk.lower(b.params, b.cache, b.cur_tok,
                                     jnp.zeros((B, ck), jnp.int32), zi, zi,
                                     zb, zb)
    names = [ln.split('op_name="', 1)[1].split('"', 1)[0]
             for ln in low.compile().as_text().splitlines()
             if 'op_name="' in ln]
    for region in REGIONS:
        assert any(f"/{region}/" in n or n.endswith(f"/{region}")
                   for n in names), region

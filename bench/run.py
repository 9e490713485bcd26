#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) is looked up by name in
``BENCHMARK.json``; its configuration file, its mix and each of its metrics'
readers are found in this directory by name. Set-up builds the engine, puts
the benchmark's weights in it and warms every program the window uses; the
window offers the mix open loop for ``--seconds``; then the served tokens
are checked against the float32 reference of the configuration's
architecture (``harness.arch_module``). ``--trace 1`` runs the engine
with tracing on, records the end of the window with the JAX profiler, and
reports the per-layer metrics instead of the end-to-end ones.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), and
last ``checks``, each compared number beside its limit. Progress and the
set-up split go to stderr. Without a TPU it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "metrics"))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import traffic  # noqa: E402
from harness import log  # noqa: E402

TRACE_S = 3.0          # seconds the profiler records, at the window's end


def reader(name: str):
    """``metrics/<name>.py``, else the file of the name without its last
    dotted part (``tick.host_ms.tpot`` -> ``tick.host_ms.py``): a quantity
    split by the metric it moves keeps one reader."""
    for stem in (name, name.rsplit(".", 1)[0]):
        path = os.path.join(BENCH, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise SystemExit(f"bench: no reader for metric {name!r}")


def facts_of(cell, out, setup_s: float) -> dict:
    stamps = list(out.stamps.values())
    return {"setup_s": setup_s, "t0": out.t0, "window_s": out.t_end - out.t0,
            "t_drained": out.t_drained, "stamps": stamps,
            "committed_in_window": out.committed_in_window,
            "ticks": out.ticks, "max_batch": cell.max_batch,
            "kv": out.kv, "config": cell.cfg, "trace": None}


def run_cell(wl, cfg, mix, args, devs, t_start: float) -> dict:
    """Set-up, window and check of one cell on ``devs``; the result."""
    d0 = devs[0]

    cell = harness.Cell(cfg, args.seed, trace=bool(args.trace))
    vocab = cfg["model"]["vocab_size"]
    cell.warm_up(vocab)
    plan = traffic.schedule(mix, cell.split, vocab, args.seed, args.seconds)
    too_long = [p.rid for p in plan if len(p.prompt) > cell.prompt_len]
    if too_long:
        raise SystemExit(f"bench: prompts longer than prompt_len: {too_long}")
    log(f"traffic {wl['traffic']}: {traffic.describe(plan)}")
    t_ready = time.perf_counter()
    out = cell.run_window(plan, args.seconds, drain_s=mix.get("drain_s", 0.0),
                          trace_s=TRACE_S if args.trace else 0.0,
                          fill=bool(mix.get("fill_before_window")))
    setup_s = out.t0 - t_start      # the engine's clock is perf_counter
    sp = cell.split_s
    log(f"setup: total {setup_s:.3f} s = engine load {sp['engine_load']:.3f}"
        f" (XLA compile {sp['compile']:.3f} in {cell.n_compiled} programs, "
        f"cache load {sp['cache_load']:.3f} of {cell.n_loaded}) + weights "
        f"{sp['weights']:.3f} + warm-up {sp['warm_up']:.3f} + batch fill "
        f"{out.t0 - t_ready:.3f} + imports and schedule "
        f"{t_ready - t_start - sum(sp[k] for k in ('engine_load', 'weights', 'warm_up')):.3f}")
    log(f"memory: peak after set-up {cell.peaks}")
    kv = out.kv[1] or {}
    log(f"memory: in use at the window's close {out.in_use_bytes}; KV pages"
        f" reserved {kv.get('used_pages', 0)} of {kv.get('usable_pages', 0)}"
        f"; K and V of the live tokens {out.kv_live_bytes}")
    late = np.asarray(out.lateness_s or [0.0]) * 1e3
    log(f"window: {out.t_end - out.t0:.3f} s, {len(out.stamps)} requests due,"
        f" generator lateness p50 {np.median(late):.3f} ms max "
        f"{late.max():.3f} ms; {out.committed_in_window} tokens committed; "
        f"compiles in window {out.compiles_in_window}; "
        f"drained {out.t_drained - out.t_end:.3f} s after")
    if out.step_s:
        wall = np.asarray([w for _, w, _ in out.step_s]) * 1e3
        slow = [(round(t, 3), round(w * 1e3, 3), pos)
                for t, w, pos in out.step_s if w * 1e3 > 2 * np.median(wall)]
        pauses = [w * 1e3 for _, w in out.gc_s]
        log(f"window steps: {len(wall)}, median {np.median(wall):.3f} ms, "
            f"max {wall.max():.3f} ms; over twice the median (start s, "
            f"ms, longest row after): {slow[:12]}; GC pauses {len(pauses)} (full "
            f"{sum(g == 2 for g, _ in out.gc_s)}), total "
            f"{sum(pauses):.3f} ms, longest {max(pauses, default=0):.3f} ms")
    stats = d0.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    facts = facts_of(cell, out, setup_s)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": peak,
              "memory_in_window_bytes": out.in_use_bytes}
    breakdown = None
    if args.trace:
        red = harness.reduce_trace(cell, out, d0.device_kind)
        facts["trace"] = red or None
        if red:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = {"device_ops": [list(x) for x in red["device_ops"]],
                         "idle_gaps": [list(x) for x in red["idle_gaps"]]}
            log(f"trace: {red['window_s']:.3f} s, busy {red['busy_s']:.3f} s"
                + "".join(f", {k} {v['s']:.4f} s in {v['calls']} events"
                          for k, v in red["kernels"].items()))

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in wl["metrics"][kind]:
        v = reader(m["name"])(facts)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ---------------------------------------------------------- the check
    samples = cell.samples(out, cfg["check"]["sample_per_rung"], args.seed)
    cell.release()
    key = cell.form.run_key(args.seed)
    limit = cfg["check"]["max_logit_gap"]
    checks, correct = {}, True
    t = time.perf_counter()
    for i, r in enumerate(cfg["rungs"]):
        smp = samples.get(r["name"], [])
        if not smp:
            checks[f"served.{r['name']}"] = {"value": 0, "limit": 1}
            correct = False
            continue
        g = cell.form.served_gaps(cell.arch[r["name"]], key, i,
                                  cfg["model"]["param_dtype"], smp,
                                  max_len=cell.prompt_len + cell.max_new,
                                  max_rows=cell.max_new)
        checks[f"logit_gap.{r['name']}"] = {"value": g["gap"],
                                            "limit": limit}
        log(f"check {r['name']}: {len(smp)} requests, {g['tokens']} served "
            f"tokens, widest logit gap {g['gap']:.6g} (limit {limit})")
        correct = correct and g["gap"] <= limit
    log(f"reference: {time.perf_counter() - t:.3f} s")

    attempted = len(out.stamps)
    failed = sum(1 for s in out.stamps.values()
                 if s.refused or (mix.get("drain_s", 0) > 0 and s.done is None))
    for k, c in checks.items():
        log(f"compare {k}: {c['value']} limit {c['limit']}")
    res = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["checks"] = checks
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    wl, cfg, mix = harness.load_cell(args.workload)
    import jax
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # every program goes to the cache, however quick it was to compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = harness.require_chips(wl["chips"])
    log(f"bench: {wl['name']} seed {args.seed} on {devs[0].platform} "
        f"{devs[0].device_kind} x{len(devs)}; jax {jax.__version__}; "
        f"cache {cache_dir}")
    res = run_cell(wl, cfg, mix, args, devs, T_START)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

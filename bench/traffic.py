"""The one traffic generator. A mix is a data file, ``traffic/<mix>.json``:

    arrival      {"kind": "poisson", "rate_per_s": r}  open loop over the window
                 {"kind": "backlog", "count": n}       n requests due at t = 0
    prompt       {"dist": "lognormal", "median", "sigma", "min", "max"}
                 or {"dist": "uniform", "min", "max"}
    output       the same forms, for the number of tokens to generate
    prefix       optional {"share", "count", "length", "zipf_s"}: a share of
                 the requests open with one of ``count`` system prompts of
                 ``length`` tokens, picked by Zipf with exponent ``zipf_s``
    shape_seed   seed of the set of sizes and arrivals
    shuffle_block  optional: the run's seed shuffles the requests' order
                 within consecutive blocks of this many (default: the whole
                 schedule)

The set of shapes (sizes, prefix picks, rungs in the configuration's
``split``) and the arrival times are drawn from ``shape_seed`` alone. The
run's ``--seed`` shuffles which shape comes at which arrival, within each
block, and draws every token. So every seed offers the same work in
another order, on other data.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np


@dataclass
class Planned:
    """One request of the schedule."""
    rid: int
    due_s: float          # offset from the window's start
    rung: str
    prompt: np.ndarray    # int32 tokens
    max_new: int
    prefix: int           # system prompt index, -1 for none


def load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _sizes(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown size distribution {spec['dist']!r}")


def _counts(split: Dict[str, float], n: int) -> List[str]:
    """Rung labels in fixed proportions (largest remainder)."""
    names = sorted(split)
    raw = [split[k] * n / sum(split.values()) for k in names]
    got = [int(math.floor(r)) for r in raw]
    for i in sorted(range(len(names)), key=lambda i: got[i] - raw[i])[
            :n - sum(got)]:
        got[i] += 1
    return [k for k, c in zip(names, got) for _ in range(c)]


def _shuffled(n: int, block: int, seed: int) -> np.ndarray:
    """A permutation of ``range(n)`` that keeps each block of ``block``
    consecutive positions, shuffled within it by the seed."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [seed & 0xFFFFFFFF, seed >> 32, 0x0DE5]))
    return np.concatenate([b + rng.permutation(min(block, n - b))
                           for b in range(0, n, block)])


def count(mix: Dict, seconds: float) -> int:
    arr = mix["arrival"]
    if arr["kind"] == "backlog":
        return int(arr["count"])
    if arr["kind"] == "poisson":
        return max(1, int(round(arr["rate_per_s"] * seconds)))
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def schedule(mix: Dict, split: Dict[str, float], vocab: int, seed: int,
             seconds: float) -> List[Planned]:
    n = count(mix, seconds)
    shape = np.random.default_rng(int(mix["shape_seed"]))
    prompt_len = _sizes(mix["prompt"], n, shape)
    max_new = _sizes(mix["output"], n, shape)
    pre = mix.get("prefix")
    picks = np.full((n,), -1, np.int64)
    if pre:
        shared = shape.random(n) < pre["share"]
        w = 1.0 / np.arange(1, pre["count"] + 1) ** pre["zipf_s"]
        picks[shared] = shape.choice(pre["count"], int(shared.sum()),
                                     p=w / w.sum())
    rungs = np.array(_counts(split, n))[shape.permutation(n)]
    if mix["arrival"]["kind"] == "poisson":
        # a Poisson process with n arrivals in the window: uniform order
        # statistics
        t = np.sort(shape.uniform(0.0, seconds, n))
        gaps = np.diff(np.concatenate([[0.0], t]))
    else:
        gaps = np.zeros((n,))

    due = np.cumsum(gaps)
    order = _shuffled(n, int(mix.get("shuffle_block", n)), seed)
    prompt_len, max_new, picks, rungs = (prompt_len[order], max_new[order],
                                         picks[order], rungs[order])
    rng = np.random.default_rng(np.random.SeedSequence(
        [seed & 0xFFFFFFFF, seed >> 32, 0x7A11]))
    prefixes = []
    if pre:
        prefixes = [rng.integers(0, vocab, pre["length"]).astype(np.int32)
                    for _ in range(pre["count"])]
    out = []
    for i in range(n):
        plen = int(prompt_len[i])
        body = rng.integers(0, vocab, plen).astype(np.int32)
        if picks[i] >= 0:
            head = prefixes[picks[i]]
            # the prompt keeps its drawn length, or the prefix plus a
            # tail of ``min_tail`` tokens where that is longer
            tail = max(plen - len(head), int(pre.get("min_tail", 16)))
            body = np.concatenate([head, body[:tail]])
        out.append(Planned(rid=i, due_s=float(due[i]), rung=str(rungs[i]),
                           prompt=body, max_new=int(max_new[i]),
                           prefix=int(picks[i])))
    return out


def describe(plan: Sequence[Planned]) -> Dict:
    """Counts for the log: the schedule's shape, not its tokens."""
    pl = np.array([len(p.prompt) for p in plan])
    mn = np.array([p.max_new for p in plan])
    return {"requests": len(plan), "prompt_tokens": int(pl.sum()),
            "prompt_p50": float(np.median(pl)), "prompt_max": int(pl.max()),
            "output_tokens": int(mn.sum()), "output_max": int(mn.max()),
            "shared": int(sum(p.prefix >= 0 for p in plan)),
            "last_due_s": float(max(p.due_s for p in plan))}

"""From a JAX profiler trace to device busy time, kernel time and idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. A TPU shows up as planes ``/device:TPU:<n>``
whose line ``XLA Ops`` holds one event per operation run on the chip; host
threads are on ``/host:CPU``. Timestamps of all planes share one clock.

Everything here is plain arithmetic on intervals so that it can be checked
on a small recorded trace without a chip.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
# control flow: their events span the operations inside them
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """Device operations per chip, and host events by name."""
    device_ops: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def find_xplane(root: str) -> str:
    hits = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return hits[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [Event(e.name, float(e.start_ns), float(e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            tr.device_ops[plane.name] = sorted(ops, key=lambda e: e.start_ns)
        elif plane.name.startswith("/host:CPU"):
            tr.host.extend(Event(e.name, float(e.start_ns),
                                 float(e.duration_ns))
                           for line in plane.lines for e in line.events)
    tr.host.sort(key=lambda e: e.start_ns)
    return tr


def op_kind(name: str) -> str:
    """An operation's kind from its HLO text: ``%paged_flash_decode_bkhd.105
    = bf16[...] custom-call(...)`` -> ``paged_flash_decode_bkhd``. A Pallas
    kernel keeps the name of the function that calls ``pallas_call``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, tail = head.rpartition(".")
    return base if base and tail.isdigit() else head


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(ivs: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def busy_ns(ops: Sequence[Event], lo: float, hi: float) -> float:
    """Time in [lo, hi] in which at least one operation ran."""
    return sum(e - s for s, e in clip(union((o.start_ns, o.end_ns)
                                            for o in ops), lo, hi))


def gaps(ops: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of [lo, hi]: no operation running."""
    out, t = [], lo
    for s, e in clip(union((o.start_ns, o.end_ns) for o in ops), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_time_ns(ops: Sequence[Event], lo: float, hi: float,
               match) -> Tuple[float, int]:
    """Summed device time, within [lo, hi], of operations whose name
    ``match`` accepts, and how many there were."""
    tot, n = 0.0, 0
    for o in ops:
        if match(o.name):
            s, e = max(o.start_ns, lo), min(o.end_ns, hi)
            if e > s:
                tot += e - s
                n += 1
    return tot, n


def top_ops(ops: Sequence[Event], lo: float, hi: float, k: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``k`` operation kinds that took most device time, in seconds
    (control-flow operations, which hold others, left out)."""
    acc: Dict[str, float] = {}
    for o in ops:
        s, e = max(o.start_ns, lo), min(o.end_ns, hi)
        kind = op_kind(o.name)
        if e > s and kind not in CONTAINERS:
            acc[kind] = acc.get(kind, 0.0) + (e - s)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [(n, t * 1e-9) for n, t in top]


def label_at(spans: Sequence[Tuple[float, float, str]], t: float) -> str:
    """Name of the innermost (shortest) span holding time ``t``."""
    best: Optional[Tuple[float, str]] = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "untraced host"


def longest_gaps(idle: Sequence[Tuple[float, float]],
                 spans: Sequence[Tuple[float, float, str]], k: int = 10
                 ) -> List[Tuple[str, float]]:
    """The ``k`` longest idle gaps, each named by the host span it fell
    in (at its midpoint), in seconds."""
    top = sorted(idle, key=lambda g: g[0] - g[1])[:k]
    return [(label_at(spans, (s + e) / 2), (e - s) * 1e-9) for s, e in top]

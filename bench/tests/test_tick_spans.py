"""The readers of the engine tick's spans, on synthetic ``TickRecord``s:
``tick.launch_ms`` (mean launch span of the non-idle ticks) and
``tick.self_ms`` (mean engine tick less the union of its phases), and both
silent on a program whose records carry no spans."""
from types import SimpleNamespace

import pytest

import run
from repro.obs.trace import TickRecord

MS = 1e-3


def _rec(kind, backend="b0", **spans):
    return TickRecord(backend=backend, t=0.0, kind=kind,
                      spans={k: (s * MS, e * MS) for k, (s, e) in
                             spans.items()})


def _facts(ticks):
    return {"ticks": ticks}


def test_launch_reader_means_the_non_idle_ticks():
    read = run.reader("tick.launch_ms.tput")
    ticks = [_rec("decode", tick=(0, 10), dispatch=(1, 3), launch=(1, 1.5)),
             _rec("fused", tick=(10, 20), dispatch=(11, 14),
                  launch=(11, 12.5)),
             _rec("idle", tick=(20, 21), dispatch=(20, 20.5))]
    assert read(_facts(ticks)) == pytest.approx(1.0)
    assert read(_facts(ticks[2:])) is None      # idle ticks launch nothing


def test_self_reader_counts_the_gaps_between_phases():
    read = run.reader("tick.self_ms.tput")
    one = (0 * MS, 10 * MS)
    # two backends in one engine tick: b0's phases leave a 1 ms gap before
    # dispatch, b1's start 1 ms after b0's commit and end 1 ms before the
    # tick does; launch and commit_wait nest and add nothing
    b0 = _rec("decode", "b0", preempt=(0.5, 1), admit=(1, 2),
              dispatch=(3, 4), launch=(3, 3.5), commit=(4, 5),
              commit_wait=(4, 4.5))
    b1 = _rec("decode", "b1", preempt=(6, 6.5), admit=(6.5, 7),
              dispatch=(7, 8), commit=(8, 9))
    for r in (b0, b1):
        r.spans["tick"] = one
    # a second tick, all phases end to end: no self time
    b2 = _rec("decode", "b0", tick=(10, 14), preempt=(10, 11),
              admit=(11, 12), dispatch=(12, 13), commit=(13, 14))
    # tick 1: 10 - (0.5 + 1 + 1 + 1 + 3) = 3.5 ms; tick 2: 0 ms
    assert read(_facts([b0, b1, b2])) == pytest.approx(1.75)


@pytest.mark.parametrize("name", ["tick.launch_ms.tput", "tick.self_ms.tput"])
def test_readers_are_silent_without_spans(name):
    read = run.reader(name)
    legacy = SimpleNamespace(kind="decode", backend="b0", preempt_ms=0.1,
                             admit_ms=0.1, exec_ms=1.0)
    assert read(_facts([legacy])) is None
    assert read(_facts([])) is None

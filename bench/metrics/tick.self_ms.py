"""tick.self_ms: mean over the window's engine ticks of the tick's self
time in ms: the ``tick`` span (the engine's step from entry to return,
over every backend) less the union of its phases on every backend —
preempt, admit, dispatch, commit (``TickRecord.spans``). None where the
program records no spans."""

PHASES = ("preempt", "admit", "dispatch", "commit")


def read(facts):
    ticks = {}          # the engine tick's span -> its backends' phases
    for t in facts["ticks"]:
        sp = getattr(t, "spans", None) or {}
        if "tick" in sp:
            ticks.setdefault(sp["tick"], []).extend(
                sp[p] for p in PHASES if p in sp)
    if not ticks:
        return None
    total = 0.0
    for (t0, t1), phases in ticks.items():
        covered, end = 0.0, t0
        for s, e in sorted(phases):
            s, e = max(s, end), min(e, t1)
            if e > s:
                covered += e - s
                end = e
        total += (t1 - t0) - covered
    return 1e3 * total / len(ticks)

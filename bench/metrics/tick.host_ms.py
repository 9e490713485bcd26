"""tick.host_ms: mean over the window's engine ticks of the host phases
that do not launch device work: preempt + admit + commit, less the time
the commit waited for the device in its read-back (TickRecord)."""


def read(facts):
    ticks = [t for t in facts["ticks"] if t.kind != "idle"]
    if not ticks:
        return None
    tot = 0.0
    for t in ticks:
        c = t.commit_ms if t.commit_ms == t.commit_ms else 0.0
        w = t.commit_wait_ms if t.commit_wait_ms == t.commit_wait_ms else 0.0
        tot += t.preempt_ms + t.admit_ms + c - w
    return tot / len(ticks)

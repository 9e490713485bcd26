"""tick.launch_ms: mean over the window's non-idle engine ticks of the
``launch`` span — the jitted call alone, from the call to its return —
in ms: the host time the runtime takes to accept a step
(``TickRecord.spans``). None where the program records no spans."""


def read(facts):
    spans = [getattr(t, "spans", None) or {} for t in facts["ticks"]
             if t.kind != "idle"]
    launch = [s["launch"] for s in spans if "launch" in s]
    if not launch:
        return None
    return 1e3 * sum(e - s for s, e in launch) / len(launch)

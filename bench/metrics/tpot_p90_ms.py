"""tpot_p90_ms: 90th percentile, over every request due in the window, of
(completion - first token) / (output tokens - 1). A request refused or not
finished by the drain's end counts as its due time to the drain's end:
above every served one. Requests of one output token have no gap."""
from _lib import p90


def read(facts):
    end = facts["t_drained"]
    v = []
    for s in facts["stamps"]:
        if s.refused or s.done is None:
            v.append((end - s.due) * 1e3)
        elif s.n_out > 1:
            v.append((s.done - s.first) * 1e3 / (s.n_out - 1))
    return p90(v) if v else None

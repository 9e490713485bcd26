"""ttft_p90_ms: 90th percentile, over every request due in the window, of
its due time to its first committed token, as the benchmark saw it after
each engine step. A request refused, or without a token when the drain cap
ran out, counts at the drain's end: above every served one."""
from _lib import p90


def read(facts):
    end = facts["t_drained"]
    v = [((s.first if s.first is not None and not s.refused else end)
          - s.due) * 1e3 for s in facts["stamps"]]
    return p90(v) if v else None

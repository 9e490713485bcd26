"""sched.queue_wait_p90_ms: 90th percentile of service_start - arrival
(the engine's stamps) over the window's requests; one that never entered
service counts until the drain's end."""
from _lib import p90


def read(facts):
    end = facts["t_drained"]
    v = []
    for s in facts["stamps"]:
        r = s.req
        start = r.service_start if r.service_start > 0 else end
        v.append((start - r.arrival) * 1e3)
    return p90(v) if v else None

"""kv.prefix_hit_rate: prefix-index hits over lookups in the window, in
percent (kv_pool_stats() before and after). Nothing to read without
lookups."""


def read(facts):
    a, b = facts["kv"]
    if a is None or b is None:
        return None
    looks = b["prefix_lookups"] - a["prefix_lookups"]
    if looks <= 0:
        return None
    return 100.0 * (b["prefix_hits"] - a["prefix_hits"]) / looks

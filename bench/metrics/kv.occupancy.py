"""kv.occupancy: pages in use over usable pages, mean over the window's
ticks, in percent (TickRecord.pool_occupancy, from the paged pool)."""


def read(facts):
    occ = [t.pool_occupancy for t in facts["ticks"]
           if t.pool_occupancy == t.pool_occupancy]
    return 100.0 * sum(occ) / len(occ) if occ else None

"""sched.batch_occupancy: mean over the window's ticks of occupied slots
over max_batch, in percent (TickRecord.active)."""


def read(facts):
    ticks = facts["ticks"]
    if not ticks:
        return None
    return 100.0 * sum(t.active for t in ticks) / (len(ticks)
                                                   * facts["max_batch"])

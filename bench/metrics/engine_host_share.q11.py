"""Host time in ``StreamEngine.run_tick`` outside the store's reads and
writes and the generator (routing, queues, operator compute, budgets)
as a share of the window's wall time.
In ``q11.steady``; moves ``events_per_s.q11``."""

PARTS = ("bench.tick", "lsm.get_batch", "lsm.put_batch", "source.generate")


def read(run):
    h = run.hooks
    if run.window_s <= 0 or any(p not in h.installed for p in PARTS):
        return None
    tick, get, put, src = (h.seconds[p] for p in PARTS)
    return 100.0 * (tick - get - put - src) / run.window_s

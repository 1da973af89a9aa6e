"""Host time in ``LSMStore.get_batch`` (the store's read path: memtable
runs, CLOCK cache, level probes) as a share of the window's wall time.
In ``q11.steady``; moves ``events_per_s.q11``."""


def read(run):
    return run.hooks.share("lsm.get_batch", run.window_s)

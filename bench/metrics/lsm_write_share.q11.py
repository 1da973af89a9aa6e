"""Host time in ``LSMStore.put_batch`` (the write path: delta runs,
consolidation, flush and compaction) as a share of the window's wall
time.
In ``q11.steady``; moves ``events_per_s.q11``."""


def read(run):
    return run.hooks.share("lsm.put_batch", run.window_s)

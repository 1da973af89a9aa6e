"""Host time in ``LSMStore.snapshot`` (consolidating each task's whole
state) inside ``StreamEngine.reconfigure`` calls, as a share of the time
of those calls.  Taken from the annotations' intervals, so that the
benchmark's own snapshots between rescales do not count."""


def read(run):
    return run.summary.span_share("lsm.snapshot", "engine.reconfigure")

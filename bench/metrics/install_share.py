"""Host time in ``StreamEngine._install_partitions`` (re-partitioning
state onto the new tasks, cache prewarm) inside ``reconfigure`` calls,
as a share of the time of those calls."""


def read(run):
    return run.summary.span_share("engine.install", "engine.reconfigure")

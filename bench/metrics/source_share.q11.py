"""Host time in the load generator as a share of the window's wall time:
shows whether generating events starves the engine.
In ``q11.steady``; moves ``events_per_s.q11``."""


def read(run):
    return run.hooks.share("source.generate", run.window_s)

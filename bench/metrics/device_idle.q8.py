"""Share of the traced window in which no operation ran on the device,
in ``q8.steady``; moves ``events_per_s.q8``."""


def read(run):
    return run.summary.idle_share()

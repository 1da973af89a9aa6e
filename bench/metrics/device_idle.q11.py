"""Share of the traced window in which no operation ran on the device,
in ``q11.steady``; moves ``events_per_s.q11``."""


def read(run):
    return run.summary.idle_share()

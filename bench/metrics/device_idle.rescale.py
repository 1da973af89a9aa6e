"""Share of the traced window in which no operation ran on the device,
in the rescale cells."""


def read(run):
    return run.summary.idle_share()

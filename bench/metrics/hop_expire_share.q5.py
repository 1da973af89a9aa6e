"""Host time in the program's ``hop.expire`` span (``hot_auctions``: the
purge of the counts of every window the watermark passed, on each task
every tick) as a share of the traced window.  In ``q5.steady``; moves
``events_per_s.q11``.  None where the program opens no such span."""


def read(run):
    return run.summary.span_share("hop.expire", "bench.window")

"""Host time in the program's ``hop.fire`` span (``hot_items``: the
per-window maximum's update, and the close of the windows the watermark
passed) as a share of the traced window.  In ``q5.steady``; moves
``events_per_s.q11``.  None where the program opens no such span."""


def read(run):
    return run.summary.span_share("hop.fire", "bench.window")

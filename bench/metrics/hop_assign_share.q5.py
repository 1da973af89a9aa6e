"""Host time in the program's ``hop.assign`` span (window assignment of
each bid and the batch's count of its (auction, window) pairs) as a
share of the traced window.  In ``q5.steady``; moves
``events_per_s.q11``.  None where the program opens no such span."""


def read(run):
    return run.summary.span_share("hop.assign", "bench.window")

"""Host time in the program's ``hop.combine`` span (the combiner: each
window's largest count in the batch and the rows at it) as a share of
the traced window.  In ``q5.steady``; moves ``events_per_s.q11``.  None
where the program opens no such span."""


def read(run):
    return run.summary.span_share("hop.combine", "bench.window")

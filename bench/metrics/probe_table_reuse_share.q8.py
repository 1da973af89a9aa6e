"""Share of the probe's device calls in the window whose table was
already on the device (the program's counters
``sorted_probe.table_reuses`` over ``sorted_probe.calls``), in percent:
how often a call sent only its queries.  In ``q8.p1``; moves
``events_per_s.q8``.  None where the program does not count uploads and
reuses (a counter that did not move is missing from the run's counts;
each call moves one of the two)."""


def read(run):
    counted = run.notes.get("counts", {})
    calls = counted.get("sorted_probe.calls")
    if not calls or not ({"sorted_probe.table_uploads",
                          "sorted_probe.table_reuses"} & counted.keys()):
        return None
    return 100.0 * counted.get("sorted_probe.table_reuses", 0) / calls

"""Unique (auction, window) keys the hopping-window count wrote (the
program's counter ``hop.updates``) per bid it processed in the traced
window: how far the batch's count folds its (bid, window) pairs.  In
``q5.steady``; moves ``events_per_s.q11``.  None where the program does
not count them."""


def read(run):
    updates = run.notes.get("counts", {}).get("hop.updates")
    events = run.notes.get("events", 0)
    if not updates or not events:
        return None
    return updates / events

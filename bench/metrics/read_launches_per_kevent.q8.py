"""Device programs launched inside ``LSMStore.get_batch`` intervals, per
1,000 events the stateful operator processed in the traced window.
In ``q8.steady``; moves ``events_per_s.q8``."""


def read(run):
    n = run.summary.launches_in("lsm.get_batch")
    events = run.notes.get("events", 0)
    if n is None or not events:
        return None
    return 1000.0 * n / events

"""The probe's share of its roofline at the store's call site
(``LSMStore._probe_run``): the least time of every call of the traced
window (``bench.roofline.probe_bytes`` of its unpadded shape over the
chip's HBM bandwidth) over the device time inside those calls.
In ``q11.steady``; moves ``events_per_s.q11``."""

from bench.roofline import least_seconds, probe_bytes


def read(run):
    device_s = run.summary.device_s_in("lsm.probe")
    shapes = run.hooks.shapes.get("lsm.probe")
    if not device_s or not shapes:
        return None
    total = sum(probe_bytes(t, q) for t, q in shapes)
    return 100.0 * least_seconds(total, run.device_kind) / device_s

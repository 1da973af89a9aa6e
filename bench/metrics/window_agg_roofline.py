"""The consolidation weight sum's share of its roofline at the store's
call site (``LSMStore._segment_sum``): least time of every call of the
traced window (``bench.roofline.segment_sum_bytes`` of its unpadded
shape over the chip's HBM bandwidth) over the device time inside them."""

from bench.roofline import least_seconds, segment_sum_bytes


def read(run):
    device_s = run.summary.device_s_in("lsm.segment_sum")
    shapes = run.hooks.shapes.get("lsm.segment_sum")
    if not device_s or not shapes:
        return None
    total = sum(segment_sum_bytes(e, s, v) for e, s, v in shapes)
    return 100.0 * least_seconds(total, run.device_kind) / device_s

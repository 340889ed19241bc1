"""A kernel's share of its roofline: the least time the chip could
take for the bytes ``chipbench/roofline.py`` computes from the
problem's shapes, over the device time the trace measured.  MaxSum on
small tables does a handful of adds and mins per byte, so the bound
is the HBM one, and the share is of that bound.  Args: ``per`` (the
runner's value that counts the supersteps traced)."""

from chipbench import roofline


def read(capture, per):
    device = capture.get("device_trace")
    shapes = capture.get("shapes")
    count = capture.get("values", {}).get(per)
    if not device or not shapes or not count:
        return None
    least_s = (roofline.maxsum_superstep_bytes(**shapes)
               / roofline.peak(capture["device_kind"], "hbm_bytes_per_s"))
    return 100.0 * least_s / (device["busy_s"] / count)

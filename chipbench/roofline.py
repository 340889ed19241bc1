"""Bytes one MaxSum superstep has to move, from the problem's shapes,
and the table of peaks.

The arithmetic follows ``pydcop_tpu/engine/roofline.py``
``maxsum_superstep_bytes`` (PR 25 copied it as a function of shapes):
read every factor's cost table once; six passes over the messages
(old and new, both directions, plus the belief sum and its
subtraction); four passes over the ``[V, D]`` belief table; the
gather indices.  It counts the problem's own shapes, not the
program's padded ones, so padding cannot raise the share.  The
program's ``TPU_VMEM_BYTES`` / ``vmem_resident`` are assumptions and
are not copied: the share below is always of the HBM bound, and a
working set that stays in fast memory simply reads far under it.
"""

MESSAGE_PASSES = 6
BELIEF_PASSES = 4
INDEX_BYTES = 4

# device_kind -> peaks.  Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s).  A device that is not
# here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}


def peak(device_kind, key):
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "chipbench/roofline.py PEAKS")
    return PEAKS[device_kind][key]


def maxsum_superstep_bytes(variables, domain, factors_by_arity,
                           itemsize=4):
    """``factors_by_arity`` maps an arity to the number of factors of
    that arity; every variable has ``domain`` values."""
    total = BELIEF_PASSES * variables * domain * itemsize
    for arity, count in factors_by_arity.items():
        arity = int(arity)
        total += count * domain ** arity * itemsize
        total += MESSAGE_PASSES * count * arity * domain * itemsize
        total += count * arity * INDEX_BYTES
    return total

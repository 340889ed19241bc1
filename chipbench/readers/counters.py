"""Difference of two ``aotcache.counters()`` reads: what the
persistent compile cache saw between the start of the window and the
end of the traced block.  Args: ``keys`` (``misses`` = programs that
XLA compiled; ``hits`` = programs loaded from the disk cache)."""


def read(capture, keys):
    before = capture.get("counters_before")
    after = capture.get("counters_after")
    if before is None or after is None:
        return None
    return sum(after[key] - before[key] for key in keys)

"""A number the runner computed itself in the traced run (the
benchmark's own timer, a ratio of costs).  Args: ``key``."""


def read(capture, key):
    return capture.get("values", {}).get(key)

"""Difference of two ``GET /stats`` reads of the serve plane.

Args of a metric file: ``keys`` (dotted paths into ``/stats``; a path
that ends at an object sums its numbers), ``per`` (a dotted path: the
sum is divided by its difference), ``scale``, and ``subtract_from``
(a name in the runner's ``values``: the result is that value minus
the scaled quotient).
"""


def lookup(stats, path):
    """The number at a dotted path; an object there is summed."""
    node = stats
    for part in path.split("."):
        node = node[part]
    if isinstance(node, dict):
        return sum(v for v in node.values()
                   if isinstance(v, (int, float)))
    return node


def difference(capture, path):
    return (lookup(capture["stats_after"], path)
            - lookup(capture["stats_before"], path))


def read(capture, keys, per=None, scale=1.0, subtract_from=None):
    if not capture.get("stats_before") or not capture.get("stats_after"):
        return None
    value = sum(difference(capture, key) for key in keys)
    if per is not None:
        count = difference(capture, per)
        if not count:
            return None
        value /= count
    value *= scale
    if subtract_from is not None:
        value = capture["values"][subtract_from] - value
    return value

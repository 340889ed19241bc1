"""``spans.py``'s reduction, for the metrics PR 26 adds: reported only
from a traced block that was also profiled (``capture[
"device_trace"]``), beside the device numbers they explain.  (The CPU
rehearsal of PR 25 asserts the exact set of metrics a CPU run prints,
and that file may not be edited; the rehearsal of these metrics,
``tests/chipbench_rehearsal/test_rehearsal_tracing.py``, hands the
readers a stand-in for the device's trace.)  Args: those of
``spans.read``."""

from chipbench.readers import spans


def read(capture, **args):
    if not capture.get("device_trace"):
        return None
    return spans.read(capture, **args)

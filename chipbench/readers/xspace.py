"""What the readers of PR 26 take from the traced block's
``.xplane.pb``, beyond the busy time ``xplane.py`` reads: the
program's own spans, which the tracer's profiler bridge writes as
``pydcop:<name>`` annotations on the host planes (one clock with the
device's operations), and the ``jax.named_scope`` of every HLO
instruction.  Not a reader itself (no ``read``).

On a TPU v5e the events of the ``XLA Ops`` line are named by their
HLO text without its metadata and carry no op-name stat (probed on
the chip, PR 26), so the scope of an operation is looked up in the
HLO module the profiler stores on the ``/host:metadata`` plane, under
the name of the ``XLA Modules`` event that encloses the operation.
``jax.profiler.ProfileData`` does not expose that plane's event
metadata, so the few protobuf fields needed are read from the file's
bytes directly (field numbers of tsl ``xplane.proto`` and xla
``hlo.proto``; wire format only, no generated code).
"""

import functools
import glob
import os

from chipbench.readers import xplane

ANNOTATION_PREFIX = "pydcop:"
METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"


def profile_path(capture):
    """The traced block's ``.xplane.pb`` (``lib.traced_block`` writes
    the profile beside the span file), or None."""
    spans = capture.get("spans")
    if not spans:
        return None
    found = sorted(glob.glob(os.path.join(
        os.path.dirname(spans), "profile", "**", "*.xplane.pb"),
        recursive=True))
    return found[0] if found else None


# --------------------------------------------------------------------- #
# protobuf wire format


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def fields(buf):
    """``(field number, value)`` of one message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    buf = memoryview(buf)
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        number, wire_type = key >> 3, key & 7
        if wire_type == 0:
            value, i = _varint(buf, i)
        elif wire_type == 2:
            length, i = _varint(buf, i)
            value, i = buf[i:i + length], i + length
        elif wire_type in (1, 5):
            width = 8 if wire_type == 1 else 4
            value, i = buf[i:i + width], i + width
        else:
            raise ValueError(f"protobuf wire type {wire_type}")
        yield number, value


def _sub(buf, number):
    return [value for n, value in fields(buf) if n == number]


def _text(buf, number):
    found = _sub(buf, number)
    return bytes(found[0]).decode("utf-8", "replace") if found else ""


def instruction_op_names(raw):
    """``{module name as the trace spells it: {HLO instruction name:
    op_name}}`` from the serialized ``XSpace`` in ``raw``.  The
    op_name is JAX's name stack, ``jit(f)/while/body/maxsum/f2v/add``."""
    out = {}
    for plane in _sub(raw, 1):                      # XSpace.planes
        if _text(plane, 2) != METADATA_PLANE:       # XPlane.name
            continue
        for entry in _sub(plane, 4):                # XPlane.event_metadata
            for meta in _sub(entry, 2):             # map value
                names = out.setdefault(_text(meta, 2), {})
                for stat in _sub(meta, 5):          # XEventMetadata.stats
                    for proto in _sub(stat, 6):     # XStat.bytes_value
                        _module_op_names(proto, names)
    return out


def _module_op_names(hlo_proto, names):
    for module in _sub(hlo_proto, 1):               # HloProto.hlo_module
        for computation in _sub(module, 3):         # .computations
            for instruction in _sub(computation, 2):  # .instructions
                for meta in _sub(instruction, 7):   # .metadata
                    op_name = _text(meta, 2)        # OpMetadata.op_name
                    if op_name:
                        names[_text(instruction, 1)] = op_name


# --------------------------------------------------------------------- #
# the trace, reduced to what the readers use


@functools.lru_cache(maxsize=2)
def load(path):
    """``{"ops": [[(name, start_ns, duration_ns)] per device plane],
    "modules": the same for the ``XLA Modules`` line, "annotations":
    [(span name, start_ns, duration_ns)] over all host threads,
    "op_names": instruction_op_names}``.  One clock: every start is
    the profiler's."""
    data = xplane.load(path)
    annotations = []
    for plane in data.planes:
        if not plane.name.startswith(HOST_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ANNOTATION_PREFIX):
                    annotations.append(
                        (e.name[len(ANNOTATION_PREFIX):].split("#")[0],
                         e.start_ns, e.duration_ns))
    with open(path, "rb") as f:
        raw = f.read()
    return {"ops": xplane.device_events(data),
            "modules": xplane.device_events(data, line_name=MODULES_LINE),
            "annotations": annotations,
            "op_names": instruction_op_names(raw)}

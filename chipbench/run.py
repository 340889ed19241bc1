"""The benchmark's command.

    python chipbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process per run: loads, warms up (set-up), measures for
``--seconds``, checks every answer, and prints as its last line one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, traced ``breakdown``, and last ``compared``: each number
that ``correct`` rests on, of the run's worst answer, beside its
limit; the same as the last lines of standard error).  The line
parses under a strict JSON parser: a number compared that is not
finite is printed as a string, and the run is not ``correct``.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, and notes before the last line the names
BENCHMARK.json lists for the cell that no reader found a value for
(``listed_and_read_nothing``).

The cell is looked up by name in BENCHMARK.json; its configuration
file names a ``kind``, and ``runners/<kind>.py`` runs it.  In a traced
run every file in ``metrics/`` whose ``kinds`` holds that kind is
evaluated by ``readers/<reader>.py``.  Any failure (no TPU, a name
that resolves to no file, an answer that cannot be had) exits
non-zero and prints no last line.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.lib import BenchFailure, module_by_name, note  # noqa: E402

# What every run must have run on.  (The CPU rehearsal,
# tests/chipbench_rehearsal, patches it from the test.)
PLATFORM = "tpu"
BREAKDOWN_ENTRIES = 5


@dataclasses.dataclass
class Cell:
    """What a runner is given."""

    name: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    device: dict
    device_capture: bool
    workdir: str
    window_start: float = None

    def start_window(self):
        """The runner calls this when set-up is over."""
        self.window_start = time.perf_counter()
        return self.window_start


def load_json(path, what):
    if not os.path.isfile(path):
        raise BenchFailure(f"{what}: no file {path}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def resolve(bench_path, workload):
    """``(benchmark, cell entry, configuration, traffic mix, data
    directory)`` of a cell named in the BENCHMARK.json at
    ``bench_path``."""
    bench = load_json(bench_path, "benchmark")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(
            f"cell {workload!r} is not in the workloads of {bench_path} "
            f"(it has {sorted(cells)})")
    entry = cells[workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    if entry["config"] not in files:
        raise BenchFailure(
            f"configuration {entry['config']!r} is not in the configs "
            f"of {bench_path}")
    config_path = os.path.join(os.path.dirname(bench_path),
                               files[entry["config"]])
    config = load_json(config_path,
                       f"configuration {entry['config']!r}")
    data_dir = os.path.dirname(os.path.dirname(config_path))
    traffic = load_json(
        os.path.join(data_dir, "traffic", f"{entry['traffic']}.json"),
        f"traffic mix {entry['traffic']!r}")
    if traffic["kind"] != config["kind"]:
        raise BenchFailure(
            f"traffic mix {entry['traffic']!r} is of kind "
            f"{traffic['kind']!r}, configuration {entry['config']!r} of "
            f"kind {config['kind']!r}")
    return bench, entry, config, traffic, data_dir


def listed(bench, section, workload):
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell
    reports: those with no ``workloads`` key, or with the cell in it."""
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


def device_info(chips):
    """The device as JAX reports it; fails off the chip."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if info["platform"] != PLATFORM:
        raise BenchFailure(f"JAX resolved platform {info['platform']!r}, "
                           f"not {PLATFORM!r}")
    if info["count"] < chips:
        raise BenchFailure(
            f"{chips} chip(s) needed, JAX sees {info['count']}")
    return info


def memory_peak_bytes():
    """Peak bytes in use on the fullest chip (0 where the backend
    does not say, as the CPU does not)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def per_layer_metrics(data_dir, kind, capture):
    """``{name: (value, unit)}`` of every metric file of this kind
    whose reader found something to read."""
    out = {}
    directory = os.path.join(data_dir, "metrics")
    for filename in sorted(os.listdir(directory)):
        if not filename.endswith(".json"):
            continue
        spec = load_json(os.path.join(directory, filename), "metric")
        if kind not in spec["kinds"]:
            continue
        reader = module_by_name("readers", spec["reader"],
                                f"reader of metric {spec['name']!r}")
        value = reader.read(capture, **spec.get("args", {}))
        if value is not None:
            out[spec["name"]] = (float(value), spec["unit"])
    return out


def strict(compared):
    """``compared`` with every number that is not finite as a string
    (``"inf"``, ``"nan"``), which a strict JSON parser takes, and
    whether all of them were finite."""
    out = {name: [x if math.isfinite(x) else str(x) for x in pair]
           for name, pair in compared.items()}
    return out, all(math.isfinite(x) for pair in compared.values()
                    for x in pair)


def breakdown(capture):
    """The device operations with the largest total time, and where
    the host's time went in the traced block: the self time of the
    program's own spans, by name (the host's clock is not aligned
    with the device's, so a gap is not matched to a span)."""
    from chipbench.readers import spans

    out = {}
    device = capture.get("device_trace")
    if device:
        out["device_ops"] = [[name, seconds] for name, seconds
                             in device["ops"][:BREAKDOWN_ENTRIES]]
    if capture.get("spans"):
        own = spans.by_name(spans.load(capture["spans"]), "self")
        totals = sorted(((name, sum(v) / 1e6) for name, v in own.items()),
                        key=lambda kv: -kv[1])
        out["idle_gaps"] = [[f"host:{name}", seconds] for name, seconds
                            in totals[:BREAKDOWN_ENTRIES]]
    return out


def run(args, started):
    bench, entry, config, traffic, data_dir = resolve(
        os.path.abspath(args.bench), args.workload)
    runner = module_by_name("runners", config["kind"],
                            f"runner of kind {config['kind']!r}")
    from pydcop_tpu.engine import aotcache

    # Before the first jit, like every entry point of the program:
    # JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.cache/jax.
    cache_dir = aotcache.enable_persistent_compile_cache()
    device = device_info(entry["chips"])
    note(cell=entry["name"], seed=args.seed, seconds=args.seconds,
         trace=args.trace, compile_cache=cache_dir,
         cache_entries=aotcache.disk_stats(cache_dir)["entries"],
         **device)
    with tempfile.TemporaryDirectory(prefix="chipbench-") as workdir:
        cell = Cell(
            name=entry["name"], config=config, traffic=traffic,
            seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            device=device, device_capture=device["platform"] == "tpu",
            workdir=workdir)
        result = runner.run(cell)
        if cell.window_start is None:
            raise BenchFailure("the runner never started its window")
        compared, finite = strict(result["compared"])
        line = {"correct": bool(result["correct"]) and finite,
                "attempted": int(result["attempted"]),
                "failed": int(result["failed"]),
                "metrics": None, "device": device}
        if cell.trace:
            capture = result["capture"]
            capture["device_kind"] = device["kind"]
            found = per_layer_metrics(data_dir, config["kind"], capture)
            names = [m["name"]
                     for m in listed(bench, "per_layer", entry["name"])]
            metrics = {name: found[name] for name in names
                       if name in found}
            # Said in the run itself, not first by the driver: a
            # listed metric whose reader found nothing to read.
            note(listed_and_read_nothing=[
                name for name in names if name not in found])
            if capture.get("device_trace"):
                device["busy_s"] = capture["device_trace"]["busy_s"]
                device["window_s"] = capture["traced_wall_s"]
            line["breakdown"] = breakdown(capture)
        else:
            values = dict(result["end_to_end"],
                          setup_s=cell.window_start - started)
            metrics = {}
            for m in listed(bench, "end_to_end", entry["name"]):
                if m["name"] not in values:
                    raise BenchFailure(
                        f"cell {entry['name']!r} reports no {m['name']}")
                metrics[m["name"]] = (values[m["name"]], m["unit"])
    line["metrics"] = {name: {"value": value, "unit": unit}
                       for name, (value, unit) in metrics.items()}
    device["memory_peak_bytes"] = memory_peak_bytes()
    # Last in the line: each number `correct` rests on, of the run's
    # worst answer, beside its limit.
    line["compared"] = compared
    return line


def main(argv=None, started=None):
    started = started or time.perf_counter()
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--bench", default=os.path.join(ROOT, "BENCHMARK.json"),
        help="the BENCHMARK.json that names the cell (the rehearsal "
             "test gives its own, with tiny configurations)")
    args = parser.parse_args(argv)
    try:
        line = run(args, started)
        last = json.dumps(line, allow_nan=False)
    except Exception as exc:  # noqa: BLE001 - reported, exit non-zero
        traceback.print_exc()
        print(f"chipbench: FAILED: {type(exc).__name__}: {exc}",
              file=sys.stderr)
        return 1
    for name, (value, limit) in line["compared"].items():
        print(f"chipbench: compared {name}: {value!r} (limit {limit!r})",
              file=sys.stderr)
    print(last, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(started=T0))

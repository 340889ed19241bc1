"""Run sets of runs of cells, one process each, and print the spreads
the bounds are set from.  A helper for the builder of a benchmark PR
(the driver makes its own runs); it touches no JAX itself, so each
child has the chip to itself.

    python chipbench/sets.py --cells gc10k_maxsum --sets 2 --runs 6 \
        --seconds 30 [--trace-seed 1] [--out chiprun_out/sets.json]

Every run of a set has another seed; both sets use the same seeds.  A
spread is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEEDS = (1, 2147483659, 3000000001, 77, 4100000007, 123456789,
         2718281828, 31337)


def one_run(cell, seed, seconds, trace, timeout):
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        capture_output=True, text=True, timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    out = {"cell": cell, "seed": seed, "trace": trace, "rc": proc.returncode,
           "wall_s": time.perf_counter() - t0, "notes": lines[:-1]}
    if proc.returncode == 0:
        out["line"] = json.loads(lines[-1])
    else:
        out["notes"] = lines
        out["stderr"] = proc.stderr[-3000:]
    return out


def brief(run):
    """One run on one line: its seed, wall time and numbers."""
    out = {"seed": run["seed"], "rc": run["rc"],
           "wall_s": round(run["wall_s"], 1)}
    if run["rc"] != 0:
        return {**out, "stderr": run["stderr"][-1500:]}
    line = run["line"]
    out.update({k: line[k] for k in ("correct", "attempted", "failed")})
    out.update({k: v["value"] for k, v in line["metrics"].items()})
    return out


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--cells", nargs="+", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace-seed", type=int, default=None,
                        help="also make one --trace 1 run of each cell")
    parser.add_argument("--timeout", type=float, default=1200)
    parser.add_argument("--out", default=os.path.join(
        "chiprun_out", "sets.json"))
    args = parser.parse_args(argv)
    record = []
    for cell in args.cells:
        sets = []
        for s in range(args.sets):
            runs = [one_run(cell, SEEDS[i], args.seconds, 0, args.timeout)
                    for i in range(args.runs)]
            record += runs
            sets.append(runs)
            for r in runs:
                print(json.dumps({"cell": cell, "set": s, **brief(r)}),
                      flush=True)
        names = sorted({n for runs in sets for r in runs if r["rc"] == 0
                        for n in r["line"]["metrics"]})
        for name in names:
            per_set = [[r["line"]["metrics"][name]["value"]
                        for r in runs if r["rc"] == 0] for runs in sets]
            # The first run of the first set is the one that compiles.
            steady = [v[1:] if name == "setup_s" and i == 0 else v
                      for i, v in enumerate(per_set)]
            print(json.dumps({
                "cell": cell, "metric": name,
                "medians": [statistics.median(v) for v in steady],
                "spreads": [spread(v) if len(v) >= 2 else None
                            for v in steady]}), flush=True)
        if args.trace_seed is not None:
            traced = one_run(cell, args.trace_seed, args.seconds, 1,
                             args.timeout)
            record.append(traced)
            print(json.dumps({"cell": cell, "traced": traced.get(
                "line", traced.get("stderr")), "notes": [
                    n[:3000] for n in traced["notes"]]}), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(record, f, indent=1)
    return 0 if all(r["rc"] == 0 and r["line"]["correct"]
                    for r in record) else 1


if __name__ == "__main__":
    sys.exit(main())

"""The spread of each metric over a set of runs, as the bounds are set from
it: the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median.

    python chipbench/spread.py <set1.jsonl> [<set2.jsonl> ...]

Each file holds the result lines of one set of runs of one cell (one run a
line, as `run.py` prints them).  A bound is about five times the widest
spread over the cells, never under 1%; `setup_s` leaves out each set's first
run, which compiles.
"""
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths):
    for path in paths:
        with open(path) as f:
            lines = [json.loads(l) for l in f if l.strip().startswith("{")]
        print(f"{path}: {len(lines)} runs, correct "
              f"{sum(bool(l['correct']) for l in lines)}")
        for name in sorted({m for l in lines for m in l["metrics"]}):
            values = [l["metrics"][name]["value"] for l in lines
                      if name in l["metrics"]]
            if name == "setup_s":
                values = values[1:]
            if len(values) < 2:
                continue
            print(f"  {name}: median {statistics.median(values):.6g} "
                  f"spread {100 * spread(values):.3f}% "
                  f"min {min(values):.6g} max {max(values):.6g} "
                  f"n {len(values)}")


if __name__ == "__main__":
    main(sys.argv[1:])

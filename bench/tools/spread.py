"""Spread of repeated runs, the basis of each metric's bound.

  python3 bench/tools/spread.py SET_A.jsonl [SET_B.jsonl ...]

Each file holds the result lines (the last stdout line of
``bench/run.py``) of one set of runs of one cell. For every metric this
prints each set's median and spread (the distance between the first and
third quartile, as ``statistics.quantiles(values, n=4)`` gives them, as a
share of the median), the widest spread, five times it as a bound, and
the shift between the sets' medians.
"""
import json
import pathlib
import statistics
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from harness import stats  # noqa: E402


def load(path):
    out = []
    for line in open(path):
        line = line.strip()
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


def main() -> int:
    sets = [load(p) for p in sys.argv[1:]]
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    for name in names:
        rows = []
        for s in sets:
            vals = [r["metrics"][name]["value"] for r in s
                    if name in r["metrics"]]
            if len(vals) >= 2:
                rows.append((statistics.median(vals), stats.spread(vals),
                             len(vals)))
        if not rows:
            continue
        widest = max(sp for _, sp, _ in rows)
        shift = (max(m for m, _, _ in rows) - min(m for m, _, _ in rows)) \
            / min(m for m, _, _ in rows)
        print(json.dumps({"metric": name,
                          "sets": [{"median": m, "spread": sp, "runs": n}
                                   for m, sp, n in rows],
                          "widest_spread": widest, "bound_5x": 5 * widest,
                          "median_shift": shift}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

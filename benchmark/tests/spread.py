"""Spreads of a cell's two sets of runs, as the bounds' rule reads them.

    python3 benchmark/tests/spread.py set1.jsonl set2.jsonl

Each file holds one result line (the last line of a ``--trace 0`` run) per
line. For every metric: each set's median and spread (the distance between
the first and third quartile of ``statistics.quantiles(values, n=4)`` over
the median), the wider of the two, five times it, and how far the second
set's median lies from the first's.
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def read(path) -> dict:
    out: dict = {}
    with open(path) as f:
        for text in f:
            if not text.strip():
                continue
            line = json.loads(text)
            if not line["correct"]:
                print(f"NOT CORRECT in {path}: {line.get('compared')}")
            for name, m in line["metrics"].items():
                out.setdefault(name, []).append(m["value"])
    return out


def main(paths) -> int:
    sets = [read(p) for p in paths]
    for name in sets[0]:
        rows = [s[name] for s in sets if name in s]
        meds = [statistics.median(r) for r in rows]
        spreads = [spread(r) for r in rows]
        drift = abs(meds[-1] - meds[0]) / meds[0]
        print(f"{name}: medians {meds}  spreads "
              f"{[round(x, 5) for x in spreads]}  5x widest "
              f"{5 * max(spreads):.4f}  drift of medians {drift:.4f}  "
              f"n {[len(r) for r in rows]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""Read a cell's compared numbers over many seeds in one process, on the
chip, and the control's on some of them (the reference in a lower
precision, put in the program's place). The limits in the cell's file are
set from what this prints; the benchmark's own runs never run it.

    python3 benchmark/tests/calibrate.py --workload <cell> --seconds 8 \\
        --seeds 11 12 13 ... --control w8 --control-seeds 3
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent.parent))

import lib  # noqa: E402
import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", default="w8")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--trace-seeds", type=int, default=0)
    ap.add_argument("--sweep", nargs="*", default=[],
                    help="key=v1,v2,...: one run a value (next seed each)")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    bench = lib.read_json(run.REPO / "BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == args.workload)
    run.place_caches()
    device = run.require_chips(entry["chips"])
    out = pathlib.Path("chiprun_out/calibrate")
    out.mkdir(parents=True, exist_ok=True)
    rows = []
    plan = [(seed, None) for seed in args.seeds]
    for spec in args.sweep:        # every value on every seed
        key, _, values = spec.partition("=")
        plan = [(seed, {key: json.loads(v)})
                for v in values.split(",") for seed in args.seeds]
    if args.trace_seeds:           # say what the traces hold, by name
        reduce = lib.load("trace/reduce.py")
        real = reduce.reduce

        def describing(trace_dir, chips):
            with open(out / f"{args.workload}{args.tag}.trace.txt", "a") as f, \
                    contextlib.redirect_stdout(f):
                reduce.describe(trace_dir)
            return real(trace_dir, chips)

        reduce.reduce = describing
    for i, (seed, override) in enumerate(plan):
        line = run.execute(
            bench, args.workload, seed, args.seconds,
            i < args.trace_seeds, dict(device),
            control=args.control if i < args.control_seeds else None,
            mix_overrides=override)
        row = {"seed": seed, "override": override, "correct": line["correct"],
               "compared": {k: v["value"] for k, v in line["compared"].items()},
               "control": line["notes"].get("control"),
               "faults": {k: v for k, v in line["notes"].items()
                          if k.startswith("fault_")},
               "metrics": {k: v["value"] for k, v in line["metrics"].items()},
               "memory_peak_bytes": line["device"]["memory_peak_bytes"],
               "notes": line["notes"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        (out / f"{args.workload}{args.tag}.json").write_text(json.dumps(rows, indent=1))
    # a control or a planted fault that the cell's limits call correct
    # sets no upper reading: say so, loudly
    passed = [(r["seed"], k) for r in rows
              for k, v in [("control", r["control"]), *r["faults"].items()]
              if v and v["correct"]]
    for seed, what in passed:
        print(f"CORRECT WHERE IT MUST NOT BE: {what} on seed {seed}")
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())

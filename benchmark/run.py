"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process: it finds the cell in ``BENCHMARK.json``, loads the
cell's files by name (``workloads/<cell>.json``, the configuration's file,
``traffic/<mix>.json``, ``drivers/<driver>.py``), looks for the chips the
cell asks for and fails without them, lets the driver set up, warm up and
measure for ``--seconds``, and prints one JSON object as its last line.
With ``--trace 1`` a part of the window is traced, ``trace/reduce.py``
turns the trace into device times, and each per-layer metric of
``BENCHMARK.json`` is read by ``layer_metrics/<stem>.py``.

A new cell, configuration, traffic mix or per-layer metric is new files and
new entries; nothing here names one.
"""

from __future__ import annotations

import time

T_START = time.monotonic()      # set-up counts from the process's start

import argparse
import json
import os
import pathlib
import shutil
import sys
import types

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(REPO))

import lib  # noqa: E402

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
TRACE_OFFSET_S = 1.0        # a --trace 1 run traces this part of the window
TRACE_SECONDS = 5.0


def place_caches() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where the environment puts it); every program is kept, however
    quick its compile, so that a second run compiles nothing."""
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_compile_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_chips(chips: int) -> dict:
    """The device as JAX reports it; any other platform than the TPU, or
    another count than the cell asks for, ends the run with no result."""
    import jax

    devices = jax.devices()
    report = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if report["platform"] != "tpu" or report["count"] != chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} TPU chip(s); JAX found "
            f"{report['count']} x {report['platform']} ({report['kind']})")
    return report


def find_cell(bench: dict, name: str, root: pathlib.Path) -> types.SimpleNamespace:
    """The cell's entry and its files, found by name under ``root`` (the
    checkout; a test gives a directory of tiny files laid out alike)."""
    data = root / bench["paths"][0]
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cell = lib.read_json(data / "workloads" / f"{name}.json")
    cfg = lib.read_json(root / config["file"])
    mix = lib.read_json(data / "traffic" / f"{entry['traffic']}.json")
    mix.update(cell.get("traffic_params", {}))
    return types.SimpleNamespace(entry=entry, cell=cell, cfg=cfg, mix=mix)


def _tracer(trace: bool, trace_dir: pathlib.Path):
    """-> trace_window(t0, t1): with ``--trace 1`` traces
    ``TRACE_SECONDS`` of the window (all of a shorter one) and returns
    where the trace lies and what it spans; otherwise returns None at
    once. The Python tracer is off: it would slow the host it measures."""
    def trace_window(t0: float, t1: float):
        if not trace:
            return None
        import jax

        span = min(TRACE_SECONDS, t1 - t0)
        begin = t0 + min(TRACE_OFFSET_S, max(0.0, t1 - t0 - span))
        time.sleep(max(0.0, begin - time.monotonic()))
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        started = time.monotonic()
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        time.sleep(max(0.0, started + span - time.monotonic()))
        stopped = time.monotonic()
        jax.profiler.stop_trace()
        return {"dir": str(trace_dir), "window_s": stopped - started}
    return trace_window


def per_layer(bench: dict, name: str, reported: set, facts: dict) -> dict:
    """Each per-layer metric of this cell, read by its own small reader;
    a reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and name not in cells:
            continue
        if cells is None and m["moves"] not in reported:
            continue
        stem, _, suffix = m["name"].partition(".")
        reader = lib.load(f"layer_metrics/{stem}.py")
        value = reader.read(facts, suffix)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(bench: dict, name: str, seed: int, seconds: float, trace: bool,
            device: dict, root: pathlib.Path = REPO,
            trace_dir: pathlib.Path | None = None,
            control: str | None = None,
            mix_overrides: dict | None = None) -> dict:
    """A whole run but for the look for chips: -> the result line's dict.
    ``control`` (tests/calibrate.py only; never the benchmark's own runs)
    also reads the reference in that lower precision against the limits,
    under ``notes["control"]``; ``mix_overrides`` (the same tool's sweep
    for the knee) replaces keys of the traffic mix."""
    found = find_cell(bench, name, root)
    found.mix.update(mix_overrides or {})
    trace_dir = trace_dir or REPO / ".bench_trace" / name
    ctx = types.SimpleNamespace(
        cell=found.cell, cfg=found.cfg, mix=found.mix, seed=int(seed),
        chips=found.entry["chips"], seconds=float(seconds), t_start=T_START,
        control=control, trace_window=_tracer(trace, trace_dir))
    driver = lib.load(f"drivers/{found.cell['driver']}.py")
    rec = driver.run(ctx)

    values = dict(rec["e2e"])
    values["setup_s"] = rec["setup_s"]
    wanted = [m for m in bench["end_to_end"]
              if name in m.get("workloads", [name])]
    device = dict(device, memory_peak_bytes=rec["memory_peak_bytes"])
    line = {"correct": False, "attempted": rec["attempted"],
            "failed": rec["failed"]}
    if trace:
        peaks = lib.read_json(HERE / "peaks.json").get(device["kind"])
        if peaks is None:
            raise SystemExit(f"benchmark: no peaks on record for "
                             f"{device['kind']!r} in benchmark/peaks.json")
        reduce = lib.load("trace/reduce.py")
        reduced = reduce.reduce(rec["traced"]["dir"], device["count"])
        facts = dict(rec["facts"], peaks=peaks, trace=reduced,
                     e2e=values,
                     trace_window_s=rec["traced"]["window_s"])
        reported = {m["name"] for m in wanted if m["name"] in values}
        line["metrics"] = per_layer(bench, name, reported, facts)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = reduced["breakdown"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in wanted if m["name"] in values}
    checks = rec["checks"]
    missing = [m["name"] for m in wanted if m["name"] not in values]
    if missing:       # an end-to-end metric with nothing to read: not a run
        checks.add("end_to_end_metrics_missing", len(missing), 0)
    line["device"] = device
    line["notes"] = rec["notes"]
    line["correct"] = checks.ok
    line["compared"] = checks.as_dict()
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (REPO / "tony_tpu").is_dir():
        raise SystemExit("benchmark: the system under test (tony_tpu/) is "
                         "not in this checkout")
    bench = lib.read_json(REPO / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        raise SystemExit(f"benchmark: no workload {args.workload!r}")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    place_caches()
    device = require_chips(entry["chips"])
    line = execute(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), device)
    # what the run saw besides its metrics (a serving cell's times to the
    # first token among them), on an earlier line of every run
    print(json.dumps({"notes": line.pop("notes")}), flush=True)
    for name, row in line["compared"].items():
        print(f"compared {name}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

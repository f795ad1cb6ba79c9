"""From a profiler trace (``.xplane.pb``) to device times.

``reduce(dir, chips)`` reads the newest trace under ``dir`` with nothing but
``jax.profiler.ProfileData`` and returns, averaged over the device planes:

- ``busy_s``: the union of the intervals in which an operation ran on the
  device (the "XLA Ops" line; operations nest, the union counts once);
- ``window_s``: the span of the trace, over every plane, host included;
- ``programs``: {module name: [runs, seconds]} from the "XLA Modules" line:
  one event is one execution of one jitted program;
- ``ops``: {op name: [events, seconds]} from the "XLA Ops" line;
- ``steps`` and ``step_ops``: where the run marked its steps
  (``StepTraceAnnotation``: the device's "Steps" line), how many whole
  steps the trace holds and the tally of the operations that began inside
  one of them, so that a kernel's time a step does not count the cut steps
  at the trace's ends;
- ``breakdown``: the ten operations that took most device time (loop
  wrappers left out, since their bodies are counted) and the ten longest
  idle gaps of the first device, each named by the host span that covers
  most of it.

Run as a script on a trace directory it prints what the trace holds: look
at one by hand before trusting a name.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import sys

OPS_LINE, MODULES_LINE, STEPS_LINE = "XLA Ops", "XLA Modules", "Steps"
_WRAPPERS = ("while", "conditional", "call")


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.duration_ns))
            for ev in line.events]


def union_seconds(intervals) -> tuple:
    """-> (seconds covered, [(gap_start_ns, gap_ns)] between the pieces)."""
    covered, gaps, end = 0.0, [], None
    for start, dur in sorted(intervals):
        stop = start + dur
        if end is None:
            covered, end = dur, stop
        elif start > end:
            gaps.append((end, start - end))
            covered += dur
            end = stop
        elif stop > end:
            covered += stop - end
            end = stop
    return covered * 1e-9, gaps


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:")


def _tally(events) -> dict:
    out: dict = collections.defaultdict(lambda: [0, 0.0])
    for name, _, dur in events:
        out[name][0] += 1
        out[name][1] += dur * 1e-9
    return out


def _inside(events, intervals):
    """The events that begin inside one of the (start, duration) intervals."""
    spans = sorted(intervals)
    starts = [a for a, _ in spans]
    out = []
    for ev in events:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < spans[i][0] + spans[i][1]:
            out.append(ev)
    return out


def _name_gaps(gaps, host_events, limit=10):
    """The longest gaps, each with the host span that overlaps it most."""
    named = []
    for start, dur in sorted(gaps, key=lambda g: -g[1])[:limit]:
        stop, best, best_ov = start + dur, "(no host span)", 0.0
        for name, hs, hd in host_events:
            ov = min(stop, hs + hd) - max(start, hs)
            if ov > best_ov:
                best, best_ov = name, ov
        named.append([best, dur * 1e-9])
    return named


def reduce(trace_dir: str, chips: int) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(newest_xplane(trace_dir))
    lo, hi = float("inf"), 0.0
    devices, host_events = [], []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = _events(line)
            if evs:
                lo = min(lo, min(s for _, s, _ in evs))
                hi = max(hi, max(s + d for _, s, d in evs))
            lines.setdefault(line.name, []).extend(evs)
        if _is_device(plane.name):
            devices.append((plane.name, lines))
        elif plane.name.startswith("/host:"):
            for evs in lines.values():
                host_events.extend(e for e in evs if e[2] >= 50e3)
    devices.sort(key=lambda d: d[0])
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    programs, ops, step_ops = ({} for _ in range(3))
    busy, steps = [], 0

    def add(table, events):
        for name, (n, t) in _tally(events).items():
            row = table.setdefault(name, [0, 0.0])
            row[0] += n
            row[1] += t

    first_gaps = None
    for _, lines in devices[:chips]:
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        seconds, gaps = union_seconds((s, d) for _, s, d in op_events)
        busy.append(seconds)
        if first_gaps is None:
            first_gaps = gaps
        add(programs, lines.get(MODULES_LINE, []))
        add(ops, lines.get(OPS_LINE, []))
        marks = [(s, d) for _, s, d in lines.get(STEPS_LINE, [])]
        steps += len(marks)
        add(step_ops, _inside(lines.get(OPS_LINE, []), marks))
    n_dev = len(busy)
    programs = {k: [v[0] / n_dev, v[1] / n_dev] for k, v in programs.items()}
    ops = {k: [v[0] / n_dev, v[1] / n_dev] for k, v in ops.items()}
    step_ops = {k: [v[0] / n_dev, v[1] / n_dev] for k, v in step_ops.items()}
    top = sorted(((k, v[1]) for k, v in ops.items()
                  if not k.lstrip("%").startswith(_WRAPPERS)),
                 key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / n_dev,
        "window_s": max(0.0, hi - lo) * 1e-9,
        "devices": n_dev, "programs": programs, "ops": ops,
        "steps": steps / n_dev, "step_ops": step_ops,
        "breakdown": {"device_ops": [[k, t] for k, t in top],
                      "idle_gaps": _name_gaps(first_gaps or [], host_events)},
    }


def program_time(reduced: dict, needles) -> tuple:
    """(runs, seconds) of the programs whose name holds one of ``needles``."""
    runs = seconds = 0.0
    for name, (n, t) in reduced["programs"].items():
        if any(s in name for s in needles):
            runs, seconds = runs + n, seconds + t
    return runs, seconds


def op_time(reduced: dict, needles, table: str = "ops") -> tuple:
    runs = seconds = 0.0
    for name, (n, t) in reduced[table].items():
        if any(s in name for s in needles):
            runs, seconds = runs + n, seconds + t
    return runs, seconds


def describe(trace_dir: str) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(newest_xplane(trace_dir))
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = _events(line)
            if not evs:
                continue
            top = sorted(_tally(evs).items(), key=lambda kv: -kv[1][1])[:12]
            print(f"  line {line.name!r}: {len(evs)} events")
            for name, (n, t) in top:
                print(f"    {t:10.6f}s x{n:<7d} {name[:110]}")


if __name__ == "__main__":
    describe(sys.argv[1])

"""Device time of a program's runs by part of the step, from the trace and
the program's own map of its instructions.

``tally(facts, key)`` -> ``(runs, seconds, {part: op seconds})`` over the whole
runs of the programs that ``facts["programs"][key]`` names, averaged over
the first ``facts["chips"]`` device planes, or None. A run is one "XLA
Modules" event; the first and the last program of a plane are left out,
since the trace's edges may cut them, so every run counted is whole. Each
"XLA Ops" event that begins inside a run is named by its instruction (the
text between ``%`` and `` =``) and given the part that
``tony_tpu.observability.program_parts`` maps that instruction to in the
compiled program ("other" where it names none); loop wrappers are left
out, as ``reduce.py`` leaves them out, since their bodies' operations are
counted. A reader's "other" is the runs' time less the named parts, so the
parts add up to the runs; the operations' own sum over the runs' time says
that nothing was counted twice or missed.

None where the program has no map (the program before ``program_parts``
existed; a module of another process), the trace holds no whole run, or
there is no trace: a reader that gets None returns None.
"""

from __future__ import annotations

import bisect
import collections
import functools

import lib

MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
WRAPPERS = ("while", "conditional", "call")


def _instruction(op_name: str) -> str:
    return op_name[1:].split(" = ", 1)[0] if op_name.startswith("%") else op_name


def _runs(data, needles: tuple, chips: int) -> tuple:
    """-> ([(module name, duration_ns, [(op name, duration_ns)])]: every
    whole run of the needled programs on the first ``chips`` device
    planes, the number of those planes)."""
    planes = sorted((p for p in data.planes
                     if p.name.startswith("/device:TPU:")),
                    key=lambda p: p.name)[:chips]
    out = []
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        if MODULES_LINE not in lines or OPS_LINE not in lines:
            continue
        mods = sorted((float(ev.start_ns), float(ev.duration_ns), ev.name)
                      for ev in lines[MODULES_LINE].events)
        whole = [m for m in mods[1:-1] if any(n in m[2] for n in needles)]
        if not whole:
            continue
        starts = [m[0] for m in whole]
        ops = [[] for _ in whole]
        for ev in lines[OPS_LINE].events:
            if ev.name.lstrip("%").startswith(WRAPPERS):
                continue
            s = float(ev.start_ns)
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < whole[i][0] + whole[i][1]:
                ops[i].append((ev.name, float(ev.duration_ns)))
        out.extend((m[2], m[1], o) for m, o in zip(whole, ops))
    return out, len(planes)


@functools.lru_cache(maxsize=8)
def _tally(path: str, needles: tuple, chips: int):
    from tony_tpu import observability

    program_parts = getattr(observability, "program_parts", None)
    if program_parts is None:
        return None
    data = lib.load("trace/host_spans.py").parse(path)[0]
    runs, n_planes = _runs(data, needles, chips)
    if not runs:
        return None
    by_module = collections.defaultdict(list)
    for name, dur, ops in runs:
        by_module[name].append((dur, ops))
    seconds, parts = 0.0, collections.Counter()
    for name, its_runs in by_module.items():
        seen = {op for _, ops in its_runs for op, _ in ops}
        mapping = program_parts(name, seen)
        if mapping is None:
            return None
        for dur, ops in its_runs:
            seconds += dur * 1e-9
            for op, op_dur in ops:
                parts[mapping.get(_instruction(op), "other")] += op_dur * 1e-9
    return (len(runs) / n_planes, seconds / n_planes,
            {k: v / n_planes for k, v in parts.items()})


def tally(facts: dict, key: str):
    needles = tuple(facts.get("programs", {}).get(key) or ())
    if not needles:
        return None
    trace_root = lib.load("trace/host_spans.py").TRACE_ROOT
    try:
        path = lib.load("trace/reduce.py").newest_xplane(str(trace_root))
    except FileNotFoundError:
        return None
    return _tally(path, needles, int(facts.get("chips", 1)))


def part_ms(found, part: str, steps_a_run: int):
    """Device ms a step of ``part`` ("other": the runs' time less every
    named part) from ``tally``'s result; None where nothing was found or no
    operation fell to the part."""
    if found is None:
        return None
    runs, seconds, parts = found
    steps = runs * steps_a_run
    if part == "other":
        value = seconds - sum(v for k, v in parts.items() if k != "other")
    elif part in parts:
        value = parts[part]
    else:
        return None
    return value / steps * 1e3

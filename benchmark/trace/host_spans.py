"""The program's own spans, from the host planes of the run's trace.

``spans(prefix)`` -> ``[(name, thread line, start_ns, duration_ns,
{count: value})]``: every event of a ``/host:`` plane whose name starts
with ``prefix``, in the order of its start, with the counts the program put
on it (``TraceAnnotation``'s keywords) as a dict. The device planes are
``reduce.py``'s; this reads what ``tony_tpu.observability.phase`` writes.

``facts`` carries no path to the trace, so the trace is the newest one
under ``<repo>/.bench_trace``, where ``run.py`` puts it and whence it
removes it only once the readers have run. It is parsed once a process. No
trace there, or no such span in it (a program without them), gives an empty
list, and a reader that gets one returns None.
"""

from __future__ import annotations

import functools

import lib

TRACE_ROOT = lib.ROOT.parent / ".bench_trace"


@functools.lru_cache(maxsize=1)
def parse(path: str) -> tuple:
    """-> (the parsed trace, which its events stay valid with; every event
    of its host planes as (name, thread line, start_ns, duration_ns,
    event), in the order of their starts)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            # one line a thread, and threads share names: number them
            thread = f"{plane.name}/{i}:{line.name}"
            events.extend((ev.name, thread, float(ev.start_ns),
                           float(ev.duration_ns), ev) for ev in line.events)
    events.sort(key=lambda e: e[2])
    return data, events


def spans(prefix: str, trace_dir=None) -> list:
    try:
        path = lib.load("trace/reduce.py").newest_xplane(
            str(trace_dir or TRACE_ROOT))
    except FileNotFoundError:
        return []
    return [(name, thread, start, dur, dict(ev.stats))
            for name, thread, start, dur, ev in parse(path)[1]
            if name.startswith(prefix)]


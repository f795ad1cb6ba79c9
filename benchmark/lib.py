"""Small shared pieces of the harness: loading a module of the benchmark by
its path, percentiles, and the record a driver hands back."""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent      # benchmark/
_MODULES: dict = {}


def load(rel: str):
    """Import ``benchmark/<rel>`` by path (once); the benchmark's
    directories are data and small files found by name, not a package."""
    path = (ROOT / rel).resolve()
    if path not in _MODULES:
        name = "bench_" + "_".join(path.relative_to(ROOT).with_suffix("").parts)
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def read_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default) of a non-empty list."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def peak_memory_bytes() -> int:
    """Peak bytes held on the fullest local device: the allocator's
    ``peak_bytes_in_use`` (arrays) plus its ``peak_bytes_reserved`` (the
    temporaries of the largest program loaded, which ``bytes_in_use``
    leaves out: PERF.md 4). 0 where the backend keeps no statistics, as
    the CPU's."""
    import jax

    def held(d) -> int:
        s = d.memory_stats() or {}
        return int(s.get("peak_bytes_in_use", 0)) \
            + int(s.get("peak_bytes_reserved", 0))

    return max(held(d) for d in jax.local_devices())


class CompileWatch:
    """Counts every program JAX builds or loads from its persistent cache
    (one ``backend_compile_duration`` event each; a hit in the in-memory
    cache fires none), with the programs' names: a driver reads it at the
    window's two ends, and anything between them fails the run."""

    _EVENT = "/jax/core/compile/backend_compile_duration"
    _installed: "CompileWatch | None" = None

    def __init__(self):
        self.names: list = []

    @classmethod
    def install(cls) -> "CompileWatch":
        if cls._installed is None:
            import jax.monitoring

            watch = cls._installed = cls()
            jax.monitoring.register_event_duration_secs_listener(
                lambda event, duration, **kw: watch.names.append(
                    str(kw.get("fun_name", "?")))
                if event == cls._EVENT else None)
        return cls._installed

    @property
    def count(self) -> int:
        return len(self.names)


class Checks:
    """The numbers that decide ``correct``, each beside its limit. A number
    is compared as ``value <= limit``; a limit of None means the cell's
    file sets none, which fails the run rather than passing it."""

    def __init__(self, limits: dict):
        self.limits = dict(limits or {})
        self.rows: list = []

    def add(self, name: str, value, limit=None) -> None:
        if limit is None:
            limit = self.limits.get(name)
        self.rows.append((name, float(value), limit))

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(
            lim is not None and val == val and val <= lim
            for _, val, lim in self.rows)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": l} for n, v, l in self.rows}

    def judge(self, numbers: dict) -> dict:
        """What these limits make of another set of the same numbers (a
        control's, a planted fault's): ``correct`` has to come out false."""
        other = Checks(self.limits)
        for name, value in numbers.items():
            other.add(name, value)
        return {"correct": other.ok, "compared": other.as_dict()}

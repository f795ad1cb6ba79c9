"""``trace/reduce.py`` on a small trace recorded on the chip
(``small.xplane.pb``, made by ``record_trace.py``: three rounds of a small
jitted matmul, a 20 ms host sleep under a ``bench_host_span`` annotation,
and a small jitted scan)."""

from __future__ import annotations

import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import lib  # noqa: E402

reduce = lib.load("trace/reduce.py")


def test_union_counts_nested_and_overlapping_intervals_once():
    covered, gaps = reduce.union_seconds(
        [(0, 10e9), (2e9, 3e9), (8e9, 4e9), (20e9, 1e9)])
    assert covered == 13.0
    assert gaps == [(12e9, 8e9)]


def test_reduce_small_recorded_trace(tmp_path):
    (tmp_path / "small.xplane.pb").write_bytes(
        (HERE / "small.xplane.pb").read_bytes())
    out = reduce.reduce(str(tmp_path), 1)
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    runs, seconds = reduce.program_time(out, ["bench_square"])
    assert runs == 3 and seconds > 0
    runs, seconds = reduce.program_time(out, ["bench_scan"])
    assert runs == 3 and seconds > 0
    assert 1 <= len(out["breakdown"]["device_ops"]) <= 10
    assert not any(name.lstrip("%").startswith("while")
                   for name, _ in out["breakdown"]["device_ops"])
    gaps = out["breakdown"]["idle_gaps"]
    assert 1 <= len(gaps) <= 10
    # the longest gaps are the 20 ms sleeps, under the host span around them
    assert gaps[0][1] > 0.015
    assert any("bench_host_span" in name for name, _ in gaps[:3])


def test_only_events_that_begin_inside_a_marked_step_count():
    events = [("a", 5.0, 1.0), ("b", 10.0, 2.0), ("c", 19.5, 3.0),
              ("d", 20.0, 1.0), ("e", 31.0, 1.0)]
    inside = reduce._inside(events, [(10.0, 10.0), (30.0, 5.0)])
    assert [e[0] for e in inside] == ["b", "c", "e"]

"""``BENCHMARK.json`` against the limits a check refuses it by before any
run: its keys, names, lengths, the cross-references between cells,
configurations and metrics, and the files each entry needs under
``benchmark/``."""

from __future__ import annotations

import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parent.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection)_size"
                   r"|_dim$|_rank$|head_size|expansion|experts_per_tok")


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_benchmark_json_meets_the_contract():
    raw = (REPO / "BENCHMARK.json").read_bytes()
    assert len(raw) <= 64 * 1024
    b = json.loads(raw)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(b["command"]) <= 32 and all(_line(w) for w in b["command"])
    assert 1 <= len(b["paths"]) <= 16
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    # a full check with all 24 cells fits
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200

    configs = {c["name"]: c for c in b["configs"]}
    assert len(configs) == len(b["configs"]) <= 24
    files = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert c["file"] not in files and (REPO / c["file"]).is_file()
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key

    cells = {w["name"]: w for w in b["workloads"]}
    assert len(cells) == len(b["workloads"]) <= 24
    pairs = set()
    data = REPO / b["paths"][0]
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = json.loads((data / "workloads" / f"{w['name']}.json").read_text())
        assert (data / "drivers" / f"{cell['driver']}.py").is_file()
        assert (data / "traffic" / f"{w['traffic']}.json").is_file()
        assert cell.get("limits"), "a cell without limits is never correct"
    assert {w["config"] for w in b["workloads"]} == set(configs)
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(cells) // 4)

    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert len(e2e) == len(b["end_to_end"]) <= 16 and "setup_s" in e2e
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", [])) <= set(cells)

    def reported_in(metric, cell):
        return cell in metric.get("workloads", list(cells))

    layers = {m["name"]: m for m in b["per_layer"]}
    assert len(layers) == len(b["per_layer"]) <= 128
    assert not set(layers) & set(e2e)
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert _line(m["layer"]) and m["moves"] in e2e
        for cell in m.get("workloads", []):
            assert cell in cells and reported_in(e2e[m["moves"]], cell)
        stem = m["name"].partition(".")[0]
        assert (data / "layer_metrics" / f"{stem}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            assert any("mfu" in re.split(r"[._]", o["name"])
                       and o["moves"] == m["moves"] for o in b["per_layer"])
    for cell in cells:
        assert sum(reported_in(m, cell) for m in b["end_to_end"]) >= 2
        assert any(cell in m.get("workloads", [cell])
                   for m in b["per_layer"])

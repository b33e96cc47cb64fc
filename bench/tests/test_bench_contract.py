"""`BENCHMARK.json` against the rules it must keep, the peaks table, and
the copied work counts."""
import json
import math
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import roofline  # noqa: E402
from bench.harness.cell import load_cell  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_units(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert spec["command"][1] == "bench/run.py"
    metrics = spec["end_to_end"] + spec["per_layer"]
    names = [x["name"] for x in spec["configs"] + spec["workloads"]
             + metrics]
    assert len(names) == len(set(names))
    for x in names:
        assert NAME.match(x), x
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in spec["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["unit"] != "%" or not m["name"].endswith("mfu")
    assert len(json.dumps(spec)) < 64 * 1024


def _line(text) -> bool:
    return (isinstance(text, str) and 0 < len(text) <= 200
            and "\n" not in text and "\t" not in text)


def test_entries_have_exactly_their_keys(spec):
    assert 0 < len(spec["command"]) <= 32
    assert all(_line(w) for w in spec["command"])
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert _line(w["why"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in spec["per_layer"]:
        assert _line(m["layer"])


def test_cells_report_what_they_must(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)
    pairs = {(w["config"], w["traffic"]) for w in spec["workloads"]}
    assert len(pairs) == len(spec["workloads"])
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    for w in spec["workloads"]:
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        own = [n for n, m in e2e.items() if n != "setup_s"
               and w["name"] in m.get("workloads", [w["name"]])]
        layer = [m for m in spec["per_layer"]
                 if w["name"] in m["workloads"]]
        assert own and layer, w["name"]
        for m in layer:       # a cell that reports it reports what it moves
            assert m["moves"] in own, (w["name"], m["name"])
        cell = load_cell(w["name"])
        assert cell.config["chips"] == w["chips"]
        assert cell.traffic["kind"] in ("fit", "serve")
        assert cell.limits
    for m in spec["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))


def test_configs_files_and_budget(spec):
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)
    for c in spec["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert 0 < len(c["source"]) <= 200
    s = spec["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    full = (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200
    assert full <= 43200


def test_peaks_by_device_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_work_counts_match_the_programs_model():
    from repro.perf import roofline as program

    for shape in ((4_898_431, 23, 41), (11_000_000, 2, 28), (7, 3, 5)):
        assert roofline.sweep_flops(*shape) == program.sweep_flops(*shape)
        assert roofline.sweep_bytes(*shape) == program.sweep_bytes(*shape)


def test_roofline_share_and_bound():
    n, c, d = 4_898_431, 23, 41
    t_bytes = roofline.sweep_bytes(n, c, d) / 819e9
    share, bound = roofline.sweep_roofline(n, c, d, 2 * t_bytes,
                                           "TPU v5 lite")
    assert bound == "bytes" and share == pytest.approx(50.0)
    share, bound = roofline.sweep_roofline(4096, 4096, 4096, 1.0,
                                           "TPU v5 lite")
    assert bound == "flops"
    assert share == pytest.approx(
        100 * roofline.sweep_flops(4096, 4096, 4096) / 197e12)
    assert math.isfinite(share)

"""BENCHMARK.json keeps to the benchmark's contract, and every piece of a
cell (configuration, traffic, per-layer reader) is found by name."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TEXT = re.compile(r"^[^\t\n\r]{1,200}$")
MAN = harness.manifest()
CELLS = [c["name"] for c in MAN["workloads"]]


def test_top_level_keys_and_size():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_command_and_paths():
    cmd, paths = MAN["command"], MAN["paths"]
    assert 1 <= len(paths) <= 16 and len(cmd) <= 32
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for w in cmd:
        assert TEXT.match(w) and not w.startswith("/") and ".." not in w
    files = [w for w in cmd if os.path.isfile(os.path.join(ROOT, w))]
    assert files and all(any(f.startswith(p + "/") for p in paths)
                         for f in files)


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = MAN["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_texts():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MAN[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e and group != "end_to_end" and group != "per_layer":
                    assert TEXT.match(e[k]), (e["name"], k)
            if group == "per_layer":
                assert TEXT.match(e["layer"])
    for c in MAN["workloads"]:
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_by_name(cell):
    c, cfg, traffic = harness.cell_files(MAN, cell)
    assert c["chips"] in (1, 4)
    assert set(c) == {"name", "config", "traffic", "chips", "why"}
    assert cfg["name"] == c["config"]
    assert traffic["loop"]["kind"] in harness.load.LOOPS
    assert set(cfg["templates"]) <= set(harness.templates.BUILDERS)
    assert set(harness.load.weights(traffic)) <= set(cfg["templates"])
    e2e = [m["name"] for m in harness.metrics_of(MAN, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert all(name in harness.E2E for name in e2e)
    layer = harness.metrics_of(MAN, cell, "per_layer")
    assert layer
    for m in layer:
        assert callable(harness.reader(m["name"]))


def test_configs():
    pairs = set()
    for c in MAN["workloads"]:
        assert (c["config"], c["traffic"]) not in pairs
        pairs.add((c["config"], c["traffic"]))
    assert sum(c["chips"] == 4 for c in MAN["workloads"]) <= max(
        1, len(CELLS) // 2)
    used = {c["config"] for c in MAN["workloads"]}
    files = set()
    for cfg in MAN["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        assert cfg["name"] in used and TEXT.match(cfg["source"])
        assert TEXT.match(cfg["why"])
        assert cfg["file"] not in files and any(
            cfg["file"].startswith(p + "/") for p in MAN["paths"])
        files.add(cfg["file"])
        assert len(cfg["reduced"]) <= 16
        body = harness.load_json(ROOT, cfg["file"])
        assert body["reduced"] == cfg["reduced"]
        for k in cfg["reduced"]:
            assert NAME.match(k)
            assert not re.search(r"(_dim|_rank|width|hidden)$", k)


def test_metrics():
    e2e = {m["name"] for m in MAN["end_to_end"]}
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in e2e
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    layers = {}
    for m in MAN["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_peaks_name_their_source():
    peaks = harness.load_json(harness.BENCH, "peaks.json")
    for kind, p in peaks.items():
        assert p["flops_per_s"] > 0 and p["hbm_bytes_per_s"] > 0
        assert p["source"]
    assert json.dumps(peaks)

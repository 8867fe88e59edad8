"""The harness finds every cell, configuration, traffic mix and metric
by name from the data files, and an unknown name is an error."""
import json
import os
import re

import pytest

from bench import registry

SPEC = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
LAYER_METRICS = [m["name"] for m in SPEC["per_layer"]]
E2E = {m["name"]: m for m in SPEC["end_to_end"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    c = registry.load_cell(cell)
    assert c.chips == 1
    assert c.driver().run
    assert c.generator().make
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"


@pytest.mark.parametrize("metric", LAYER_METRICS)
def test_reader_matches_its_entry(metric):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == metric)
    mod = registry.reader(metric)
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    moved = E2E[entry["moves"]]
    # Every cell that reports the metric reports what it moves.
    for cell in entry["workloads"]:
        assert registry.reports(moved, cell)


def test_config_files_match_benchmark():
    for c in SPEC["configs"]:
        data = registry.config(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert data["assumed"]


def test_traffic_files_hold_rate_or_keys_and_why():
    for w in SPEC["workloads"]:
        t = registry.traffic(w["traffic"])
        assert t["why"]
        assert ("rate_qps" in t) or ("n_keys" in t)


def test_names_and_bounds_keep_the_contract():
    for entry in SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] \
            + SPEC["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert E2E["setup_s"]["bound"] <= 0.25
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("lookup", [
    lambda: registry.load_cell("no-such-cell"),
    lambda: registry.config("no-such-config"),
    lambda: registry.traffic("no-such-mix"),
    lambda: registry.reader("no_such_metric"),
    lambda: registry.peaks("TPU v99"),
])
def test_unknown_name_is_an_error(lookup):
    with pytest.raises(registry.UnknownName):
        lookup()


def test_peak_table_has_the_v5e():
    p = registry.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12


def test_paths_hold_only_the_benchmark():
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][:3] == ["python3", "-m", "bench.run"]
    assert os.path.isfile(os.path.join(registry.BENCH, "run.py"))

"""BENCHMARK.json against the benchmark's contract, and the names it gives
resolved to files (CPU only)."""
from __future__ import annotations

import json
import os
import re

import numpy as np
import pytest

from chipbench import harness
from chipbench.generators import FleetTraffic, train_tokens

MANIFEST = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
WIDTH_KEYS = {"hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "num_experts_per_tok"}
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(harness.ROOT, p))
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) <= 64 * 1024


def test_every_name_and_unit_uses_allowed_characters():
    names = ([c["name"] for c in MANIFEST["configs"]] + CELLS
             + [w["traffic"] for w in MANIFEST["workloads"]]
             + [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
             + [k for c in MANIFEST["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in MANIFEST[group]]
        assert len(got) == len(set(got)), group


def test_entries_have_exactly_their_keys():
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")


def test_every_configuration_has_a_cell_and_a_file_of_its_own():
    used = {w["config"] for w in MANIFEST["workloads"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("chipbench/")
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
        assert not WIDTH_KEYS & set(c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])


def test_reduced_lists_every_key_changed_from_the_source():
    for c in MANIFEST["configs"]:
        cfg = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        published = cfg["published"]
        assert set(published) == set(c["reduced"])
        for key, value in published.items():
            assert cfg[key] != value, key


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name_and_reports_what_it_must(cell):
    resolved = harness.resolve_cell(MANIFEST, cell)
    e2e = {m["name"] for m in resolved.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert resolved.per_layer
    assert os.path.isfile(harness.traffic_path(resolved.traffic_name))
    assert os.path.isfile(harness.limits_path(cell))
    assert os.path.isfile(os.path.join(harness.BENCH_DIR, "systems",
                                       resolved.config["system"] + ".py"))
    for m in resolved.per_layer:
        assert m["moves"] in e2e, (m["name"], cell)
        assert callable(harness.load_reader(m["name"]))


def test_per_layer_metrics_move_an_end_to_end_metric_of_each_of_their_cells():
    layers = {}
    for m in MANIFEST["per_layer"]:
        for cell in m["workloads"]:
            assert cell in CELLS
            e2e = {x["name"] for x in harness.resolve_cell(MANIFEST, cell).end_to_end}
            assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_at_most_half_the_cells_take_four_chips():
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 2)


def test_a_full_check_fits_its_time_with_24_cells():
    rs = MANIFEST["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_train_tokens_are_deterministic_per_seed():
    a = train_tokens(2**40 + 7, 2, 2, 64, 49155)
    b = train_tokens(2**40 + 7, 2, 2, 64, 49155)
    c = train_tokens(2**40 + 8, 2, 2, 64, 49155)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])


def test_fleet_traffic_is_deterministic_and_seeds_only_reorder_it():
    params = harness.load_json(harness.traffic_path("screen_failslow"))
    big = 3_000_000_017
    a, b = FleetTraffic(params, 1024, big), FleetTraffic(params, 1024, big)
    assert np.array_equal(a.sample(500), b.sample(500))
    c = FleetTraffic(params, 1024, -big)
    assert not np.array_equal(a.faulty, c.faulty)
    # one sequence of (onset, severity, shape) for every seed
    for attr in ("onset", "severity", "ramp"):
        assert np.array_equal(getattr(a, attr), getattr(c, attr))
    assert len(set(a.faulty.tolist())) == a.faulty.size
    # severities and shapes in equal shares, every pairing of them present
    _, per_severity = np.unique(a.severity, return_counts=True)
    assert per_severity.max() - per_severity.min() <= 1
    assert abs(int(np.sum(a.ramp == 1)) - int(np.sum(a.ramp > 1))) <= 1
    assert len(set(zip(a.severity.tolist(), a.ramp.tolist()))) == 2 * len(params["severities"])

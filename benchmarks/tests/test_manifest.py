"""``BENCHMARK.json`` against the files it names and the contract's rules
that can be checked without a chip."""

import json
import os
import re

import pytest

from benchmarks.harness import manifest

B = json.load(open(os.path.join(manifest.REPO_ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = re.compile(r"(_size$|head_dim|_dim$|_rank$|num_experts_per_tok)")


def test_top_level_keys_and_sizes():
    assert sorted(B) == sorted(["command", "paths", "run_seconds", "configs",
                                "workloads", "end_to_end", "per_layer"])
    assert B["command"] == ["python3", "benchmarks/run.py"]
    assert B["paths"] == ["benchmarks"]
    assert 1 <= B["run_seconds"] <= 51
    # a full check of 24 cells must fit into 43200 s
    assert (2 + 14 * 24) * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(manifest.REPO_ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in B[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                        and "\t" not in e[key], (e["name"], key)
    assert len(names) == len(set(names))
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in B["end_to_end"]:
        assert sorted(set(m) - {"workloads"}) == ["better", "bound", "name",
                                                  "source", "unit"]
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in B["per_layer"]:
        assert sorted(set(m) - {"workloads"}) == ["better", "layer", "moves",
                                                  "name", "source", "unit"]
    assert any(m["name"] == "setup_s" and m["bound"] == 0.1
               for m in B["end_to_end"])


def test_cells_configs_and_files():
    cfgs = {c["name"]: c for c in B["configs"]}
    used = set()
    pairs = set()
    for w in B["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        cell = manifest.Cell(w["name"])
        assert cell.config["layout"]["chips"] == w["chips"]
        assert cell.traffic["kind"] == cell.config["runner"]
        assert cell.reference() is not None
        manifest.Cell(w["name"], rehearse=True)      # the tiny sizes merge
    assert used == set(cfgs)
    four = sum(1 for w in B["workloads"] if w["chips"] == 4)
    assert four <= max(len(B["workloads"]) // 4, 1)
    files = [c["file"] for c in B["configs"]]
    assert len(set(files)) == len(files)
    for c in B["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert c["file"].startswith("benchmarks/")
        body = json.load(open(os.path.join(manifest.REPO_ROOT, c["file"])))
        assert body["source"] == c["source"]
        # every published number is in the file under the same key, changed
        # only where "reduced" says so — and never a width
        for key, value in body["published"].items():
            if key in c["reduced"]:
                assert not WIDTHS.search(key), key
                assert body[key] != value
            else:
                assert body[key] == value, (c["name"], key)
        assert sorted(body["reduced"]) == sorted(c["reduced"])


def test_every_cell_reports_what_the_contract_asks():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    for w in B["workloads"]:
        cell = manifest.Cell(w["name"])
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert len(cell.per_layer) >= 1
    for m in B["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s", m["name"]
        moved = e2e[m["moves"]].get("workloads")
        assert "workloads" in m, m["name"]
        if moved is not None:
            # reported only where the metric it moves is
            assert set(m["workloads"]) <= set(moved), m["name"]


def test_every_per_layer_metric_has_a_reader_that_agrees():
    layers = {}
    for m in B["per_layer"]:
        cell = manifest.Cell(m["workloads"][0])
        reader = cell.layer_metric(m["name"])
        assert callable(reader.read)
        assert reader.LAYER == m["layer"], m["name"]
        assert reader.UNIT == m["unit"], m["name"]
        assert reader.SOURCE == m["source"], m["name"]
        layers.setdefault(m["layer"], []).append(m["name"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert set(layers) == {"entry", "train loop", "serve loop", "scheduler",
                           "kv cache", "compiled programs", "model",
                           "collectives", "kernels", "device"}


def test_an_unknown_device_kind_has_no_peaks():
    assert manifest.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        manifest.peaks_for("cpu")

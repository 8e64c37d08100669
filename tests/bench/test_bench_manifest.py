"""BENCHMARK.json against the contract's shape, and every name in it
resolving to its files."""

from __future__ import annotations

import json
import re

import pytest

from bench_tiny import ROOT, bench_at

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./\-]{1,200}$")


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert all(PATH.match(p) and ".." not in p for p in MANIFEST["paths"])
    assert MANIFEST["command"][1].split("/")[0] in MANIFEST["paths"]


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            yield group, entry


@pytest.mark.parametrize("group,entry", list(_names()),
                         ids=lambda x: x if isinstance(x, str) else x["name"])
def test_names_and_units(group, entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for k in ("config", "traffic"):
        if k in entry:
            assert NAME.match(entry[k])
    for k in ("why", "layer", "source"):
        if k in entry:
            assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k]


def test_names_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"]
               for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_resolves_to_its_files(cell):
    bench = bench_at()
    c = bench.cell(cell)
    assert c.chips in (1, 4)
    assert (ROOT / "bench" / "kinds" / f"{c.traffic['kind']}.py").is_file()
    assert callable(bench.module("refs", c.config_name).loss)
    assert callable(bench.module("flops", c.config_name).round_flops)
    assert c.limits and set(c.limits) <= {"loss_gap", "grad1_gap",
                                          "delta_gap", "frozen_change"}
    assert c.limits.get("frozen_change") == 0.0
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_metric_reader_exists(metric):
    assert callable(bench_at().module("metrics", metric).read)


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda e: e["name"])
def test_config_file_matches_manifest(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]


def test_peaks_unknown_device_is_an_error():
    bench = bench_at()
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        bench.peaks("cpu")

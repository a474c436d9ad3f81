"""Every configuration, cell, traffic and metric file is found by name."""

import importlib
import json

import pytest

from bench import families, generator, run

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_by_name(cell):
    spec = run.load_cell(cell)
    cfg = run.program_config(spec.sizes)
    assert cfg.n_layers == spec.sizes["n_layers"]
    assert spec.cell["session"]["seq_len"] >= generator.max_context(
        spec.traffic)
    assert set(spec.cell["check"]) == {"gap"}


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_state_their_cut(entry):
    sizes = json.loads((run.ROOT / entry["file"]).read_text())
    changed = set(sizes["reduced"]) | {
        k for k in sizes.get("departures", {}) if k in sizes}
    assert sorted(changed) == sorted(entry["reduced"])
    assert sizes["source"] and sizes["deployment"] and sizes["assumed"]


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_name_a_family_that_loads(entry):
    sizes = families.load(run.ROOT / entry["file"])
    family = families.of(sizes)
    assert family.__name__ == f"bench.families.{sizes['family']}"
    assert family.leaf_specs(sizes) and family.program_fields(sizes)


@pytest.mark.parametrize("family", [None, "no_such_family", "../configs"],
                         ids=["missing", "unknown", "not_a_name"])
def test_config_file_without_a_known_family_stops(tmp_path, family):
    """No silent default: the run stops and names the file."""
    sizes = json.loads((run.ROOT / BENCH["configs"][0]["file"]).read_text())
    sizes.pop("family")
    if family is not None:
        sizes["family"] = family
    path = tmp_path / "odd.json"
    path.write_text(json.dumps(sizes))
    with pytest.raises(SystemExit, match="odd.json"):
        families.load(path)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    reader = importlib.import_module(f"bench.metrics.{metric['name']}")
    assert callable(reader.read)
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert set(metric["workloads"]) <= set(CELLS)

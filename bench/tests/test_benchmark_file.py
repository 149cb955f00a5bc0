"""``BENCHMARK.json`` and the benchmark's files agree: every cell, its
configuration and traffic mix, every program library and the programs its
cells draw, every per-layer metric's reader and every end-to-end metric a
traffic kind declares are found by their names."""
import json
import re
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KINDS = sorted(p.stem for p in (BENCH / "traffic").glob("*.py"))


def _kind(name):
    return harness.load_module(BENCH / "traffic" / f"{name}.py")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_file_matches(cell):
    f = json.loads((BENCH / "workloads" / f"{cell['name']}.json").read_text())
    assert f == {"config": cell["config"], "traffic": cell["traffic"],
                 "chips": cell["chips"]}
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    assert (BENCH / "traffic" / f"{mix['kind']}.py").is_file()
    reported = {m["name"] for m in SPEC["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])}
    assert reported == set(_kind(mix["kind"]).END_TO_END)
    assert len(cell["why"]) <= 200 and NAME.match(cell["name"])


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cuts(cfg):
    f = json.loads((ROOT / cfg["file"]).read_text())
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    assert sorted(f["reduced"]) == sorted(cfg["reduced"])
    assert f["source"] == cfg["source"] and "assumed" in f


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_library_holds_its_cells_programs(cfg):
    f = json.loads((ROOT / cfg["file"]).read_text())
    lib_file = BENCH / "programs" / f"{f['programs']}.py"
    assert lib_file.is_file()
    lib = harness.load_module(lib_file)
    cells = [c for c in SPEC["workloads"] if c["config"] == cfg["name"]]
    assert cells
    for c in cells:
        mix = json.loads((BENCH / "traffic" / f"{c['traffic']}.json")
                         .read_text())
        drawn = _kind(mix["kind"]).program_names(f, mix)
        assert drawn and set(drawn) <= set(lib.NAMES), c["name"]


@pytest.mark.parametrize("kind", KINDS)
def test_kind_declares_its_end_to_end_metrics(kind):
    declared = _kind(kind).END_TO_END
    assert "setup_s" in declared and len(set(declared)) == len(declared)
    assert set(declared) <= {m["name"] for m in SPEC["end_to_end"]}


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(metric):
    src = (BENCH / "metrics" / f"{metric['name']}.py").read_text()
    assert f'UNIT = "{metric["unit"]}"' in src
    assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
    cells = {c["name"] for c in SPEC["workloads"]}
    assert set(metric["workloads"]) <= cells


def test_names_and_units():
    items = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] + \
        SPEC["per_layer"]
    names = [i["name"] for i in items]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"])
               for m in SPEC["end_to_end"] + SPEC["per_layer"])
    assert len(json.dumps(SPEC)) < 64 * 1024

"""The benchmark is driven by data: every cell, configuration, traffic mix,
family and per-layer metric is found by its name, and a new one is added
by adding files and entries."""
import json
import pathlib
import re
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_is_found_by_name(cell):
    c = harness.load_cell(cell)
    assert c.family.__name__.endswith("." + c.conf["family"])
    for part in ("init", "make_batch", "positions", "linears", "attention",
                 "program", "reference_loss"):
        assert callable(getattr(c.family, part))
    assert c.traffic["limits"] and all(
        v is not None for v in c.traffic["limits"].values())
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda e: e["name"])
def test_every_metric_reader_is_found_by_name(entry):
    mod = harness.load_metric(entry["name"])
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert callable(mod.read)


def test_names_and_units():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in METRICS]
             + [w["traffic"] for w in BENCH["workloads"]])
    assert all(NAME.match(n) for n in names), names
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        assert len({x["name"] for x in group}) == len(group)
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    assert all(m["better"] in ("lower", "higher") for m in METRICS)
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()


def test_at_most_one_cell_on_four_chips():
    chips = [w["chips"] for w in BENCH["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= 1


def test_every_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert "workloads" not in target or cell in target["workloads"]


def test_a_new_cell_is_only_new_files_and_entries(tmp_path):
    """A cell with a new traffic mix, found without editing a file."""
    (tmp_path / "benchmarks" / "chip").mkdir(parents=True)
    shutil.copytree(ROOT / "benchmarks" / "chip" / "configs",
                    tmp_path / "benchmarks" / "chip" / "configs")
    shutil.copytree(ROOT / "benchmarks" / "chip" / "traffic",
                    tmp_path / "benchmarks" / "chip" / "traffic")
    glue = json.loads(
        (tmp_path / "benchmarks/chip/traffic/squad384-int16.json").read_text())
    glue["seq_len"] = 128
    (tmp_path / "benchmarks/chip/traffic/glue128-int16.json").write_text(
        json.dumps(glue))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "bert-base.glue128-int16",
                               "config": "bert-base",
                               "traffic": "glue128-int16", "chips": 1,
                               "why": "short sequences"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("bert-base.glue128-int16", tmp_path)
    assert cell.traffic["seq_len"] == 128
    assert cell.family.positions(cell.conf, cell.traffic) == 32 * 128
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell("bert-base.absent", tmp_path)

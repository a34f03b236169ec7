"""BENCHMARK.json against the contract's rules that can be checked here,
every cell's files found by name, and a configuration, a traffic mix and
a per-layer metric added by new files and new entries alone."""

import json
import pathlib
import re
import shutil

import pytest
import yaml

import run_cell

BENCH = pathlib.Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_exactly_the_contracts_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("name", sorted(
    {m["name"] for m in METRICS}
    | {w[k] for w in SPEC["workloads"] for k in ("name", "config", "traffic")}
    | {c["name"] for c in SPEC["configs"]}
    | {r for c in SPEC["configs"] for r in c["reduced"]}))
def test_names_within_the_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    extra = set(metric) - {"name", "unit", "better", "source", "bound",
                           "layer", "moves", "workloads"}
    assert not extra, extra


def test_names_are_unique_and_one_line():
    for group in (METRICS, SPEC["workloads"], SPEC["configs"]):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))
    for entry in SPEC["workloads"] + SPEC["configs"]:
        for key in ("why", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200, (entry["name"], key)
                assert "\n" not in entry[key] and "\t" not in entry[key]


def test_bounds():
    bounds = {m["name"]: m["bound"] for m in SPEC["end_to_end"]}
    assert "setup_s" in bounds and bounds["setup_s"] <= 0.1
    assert all(0.01 <= b <= 0.1 for b in bounds.values())
    assert all("bound" not in m for m in SPEC["per_layer"])


def _cells_of(metric):
    return metric.get("workloads", [w["name"] for w in SPEC["workloads"]])


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_moves_names_an_end_to_end_metric_each_of_its_cells_reports(metric):
    target = next(m for m in SPEC["end_to_end"]
                  if m["name"] == metric["moves"])
    assert set(_cells_of(metric)) <= set(_cells_of(target))
    assert (BENCH / "metrics" / f"{metric['name']}.py").is_file()


def test_every_cell_reports_setup_one_more_and_a_layer_metric():
    for w in SPEC["workloads"]:
        e2e = [m["name"] for m in SPEC["end_to_end"] if w["name"] in _cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in _cells_of(m) for m in SPEC["per_layer"])


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cells_files_are_found_by_name(cell):
    got = run_cell.load_cell(cell["name"], rehearsal=False)
    conf = got["conf"]
    assert {"source", "reduced", "assumed", "dataset", "program", "toy",
            "limits"} <= set(conf)
    config = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert conf["reduced"] == config["reduced"]
    assert len(conf["source"]) <= 200
    assert config["file"] == f"benchmarks/configs/{cell['config']}.yaml"
    assert "why" in got["traffic"]
    for fn in ("init", "forward", "train_flops_per_sample"):
        assert callable(getattr(got["reference"], fn))


def test_peaks_table_is_keyed_by_device_kind_with_its_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"] == {"bf16_tflops": 197.0, "int8_tops": 393.0,
                                    "hbm_gbps": 819.0, "hbm_gb": 16.0}
    assert "Google Cloud" in peaks["_source"]


def test_a_config_a_mix_and_a_metric_are_added_by_files_alone(tmp_path,
                                                              monkeypatch):
    """A later PR's move, played on a copy: new files, new entries, no
    edit of a file that is there."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "_work", "__pycache__", "tests"))
    conf = yaml.safe_load((bench / "configs" / "bert_base_c7.yaml").read_text())
    conf["program"]["learning"]["batch-size"] = 64
    (bench / "configs" / "throwaway.yaml").write_text(yaml.safe_dump(conf))
    shutil.copy(bench / "configs" / "bert_base_c7.py",
                bench / "configs" / "throwaway.py")
    (bench / "traffic" / "tenth.json").write_text(json.dumps(
        {"why": "a tenth of an epoch a round", "loop": "closed",
         "epoch_fraction": 0.1, "validate": True, "checkpoint": True,
         "trace_rounds": 2}))
    (bench / "metrics" / "steps_seen.py").write_text(
        "def read(run):\n    return float(run['steps_in_window'])\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "throwaway", "source": "test",
                            "file": "benchmarks/configs/throwaway.yaml",
                            "reduced": conf["reduced"], "why": "test"})
    spec["workloads"].append({"name": "throwaway.tenth", "config": "throwaway",
                              "traffic": "tenth", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "steps_seen", "unit": "count",
                              "better": "higher", "source": "program_counter",
                              "layer": "compiled step",
                              "moves": "round_throughput",
                              "workloads": ["throwaway.tenth"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run_cell, "ROOT", tmp_path)
    monkeypatch.setattr(run_cell, "HERE", bench)

    got = run_cell.load_cell("throwaway.tenth", rehearsal=False)
    assert got["conf"]["program"]["learning"]["batch-size"] == 64
    assert got["traffic"]["epoch_fraction"] == 0.1
    names = [m["name"] for m in got["per_layer"]]
    assert "steps_seen" in names and "mfu" in names
    reader = run_cell.load_module(bench / "metrics" / "steps_seen.py")
    assert reader.read({"steps_in_window": 7}) == 7.0
    # and the cells that were there do not see the new metric
    old = run_cell.load_cell("bert_base_c7.round", rehearsal=False)
    assert "steps_seen" not in [m["name"] for m in old["per_layer"]]

"""The benchmark is data: every name in BENCHMARK.json resolves to its
files, and the file keeps to the benchmark's contract."""

import json
import math
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

import run
import weights

ROOT = pathlib.Path(run.__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_a_full_check_of_24_cells_fits_its_time():
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_config_and_mix(cell):
    c = run.find_cell(ROOT, cell)
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert cell == f"{w['config']}.{w['traffic']}"
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert c.config["name"] == w["config"]
    assert callable(run.arrival_law(c.mix["arrivals"]).drive)
    fam = run.family(c.config)
    assert all(callable(getattr(fam, f)) for f in (
        "make_weights", "logit_gaps", "model", "program_params"))
    # the correctness limit, set from chip readings, and the rows it is
    # judged on
    assert c.config["correct"]["max_logit_gap"] > 0
    assert c.mix["check"]["length"] % 256 == 0
    assert c.mix["check"]["length"] >= c.mix["reach_tokens"]


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    path = ROOT / conf["file"]
    assert path.parts[len(ROOT.parts)] == "chipbench"
    cfg = json.loads(path.read_text())
    assert set(conf["reduced"]) == set(cfg["reduced"])
    for key in conf["reduced"]:
        assert NAME.match(key) and key in cfg["published"]
    n = sum(math.prod(s) for s in weights.shapes(cfg).values())
    assert n == cfg["parameters"]
    kv = 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * 2
    assert kv == cfg["kv_bytes_per_token"]


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_resolves_to_its_reader(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert callable(run.reader(m["name"]).read)
    if m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["layer"] and "\n" not in m["layer"]
        assert set(m["workloads"]) <= set(CELLS)
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    else:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    if "_roofline" in m["name"] or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_every_cell_of_a_layer_metric_reports_what_it_moves():
    for m in BENCH["per_layer"]:
        for cell in m["workloads"]:
            moved = next(e for e in BENCH["end_to_end"]
                         if e["name"] == m["moves"])
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_e2e_and_a_layer_metric(cell):
    c = run.find_cell(ROOT, cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))


def test_peaks_by_device_kind():
    p = run.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        run.peaks_for("TPU v9 imaginary")


def test_a_run_without_a_tpu_exits_nonzero_with_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A checkout holding only BENCHMARK.json and chipbench/ has no system
    under test: the run fails and prints no result line."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env={**env, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""

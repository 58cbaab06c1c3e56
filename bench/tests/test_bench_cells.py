"""The harness finds cells, configurations, traffic mixes and metric readers
by name alone, ``BENCHMARK.json`` keeps to the contract's shape, and a run
without a TPU exits non-zero with no result."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchkit import cells

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return cells.load_spec(ROOT)


def test_every_cell_resolves_with_its_pieces(spec):
    for w in spec["workloads"]:
        cell = cells.resolve(spec, w["name"])
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        assert cell.traffic["query_streams"] >= 1
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(cells.reader(m["name"]))


def test_spec_shape(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(spec["paths"][0] + "/")
        with open(ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert "bound" not in m
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in spec["workloads"]}


def test_new_cell_is_found_from_added_files_alone(tmp_path, spec):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench / "configs" / "ssb_tiny.json").write_text(json.dumps(
        {"name": "ssb_tiny", "sf": 0.001, "reduced": ["sf"]}))
    (bench / "traffic" / "one_stream.json").write_text(json.dumps(
        {"query_streams": 1, "dispatchers": 1, "appends_per_s": 0,
         "check_sample": 4}))
    (bench / "metrics" / "queries_done.py").write_text(
        "def read(ctx):\n    return ctx['queries_done'] or None\n")
    new = json.loads(json.dumps(spec))
    new["configs"].append({"name": "ssb_tiny", "source": "x",
                           "file": "bench/configs/ssb_tiny.json",
                           "reduced": ["sf"], "why": "x"})
    new["workloads"].append({"name": "tiny.one", "config": "ssb_tiny",
                             "traffic": "one_stream", "chips": 1,
                             "why": "x"})
    new["per_layer"].append({"name": "queries_done", "unit": "queries",
                             "better": "higher", "source": "host_clock",
                             "layer": "serving", "moves": "queries_per_s",
                             "workloads": ["tiny.one"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    cell = cells.resolve(cells.load_spec(tmp_path), "tiny.one",
                         root=tmp_path, bench=bench)
    assert cell.config["sf"] == 0.001
    assert cell.traffic["query_streams"] == 1
    assert [m["name"] for m in cell.per_layer] == ["queries_done"]
    assert {m["name"] for m in cell.end_to_end} == {
        m["name"] for m in spec["end_to_end"] if "workloads" not in m}
    read = cells.reader("queries_done", bench=bench)
    assert read({"queries_done": 7}) == 7 and read({"queries_done": 0}) is None


def test_unknown_cell_and_device_kind_are_errors(spec):
    with pytest.raises(KeyError):
        cells.resolve(spec, "no_such_cell")
    with pytest.raises(KeyError):
        cells.peaks("TPU v9 imaginary")
    assert cells.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _run(cwd, env_extra=None):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ssb1_uniform_read",
         "--seed", "2147483648", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""

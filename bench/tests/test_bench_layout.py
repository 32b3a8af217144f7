"""The benchmark finds every piece of a cell by name, so a later change
adds files without editing any, and it refuses to run without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import common

BENCHMARK = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    spec = common.resolve(cell)
    assert spec["config"]["name"] == spec["cell"]["config"]
    assert os.path.exists(os.path.join(
        common.BENCH, "paths", spec["traffic"]["path"] + ".py"))
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert callable(common.load_reader(m["reader"]))
        assert m["moves"] in names


def test_benchmark_file_keeps_the_contract_shape():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCHMARK[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCHMARK["configs"]:
        assert c["file"].startswith("bench/")
    four = sum(w["chips"] == 4 for w in BENCHMARK["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_added_cell_is_found_without_code_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(common.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["workloads"].append({"name": "t3-50w.mc.serve-c8",
                               "config": "table3-50w",
                               "traffic": "t3-50w.mc.serve-c8", "chips": 1,
                               "why": "a cell added as data"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "t3-50w.mc.serve" in m.get("workloads", []):
            m["workloads"].append("t3-50w.mc.serve-c8")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = dict(common.load_json(os.path.join(
        common.BENCH, "workloads", "t3-50w.mc.serve.json")),
        chunk_intervals=8)
    (root / "bench" / "workloads" / "t3-50w.mc.serve-c8.json").write_text(
        json.dumps(traffic))
    spec = common.resolve("t3-50w.mc.serve-c8", root=str(root))
    assert spec["traffic"]["chunk_intervals"] == 8
    assert {m["name"] for m in spec["per_layer"]} == {
        m["name"] for m in common.resolve("t3-50w.mc.serve")["per_layer"]}


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    p = _run(common.ROOT, {})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_refuses_in_a_bare_benchmark_directory(tmp_path):
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(common.ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""

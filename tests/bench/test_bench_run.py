"""The runner's refusals and the benchmark's layout."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.run import _load_module  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def _run(cwd, *extra):
    cell = BENCH["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(2 ** 31 + 5), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=_cpu_env(), capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or '"metrics"' not in lines[-1]


def test_exits_nonzero_without_an_accelerator():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "accelerator" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)


def test_names_and_files_resolve():
    assert BENCH["command"] == ["python3", "bench/run.py"]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert (ROOT / "bench" / "reference"
                / f"{cfg['reference']}.py").exists()
    for w in BENCH["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert w["chips"] == 1
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        mod = _load_module(ROOT / "bench" / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_setup_another_e2e_and_a_layer(cell):
    def reported(kind):
        return [m["name"] for m in BENCH[kind]
                if cell in m.get("workloads", [cell])]

    e2e = reported("end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2
    assert reported("per_layer")

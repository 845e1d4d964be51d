"""A configuration's deployment names the engine the harness builds:
without ``mesh`` it is the engine every one-chip cell has always had;
with ``"mesh": {"seq": 4}`` the harness serves the cell over four
devices on the program's sequence-sharded path, the weights replicated
on each, and the check still reads correct."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.core import sut  # noqa: E402

GPT2 = json.loads((ROOT / "bench" / "configs" / "gpt2-small.json")
                  .read_text())


class _Recorder:
    def __init__(self, cfg, params, **kw):
        self.args, self.kw = (cfg, params), kw


def test_deployment_without_mesh_builds_the_one_chip_engine(monkeypatch):
    monkeypatch.setattr(sut, "ContinuousBatchingEngine", _Recorder)
    deploy = GPT2["deployment"]
    assert "mesh" not in deploy
    assert sut.mesh_context(deploy, jax.devices()[:1]) is None
    assert sut.weight_sharding(None) is None
    eng = sut.make_engine("cfg", "params", deploy, 2 ** 32 + 11)
    assert eng.args == ("cfg", "params")
    assert eng.kw == {"slots": 8, "max_len": 1024, "astra_mode": "off",
                      "cache_mode": "paged", "page_size": 16,
                      "use_pallas": True, "seed": 11}


@pytest.mark.parametrize("mesh", [{"seq": 4}, {"seq": 1, "data": 1}])
def test_mesh_that_does_not_fit_the_cell_is_refused(mesh):
    deploy = dict(GPT2["deployment"], mesh=mesh)
    with pytest.raises(ValueError):
        sut.mesh_context(deploy, jax.devices()[:1])


# run in a process of its own, which sees 4 CPU devices
_SCRIPT = r"""
import json, sys
from pathlib import Path
root, tests = Path(sys.argv[1]), Path(sys.argv[2])
sys.path[:0] = [str(tests), str(tests.parents[1]), str(tests.parents[1] / "src")]
import jax
import test_bench_check as tbc
import bench.run as br
from bench.core import sut

cfg = getattr(tbc, sys.argv[3])
cfg = dict(cfg, deployment=dict(cfg["deployment"], mesh={"seq": 4}))
tbc.make_root(root, cfg, chips=4)

seen = {}
make_engine = sut.make_engine

def recording(mcfg, params, deploy, seed, mesh_ctx=None):
    eng = make_engine(mcfg, params, deploy, seed, mesh_ctx)
    leaves = jax.tree.leaves(eng.params)
    seen["sharded"] = eng.backend.sharded
    seen["seq_shards"] = mesh_ctx.num_seq_shards
    seen["leaves"] = len(leaves)
    seen["replicated"] = sum(
        l.sharding.is_fully_replicated and len(l.sharding.device_set) == 4
        for l in leaves)
    return eng

sut.make_engine = recording
out = br.run_cell(root, "tiny-chat", 2 ** 31 + 21, float(sys.argv[4]), False,
                  require_accelerator=False)
print(json.dumps({"out": out, "seen": seen}))
"""


# float32 reads the reference's gap of 0; bfloat16, the cells' dtype, is
# held to its own limit (on 4 CPU devices sound runs read up to 0.0217
# over 18 seeds, against test_bench_check's BF16_LIMIT of 0.08)
@pytest.mark.parametrize("cfg,seconds", [("TINY", 1.5), ("TINY_BF16", 3.0)],
                         ids=["float32", "bfloat16"])
def test_mesh_cell_serves_sharded_and_reads_correct(tmp_path, cfg, seconds):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(tmp_path / "root"),
         str(Path(__file__).resolve().parent), cfg, str(seconds)],
        capture_output=True, text=True, timeout=120, env=env, cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    out, seen = got["out"], got["seen"]
    assert seen["sharded"] is True and seen["seq_shards"] == 4
    assert seen["leaves"] > 0 and seen["replicated"] == seen["leaves"]
    assert out["device"]["count"] == 4
    assert out["correct"] is True, out["check"]
    assert out["check"]["requests_checked"]["value"] >= 1

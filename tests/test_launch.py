"""Launcher substrate: step bundles build, lower AND compile on a tiny mesh
with reduced configs — integration coverage for steps.py/sharding.py without
the 512-device dry-run environment."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import cost_analysis, make_mesh
from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.launch import steps as S
from repro.launch.mesh import make_host_mesh


def tiny_mesh():
    return make_mesh((1, 1), ("data", "model"))


def compile_bundle(bundle, mesh):
    jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     donate_argnums=bundle.donate_argnums)
    with mesh:
        return jitted.lower(*bundle.abstract_args).compile()


@pytest.mark.parametrize("arch", ["starcoder2-3b", "dbrx-132b",
                                  "mamba2-130m", "recurrentgemma-9b"])
def test_train_bundle_compiles(arch):
    cfg = get_config(arch).reduced()
    shape = ShapeSpec("t", 64, 4, "train")
    mesh = tiny_mesh()
    b = S.build_train(cfg, shape, mesh)
    c = compile_bundle(b, mesh)
    assert cost_analysis(c)


@pytest.mark.parametrize("arch", ["codeqwen1.5-7b", "internvl2-26b",
                                  "seamless-m4t-large-v2"])
def test_prefill_bundle_compiles(arch):
    cfg = get_config(arch).reduced()
    shape = ShapeSpec("p", 64, 4, "prefill")
    mesh = tiny_mesh()
    b = S.build_prefill(cfg, shape, mesh)
    compile_bundle(b, mesh)


@pytest.mark.parametrize("arch", ["gemma2-27b", "mamba2-130m"])
def test_decode_bundle_compiles(arch):
    cfg = get_config(arch).reduced()
    shape = ShapeSpec("d", 128, 4, "decode")
    mesh = tiny_mesh()
    b = S.build_decode(cfg, shape, mesh)
    compile_bundle(b, mesh)


def test_train_bundle_executes_and_updates(tmp_path):
    """Concrete end-to-end: one optimizer step through the bundle."""
    cfg = get_config("starcoder2-3b").reduced()
    shape = ShapeSpec("t", 32, 2, "train")
    mesh = tiny_mesh()
    b = S.build_train(cfg, shape, mesh)
    from repro.models import model_factory as mf
    from repro.training import optimizer as opt_mod

    params = mf.init_params(jax.random.PRNGKey(0), cfg)
    opt = opt_mod.init_opt_state(params, opt_mod.AdamWConfig())
    batch = mf.input_specs(cfg, shape, concrete=True,
                           key=jax.random.PRNGKey(1))
    with mesh:
        p2, o2, metrics = jax.jit(b.fn)(params, opt, batch,
                                        jax.random.PRNGKey(2))
    assert np.isfinite(float(metrics["loss"]))
    # params actually moved
    delta = max(float(jnp.max(jnp.abs(a - b_)))
                for a, b_ in zip(jax.tree.leaves(p2),
                                 jax.tree.leaves(params)))
    assert delta > 0


def test_combo_supported_rules():
    from repro.configs import SHAPE_BY_NAME

    long = SHAPE_BY_NAME["long_500k"]
    ok, _ = S.combo_supported(get_config("mamba2-130m"), long)
    assert ok
    ok, reason = S.combo_supported(get_config("llama3-405b"), long)
    assert not ok and "sub-quadratic" in reason


def test_expert_parallel_override_targets_expert_dim():
    from jax.sharding import PartitionSpec as P

    cfg = get_config("dbrx-132b")
    mesh = tiny_mesh()
    leaf = jax.ShapeDtypeStruct((40, 16, 6144, 10752), jnp.bfloat16)
    tree = {"stages": [{"sub0": {"moe": {"w_up": leaf}}}]}
    shd0 = jax.tree.map(lambda l: None, tree)
    out = S._apply_expert_parallel(cfg, tree, shd0, mesh, "model")
    spec = out["stages"][0]["sub0"]["moe"]["w_up"].spec
    assert spec == P(None, "model", None, "data")


def test_host_mesh_shapes():
    m = make_host_mesh(1, 1)
    assert dict(m.shape) == {"data": 1, "model": 1}


def test_compile_cache_dir_honours_env_and_is_fixed(monkeypatch, tmp_path):
    """The entry points' compile cache: ``JAX_COMPILATION_CACHE_DIR`` wins
    when set; otherwise one fixed directory inside the checkout, the same
    on every call (a moving directory would never hit).  Only the path is
    resolved here: the tests never turn the cache on."""
    from repro.launch import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == os.path.join(root, ".jax_cache")

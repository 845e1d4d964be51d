"""repro.compat: the JAX API seams + the no-direct-use invariant."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.analysis import run_rules


def test_no_direct_version_sensitive_jax_apis():
    # the tokenize-based grep lives in repro.analysis now (rule compat-api,
    # with compat.py as the structural exemption); this stays the
    # compat-owned assertion that the tree holds the invariant
    findings = run_rules(rules=["compat-api"])
    assert not findings, (
        "version-sensitive JAX APIs used directly (route through "
        "repro/compat.py):\n" + "\n".join(str(f) for f in findings))


def test_shard_map_runs_with_check_vma_kwarg():
    mesh = compat.make_mesh((1,), ("model",))

    def body(x):
        return x * compat.axis_size("model")

    y = compat.shard_map(body, mesh=mesh, in_specs=(P("model"),),
                         out_specs=P("model"), check_vma=False)(
        jnp.arange(4.0))
    assert jnp.allclose(y, jnp.arange(4.0))


def test_make_mesh_shapes_and_names():
    m = compat.make_mesh((1, 1), ("data", "model"))
    assert dict(m.shape) == {"data": 1, "model": 1}


def test_cost_analysis_normalized_to_flat_dict():
    compiled = jax.jit(lambda x: x @ x).lower(
        jnp.zeros((16, 16), jnp.float32)).compile()
    cost = compat.cost_analysis(compiled)
    assert isinstance(cost, dict)
    assert cost.get("flops", 0.0) > 0.0


def test_cost_analysis_lets_errors_raise():
    """A failing analysis is an error, not an empty report; a backend with
    no analysis at all (None) gives {}."""
    class Broken:
        def cost_analysis(self):
            raise RuntimeError("analysis failed")

    class Absent:
        def cost_analysis(self):
            return None

    with pytest.raises(RuntimeError, match="analysis failed"):
        compat.cost_analysis(Broken())
    assert compat.cost_analysis(Absent()) == {}

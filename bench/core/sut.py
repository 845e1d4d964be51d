"""The system under test: the repository's ``ContinuousBatchingEngine``
(what ``repro.launch.serve --continuous`` drives), built from a
configuration file's deployment, with the program config and weights that
the configuration's family file (``bench/families/<family>.py``) makes.

This module, the load loop and the family files are the benchmark's only
importers of the program.  The engine's own tuning knobs
(``decode_chunk``, ``prefill_chunk``) are left at the program's defaults.

The deployment keys read here: ``cache_mode``, ``slots``, ``max_len``,
``page_size`` and ``use_pallas``, always; ``mesh``, optional:
``{"seq": N}`` splits each sequence over the cell's N chips (the
program's sequence-sharded path, over a one-axis mesh ``("model",)``; N
has to be the cell's ``chips``).  A mesh cell's weights are replicated,
every chip holding the whole model (``weight_sharding``) and a 1/N share
of each sequence.  A deployment without ``mesh`` builds the engine it
always built.  ASTRA's attention stays off (``astra_mode="off"``): every
cell serves at the precision its configuration's ``compute`` states.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.compat import make_mesh
from repro.configs.base import ModelConfig
from repro.core.sequence_parallel import MeshContext
from repro.serving import steps as serving_steps
from repro.serving.scheduler import ContinuousBatchingEngine

SEQ_AXIS = "model"


def mesh_context(deployment: Dict, devices: Sequence) -> Optional[MeshContext]:
    """The cell's mesh from ``deployment["mesh"]`` over ``devices`` (the
    cell's chips), or None where the deployment names none.  A mesh whose
    size is not the cell's chip count, or with a key other than
    ``seq``, is refused."""
    mesh = deployment.get("mesh")
    if mesh is None:
        return None
    if set(mesh) != {"seq"}:
        raise ValueError(f"a deployment's mesh names only 'seq'; got "
                         f"{sorted(mesh)}")
    n = int(mesh["seq"])
    if n != len(devices):
        raise ValueError(f"the deployment splits each sequence over {n} "
                         f"chips; the cell has {len(devices)}")
    return MeshContext(mesh=make_mesh((n,), (SEQ_AXIS,),
                                      devices=list(devices)),
                       batch_axes=(), seq_axis=SEQ_AXIS)


def weight_sharding(ctx: Optional[MeshContext]):
    """Every weight replicated over the mesh's chips, or None (no mesh:
    the default device)."""
    if ctx is None:
        return None
    return NamedSharding(ctx.mesh, PartitionSpec())


def make_engine(mcfg: ModelConfig, params, deployment: Dict, seed: int,
                mesh_ctx: Optional[MeshContext] = None
                ) -> ContinuousBatchingEngine:
    kw = {} if mesh_ctx is None else {"mesh_ctx": mesh_ctx}
    return ContinuousBatchingEngine(
        mcfg, params, slots=int(deployment["slots"]),
        max_len=int(deployment["max_len"]), astra_mode="off",
        cache_mode=deployment["cache_mode"],
        page_size=int(deployment["page_size"]),
        use_pallas=bool(deployment["use_pallas"]),
        seed=seed & 0x7FFFFFFF, **kw)


def prefill_shapes(eng: ContinuousBatchingEngine,
                   length: int) -> List[Tuple[int, int]]:
    """The (chunk width, attention view) programs a prompt of ``length``
    tokens runs through, per the engine's own chunk plan."""
    return [(w, serving_steps.view_bucket(s0 + w, eng.max_len))
            for s0, w in serving_steps.plan_chunks(length,
                                                   eng.prefill_buckets)]


def warm_lengths(eng: ContinuousBatchingEngine,
                 lengths: List[int]) -> List[int]:
    """A short list of prompt lengths, from ``lengths``, whose chunk plans
    together reach every (width, view) program that any of ``lengths``
    reaches (greedy cover, longest first)."""
    need = {ln: set(prefill_shapes(eng, ln)) for ln in lengths}
    todo = set().union(*need.values())
    picked = []
    while todo:
        best = max(sorted(need, reverse=True),
                   key=lambda ln: len(need[ln] & todo))
        picked.append(best)
        todo -= need[best]
    return picked


def warm_up(eng: ContinuousBatchingEngine, prompts: List[List[int]]) -> None:
    """Run every prefill and decode program the cell can reach: the given
    prompts, admitted together so that slots other than 0 are used too,
    with a few output tokens each; then clear the engine's records."""
    for p in prompts:
        eng.submit(p, max(2 * eng.decode_chunk, 2))
    while not eng.idle:
        eng.step()
    jax.block_until_ready((eng.caches, eng.lengths, eng.cur_token))
    eng.finished.clear()

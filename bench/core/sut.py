"""The system under test: the repository's ``ContinuousBatchingEngine``
(what ``repro.launch.serve --continuous`` drives), built from a
configuration file's deployment, with the program config and weights that
the configuration's family file (``bench/families/<family>.py``) makes.

This module, the load loop and the family files are the benchmark's only
importers of the program.  The engine's own tuning knobs
(``decode_chunk``, ``prefill_chunk``) are left at the program's defaults.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax

from repro.configs.base import ModelConfig
from repro.serving import steps as serving_steps
from repro.serving.scheduler import ContinuousBatchingEngine


def make_engine(mcfg: ModelConfig, params, deployment: Dict,
                seed: int) -> ContinuousBatchingEngine:
    return ContinuousBatchingEngine(
        mcfg, params, slots=int(deployment["slots"]),
        max_len=int(deployment["max_len"]), astra_mode="off",
        cache_mode=deployment["cache_mode"],
        page_size=int(deployment["page_size"]),
        use_pallas=bool(deployment["use_pallas"]),
        seed=seed & 0x7FFFFFFF)


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

"""Disaggregated prefill/decode serving: VQ-compressed KV hand-off.

The device set splits into a *prefill group* and a *decode group*
(``split="P:D"``).  The prefill group runs the chunked prefill — sequence-
sharded over its own mesh when P > 1 — and the finished cache migrates to
the decode group, which decodes on its own mesh (D > 1 shards sequences
again on arrival).  Under ``cache_mode="vq"`` the migrated state is the
*stripped* prefill cache: per-layer VQ code slabs (plus fp rings for the
windowed layers, whose in-window state is never quantized), so the wire
carries ``G * code_bytes`` per token per layer instead of ``d_kv * 4`` —
the same ~8-16x reduction the paper's Appendix-G cache accounting promises.
``cache_mode="fp"`` ships full-precision slabs and is the baseline the
compression is measured against.

The hand-off is executed (the cache tree crosses the host boundary between
the two engines' device groups) and *accounted*: ``migration_bytes`` are
measured from the migrated leaves, the fp-equivalent bytes are derived from
the same tree's geometry, and ``core.comm_model.migration_report`` costs
both at the paper's 10-500 Mbps bandwidth grid.

Paged modes are rejected: page pools hold pool-global page ids that do not
survive re-admission into a different group's pool — the slab hand-off is
the contiguous-layout feature.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh
from repro.core.comm_model import migration_report
from repro.core.sequence_parallel import LOCAL, MeshContext
from repro.serving import cache_backend as cbe
from repro.serving import steps as serving_steps
from repro.serving.engine import GenerationResult, ServingEngine

# slab leaves that ride the wire as codes; everything else ships as-is
_CODE_LEAVES = ("k_codes", "v_codes")


def parse_split(split: str) -> Tuple[int, int]:
    """``"P:D"`` -> (prefill_devices, decode_devices)."""
    try:
        p, d = (int(x) for x in split.split(":"))
    except ValueError:
        raise ValueError(f"--disagg expects 'P:D' device counts, got "
                         f"{split!r}") from None
    if p < 1 or d < 1:
        raise ValueError(f"--disagg needs at least one device per group, "
                         f"got {split!r}")
    return p, d


def _mesh_for(devices, n: int) -> MeshContext:
    if n == 1:
        return LOCAL
    return MeshContext(mesh=make_mesh((n,), ("model",), devices=devices),
                       batch_axes=(), seq_axis="model")


def _cache_wire_bytes(caches, cfg) -> Tuple[int, int]:
    """(migrated_bytes, fp_equivalent_bytes) for a stripped slab cache.

    Code slabs — (..., S, G) with any leading layer-stack/batch axes —
    count their own nbytes against the fp cache the same positions would
    occupy (``d_kv * 4`` bytes per position); fp leaves (windowed rings,
    recurrent state, fp-mode slabs) ship at face value.
    """
    d_kv = cfg.num_kv_heads * cfg.head_dim
    coded = fp_equiv = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        name = str(path[-1])
        nbytes = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        coded += nbytes
        if any(key in name for key in _CODE_LEAVES):
            positions = int(np.prod(leaf.shape[:-1]))  # drop the G axis
            fp_equiv += positions * d_kv * 4
        else:
            fp_equiv += nbytes
    return coded, fp_equiv


class DisaggregatedEngine:
    """Prefill on one device group, decode on another, slab hand-off in
    between.  Greedy outputs are identical to a single ``ServingEngine``
    with the same ``cache_mode`` — disaggregation moves the cache, never
    the numerics."""

    def __init__(self, cfg, params, *, max_len: int = 256,
                 split: str = "1:1", astra_mode: str = "off",
                 cache_mode: str = "fp", decode_chunk: Optional[int] = None,
                 use_pallas: bool = False,
                 bandwidths_mbps: Sequence[float] = (10.0, 100.0, 500.0)):
        if cbe.get_backend(cache_mode).paged:
            raise ValueError(
                f"cache_mode={cache_mode!r}: disaggregated hand-off "
                "migrates contiguous slabs; paged pools hold pool-global "
                "page ids that don't survive re-admission into the decode "
                "group's pool — use 'fp' or 'vq'")
        self.cfg = cfg
        self.num_prefill, self.num_decode = parse_split(split)
        for n, group in ((self.num_prefill, "prefill"),
                         (self.num_decode, "decode")):
            if n > 1 and max_len % n:
                raise ValueError(
                    f"max_len={max_len} must divide across the {n} "
                    f"{group}-group devices (the shard cache splits the "
                    f"sequence dimension evenly)")
        devices = jax.devices()
        if self.num_prefill + self.num_decode > len(devices):
            raise ValueError(
                f"split {split!r} needs "
                f"{self.num_prefill + self.num_decode} devices (disjoint "
                f"prefill and decode groups), host has {len(devices)}")
        pre = devices[:self.num_prefill]
        dec = devices[self.num_prefill:self.num_prefill + self.num_decode]
        self.prefill_engine = ServingEngine(
            cfg, params, max_len=max_len, astra_mode=astra_mode,
            cache_mode=cache_mode, decode_chunk=decode_chunk,
            use_pallas=use_pallas,
            mesh_ctx=_mesh_for(pre, self.num_prefill))
        self.decode_engine = ServingEngine(
            cfg, params, max_len=max_len, astra_mode=astra_mode,
            cache_mode=cache_mode, decode_chunk=decode_chunk,
            use_pallas=use_pallas,
            mesh_ctx=_mesh_for(dec, self.num_decode))
        self.decode_device = dec[0]
        self.max_len = max_len
        self.cache_mode = cache_mode
        self.bandwidths_mbps = tuple(bandwidths_mbps)
        # running hand-off accounting (one entry per generate() call)
        self.migration_bytes = 0
        self.migration_fp_bytes = 0
        self.migrations = 0

    def _migrate(self, last_logits, caches):
        """Move the finished prefill state to the decode group; the
        device_get/device_put pair is the wire crossing."""
        coded, fp_equiv = _cache_wire_bytes(caches, self.cfg)
        self.migration_bytes += coded
        self.migration_fp_bytes += fp_equiv
        self.migrations += 1
        host_logits, host_caches = jax.device_get((last_logits, caches))
        de = self.decode_engine
        if self.num_decode == 1:
            return jax.device_put((host_logits, host_caches),
                                  self.decode_device)
        # D > 1: straight onto the decode mesh, in the shardings its
        # decode step returns (the first step then compiles the last time)
        return (de.backend.commit_rows(host_logits, de.decode_ctx),
                de.backend.commit_caches(host_caches, de.decode_ctx))

    def migration_report(self) -> dict:
        """fp-vs-coded hand-off bytes and transfer times at the bandwidth
        grid (``core.comm_model.migration_report``), plus per-migration
        averages."""
        rep = migration_report(self.migration_fp_bytes, self.migration_bytes,
                               self.bandwidths_mbps)
        rep["migrations"] = self.migrations
        rep["bytes_per_migration"] = (
            self.migration_bytes / max(self.migrations, 1))
        rep["split"] = f"{self.num_prefill}:{self.num_decode}"
        rep["cache_mode"] = self.cache_mode
        return rep

    def generate(self, prompts: Sequence[Sequence[int]], *,
                 max_new_tokens: int = 32, temperature: float = 0.0,
                 top_k: int = 0, eos_id: Optional[int] = None,
                 seed: int = 0) -> GenerationResult:
        b = len(prompts)
        lens = np.array([len(p) for p in prompts], np.int32)
        if int(lens.max()) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt length {int(lens.max())} + max_new_tokens "
                f"{max_new_tokens} exceeds max_len={self.max_len}")
        toks = np.zeros((b, int(max(lens.max(), 1))), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p

        # prefill group: chunked (seq-sharded when P > 1) prefill
        last_logits, caches, _ = self.prefill_engine._run_prefill(
            toks, lens, max_new_tokens)
        # the hand-off: codes (fp for windowed rings) cross to decode
        last_logits, caches = self._migrate(last_logits, caches)

        # decode group: standard chunked decode loop
        de = self.decode_engine
        rng = jax.random.PRNGKey(seed)
        rng, sub = jax.random.split(rng)
        eos_arr = serving_steps.as_eos_array(eos_id, b)
        cur, done = serving_steps.first_token(sub, last_logits,
                                              eos_arr,
                                              temperature=temperature,
                                              top_k=top_k)
        first, done_h, prefill_logits = jax.device_get(
            (cur, done, last_logits))
        out = [[int(first[i])] for i in range(b)]
        rows = functools.partial(de.backend.commit_rows, ctx=de.decode_ctx)
        lengths = rows(jnp.asarray(lens))
        budget = max_new_tokens - 1
        chunk = de.decode_chunk
        remaining = rows(jnp.full((b,), budget, jnp.int32))
        emitted = 0
        while emitted < budget and not done_h.all():
            rng, sub = jax.random.split(rng)
            toks_d, valid_d, cur, caches, lengths, remaining, done = \
                de._decode_chunk(de.params, cur, caches, lengths, remaining,
                                 eos_arr, done, sub, None, num_steps=chunk,
                                 temperature=temperature, top_k=top_k)
            toks_h, valid_h, done_h = jax.device_get((toks_d, valid_d, done))
            for i in range(b):
                for j in range(chunk):
                    if valid_h[i, j]:
                        out[i].append(int(toks_h[i, j]))
            emitted += chunk
        return GenerationResult(tokens=out,
                                prefill_logits=np.asarray(prefill_logits))

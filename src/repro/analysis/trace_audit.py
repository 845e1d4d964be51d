"""Compiled-artifact audit of the jitted serving steps.

Where ``source.py`` lints what the code *says*, this module lints what
the compiler actually *built*: for a (cache_mode, use_pallas) matrix it
constructs a reduced serving engine, lowers the jitted ``decode_chunk``
and ``prefill_chunk`` through ``CountingJit.lower``, and audits

* the optimized HLO via :mod:`repro.analysis.hlo` — no embed/table-sized
  all-gather in the decode step (the dryrun invariant, now shared),
  ``input_output_alias`` entries present whenever the step was built
  with donated cache buffers on a platform that aliases, and, for a
  donated step whose backend keeps its page pools resident in the layer
  scan, no op that copies a stacked pool or works on one layer's slice
  of it (``hlo-pool-copy``);
* kernel engagement via ``kernels.ops.KERNEL_INVOCATIONS`` deltas — with
  ``use_pallas=True`` the Pallas wrappers must have traced (a silent
  jnp fallback passes every parity test while shipping the slow path),
  and with ``use_pallas=False`` they must NOT have.

Heavier than the source rules (it compiles real steps), so the CLI runs
it only under ``--trace`` and the pytest wrapper keeps the matrix small.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis import hlo as hlo_lint
from repro.analysis.rules import Finding

# (cache_mode, use_pallas[, seq_sharded]) combos the CLI audits under
# --trace; the seq-sharded rows lower the mesh decode + chunked-prefill
# steps (shard_map over every host device) through the same auditors
DEFAULT_MATRIX: Tuple[Tuple, ...] = (
    ("fp", False),
    ("fp", True),
    ("fp", False, True),
    ("fp", True, True),
    ("vq", True, True),
    # (..., seq_sharded, donate): the pool audit needs donated steps, which
    # the CPU's default would filter out
    ("paged", False, False, True),
    ("paged_vq", False, False, True),
)

# numpy dtype name -> HLO element type, for the stacked pools' HLO types
_HLO_DTYPES = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
               "uint8": "u8", "uint16": "u16", "int32": "s32"}

_MODELS: Dict[Tuple[str, bool], tuple] = {}


def _small_model(arch: str, astra: bool):
    """Reduced config + params, cached per (arch, astra) — vq layouts need
    the astra codebooks in the param tree."""
    key = (arch, astra)
    if key not in _MODELS:
        import dataclasses as dc

        import jax

        from repro.configs import get_config
        from repro.models import model_factory as mf

        cfg = get_config(arch).reduced()
        if not astra:
            cfg = dc.replace(cfg, astra=dc.replace(cfg.astra, enabled=False))
        params = mf.init_params(jax.random.PRNGKey(0), cfg)
        _MODELS[key] = (cfg, params)
    return _MODELS[key]


@dataclasses.dataclass
class StepAudit:
    """One audited compiled step: label + HLO stats + findings."""

    label: str
    hlo_lines: int
    largest_allgather_bytes: int
    num_collectives: int
    alias_entries: int
    donated: bool
    findings: List[Finding]

    def report(self) -> dict:
        return {
            "label": self.label,
            "largest_allgather_bytes": self.largest_allgather_bytes,
            "num_collectives": self.num_collectives,
            "alias_entries": self.alias_entries,
            "donated": self.donated,
            "findings": [f.to_dict() for f in self.findings],
        }


def _pool_types(caches, keys) -> List[str]:
    """HLO types (``f32[reps,N,ps,...]``) of a cache tree's leaves named in
    ``keys`` — the stacked page pools a backend keeps resident."""
    return sorted({
        f"{_HLO_DTYPES[x.dtype.name]}[{','.join(map(str, x.shape))}]"
        for stage in caches for sub in stage.values()
        for k, x in sub.items() if k in keys})


def _audit_compiled(lowered, *, label: str, embed_bytes: int,
                    donated: bool, pools=()) -> StepAudit:
    compiled = lowered.compile()
    text = compiled.as_text()
    findings = hlo_lint.audit_hlo(text, label=label,
                                  max_allgather_bytes=embed_bytes)
    aliases = hlo_lint.input_output_aliases(text)
    if donated and not aliases:
        findings.append(Finding(
            label, 1, "hlo-missing-alias",
            "step was built with donated cache argnums but the compiled "
            "module has no input_output_alias entries — XLA is copying "
            "the cache every step"))
    if donated:  # an undonated step has to copy its input pools
        findings += hlo_lint.pool_copy_findings(text, label=label,
                                                pools=pools)
    return StepAudit(
        label=label,
        hlo_lines=text.count("\n") + 1,
        largest_allgather_bytes=hlo_lint.largest_allgather_bytes(text),
        num_collectives=len(hlo_lint.find_collectives(text)),
        alias_entries=len(aliases),
        donated=donated,
        findings=findings,
    )


def engagement_findings(delta: Dict[str, int], *, use_pallas: bool,
                        label: str) -> List[Finding]:
    """KERNEL_INVOCATIONS delta vs the route the engine was asked for."""
    hits = sum(delta.values())
    if use_pallas and hits == 0:
        return [Finding(
            label, 1, "kernel-engagement",
            "use_pallas=True but no kernels.ops wrapper traced — the "
            "serving path silently fell back to the jnp epilogues")]
    if not use_pallas and hits:
        names = ", ".join(sorted(k for k, v in delta.items() if v))
        return [Finding(
            label, 1, "kernel-engagement",
            f"use_pallas=False but Pallas wrappers traced ({names}) — "
            f"the jnp reference route is being bypassed")]
    return []


def audit_serving_step(cache_mode: str = "fp", use_pallas: bool = False,
                       seq_sharded: bool = False, *,
                       arch: str = "gpt2-small", batch: int = 2,
                       max_len: int = 64, prompt_len: int = 5,
                       max_new: int = 4,
                       donate: Optional[bool] = None
                       ) -> Tuple[List[Finding], dict]:
    """Audit the compiled decode_chunk + prefill_chunk for one combo.

    ``seq_sharded=True`` builds the engine on a mesh over every host
    device (1 when ``max_len`` does not divide) so the shard_map decode
    and chunked-prefill lowerings run through the same HLO auditors — in
    particular no embed/table-sized all-gather may appear on the mesh
    paths (the partial-stats merge moves (B, H)-sized stats only).

    Returns ``(findings, report)``; an empty findings list means the
    compiled artifacts hold every audited invariant for this combo.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops
    from repro.models import transformer as tlm
    from repro.serving import steps as serving_steps
    from repro.serving.engine import ServingEngine

    # lint: allow[cache-mode-dispatch] audit-matrix input, not layout dispatch
    astra = cache_mode in ("vq", "paged_vq")
    cfg, params = _small_model(arch, astra)
    mesh_kw = {}
    num_shards = 1
    if seq_sharded:
        from repro.compat import make_mesh
        from repro.core.sequence_parallel import MeshContext

        n = jax.device_count()
        num_shards = n if max_len % n == 0 else 1
        mesh_kw["mesh_ctx"] = MeshContext(
            mesh=make_mesh((num_shards,), ("model",)), batch_axes=(),
            seq_axis="model")
    eng = ServingEngine(cfg, params, max_len=max_len, astra_mode="off",
                        cache_mode=cache_mode, page_size=8, decode_chunk=2,
                        use_pallas=use_pallas, donate=donate, **mesh_kw)
    tag = (f"{cache_mode}{'+pallas' if use_pallas else ''}"
           f"{f'+mesh{num_shards}' if seq_sharded else ''}")

    before = dict(kops.KERNEL_INVOCATIONS)
    toks = np.tile(np.arange(1, prompt_len + 1, dtype=np.int32), (batch, 1))
    lens = np.full((batch,), prompt_len, np.int32)
    last_logits, caches, block_tables = eng._run_prefill(toks, lens, max_new)

    lengths = jnp.asarray(lens)
    lowered_decode = eng._decode_chunk.lower(
        eng.params, jnp.zeros((batch,), jnp.int32), caches, lengths,
        jnp.full((batch,), max_new, jnp.int32),
        jnp.full((batch,), -1, jnp.int32), jnp.zeros((batch,), bool),
        jax.random.PRNGKey(0), block_tables, num_steps=2, temperature=0.0,
        top_k=0)
    delta = {k: v - before.get(k, 0)
             for k, v in kops.KERNEL_INVOCATIONS.items()
             if v - before.get(k, 0)}

    leaf = jax.tree.leaves(params)[0]
    embed_bytes = cfg.vocab_size * cfg.d_model * leaf.dtype.itemsize
    keys = eng.backend.resident_keys
    audits = [_audit_compiled(
        lowered_decode, label=f"decode_chunk[{tag}]", embed_bytes=embed_bytes,
        donated=bool(eng._decode_chunk.donate_argnums),
        pools=_pool_types(caches, keys))]

    if eng.prefill_mode == "chunked":
        if eng.backend.paged:
            kv = eng.backend.make_state(
                cfg, slots=batch, max_len=max_len, ctx=eng.decode_ctx,
                page_size=eng.page_size, dtype=eng.cache_dtype)
            for i in range(batch):
                kv_ok = eng.backend.advance(kv, i, prompt_len + max_new)
                assert kv_ok, "audit pool sized for its own slots"
            caches_p, tables = kv.init_cache(batch, prefill_scratch=True), \
                kv.tables()
        else:
            caches_p, tables = tlm.init_lm_cache(
                cfg, batch, max_len, eng.prefill_ctx, eng.cache_dtype,
                prefill_scratch=True), None
        w = serving_steps.plan_chunks(prompt_len, eng.prefill_buckets)[0][1]
        lowered_prefill = eng._prefill_chunk.lower(
            eng.params, jnp.zeros((batch, w), jnp.int32),
            jnp.asarray(0, jnp.int32), caches_p, lengths,
            jnp.zeros((batch, cfg.vocab_size), jnp.float32), tables,
            history_len=serving_steps.view_bucket(w, max_len))
        audits.append(_audit_compiled(
            lowered_prefill, label=f"prefill_chunk[{tag}]",
            embed_bytes=embed_bytes,
            donated=bool(eng._prefill_chunk.donate_argnums),
            pools=_pool_types(caches_p, keys)))

    findings = [f for a in audits for f in a.findings]
    findings += engagement_findings(delta, use_pallas=use_pallas,
                                    label=f"serving_steps[{tag}]")
    report = {
        "arch": arch,
        "cache_mode": cache_mode,
        "use_pallas": use_pallas,
        "seq_sharded": seq_sharded,
        "num_shards": num_shards,
        "kernel_invocations": delta,
        "steps": [a.report() for a in audits],
    }
    return findings, report


def donation_aliasing_findings(donated, others, *, label: str
                               ) -> List[Finding]:
    """Leaf-identity audit of one jitted call's arguments: an array
    reachable from BOTH the donated argument and a non-donated one makes
    donation unsound — XLA may reuse the buffer for an output while the
    other argument still reads it.  This is a *host-side* check (python
    object identity), so it catches exactly the adopt-pools style aliasing
    the HLO auditors cannot see (by lowering time both references are one
    parameter or the damage is already done)."""
    import jax

    donated_ids: Dict[int, str] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(donated)[0]:
        if hasattr(leaf, "dtype"):
            donated_ids[id(leaf)] = jax.tree_util.keystr(path)
    findings: List[Finding] = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(others)[0]:
        if id(leaf) in donated_ids:
            findings.append(Finding(
                label, 1, "donation-aliasing",
                f"non-donated argument leaf {jax.tree_util.keystr(path)} "
                f"is the same buffer as donated leaf "
                f"{donated_ids[id(leaf)]} — donating it invalidates a "
                f"live input"))
    return findings


def audit_chunked_admission(cache_mode: str = "paged", *,
                            arch: str = "gpt2-small", max_len: int = 64,
                            prompt_len: int = 20, max_new: int = 2
                            ) -> Tuple[List[Finding], dict]:
    """Drive one real chunked admission through the continuous scheduler
    and audit every slot-merge call's donated-vs-rest argument aliasing
    (the donated live cache must not share buffers with the fresh batch-1
    tree — see ``scheduler._advance_pending``'s strip_pool_leaves)."""
    from repro.serving.scheduler import ContinuousBatchingEngine

    # lint: allow[cache-mode-dispatch] audit-matrix input, not layout dispatch
    astra = cache_mode in ("vq", "paged_vq")
    cfg, params = _small_model(arch, astra)
    eng = ContinuousBatchingEngine(
        cfg, params, slots=2, max_len=max_len, astra_mode="off",
        cache_mode=cache_mode, page_size=8, decode_chunk=2)
    label = f"merge_slot[{cache_mode}]"
    findings: List[Finding] = []
    merges = [0]
    real_merge = eng._merge

    def audited_merge(live, fresh, slot):
        merges[0] += 1
        # audit as-if-donated even where the platform filtered donation
        # out (CPU): the aliasing bug only bites on TPU/GPU, but the
        # invariant must hold everywhere the code ships
        findings.extend(donation_aliasing_findings(
            live, (fresh, slot), label=label))
        return real_merge(live, fresh, slot)

    eng._merge = audited_merge
    eng.submit(list(range(1, prompt_len + 1)), max_new_tokens=max_new)
    eng.run_until_drained()
    report = {
        "cache_mode": cache_mode,
        "merge_calls": merges[0],
        "findings": [f.to_dict() for f in findings],
    }
    return findings, report


def audit_matrix(matrix: Sequence[Tuple] = DEFAULT_MATRIX,
                 **kw) -> Tuple[List[Finding], List[dict]]:
    """Run :func:`audit_serving_step` over a (cache_mode, use_pallas[,
    seq_sharded[, donate]]) matrix; returns merged findings + one report
    per combo."""
    findings: List[Finding] = []
    reports: List[dict] = []
    for cache_mode, use_pallas, *rest in matrix:
        seq_sharded = bool(rest[0]) if rest else False
        row_kw = {"donate": rest[1]} if len(rest) > 1 else {}
        f, r = audit_serving_step(cache_mode, use_pallas, seq_sharded,
                                  **{**kw, **row_kw})
        findings.extend(f)
        reports.append(r)
    return findings, reports

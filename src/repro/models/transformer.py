"""Generic decoder-LM assembler covering dense / MoE / SSM / hybrid / VLM.

A model is a sequence of *stages*; each stage is a repeated super-block of
layer kinds (e.g. gemma2 = [local, global] x 23; recurrentgemma =
[rec, rec, local] x 12 + [rec] x 2; llama4 = [attn, attn, attn, attn_nope]
x 12).  Stage parameters are stacked over the repeat dim and executed with
``lax.scan`` so 126-layer models compile in one program.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.core import navq
from repro.models import attention as attn
from repro.models import mamba2, moe as moe_mod, rglru
from repro.models.context import StepCtx
from repro.models.layers import (
    apply_mlp,
    apply_norm,
    dense_init,
    embed_init,
    init_mlp,
    init_norm,
    softcap,
    stack_params,
)

ATTN_KINDS = ("attn", "attn_nope", "local", "global")


def stages(cfg) -> List[Tuple[Tuple[str, ...], int]]:
    l = cfg.num_layers
    if cfg.arch_type == "ssm":
        return [(("ssm",), l)]
    if cfg.layer_pattern == "local_global":
        assert l % 2 == 0
        return [(("local", "global"), l // 2)]
    if cfg.layer_pattern == "rg":
        reps, rem = divmod(l, 3)
        out = []
        if reps:
            out.append((("rec", "rec", "local"), reps))
        if rem:
            out.append((("rec",) * max(rem - 1, 0) + ("local",), 1)
                       if not reps else (("rec",) * rem, 1))
        return out
    if cfg.nope_interval:
        k = cfg.nope_interval
        out = []
        if l >= k:
            out.append((tuple(["attn"] * (k - 1) + ["attn_nope"]), l // k))
        if l % k:
            out.append((("attn",) * (l % k), 1))
        return out
    return [(("attn",), l)]


def decoder_stages(cfg):
    return stages(cfg)


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------


def init_block(key: jax.Array, cfg, kind: str, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": init_norm(cfg.norm, cfg.d_model, dtype)}
    if kind in ATTN_KINDS:
        p["attn"] = attn.init_attention(ks[0], cfg, dtype)
        if cfg.astra.enabled:
            p["vq"] = attn.init_astra_vq(ks[1], cfg, dtype)
        p["norm2"] = init_norm(cfg.norm, cfg.d_model, dtype)
        if cfg.moe is not None:
            p["moe"] = moe_mod.init_moe(ks[2], cfg, dtype)
        else:
            p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.activation, dtype)
        if cfg.post_norm:
            p["post1"] = init_norm(cfg.norm, cfg.d_model, dtype)
            p["post2"] = init_norm(cfg.norm, cfg.d_model, dtype)
    elif kind == "rec":
        p["rec"] = rglru.init_rglru(ks[0], cfg, dtype)
        p["norm2"] = init_norm(cfg.norm, cfg.d_model, dtype)
        p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.activation, dtype)
    elif kind == "ssm":
        p["ssm"] = mamba2.init_mamba(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    return p


def init_block_navq(cfg, kind: str) -> Dict:
    if kind in ATTN_KINDS and cfg.astra.enabled:
        return {
            "k": navq.init_residual_stats(cfg.d_kv),
            "v": navq.init_residual_stats(cfg.d_kv),
        }
    return {}


def init_block_cache(cfg, kind: str, batch: int, max_len: int, ctx: StepCtx,
                     dtype=jnp.bfloat16, *, page_size: int = 0,
                     num_pages=0, prefill_scratch: bool = False) -> Dict:
    if kind in ATTN_KINDS:
        return attn.init_attn_cache(cfg, kind, batch, max_len, ctx, dtype,
                                    page_size=page_size, num_pages=num_pages,
                                    prefill_scratch=prefill_scratch)
    if kind == "rec":
        return rglru.init_rg_cache(cfg, batch, dtype)
    if kind == "ssm":
        return mamba2.init_mamba_cache(cfg, batch, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Block forward / decode
# ---------------------------------------------------------------------------


def block_forward(
    p: Dict,
    x: jax.Array,
    *,
    ctx: StepCtx,
    kind: str,
    causal: bool,
    rng: Optional[jax.Array],
    navq_stats: Optional[Dict],
    cache: Optional[Dict],
    lengths: Optional[jax.Array],
    block_tables=None,
    chunk_start: Optional[jax.Array] = None,
    history_len: int = 0,
    verify_starts: Optional[jax.Array] = None,
    page_base: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array], Dict, Optional[Dict]]:
    """``chunk_start`` (traced scalar) switches prefill into chunked mode:
    ``x`` is one fixed-width chunk at global offset ``chunk_start``,
    attention goes through ``ctx.backend.chunk_attend`` (causal over the
    cache written so far, viewing only the first ``history_len`` positions
    when set — a static bound from ``serving.steps.view_bucket``), and
    recurrent layers carry their boundary state across chunks explicitly.

    ``verify_starts`` ((B,) per-row offsets) switches a decode-mode step
    into speculative *verify*: ``x`` is W = k+1 positions per row scored in
    one pass through ``ctx.backend.verify_attend``.  It takes precedence
    over the plain decode dispatch and is attention-only — recurrent and
    SSM layers advance irreversible state per token and cannot re-score a
    drafted block, so they raise.

    ``page_base`` (traced) is set when ``cache`` holds the backend's
    resident leaves as layer-merged pools, and says where this layer's
    pages start in them (see ``run_stages``)."""
    cfg = ctx.cfg
    if verify_starts is not None and kind not in ATTN_KINDS:
        raise ValueError(
            f"speculative verify needs attention-only stacks; layer kind "
            f"{kind!r} carries irreversible recurrent state")
    aux = {"commit": jnp.zeros((), jnp.float32),
           "moe_aux": jnp.zeros((), jnp.float32)}
    new_navq: Dict = {}
    new_cache: Optional[Dict] = None

    if ctx.seq_sharded and ctx.mode != "decode":
        from repro.core.sequence_parallel import constrain_seq_sharded

        x = constrain_seq_sharded(x, ctx.mesh)
    h = apply_norm(p["norm1"], x, cfg.norm)
    if kind in ATTN_KINDS:
        if verify_starts is not None:
            y, new_cache = attn.attention_verify(
                p["attn"], h, cache, verify_starts, ctx=ctx, kind=kind,
                vq_params=p.get("vq"), block_tables=block_tables,
                page_base=page_base)
        elif ctx.mode == "decode":
            y, new_cache = attn.attention_decode(
                p["attn"], h, cache, lengths, ctx=ctx, kind=kind,
                vq_params=p.get("vq"), block_tables=block_tables,
                page_base=page_base)
        elif chunk_start is not None:
            y, new_cache = attn.attention_chunk(
                p["attn"], h, cache, chunk_start, lengths, ctx=ctx,
                kind=kind, vq_params=p.get("vq"),
                block_tables=block_tables, history_len=history_len,
                page_base=page_base)
        else:
            y, a, new_cache = attn.attention_forward(
                p["attn"], h, ctx=ctx, kind=kind, causal=causal,
                vq_params=p.get("vq"), navq_stats=navq_stats or None,
                rng=rng, cache=cache, block_tables=block_tables,
                lengths=lengths, page_base=page_base)
            aux["commit"] = a["commit"]
            if navq_stats:
                new_navq = {
                    "k": _stats_update(navq_stats["k"], a["navq_k_mean"],
                                       a["navq_k_var"]),
                    "v": _stats_update(navq_stats["v"], a["navq_v_mean"],
                                       a["navq_v_var"]),
                }
        if cfg.post_norm:
            y = apply_norm(p["post1"], y, cfg.norm)
        x = x + y.astype(x.dtype)
        h2 = apply_norm(p["norm2"], x, cfg.norm)
        with jax.named_scope("mlp"):
            if cfg.moe is not None:
                y2, moe_aux = moe_mod.apply_moe(p["moe"], h2, cfg, ctx)
                aux["moe_aux"] = moe_aux
            else:
                y2 = apply_mlp(p["mlp"], h2, cfg.activation)
        if cfg.post_norm:
            y2 = apply_norm(p["post2"], y2, cfg.norm)
        return x + y2.astype(x.dtype), aux, new_navq, new_cache

    if kind == "rec":
        if ctx.mode == "decode":
            y, new_cache = rglru.rg_block_decode(p["rec"], h, cache, ctx=ctx)
        else:
            y, new_cache = rglru.rg_block_forward(p["rec"], h, ctx=ctx,
                                                  cache=cache,
                                                  lengths=lengths,
                                                  start=chunk_start)
        x = x + y.astype(x.dtype)
        h2 = apply_norm(p["norm2"], x, cfg.norm)
        with jax.named_scope("mlp"):
            y2 = apply_mlp(p["mlp"], h2, cfg.activation)
        return x + y2.astype(x.dtype), aux, new_navq, new_cache

    if kind == "ssm":
        if ctx.mode == "decode":
            y, new_cache = mamba2.mamba_decode(p["ssm"], h, cache, ctx=ctx)
        else:
            y, new_cache = mamba2.mamba_forward(p["ssm"], h, ctx=ctx,
                                                cache=cache, lengths=lengths,
                                                start=chunk_start)
        return x + y.astype(x.dtype), aux, new_navq, new_cache

    raise ValueError(kind)


def _stats_update(stats, mean, var):
    return {
        "mean": 0.99 * stats["mean"] + 0.01 * mean,
        "var": 0.99 * stats["var"] + 0.01 * var,
        "count": stats["count"] + 1,
    }


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------


def init_lm(key: jax.Array, cfg, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_norm(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.rope_theta and cfg.arch_type != "ssm":
        params["pos_embed"] = embed_init(ks[1], cfg.max_seq_len, cfg.d_model,
                                         dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_size, dtype)
    if cfg.frontend == "vision" and cfg.arch_type == "vlm":
        params["projector"] = {
            "w1": dense_init(ks[3], cfg.frontend_dim, cfg.d_model, dtype),
            "w2": dense_init(ks[4], cfg.d_model, cfg.d_model, dtype),
        }
    st = []
    key_i = ks[5]
    for kinds, reps in stages(cfg):
        sub = {}
        for j, kind in enumerate(kinds):
            blocks = []
            for r in range(reps):
                key_i, sk = jax.random.split(key_i)
                blocks.append(init_block(sk, cfg, kind, dtype))
            sub[f"sub{j}"] = stack_params(blocks)
        st.append(sub)
    params["stages"] = st
    return params


def init_lm_navq(cfg) -> List[Dict]:
    out = []
    for kinds, reps in stages(cfg):
        sub = {}
        for j, kind in enumerate(kinds):
            s = init_block_navq(cfg, kind)
            if s:
                sub[f"sub{j}"] = jax.tree.map(
                    lambda x: jnp.stack([x] * reps, 0), s)
        out.append(sub)
    return out


def init_lm_cache(cfg, batch: int, max_len: int, ctx: StepCtx,
                  dtype=jnp.bfloat16, *, page_size: int = 0,
                  num_pages=0, prefill_scratch: bool = False) -> List[Dict]:
    """``num_pages`` is an int for a single shared pool size or a
    per-page-group dict (``serving.kv_cache.PagedKVCache.num_pages_by_group``)
    so windowed layers get their capped pools.  ``prefill_scratch`` adds the
    fp prefill-view slabs vq-coded layers need under chunked prefill
    (strip with ``serving.cache_backend.strip_prefill_scratch`` before the
    tree enters a decode step)."""
    out = []
    for kinds, reps in stages(cfg):
        sub = {}
        for j, kind in enumerate(kinds):
            c = init_block_cache(cfg, kind, batch, max_len, ctx, dtype,
                                 page_size=page_size, num_pages=num_pages,
                                 prefill_scratch=prefill_scratch)
            sub[f"sub{j}"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (reps,) + x.shape), c)
        out.append(sub)
    return out


# ---------------------------------------------------------------------------
# Model forward
# ---------------------------------------------------------------------------


@jax.named_scope("embed")
def _embed_inputs(params, batch: Dict, cfg) -> jax.Array:
    tokens = batch["tokens"]
    x = jnp.take(params["embed"], tokens, axis=0)
    if "pos_embed" in params:
        t = tokens.shape[1]
        x = x + params["pos_embed"][None, :t]
    if "patch_embeds" in batch and "projector" in params:
        pe = batch["patch_embeds"]
        h = jax.nn.gelu(pe @ params["projector"]["w1"], approximate=True)
        h = h @ params["projector"]["w2"]
        x = jnp.concatenate([h.astype(x.dtype), x], axis=1)
    return x


def _split_resident(cache_stage: Dict, keys) -> Tuple[Dict, Dict]:
    """Split one stage's cache ``{sub: {leaf: array}}`` into the leaves
    named in ``keys`` and the rest.  Subs with no leaf on one side are left
    out of it."""
    resident, scanned = {}, {}
    for name, sub in cache_stage.items():
        r = {k: v for k, v in sub.items() if k in keys}
        if r:
            resident[name] = r
        if len(r) < len(sub):
            scanned[name] = {k: v for k, v in sub.items() if k not in keys}
    return resident, scanned


def run_stages(
    params_stages: List[Dict],
    x: jax.Array,
    *,
    ctx: StepCtx,
    cfg,
    causal: bool,
    rng: Optional[jax.Array],
    navq_state: Optional[List[Dict]],
    caches: Optional[List[Dict]],
    lengths: Optional[jax.Array],
    block_tables=None,
    chunk_start: Optional[jax.Array] = None,
    history_len: int = 0,
    verify_starts: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array], List[Dict], Optional[List[Dict]]]:
    """Run every stage as one ``lax.scan`` over its stacked layers.

    Cache leaves the backend names in ``resident_keys`` (the paged pools,
    stacked ``(reps, N, ps, ...)``) ride the scan's carry with every
    layer's pages merged into one ``(reps * N, ps, ...)`` pool: each layer
    writes its new tokens into its own pages in place and gathers them from
    there (``page_base = layer * N``), so no step slices out, writes back
    or copies a whole layer's pool.  Every other leaf is the scan's
    per-layer xs/ys.  The returned cache tree has the input's leaves,
    shapes and dtypes either way."""
    commit = jnp.zeros((), jnp.float32)
    moe_aux = jnp.zeros((), jnp.float32)
    new_navq_all, new_caches_all = [], []
    base_rng = rng if rng is not None else jax.random.PRNGKey(0)
    keys = ctx.backend.resident_keys if caches is not None else frozenset()

    for si, (kinds, reps) in enumerate(stages(cfg)):
        p_stage = params_stages[si]
        navq_stage = (navq_state[si] if navq_state else {})
        cache_stage = (caches[si] if caches is not None else {})
        stacked, cache_stage = _split_resident(cache_stage, keys)
        pools = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                             stacked)
        # pages per layer, per sub (window and global page groups differ)
        n_pages = {name: next(iter(sub.values())).shape[1]
                   for name, sub in stacked.items()}
        rngs = jax.random.split(jax.random.fold_in(base_rng, si), reps)

        def body(carry, xs):
            xx, cm, ma, pools = carry
            p_l, rng_l, navq_l, cache_l, layer = xs
            navq_outs, cache_outs, pool_outs = {}, {}, {}
            if pools:
                from repro.kernels.ops import PATH_INVOCATIONS

                PATH_INVOCATIONS["pool_in_place"] += 1
            for j, kind in enumerate(kinds):
                name = f"sub{j}"
                nst = navq_l.get(name) or None
                cst = ({**cache_l.get(name, {}), **pools.get(name, {})}
                       if caches is not None else None)
                xx, aux, n_new, c_new = block_forward(
                    p_l[name], xx, ctx=ctx, kind=kind, causal=causal,
                    rng=jax.random.fold_in(rng_l, j), navq_stats=nst,
                    cache=cst, lengths=lengths, block_tables=block_tables,
                    chunk_start=chunk_start, history_len=history_len,
                    verify_starts=verify_starts,
                    page_base=(layer * n_pages[name] if name in pools
                               else None))
                cm = cm + aux["commit"]
                ma = ma + aux["moe_aux"]
                if n_new:
                    navq_outs[name] = n_new
                if c_new is not None:
                    pool_l, rest = _split_resident({name: c_new}, keys)
                    pool_outs.update(pool_l)
                    cache_outs.update(rest)
            return (xx, cm, ma, pool_outs), (navq_outs, cache_outs)

        scan_body = jax.checkpoint(body) if ctx.remat else body
        (x, commit, moe_aux, pools), (navq_out, cache_out) = jax.lax.scan(
            scan_body, (x, commit, moe_aux, pools),
            (p_stage, rngs, navq_stage, cache_stage, jnp.arange(reps)))
        # back to (reps, N, ...) in the default dim order, which the pools
        # carried by an outer loop (the decode chunk's step scan) keep:
        # left free, the CPU compiler reorders a pool with a size-1 minor
        # dim and copies it in and out of the layer scan every step
        pools = jax.tree.map(
            lambda a, s: with_layout_constraint(
                a.reshape(s.shape), Layout(tuple(range(s.ndim)))),
            pools, stacked)
        new_navq_all.append(navq_out)
        new_caches_all.append(
            {name: {**cache_out.get(name, {}), **pools.get(name, {})}
             for name in sorted(cache_out.keys() | pools.keys())})

    aux = {"commit": commit, "moe_aux": moe_aux}
    return x, aux, new_navq_all, (new_caches_all if caches is not None else None)


def lm_forward(
    params: Dict,
    batch: Dict,
    *,
    ctx: StepCtx,
    rng: Optional[jax.Array] = None,
    navq_state: Optional[List[Dict]] = None,
    caches: Optional[List[Dict]] = None,
    lengths: Optional[jax.Array] = None,
    block_tables=None,
) -> Tuple[jax.Array, Dict, List[Dict], Optional[List[Dict]]]:
    """Returns (logits, aux, new_navq_state, new_caches)."""
    cfg = ctx.cfg
    x = _embed_inputs(params, batch, cfg).astype(_adtype(cfg, ctx))
    x, aux, new_navq, new_caches = run_stages(
        params["stages"], x, ctx=ctx, cfg=cfg, causal=True, rng=rng,
        navq_state=navq_state, caches=caches, lengths=lengths,
        block_tables=block_tables)
    with jax.named_scope("head"):
        x = apply_norm(params["final_norm"], x, cfg.norm)
        if ctx.logits_last_only:
            # §Perf: prefill only needs the next-token distribution — skip
            # the (B, T, vocab) logits matmul for all but the final position.
            x = x[:, -1:]
        head = params["lm_head"] if "lm_head" in params else params["embed"].T
        logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
        if ctx.seq_sharded and not ctx.logits_last_only:
            from repro.core.sequence_parallel import constrain_seq_sharded

            logits = constrain_seq_sharded(logits, ctx.mesh)
        logits = softcap(logits, cfg.final_logit_softcap)
    return logits, aux, new_navq, new_caches


def lm_prefill_chunk(
    params: Dict,
    tokens: jax.Array,  # (B, W) one fixed-width chunk of the prompts
    chunk_start: jax.Array,  # scalar int32: global offset of this chunk
    caches: List[Dict],
    lengths: jax.Array,  # (B,) true prompt length per row
    last_logits: jax.Array,  # (B, V) running last-position logits
    *,
    ctx: StepCtx,
    block_tables=None,
    history_len: int = 0,
) -> Tuple[jax.Array, List[Dict]]:
    """One chunked-prefill step: advance every row's cache by one chunk and
    keep the last-*real*-position logits on device.

    Unlike ``lm_forward``, the logits matmul runs on exactly one position
    per row — the chunk-local index of ``lengths - 1`` (clipped) — and
    ``last_logits`` is where-updated only for rows whose prompt actually
    ends inside this chunk, so after the final chunk it holds every row's
    next-token distribution regardless of how ragged the batch is.
    Returns ``(last_logits, new_caches)``.
    """
    cfg = ctx.cfg
    b, w = tokens.shape
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        if "pos_embed" in params:
            # per-position clipped gather: only bucket-overhang positions
            # (junk past every row's prompt) clamp — a clamped contiguous
            # slice would shift the embeddings of the *real* tokens in the
            # tail chunk
            pos = jnp.clip(chunk_start + jnp.arange(w), 0,
                           cfg.max_seq_len - 1)
            x = x + jnp.take(params["pos_embed"], pos, axis=0)[None]
        x = x.astype(_adtype(cfg, ctx))
    x, _, _, new_caches = run_stages(
        params["stages"], x, ctx=ctx, cfg=cfg, causal=True, rng=None,
        navq_state=None, caches=caches, lengths=lengths,
        block_tables=block_tables, chunk_start=chunk_start,
        history_len=history_len)
    with jax.named_scope("head"):
        idx = jnp.clip(lengths - 1 - chunk_start, 0, w - 1)
        xl = jnp.take_along_axis(x, idx[:, None, None], axis=1)  # (B, 1, D)
        xl = apply_norm(params["final_norm"], xl, cfg.norm)
        head = params["lm_head"] if "lm_head" in params else params["embed"].T
        logits = _head_matmul(xl, head, cfg, ctx)[:, 0]
        logits = softcap(logits, cfg.final_logit_softcap)
        ends_here = ((lengths - 1 >= chunk_start)
                     & (lengths - 1 < chunk_start + w))
        last_logits = jnp.where(ends_here[:, None], logits, last_logits)
    return last_logits, new_caches


def _dim_axes(mesh, dim_size: int, candidates=("data", "model")):
    """The mesh-axis group (of ``candidates`` present in the mesh) that can
    shard a dim of ``dim_size``; () => replicate."""
    axes = tuple(a for a in candidates if a in mesh.shape)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return axes if axes and dim_size % n == 0 else ()


def _constrain(x, mesh, spec):
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _head_matmul(x: jax.Array, head: jax.Array, cfg, ctx: StepCtx
                 ) -> jax.Array:
    """(B, 1, D) @ (D, V) logits head, mesh-aware.

    Under a mesh, match x's d_model sharding to the head's (FSDP shards the
    head on d_model): the matmul then runs as local partial dots plus one
    tiny (B, 1, V) reduce, instead of materializing the full (D, V) head
    per device — a table-sized all-gather the dry-run decode assert
    forbids.  Shared by the decode step and the prefill chunk (which runs
    this once per chunk, so the all-gather would multiply)."""
    if ctx.mesh.mesh is None:
        return (x @ head.astype(x.dtype)).astype(jnp.float32)
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh.mesh
    bspec = ctx.mesh.batch_axes if ctx.mesh.batch_axes else None
    d_axes = _dim_axes(mesh, cfg.d_model)
    x = _constrain(x, mesh, P(None, None, d_axes or None))
    logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
    return _constrain(logits, mesh, P(bspec, None, None))


@jax.named_scope("embed")
def _decode_embed(params: Dict, token: jax.Array, lengths: jax.Array,
                  ctx: StepCtx) -> jax.Array:
    """Decode-step input embeddings (B, 1, D).

    Single host: a plain gather.  Under a mesh the embedding table is
    FSDP-sharded, and GSPMD used to lower the 1-token gather with an
    "Involuntary full rematerialization" (jax 0.4.x dry-run).  The one-hot
    contraction keeps the sharded table local (the dot's output inherits
    the table's d_model sharding), and the two-hop reshard — first onto the
    model axis, then replicated — walks the tiny (B, 1, D) activation into
    the batch-sharded layout the decoder scan consumes without the
    partitioner ever touching the table.
    """
    cfg = ctx.cfg
    if ctx.mesh.mesh is None:
        x = jnp.take(params["embed"], token, axis=0)
        if "pos_embed" in params:
            x = x + jnp.take(params["pos_embed"],
                             jnp.clip(lengths, 0, cfg.max_seq_len - 1),
                             axis=0)[:, None]
        return x
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh.mesh
    emb = params["embed"]
    oh = jax.nn.one_hot(token, cfg.vocab_size, dtype=emb.dtype)
    x = oh @ emb
    if "pos_embed" in params:
        pe = params["pos_embed"]
        oh_p = jax.nn.one_hot(jnp.clip(lengths, 0, cfg.max_seq_len - 1),
                              pe.shape[0], dtype=pe.dtype)
        x = x + (oh_p @ pe)[:, None]
    bspec = ctx.mesh.batch_axes if ctx.mesh.batch_axes else None
    hop = _dim_axes(mesh, cfg.d_model, ("model",))
    x = _constrain(x, mesh, P(bspec, None, hop or None))
    return _constrain(x, mesh, P(bspec, None, None))


def lm_decode_step(
    params: Dict,
    token: jax.Array,  # (B, 1)
    caches: List[Dict],
    lengths: jax.Array,  # (B,)
    *,
    ctx: StepCtx,
    block_tables=None,
) -> Tuple[jax.Array, List[Dict]]:
    cfg = ctx.cfg
    x = _decode_embed(params, token, lengths, ctx).astype(_adtype(cfg, ctx))
    x, aux, _, new_caches = run_stages(
        params["stages"], x, ctx=ctx, cfg=cfg, causal=True, rng=None,
        navq_state=None, caches=caches, lengths=lengths,
        block_tables=block_tables)
    with jax.named_scope("head"):
        x = apply_norm(params["final_norm"], x, cfg.norm)
        head = params["lm_head"] if "lm_head" in params else params["embed"].T
        logits = _head_matmul(x, head, cfg, ctx)
        logits = softcap(logits, cfg.final_logit_softcap)
    return logits, new_caches


@jax.named_scope("embed")
def _verify_embed(params: Dict, tokens: jax.Array, starts: jax.Array,
                  ctx: StepCtx) -> jax.Array:
    """Verify-step input embeddings (B, W, D) at per-row positions
    ``starts[b] + j`` — the (B, W) generalization of ``_decode_embed``,
    with the same one-hot contraction under a mesh so the FSDP-sharded
    tables stay local."""
    cfg = ctx.cfg
    w = tokens.shape[1]
    pos = jnp.clip(starts[:, None] + jnp.arange(w)[None, :], 0,
                   cfg.max_seq_len - 1)
    if ctx.mesh.mesh is None:
        x = jnp.take(params["embed"], tokens, axis=0)
        if "pos_embed" in params:
            x = x + jnp.take(params["pos_embed"], pos, axis=0)
        return x
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh.mesh
    emb = params["embed"]
    oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=emb.dtype)
    x = oh @ emb
    if "pos_embed" in params:
        pe = params["pos_embed"]
        oh_p = jax.nn.one_hot(pos, pe.shape[0], dtype=pe.dtype)
        x = x + oh_p @ pe
    bspec = ctx.mesh.batch_axes if ctx.mesh.batch_axes else None
    hop = _dim_axes(mesh, cfg.d_model, ("model",))
    x = _constrain(x, mesh, P(bspec, None, hop or None))
    return _constrain(x, mesh, P(bspec, None, None))


def lm_verify_chunk(
    params: Dict,
    tokens: jax.Array,  # (B, W) current token + k drafted continuations
    caches: List[Dict],
    lengths: jax.Array,  # (B,) per-row position of tokens[:, 0]
    *,
    ctx: StepCtx,
    block_tables=None,
) -> Tuple[jax.Array, List[Dict]]:
    """Speculative verify forward: score W = k+1 positions per row in one
    decode-shaped step.  Returns (logits (B, W, V), new_caches) — logits[b, j]
    is the target's next-token distribution after consuming tokens[b, :j+1],
    so comparing argmax/samples of position j against the drafted token j+1
    decides acceptance.  All W keys/values land in the caches; the caller
    rolls back rejected tails via :func:`lm_rollback_caches`.  Attention-only
    stacks — recurrent/SSM layers raise (see ``block_forward``)."""
    cfg = ctx.cfg
    x = _verify_embed(params, tokens, lengths, ctx).astype(_adtype(cfg, ctx))
    x, _, _, new_caches = run_stages(
        params["stages"], x, ctx=ctx, cfg=cfg, causal=True, rng=None,
        navq_state=None, caches=caches, lengths=lengths,
        block_tables=block_tables, verify_starts=lengths)
    with jax.named_scope("head"):
        x = apply_norm(params["final_norm"], x, cfg.norm)
        head = params["lm_head"] if "lm_head" in params else params["embed"].T
        logits = softcap(_head_matmul(x, head, cfg, ctx),
                         cfg.final_logit_softcap)
    return logits, new_caches


def lm_rollback_caches(
    new_caches: List[Dict],
    old_caches: List[Dict],
    starts: jax.Array,  # (B,) verify-step start positions
    accepted: jax.Array,  # (B,) how many of the W written tokens were kept
    num_tokens: int,  # static: verify width W
    *,
    ctx: StepCtx,
    block_tables=None,
) -> List[Dict]:
    """Restore windowed-ring cache slots clobbered by rejected verify writes
    (traced — runs inside the verify jit once acceptance is known).

    Global layers self-heal — stale keys past the retreated length are
    masked invalid until overwritten in order — so their trees pass through
    untouched.  SWA rings lose history on wrap and are restored from the
    pre-verify snapshot via ``ctx.backend.verify_rollback``, vmapped over
    the stacked layer-repeat dim the engines carry (``starts``/``accepted``
    and the block tables are shared across repeats)."""
    cfg = ctx.cfg
    out = []
    for si, (kinds, reps) in enumerate(stages(cfg)):
        sub_out = {}
        for j, kind in enumerate(kinds):
            key = f"sub{j}"
            new_l = new_caches[si][key]
            if not attn.kind_window(kind, cfg):
                sub_out[key] = new_l
                continue

            def roll(c, o, kind=kind):
                return ctx.backend.verify_rollback(
                    c, o, starts, accepted, num_tokens, ctx=ctx, kind=kind,
                    block_tables=block_tables)

            sub_out[key] = jax.vmap(roll)(new_l, old_caches[si][key])
        out.append(sub_out)
    return out


def _adtype(cfg, ctx: StepCtx):
    """Activation compute dtype (bf16 on the pod, fp32 in CPU smoke tests)."""
    return jnp.dtype(cfg.dtype)

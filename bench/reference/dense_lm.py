"""Plain float32 reference for dense pre-norm decoder LMs (GPT-2,
StarCoder2, Qwen2), and the seeded weights both it and the served model
use.

Independent of the program: it takes a ``Spec`` read from the
configuration file, makes the weights from the seed with its own
generator, and runs the textbook forward pass over a whole sequence (no
cache, no kernels, no batching),
one layer at a time so that a model that fills the chip still fits once
the served model's state is freed.  Every matmul runs at
``Precision.HIGHEST`` (float32 on the TPU's MXU).

The block, as the families publish it (biases left out where the
configuration file says the served model has none):
  h = N1(x); q, k, v = h Wq + bq, h Wk + bk, h Wv + bv   (biases only with
  ``qkv_bias``; GQA: q head j reads kv head j // (heads / kv_heads)); RoPE
  on q, k (rotate-half, theta from the file) or learned absolute
  positions added to the embeddings;
  x += softmax(q k^T / sqrt(hd), causal) v Wo
  x += gelu_tanh(N2(x) W_up) W_down, or with ``act="swiglu"``
  x += (silu(N2(x) W_gate) * N2(x) W_up) W_down
then N_f and the head (tied: logits = N_f(x) E^T; else N_f(x) W_head).
N is LayerNorm (scale and bias) or, with ``norm="rmsnorm"``, RMSNorm
(scale only).  Sequences longer than ``BLOCK_FROM`` positions attend in
blocks of ``Q_BLOCK`` queries, each against every key with its row's
softmax whole, and read the head in blocks of as many rows, so neither
the score matrix nor the logits of a 32k sequence are ever live at once
(under ``quant`` each block then has its own absmax scale).

``quant="fp8"`` is the control: the same forward with both operands of
every matmul rounded to float8_e4m3fn under a per-tensor absmax scale.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

NORMS = ("layernorm", "rmsnorm")
ACTS = ("gelu_tanh", "swiglu")
BLOCK_FROM = 4096  # longer sequences attend in query blocks
Q_BLOCK = 512  # queries per block: 32 heads x 512 x 32k scores are 2.1 GB
BIAS_SCALE = 0.5  # q/k/v biases ~ N(0, 0.5^2), against q/k/v of ~unit size


@dataclasses.dataclass(frozen=True)
class Spec:
    """What the reference needs of a configuration.  A family file
    (``bench/families/<family>.py``) reads it from the configuration's own
    keys."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    ffn: int
    vocab: int
    eps: float
    rope_theta: float = 0.0  # 0: learned absolute positions
    positions: int = 0  # rows of the learned position table
    norm: str = "layernorm"
    act: str = "gelu_tanh"
    tied: bool = True  # the head is the token embedding, transposed
    qkv_bias: bool = False  # q, k and v projections carry a bias

    def __post_init__(self):
        if self.norm not in NORMS or self.act not in ACTS:
            raise ValueError(f"norm {self.norm!r} / activation {self.act!r} "
                             f"not modelled (have {NORMS} / {ACTS})")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads


# ---------------------------------------------------------------------------
# Seeded weights
# ---------------------------------------------------------------------------


def base_key(seed: int) -> jax.Array:
    """A PRNG key from a non-negative seed of any size."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    seed >>= 32
    while seed:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
    return key


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _norm(key, d):
    ks, kb = jax.random.split(key)
    return 1.0 + _normal(ks, (d,), 0.1), _normal(kb, (d,), 0.1)


def embed_weights(key: jax.Array, s: Spec) -> Dict[str, jax.Array]:
    """Token (and position) embeddings, the final norm, and the head where
    it is not tied to the token embedding."""
    ke, kp, kn = jax.random.split(jax.random.fold_in(key, 0), 3)
    out = {"embed": _normal(ke, (s.vocab, s.d_model), 0.02)}
    if not s.rope_theta:
        out["pos_embed"] = _normal(kp, (s.positions, s.d_model), 0.02)
    out["lnf_scale"], out["lnf_bias"] = _norm(kn, s.d_model)
    if not s.tied:
        out["head"] = _normal(jax.random.fold_in(kn, 1),
                              (s.d_model, s.vocab), s.d_model ** -0.5)
    return out


def layer_weights(key: jax.Array, s: Spec, layer) -> Dict[str, jax.Array]:
    """Layer ``layer``'s weights (``layer`` may be traced).  The gate and
    the q/k/v biases come from keys folded in past the eight that every
    spec draws, so a spec without them draws what it always drew.
    Under ``norm="rmsnorm"`` the norms' biases are drawn and not used."""
    kl = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    k = jax.random.split(kl, 8)
    d, hd = s.d_model, s.head_dim
    w = {
        "wq": _normal(k[0], (d, s.heads * hd), d ** -0.5),
        "wk": _normal(k[1], (d, s.kv_heads * hd), d ** -0.5),
        "wv": _normal(k[2], (d, s.kv_heads * hd), d ** -0.5),
        "wo": _normal(k[3], (s.heads * hd, d), (s.heads * hd) ** -0.5),
        "w_up": _normal(k[4], (d, s.ffn), d ** -0.5),
        "w_down": _normal(k[5], (s.ffn, d), s.ffn ** -0.5),
    }
    w["ln1_scale"], w["ln1_bias"] = _norm(k[6], d)
    w["ln2_scale"], w["ln2_bias"] = _norm(k[7], d)
    if s.act == "swiglu":
        w["w_gate"] = _normal(jax.random.fold_in(kl, 8), (d, s.ffn),
                              d ** -0.5)
    if s.qkv_bias:
        kb = jax.random.split(jax.random.fold_in(kl, 9), 3)
        w["bq"] = _normal(kb[0], (s.heads * hd,), BIAS_SCALE)
        w["bk"] = _normal(kb[1], (s.kv_heads * hd,), BIAS_SCALE)
        w["bv"] = _normal(kb[2], (s.kv_heads * hd,), BIAS_SCALE)
    return w


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fp8(x):
    """Round to float8_e4m3fn under a per-tensor absmax scale, and back."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 448.0 / amax
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(eq: str, a, b, quant: Optional[str]):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _normed(x, scale, bias, s: Spec):
    if s.norm == "rmsnorm":
        return _rmsnorm(x, scale, s.eps)
    return _layernorm(x, scale, bias, s.eps)


def _rope(x, theta):
    """x: (T, H, hd), rotate-half RoPE at positions 0..T-1."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv  # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(t: int) -> Optional[int]:
    """Rows a block of a sequence of ``t`` positions, or None: whole."""
    return Q_BLOCK if t > BLOCK_FROM else None


def _attend(q, k, v, quant, block: Optional[int] = None):
    """Causal attention of q (T, H, hd) over k, v (T, H, hd): whole, or in
    blocks of ``block`` queries (the last padded), each block's rows
    against every key."""
    t, _, hd = q.shape

    def rows_out(qi, rows):
        scores = _mm("thd,shd->hts", qi, k, quant) / math.sqrt(hd)
        causal = jnp.arange(t)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _mm("hts,shd->thd", p, v, quant)

    if block is None:
        return rows_out(q, jnp.arange(t))
    nb = -(-t // block)
    qb = jnp.pad(q, ((0, nb * block - t), (0, 0), (0, 0)))
    o = jax.lax.map(lambda a: rows_out(a[1], a[0] * block + jnp.arange(block)),
                    (jnp.arange(nb), qb.reshape(nb, block, *q.shape[1:])))
    return o.reshape(nb * block, *q.shape[1:])[:t]


@functools.partial(jax.jit, static_argnames=("s", "quant", "block"))
def _layer(x, w, *, s: Spec, quant, block: Optional[int] = None):
    t = x.shape[0]
    hd, rep = s.head_dim, s.heads // s.kv_heads
    h = _normed(x, w["ln1_scale"], w["ln1_bias"], s)
    q = _mm("td,de->te", h, w["wq"], quant)
    k = _mm("td,de->te", h, w["wk"], quant)
    v = _mm("td,de->te", h, w["wv"], quant)
    if s.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = q.reshape(t, s.heads, hd)
    k = k.reshape(t, s.kv_heads, hd)
    v = v.reshape(t, s.kv_heads, hd)
    if s.rope_theta:
        q, k = _rope(q, s.rope_theta), _rope(k, s.rope_theta)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    o = _attend(q, k, v, quant, block).reshape(t, s.heads * hd)
    x = x + _mm("te,ed->td", o, w["wo"], quant)
    h = _normed(x, w["ln2_scale"], w["ln2_bias"], s)
    u = _mm("td,df->tf", h, w["w_up"], quant)
    if s.act == "swiglu":
        g = jax.nn.silu(_mm("td,df->tf", h, w["w_gate"], quant)) * u
    else:
        g = 0.5 * u * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                      * (u + 0.044715 * u ** 3)))
    return x + _mm("tf,fd->td", g, w["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("s",))
def _embed(tokens, e, *, s: Spec):
    x = e["embed"][tokens]
    if not s.rope_theta:
        # padding past the table's end reads its last row
        pos = jnp.minimum(jnp.arange(tokens.shape[0]), s.positions - 1)
        x = x + e["pos_embed"][pos]
    return x


def _head_rows(x, e, targets, s: Spec, quant):
    h = _normed(x, e["lnf_scale"], e["lnf_bias"], s)
    lg = (_mm("td,vd->tv", h, e["embed"], quant) if s.tied
          else _mm("td,dv->tv", h, e["head"], quant))
    at = jnp.take_along_axis(lg, targets[:, None], axis=1)[:, 0]
    return jnp.max(lg, -1), at, jnp.argmax(lg, -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("s", "quant", "block"))
def _head_stats(x, e, targets, *, s: Spec, quant,
                block: Optional[int] = None):
    """Per position: the best logit, the logit of ``targets`` and the
    argmax, from the head over the final hidden states; with ``block``,
    that many rows at a time, so the logits of a 32k sequence are never
    live at once."""
    if block is None:
        return _head_rows(x, e, targets, s, quant)
    t = x.shape[0]
    nb = -(-t // block)
    xb = jnp.pad(x, ((0, nb * block - t), (0, 0))).reshape(nb, block, -1)
    tb = jnp.pad(targets, (0, nb * block - t)).reshape(nb, block)
    out = jax.lax.map(lambda a: _head_rows(a[0], e, a[1], s, quant),
                      (xb, tb))
    return tuple(o.reshape(-1)[:t] for o in out)


_embed_weights = jax.jit(embed_weights, static_argnums=(1,))
_layer_weights = jax.jit(layer_weights, static_argnums=(1,))

PAD = 512  # sequences are padded to a multiple of this (fewer compiles)


class Reference:
    """The reference model for one seed: ``hidden`` runs sequences
    through every layer, ``stats`` reads the head at given targets."""

    def __init__(self, s: Spec, seed: int):
        self.s = s
        self.key = base_key(seed)
        self.e = _embed_weights(self.key, s)

    def _pad(self, seq: Sequence[int]) -> np.ndarray:
        out = np.zeros((-(-len(seq) // PAD) * PAD,), np.int32)
        out[: len(seq)] = seq
        return out

    def hidden(self, seqs: Sequence[Sequence[int]],
               quant: Optional[str] = None) -> List[jax.Array]:
        """Final hidden states (padded) of each sequence.  All sequences
        advance together one layer at a time, so each layer's weights are
        made once; padding sits after each sequence, where causal attention
        hides it."""
        xs = [_embed(jnp.asarray(self._pad(q)), self.e, s=self.s)
              for q in seqs]
        for layer in range(self.s.layers):
            w = _layer_weights(self.key, self.s, layer)
            xs = [_layer(x, w, s=self.s, quant=quant, block=_block(len(x)))
                  for x in xs]
            del w
        return xs

    def stats(self, x: jax.Array, targets: Sequence[int],
              quant: Optional[str] = None):
        """(best, at_target, argmax) numpy arrays over x's positions;
        ``targets[i]`` is the token scored at position i (padded with 0)."""
        tg = np.zeros((x.shape[0],), np.int32)
        tg[: len(targets)] = targets
        out = _head_stats(x, self.e, jnp.asarray(tg), s=self.s, quant=quant,
                          block=_block(len(x)))
        return tuple(np.asarray(a) for a in out)

"""Plain float32 reference for dense pre-norm decoder LMs (GPT-2,
StarCoder2), and the seeded weights both it and the served model use.

Independent of the program: it takes a ``Spec`` read from the
configuration file, makes the weights from the seed with its own
generator, and runs the textbook forward pass over a whole sequence (no
cache, no kernels, no batching),
one layer at a time so that a model that fills the chip still fits once
the served model's state is freed.  Every matmul runs at
``Precision.HIGHEST`` (float32 on the TPU's MXU).

The block, as both families publish it (biases left out where the
configuration file says the served model has none):
  h = LN1(x); q, k, v = h Wq, h Wk, h Wv   (GQA: q head j reads kv head
  j // (heads / kv_heads)); RoPE on q, k (rotate-half, theta from the
  file) or learned absolute positions added to the embeddings;
  x += softmax(q k^T / sqrt(hd), causal) v Wo
  x += gelu_tanh(LN2(x) W_up) W_down
then LN_f and the head (tied: logits = LN_f(x) E^T; else LN_f(x) W_head).

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

NORMS = ("layernorm",)
ACTS = ("gelu_tanh",)


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
    """Layer ``layer``'s weights (``layer`` may be traced)."""
    k = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1),
                                            layer), 8)
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


def _rope(x, theta):
    """x: (T, H, hd), rotate-half RoPE at positions 0..T-1."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv  # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def _layer(x, w, *, s: Spec, quant):
    t = x.shape[0]
    hd, rep = s.head_dim, s.heads // s.kv_heads
    h = _layernorm(x, w["ln1_scale"], w["ln1_bias"], s.eps)
    q = _mm("td,de->te", h, w["wq"], quant).reshape(t, s.heads, hd)
    k = _mm("td,de->te", h, w["wk"], quant).reshape(t, s.kv_heads, hd)
    v = _mm("td,de->te", h, w["wv"], quant).reshape(t, s.kv_heads, hd)
    if s.rope_theta:
        q, k = _rope(q, s.rope_theta), _rope(k, s.rope_theta)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = _mm("thd,shd->hts", q, k, quant) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _mm("hts,shd->thd", p, v, quant).reshape(t, s.heads * hd)
    x = x + _mm("te,ed->td", o, w["wo"], quant)
    h = _layernorm(x, w["ln2_scale"], w["ln2_bias"], s.eps)
    u = _mm("td,df->tf", h, w["w_up"], quant)
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


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def _head_stats(x, e, targets, *, s: Spec, quant):
    """Per position: the best logit, the logit of ``targets`` and the
    argmax, from the head over the final hidden states."""
    h = _layernorm(x, e["lnf_scale"], e["lnf_bias"], s.eps)
    lg = (_mm("td,vd->tv", h, e["embed"], quant) if s.tied
          else _mm("td,dv->tv", h, e["head"], quant))
    at = jnp.take_along_axis(lg, targets[:, None], axis=1)[:, 0]
    return jnp.max(lg, -1), at, jnp.argmax(lg, -1).astype(jnp.int32)


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
            xs = [_layer(x, w, s=self.s, quant=quant) for x in xs]
            del w
        return xs

    def stats(self, x: jax.Array, targets: Sequence[int],
              quant: Optional[str] = None):
        """(best, at_target, argmax) numpy arrays over x's positions;
        ``targets[i]`` is the token scored at position i (padded with 0)."""
        tg = np.zeros((x.shape[0],), np.int32)
        tg[: len(targets)] = targets
        out = _head_stats(x, self.e, jnp.asarray(tg), s=self.s, quant=quant)
        return tuple(np.asarray(a) for a in out)

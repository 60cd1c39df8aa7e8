"""The parallel-block decoder layer as pure functions of arrays: ONE
definition of the block's mathematics, called by the Layer's `forward`
(`cohere2_moe.py`) and by the serving engine's chunk-prefill and decode
programs (`inference/layered.py`).

    h  = LN(x)                                  no bias
    a  = attention(h Wq, h Wk, h Wv) Wo         the caller's `attend`
    x' = x + a + experts(h)                     both branches off ONE norm

What differs between the callers is where the keys and values live — the
whole sequence in hand (the Layer), a paged pool (the engine) — and that
is the `attend` argument: `attend(q [T, nh, hd], k, v [T, nkv, hd]) ->
[T, nh, hd]`. The attention KIND is the block's: `sliding_attention`
rotates q and k (interleaved pairs, "gptj") and sees the last `window`
positions; `full_attention` has no positional encoding and sees all.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ...incubate.nn.functional.dropless_moe import moe_forward

_F32 = jnp.float32
SLIDING = "sliding_attention"
FULL = "full_attention"


@dataclass(frozen=True)
class BlockSpec:
    """The block's static sizes (hashable: it keys compiled programs)."""
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    eps: float
    rope_theta: float
    window: int
    layer_types: tuple
    num_experts: int
    top_k: int
    first_expert: int
    num_local_experts: int
    num_shared_experts: int
    logit_scale: float

    @property
    def rope_dim(self) -> int:
        return self.head_dim


def cache_kind(kind: str) -> str:
    """The layer state a layer of attention kind `kind` keeps: its own
    name (full history, or a window)."""
    return kind


def expert_layer(kind: str) -> bool:
    """Every layer of this block has its experts."""
    return True


def rope_for(spec: BlockSpec, positions: int):
    """The programs' rotary tables for `positions` positions."""
    return rope_tables(positions, spec.rope_dim, spec.rope_theta)


def layer_norm_f32(x, gain, eps):
    """(x - mean) / sqrt(var + eps) * gain, in float32."""
    xf = x.astype(_F32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return (xf - mean) * jax.lax.rsqrt(var + eps) * gain.astype(_F32)


def layer_norm(x, gain, eps):
    return layer_norm_f32(x, gain, eps).astype(x.dtype)


def rope_tables(positions: int, head_dim: int, theta: float):
    """(cos, sin) float32 [positions, head_dim] for the interleaved
    rotation, angles worked out in float64; pair i's angle sits at
    columns 2i and 2i + 1."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                           / head_dim))
    ang = np.repeat(np.outer(np.arange(positions, dtype=np.float64), inv),
                    2, axis=-1)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def rope_interleaved(x, cos, sin):
    """Rotate pairs (2i, 2i+1) of x [T, heads, D] by cos/sin [T, D]."""
    xf = x.astype(_F32)
    pairs = xf.reshape(*xf.shape[:-1], -1, 2)
    rot = jnp.stack([-pairs[..., 1], pairs[..., 0]], axis=-1).reshape(
        xf.shape)
    return (xf * cos[:, None, :] + rot * sin[:, None, :]).astype(x.dtype)


def visible(q_pos, kv_pos, kind: str, window: int):
    """[Q, K] bool: key j visible from query t iff j <= t, and on a
    sliding layer t - j < window (the window counts the query's own
    position)."""
    d = q_pos[:, None] - kv_pos[None, :]
    ok = d >= 0
    if kind == SLIDING:
        ok = ok & (d < window)
    return ok


def grouped_attention(q, k, v, mask):
    """softmax(q k^T / sqrt(D) + mask) v with each KV head serving its
    group of query heads, one KV head at a time so that the float32
    scores held at once are [group, Q, K]. q [Q, nh, D]; k, v
    [K, nkv, D]; mask [Q, K] bool."""
    nq, nh, d = q.shape
    nkv = k.shape[1]
    rep = nh // nkv
    qg = q.reshape(nq, nkv, rep, d).transpose(1, 2, 0, 3)   # [nkv,rep,Q,D]
    kg = k.transpose(1, 0, 2)                               # [nkv,K,D]
    vg = v.transpose(1, 0, 2)
    scale = np.float32(1.0 / np.sqrt(d))

    def group(args):
        qh, kh, vh = args
        sc = jnp.einsum("rqd,kd->rqk", qh, kh,
                        preferred_element_type=_F32) * scale
        sc = jnp.where(mask[None], sc, jnp.finfo(_F32).min)
        p = jax.nn.softmax(sc, axis=-1).astype(vh.dtype)
        return jnp.einsum("rqk,kd->rqd", p, vh,
                          preferred_element_type=_F32).astype(qh.dtype)

    out = jax.lax.map(group, (qg, kg, vg))                  # [nkv,rep,Q,D]
    return out.transpose(2, 0, 1, 3).reshape(nq, nh, d)


def attend_sequence(spec: BlockSpec, kind: str):
    """`attend` for a whole sequence in hand (positions 0..T-1)."""
    def attend(q, k, v):
        pos = jnp.arange(q.shape[0])
        return grouped_attention(q, k, v,
                                 visible(pos, pos, kind, spec.window))
    return attend


def block(x, lw, spec: BlockSpec, kind: str, attend, rope=None,
          valid=None):
    """One layer over a block of tokens x [T, H]. `lw`: the layer's
    arrays (`ln`, `q`, `k`, `v`, `o`, and the expert layer's); `rope`:
    (cos, sin) [T, D] at the tokens' positions, read on sliding layers;
    `valid` [T] bool leaves padded rows out of the expert counts.
    Returns (x', picks, max_load)."""
    t = x.shape[0]
    nh, nkv, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    # the router reads the norm before it is rounded to the matmuls'
    # dtype: a pick that flips on that rounding swaps a whole expert
    h32 = layer_norm_f32(x, lw["ln"], spec.eps)
    h = h32.astype(x.dtype)
    q = (h @ lw["q"]).reshape(t, nh, hd)
    k = (h @ lw["k"]).reshape(t, nkv, hd)
    v = (h @ lw["v"]).reshape(t, nkv, hd)
    if kind == SLIDING:
        q = rope_interleaved(q, *rope)
        k = rope_interleaved(k, *rope)
    with jax.named_scope("attn.window" if kind == SLIDING else "attn.full"):
        a = attend(q, k, v)
    a = a.reshape(t, nh * hd) @ lw["o"]
    ffn, picks, max_load = moe_forward(
        h, lw, h_router=h32, top_k=spec.top_k,
        first_expert=spec.first_expert,
        num_local=spec.num_local_experts,
        num_shared=spec.num_shared_experts, valid=valid)
    out = x.astype(_F32) + a.astype(_F32) + ffn
    return out.astype(x.dtype), picks, max_load


def logits(x, final_gain, embed, spec: BlockSpec):
    """logit_scale * LN_f(x) E^T in float32 (the head is the embedding,
    read in its own dtype: no float32 copy of it is made)."""
    h = layer_norm(x, final_gain, spec.eps)
    out = jax.lax.dot_general(h, embed, (((1,), (1,)), ((), ())),
                              preferred_element_type=_F32)
    return out * np.float32(spec.logit_scale)


def head(x, params, spec: BlockSpec):
    """`logits` from the programs' parameter dictionary (tied head)."""
    return logits(x, params["final_ln"], params["embed"], spec)


def forward_sequence(params, ids, spec: BlockSpec):
    """Logits [T, V] float32 of one whole sequence `ids` [T]: the plain
    forward pass, every layer attending over the sequence in hand."""
    t = ids.shape[0]
    x = params["embed"][ids]
    cos, sin = (jnp.asarray(a) for a in
                rope_tables(t, spec.head_dim, spec.rope_theta))
    for lw, kind in zip(params["layers"], spec.layer_types):
        x, _, _ = block(x, lw, spec, kind, attend_sequence(spec, kind),
                        rope=(cos, sin))
    return logits(x, params["final_ln"], params["embed"], spec)

"""The full-attention layer of a Gated DeltaNet hybrid that keeps ordinary
K/V pages (Qwen3-Next), and the pre-norm expert FFN that closes each of
its layers, as pure functions of arrays. `gated_delta_block.py` calls
these for a hybrid whose `full` spec is this module's `BlockSpec` (a
latent-attention hybrid's is `latent_block.BlockSpec`); the Layer's
`forward` (`qwen3_next.py`) and the serving engine's programs
(`inference/layered.py`) call that.

With ZRMS(x; w) = RMS(x) (1 + w) (every gain zero-centred):

    h          = ZRMS(x; w_in)
    [q_j | g_j] = h W_q            per head j: head_dim of query, head_dim of gate
    k, v       = h W_k, h W_v      num_kv_heads x head_dim each
    q = ZRMS(q; w_qn) ;  k = ZRMS(k; w_kn)            over head_dim, a head
    q, k       rotate-half RoPE on the first `rope_dim` dims (pairs (i, i + rope_dim / 2))
    a          = softmax_{j<=t}(q . k_j / sqrt(head_dim)) v   query heads share a KV head in groups
    x1         = x + (a * sigmoid(g)) W_o
    x'         = x1 + moe(ZRMS(x1; w_post))               pre-norm, no norm on the output

`moe` is `dropless_moe.moe_forward` with the softmax router over all
experts (the top k renormalised) and the shared expert scaled by
sigmoid(h w_sg). What differs between the callers is where the keys and
values live, and that is `attend(q [T, nh, D], k, v [T, nkv, D]) -> [T,
nh, D]`, `parallel_block.block`'s contract: `attend_sequence` over the
sequence in hand, the engine's over its pages. `blocked_attention` is the
same attention a context block at a time with an online softmax, so that
no [heads, T, context] tensor exists: what a serving chunk runs over its
pages at every context length.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ...incubate.nn.functional.dropless_moe import moe_forward
from . import latent_block as lb
from . import parallel_block as pb

_F32 = jnp.float32
#: context positions one step of `blocked_attention` takes in a chunk
CTX_BLOCK = 512


@dataclass(frozen=True)
class BlockSpec:
    """The block's static sizes (hashable: it keys compiled programs).
    `layer_types`: every layer's FFN kind (`latent_block.EXPERTS`)."""
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_dim: int
    eps: float
    rope_theta: float
    layer_types: tuple
    num_experts: int
    top_k: int
    first_expert: int
    num_local_experts: int
    num_shared_experts: int = 1

    #: every norm's gain is (1 + w) (`latent_block.gain` reads this)
    zero_centred = True



def cache_kind(kind: str) -> str:
    """A full layer keeps K and V of every position in pages."""
    return pb.FULL


def rope_for(spec: BlockSpec, positions: int):
    """(cos, sin) [positions, rope_dim] of the rotate-half rotation."""
    return lb.rope_tables(positions, spec.rope_dim, spec.rope_theta)


def partial_rope(x, cos, sin):
    """Rotate-half RoPE on the first `cos.shape[-1]` dims of x [T, ..., D];
    the rest pass as they are."""
    r = cos.shape[-1]
    return jnp.concatenate([lb.rope_half(x[..., :r], cos, sin), x[..., r:]],
                           axis=-1)


def attention(x, lw, spec: BlockSpec, attend, rope):
    """The gated attention sublayer: x [T, H] -> x1 [T, H]. `lw`: `ln_in`,
    `q` [H, nh x 2D] (each head's query and gate side by side), `k`, `v`
    [H, nkv x D], `q_norm`, `k_norm` [D], `o` [nh x D, H]."""
    t = x.shape[0]
    nh, nkv, d, eps = spec.num_heads, spec.num_kv_heads, spec.head_dim, \
        spec.eps
    h = lb.rms_norm(x, lb.gain(lw["ln_in"], spec), eps)
    with jax.named_scope("qwen3_next.gated_attn"):
        qg = (h @ lw["q"]).reshape(t, nh, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        q = lb.rms_norm(q, lb.gain(lw["q_norm"], spec), eps)
        k = lb.rms_norm((h @ lw["k"]).reshape(t, nkv, d),
                        lb.gain(lw["k_norm"], spec), eps)
        v = (h @ lw["v"]).reshape(t, nkv, d)
        q, k = partial_rope(q, *rope), partial_rope(k, *rope)
    with jax.named_scope("attn.full"):
        a = attend(q, k, v)
    with jax.named_scope("qwen3_next.gated_attn"):
        a = (a.astype(_F32) * jax.nn.sigmoid(gate.astype(_F32))).astype(
            x.dtype)
        out = a.reshape(t, nh * d) @ lw["o"]
    return (x.astype(_F32) + out.astype(_F32)).astype(x.dtype)


def ffn_sublayer(x1, lw, spec: BlockSpec, kind: str, valid=None):
    """x1 + moe(ZRMS(x1; w_post)) (`ln_ffn` the gain): (x', picks,
    max_load, experts read)."""
    if kind != lb.EXPERTS:
        raise ValueError(f"a {kind!r} FFN is not this block's")
    # the router reads the norm before it is rounded to the matmuls' dtype
    h32 = lb.rms_norm_f32(x1, lb.gain(lw["ln_ffn"], spec), spec.eps)
    ffn, picks, max_load, read = moe_forward(
        h32.astype(x1.dtype), lw, h_router=h32, top_k=spec.top_k,
        first_expert=spec.first_expert, num_local=spec.num_local_experts,
        num_shared=spec.num_shared_experts, valid=valid, router="softmax",
        shared_gate=True)
    return ((x1.astype(_F32) + ffn).astype(x1.dtype), picks, max_load,
            read)


def block(x, lw, spec: BlockSpec, kind: str, attend, rope=None,
          valid=None):
    """One full layer over a block of tokens x [T, H] (`rope`: (cos, sin)
    [T, rope_dim] at the tokens' positions). Returns (x', picks,
    max_load, experts read)."""
    return ffn_sublayer(attention(x, lw, spec, attend, rope), lw, spec,
                        kind, valid)


def attend_sequence(spec: BlockSpec):
    """`attend` for a whole sequence in hand (positions 0..T-1), the
    scores whole: `parallel_block.grouped_attention` (1 / sqrt(D))."""
    def attend(q, k, v):
        pos = jnp.arange(q.shape[0])
        return pb.grouped_attention(q, k, v, pos[:, None] >= pos[None, :])
    return attend


def blocked_attention(q, kv_of, n_blocks, block, q_pos, nkv):
    """Causal grouped attention over `n_blocks` (may be traced) context
    blocks of `block` positions with an online softmax, scale 1 /
    sqrt(D). q [Q, nh, D]; `kv_of(j)` gives block j's (k, v) [block, nkv,
    D] (position j * block
    + i at row i); a query at `q_pos` sees positions <= its own. The
    float32 scores held at once are [nkv, nh / nkv, Q, block]. Returns
    [Q, nh, D] in q's dtype."""
    nq, nh, d = q.shape
    neg = jnp.finfo(_F32).min
    scale = np.float32(1.0 / np.sqrt(d))
    qg = q.reshape(nq, nkv, nh // nkv, d)

    def body(j, carry):
        m, l, acc = carry
        k, v = kv_of(j)
        sc = jnp.einsum("qgrd,bgd->grqb", qg, k.astype(q.dtype),
                        preferred_element_type=_F32) * scale
        kv_pos = j * block + jnp.arange(block)
        sc = jnp.where((q_pos[:, None] >= kv_pos[None, :])[None, None],
                       sc, neg)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "grqb,bgd->grqd", p.astype(q.dtype), v.astype(q.dtype),
            preferred_element_type=_F32)
        return m_new, l, acc

    shape = (nkv, nh // nkv, nq)
    with jax.named_scope("full_chunk_attn"):
        _, l, acc = jax.lax.fori_loop(
            0, n_blocks, body, (jnp.full(shape, neg, _F32),
                                jnp.zeros(shape, _F32),
                                jnp.zeros(shape + (d,), _F32)))
        out = (acc / l[..., None]).reshape(nh, nq, d).transpose(1, 0, 2)
    return out.astype(q.dtype)

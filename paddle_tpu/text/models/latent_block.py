"""The latent-attention (MLA) decoder layer with sandwich norms as pure
functions of arrays: ONE definition of the block's mathematics, called by
the Layer's `forward` (`pangu_ultra_moe.py`) and by the serving engine's
chunk-prefill and decode programs (`inference/layered.py`).

    h      = RMS(x; g_in)
    cq     = RMS(h Wqa; g_qa)                       low-rank query
    q      = cq Wqb -> [nh, dn + dr] ;  q = [q_nope | rope(q_rope)]
    ckv|kr = h Wkva -> [rkv | dr] ;  c = RMS(ckv; g_kva) ;  kr = rope(kr)
    cached for a position: (c, kr), rkv + dr values, no heads, no V
    k^h    = [c Wuk^h | kr] ,  v^h = c Wuv^h        Wkvb = [Wuk^h | Wuv^h]
    a      = softmax(q k^T / sqrt(dn + dr)) v Wo    the caller's `attend`
    x1     = x + RMS(a; g_post_attn)                sandwich norm
    h2     = RMS(x1; g_pre_ffn)
    f      = dense SwiGLU(h2)  |  experts(h2)       the layer's FFN kind
    x'     = x1 + RMS(f; g_post_ffn)                sandwich norm

Layers of one model differ in their FFN (`DENSE`, `EXPERTS`), not in
their cache: every layer keeps the latent row of every position.

Static options that openPangu leaves off and GigaChat3.5's MLA layers
turn on (PR 40; `gated_delta_block.py` calls this block for them):
RoPE pairs (2i, 2i + 1) with YaRN frequencies, the softmax scale times
mscale^2, the attention's output gated per channel by sigmoid(h Wgate)
before Wo, every norm gain zero-centred ((1 + w)), and the SwiGLU's gate
capped and its up-projection clipped at `swiglu_limit`. What
differs between the callers is where those rows live, and that is the
`attend` argument: `attend(q_nope [T, nh, dn], q_rope [T, nh, dr],
c [T, rkv], kr [T, dr], w_kvb [rkv, nh * (dn + dv)]) -> [T, nh, dv]`.

Two forms of the attention itself, the same numbers up to rounding:
EXPANDED (`expanded_attention`: every context row through Wkvb, then
ordinary heads of dn + dr and dv) and ABSORBED (`absorb_query`,
`absorbed_attention`, `unabsorb`: Wuk folded into the query and Wuv
applied after the sum, so that all heads attend to the ONE latent row —
what a decode step over a paged latent pool does). RoPE is rotate-half:
dimension i pairs with i + dr / 2.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ...incubate.nn.functional.dropless_moe import (clamp_gate, clamp_up,
                                                    moe_forward)

_F32 = jnp.float32
DENSE = "dense"
EXPERTS = "experts"
#: the cache kind of every layer (inference/layered.py, paged_cache.py)
LATENT = "latent"
#: context positions one step of the blocked attentions below takes
CTX_BLOCK = 512


@dataclass(frozen=True)
class BlockSpec:
    """The block's static sizes (hashable: it keys compiled programs)."""
    hidden_size: int
    num_heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    q_rank: int
    kv_rank: int
    eps: float
    rope_theta: float
    #: one FFN kind a layer
    layer_types: tuple
    num_experts: int
    top_k: int
    first_expert: int
    num_local_experts: int
    num_shared_experts: int
    routed_scale: float
    #: RoPE pairs (2i, 2i + 1) (openPangu: (i, i + dr / 2))
    rope_interleave: bool = False
    #: YaRN (factor, original positions, beta_fast, beta_slow); () none
    rope_yarn: tuple = ()
    #: the softmax scale's factor is mscale ** 2
    mscale: float = 1.0
    #: a = a * sigmoid(h Wgate) before Wo
    gated_attention: bool = False
    #: every norm's gain is (1 + w)
    zero_centred: bool = False
    #: gate <= limit and |up| <= limit in every SwiGLU; 0: no clamp
    swiglu_limit: float = 0.0

    @property
    def latent_width(self) -> int:
        """Values a cached position holds: the latent and the rotary key."""
        return self.kv_rank + self.qk_rope_dim

    # what `ServingEngine` asks of any per-layer model's spec
    @property
    def num_kv_heads(self) -> int:
        return 1

    @property
    def head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def rope_dim(self) -> int:
        return self.qk_rope_dim

    @property
    def scale(self) -> float:
        return 1.0 / float(np.sqrt(self.qk_nope_dim + self.qk_rope_dim)) \
            * self.mscale ** 2


def cache_kind(kind: str) -> str:
    """The layer state a layer of FFN kind `kind` keeps."""
    return LATENT


def expert_layer(kind: str) -> bool:
    return kind == EXPERTS


def gain(w, spec: BlockSpec):
    """A norm's gain from its weight: w, or (1 + w) in float32 where the
    model's gains are zero-centred."""
    return w.astype(_F32) + 1.0 if spec.zero_centred else w


def rms_norm_f32(x, gain, eps):
    """x / sqrt(mean(x^2) + eps) * gain, in float32."""
    xf = x.astype(_F32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + eps) * gain.astype(_F32)


def rms_norm(x, gain, eps):
    return rms_norm_f32(x, gain, eps).astype(x.dtype)


def sandwich_add(x, y, gain, eps):
    """x + RMS(y; gain): a sublayer's output is normalised before it
    joins the residual stream (the norm on its input is the caller's)."""
    return (x.astype(_F32) + rms_norm_f32(y, gain, eps)).astype(x.dtype)


def yarn_frequencies(inv, dr: int, theta: float, factor: float,
                     original: int, beta_fast: float, beta_slow: float):
    """YaRN's blend of the rotary frequencies `inv` [dr / 2]: pairs that
    turn fewer than `beta_slow` times over the `original` positions are
    interpolated (divided by `factor`), more than `beta_fast` times kept,
    a linear ramp between (DeepSeek-V3's `yarn_find_correction_range`)."""
    def dim(turns):
        return (dr * np.log(original / (turns * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(np.floor(dim(beta_fast)), 0)
    high = min(np.ceil(dim(beta_slow)), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0, 1)
    return inv / factor * ramp + inv * (1 - ramp)


def rope_tables(positions: int, dr: int, theta: float, yarn: tuple = ()):
    """(cos, sin) float32 [positions, dr] for the rotate-half rotation,
    angles worked out in float64; pair i's angle sits at columns i and
    i + dr / 2. `yarn`: `BlockSpec.rope_yarn`."""
    inv = 1.0 / (theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr))
    if yarn:
        inv = yarn_frequencies(inv, dr, theta, *yarn)
    ang = np.outer(np.arange(positions, dtype=np.float64), inv)
    ang = np.concatenate([ang, ang], axis=-1)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def rope_for(spec: BlockSpec, positions: int):
    """The programs' rotary tables for `positions` positions."""
    return rope_tables(positions, spec.rope_dim, spec.rope_theta,
                       spec.rope_yarn)


def apply_rope(x, cos, sin, spec: BlockSpec):
    """RoPE on x [T, ..., dr] by the spec's pairing. Pairs (2i, 2i + 1)
    are taken apart into halves first (evens, then odds) and rotated as
    pairs (i, i + dr / 2): the same rotation in another column order,
    and q and the cached key share that order, so every score is the
    interleaved one."""
    if spec.rope_interleave:
        lead = x.shape[:-1]
        x = x.reshape(lead + (-1, 2)).swapaxes(-1, -2).reshape(x.shape)
    return rope_half(x, cos, sin)


def rope_half(x, cos, sin):
    """Rotate pairs (i, i + D/2) of x [T, ..., D] by cos/sin [T, D]."""
    xf = x.astype(_F32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[1],)
    return (xf * cos.reshape(shape) + rot * sin.reshape(shape)).astype(
        x.dtype)


# ------------------------------------------------------------- attention

def _kvb(w_kvb, spec: BlockSpec):
    """Wkvb [rkv, nh * (dn + dv)] as (Wuk, Wuv), each [rkv, nh, d]."""
    w = w_kvb.reshape(spec.kv_rank, spec.num_heads,
                      spec.qk_nope_dim + spec.v_dim)
    return w[:, :, :spec.qk_nope_dim], w[:, :, spec.qk_nope_dim:]


def _attend_blocks(q_main, q_rope, kv_of, score_eq, value_eq, width,
                   rows_of, n_blocks, block, q_pos, spec: BlockSpec):
    """Causal attention over `n_blocks` (may be traced) context blocks
    with an online softmax, so that no [nh, Q, T] tensor ever exists.
    `rows_of(j)` gives block j's cached rows [block, >= rkv + dr]
    (position j * block + i at row i); `kv_of(c)` what a block's latents
    are attended as, (keys for `score_eq` against `q_main`, values for
    `value_eq`); the rotary key joins every head's score; a query at
    `q_pos` sees positions <= its own. Returns [Q, nh, width]."""
    rkv, dr = spec.kv_rank, spec.qk_rope_dim
    neg = jnp.finfo(_F32).min
    heads, q_rows = spec.num_heads, q_main.shape[0]

    def body(j, carry):
        m, l, acc = carry
        rows = rows_of(j).astype(q_main.dtype)
        c, kr = rows[:, :rkv], rows[:, rkv:rkv + dr]
        k, v = kv_of(c)
        sc = (jnp.einsum(score_eq, q_main, k, preferred_element_type=_F32)
              + jnp.einsum("qhd,bd->hqb", q_rope, kr,
                           preferred_element_type=_F32)) \
            * np.float32(spec.scale)
        kv_pos = j * block + jnp.arange(block)
        sc = jnp.where((q_pos[:, None] >= kv_pos[None, :])[None], sc, neg)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            value_eq, p.astype(v.dtype), v, preferred_element_type=_F32)
        return m_new, l, acc

    m0 = jnp.full((heads, q_rows), neg, _F32)
    l0 = jnp.zeros((heads, q_rows), _F32)
    acc0 = jnp.zeros((heads, q_rows, width), _F32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    return (acc / l[..., None]).transpose(1, 0, 2).astype(q_main.dtype)


def expanded_attention(q_nope, q_rope, rows_of, n_blocks, block, w_kvb,
                       q_pos, spec: BlockSpec):
    """The published form, a context block at a time: each cached latent
    goes through Wkvb to heads of keys and values. q_nope [Q, nh, dn],
    q_rope [Q, nh, dr] -> [Q, nh, dv]. This composition is what the
    Layer's `forward` runs, what a serving chunk runs off the chip or at
    widths the kernel's gate declines, and the oracle of the kernel that
    runs a serving chunk on the chip (`ops/pallas_latent_chunk.py`: the
    same products and precisions, the scores kept in VMEM; PR 38)."""
    wuk, wuv = _kvb(w_kvb, spec)

    def expand(c):
        with jax.named_scope("attn.latent.expand"):
            return (jnp.einsum("br,rhd->bhd", c, wuk),
                    jnp.einsum("br,rhd->bhd", c, wuv))

    return _attend_blocks(q_nope, q_rope, expand, "qhd,bhd->hqb",
                          "hqb,bhd->hqd", spec.v_dim, rows_of, n_blocks,
                          block, q_pos, spec)


def absorb_query(q_nope, w_kvb, spec: BlockSpec):
    """qlat^h = q_nope^h Wuk^h^T: [T, nh, dn] -> [T, nh, rkv]."""
    with jax.named_scope("attn.latent.absorb"):
        return jnp.einsum("thd,rhd->thr", q_nope, _kvb(w_kvb, spec)[0])


def unabsorb(olat, w_kvb, spec: BlockSpec):
    """a^h = olat^h Wuv^h: [T, nh, rkv] -> [T, nh, dv]."""
    with jax.named_scope("attn.latent.absorb"):
        return jnp.einsum("thr,rhd->thd", olat, _kvb(w_kvb, spec)[1])


def absorbed_attention(q_nope, q_rope, rows_of, n_blocks, block, w_kvb,
                       q_pos, spec: BlockSpec):
    """The absorbed form over the same blocks: every head attends to the
    latent rows themselves (score = qlat . c + q_rope . kr, the sum of
    P c taken in the latent space), no row is expanded. What a decode
    step does through `paged_latent_decode`; for a chunk it measured 1.47
    x slower than the expanded form, composition against composition
    (PERF.md section 6, PR 37), and a chunk on the chip is now the expanded
    form as ONE kernel (PR 38), 4 x faster again than that composition.
    Kept as the tests' second form: the same numbers by another route."""
    olat = _attend_blocks(absorb_query(q_nope, w_kvb, spec), q_rope,
                          lambda c: (c, c), "qhr,br->hqb", "hqb,br->hqr",
                          spec.kv_rank, rows_of, n_blocks, block, q_pos,
                          spec)
    return unabsorb(olat, w_kvb, spec)


def attend_sequence(spec: BlockSpec, form=expanded_attention):
    """`attend` for a whole sequence in hand (positions 0..T-1)."""
    def attend(q_nope, q_rope, c, kr, w_kvb):
        t = q_nope.shape[0]
        block = min(CTX_BLOCK, t)
        n = -(-t // block)
        rows = jnp.pad(jnp.concatenate([c, kr], axis=-1),
                       ((0, n * block - t), (0, 0)))
        return form(
            q_nope, q_rope,
            lambda j: jax.lax.dynamic_slice_in_dim(rows, j * block, block),
            n, block, w_kvb, jnp.arange(t), spec)
    return attend


# ----------------------------------------------------------------- block

def dense_ffn(h, lw, limit=0.0):
    """Wd(silu(Wg h) * (Wu h)), float32 [T, H]; `limit`: the SwiGLU clamp
    (`dropless_moe.clamp_gate`, `clamp_up`)."""
    with jax.named_scope("ffn.dense"):
        return ((jax.nn.silu(clamp_gate(h @ lw["gate"], limit))
                 * clamp_up(h @ lw["up"], limit)) @ lw["down"]).astype(_F32)


def block(x, lw, spec: BlockSpec, kind: str, attend, rope=None,
          valid=None):
    """One layer over a block of tokens x [T, H]. `lw`: the layer's
    arrays (the four sandwich gains `ln_in`, `ln_post_attn`, `ln_pre_ffn`,
    `ln_post_ffn`; `q_a`, `q_a_ln`, `q_b`, `kv_a`, `kv_a_ln`, `kv_b`,
    `o`, `attn_gate` where the attention is gated; `gate`/`up`/`down` of
    a dense layer or the expert layer's); `rope`: (cos, sin) [T, dr] at
    the tokens' positions; `valid` [T] bool leaves padded rows out of the
    expert counts. Returns (x', picks, max_load), the counts 0 on a dense
    layer."""
    t = x.shape[0]
    nh, dn, dr = spec.num_heads, spec.qk_nope_dim, spec.qk_rope_dim
    eps = spec.eps
    h = rms_norm(x, gain(lw["ln_in"], spec), eps)
    with jax.named_scope("attn.latent.q"):
        cq = rms_norm(h @ lw["q_a"], gain(lw["q_a_ln"], spec), eps)
        q = (cq @ lw["q_b"]).reshape(t, nh, dn + dr)
        q_nope, q_rope = q[..., :dn], apply_rope(q[..., dn:], *rope, spec)
    with jax.named_scope("attn.latent.kv"):
        ckv = h @ lw["kv_a"]
        c = rms_norm(ckv[:, :spec.kv_rank], gain(lw["kv_a_ln"], spec), eps)
        kr = apply_rope(ckv[:, spec.kv_rank:], *rope, spec)
    with jax.named_scope("attn.latent"):
        a = attend(q_nope, q_rope, c, kr, lw["kv_b"])
    if spec.gated_attention:
        with jax.named_scope("attn.latent.gate"):
            a = (a.astype(_F32) * jax.nn.sigmoid(
                (h @ lw["attn_gate"]).astype(_F32)).reshape(a.shape)
                 ).astype(a.dtype)
    a = a.reshape(t, nh * spec.v_dim) @ lw["o"]
    x1 = sandwich_add(x, a, gain(lw["ln_post_attn"], spec), eps)
    return ffn_sublayer(x1, lw, spec, kind, valid)


def ffn_sublayer(x1, lw, spec: BlockSpec, kind: str, valid=None):
    """x1 + RMS(ffn(RMS(x1; g_pre_ffn)); g_post_ffn) with the layer's FFN
    kind: (x', picks, max_load)."""
    eps = spec.eps
    # the router reads the norm before it is rounded to the matmuls'
    # dtype: a pick that flips on that rounding swaps a whole expert
    h32 = rms_norm_f32(x1, gain(lw["ln_pre_ffn"], spec), eps)
    h2 = h32.astype(x1.dtype)
    if kind == DENSE:
        ffn = dense_ffn(h2, lw, spec.swiglu_limit)
        picks = max_load = jnp.zeros((), jnp.int32)
    else:
        ffn, picks, max_load = moe_forward(
            h2, lw, h_router=h32, top_k=spec.top_k,
            first_expert=spec.first_expert,
            num_local=spec.num_local_experts,
            num_shared=spec.num_shared_experts, valid=valid,
            routed_scale=spec.routed_scale, swiglu_limit=spec.swiglu_limit)
    return (sandwich_add(x1, ffn, gain(lw["ln_post_ffn"], spec), eps),
            picks, max_load)


def head(x, params, spec: BlockSpec):
    """RMS_f(x) Wlm^T in float32 (the head is untied from the embedding
    and read in its own dtype: no float32 copy of it is made)."""
    h = rms_norm(x, gain(params["final_ln"], spec), spec.eps)
    return jax.lax.dot_general(h, params["head"], (((1,), (1,)), ((), ())),
                               preferred_element_type=_F32)


def forward_sequence(params, ids, spec: BlockSpec, form=expanded_attention):
    """Logits [T, V] float32 of one whole sequence `ids` [T]: the plain
    forward pass, every layer attending over the sequence in hand."""
    x = params["embed"][ids]
    tables = tuple(jnp.asarray(a) for a in rope_for(spec, ids.shape[0]))
    for lw, kind in zip(params["layers"], spec.layer_types):
        x, _, _ = block(x, lw, spec, kind, attend_sequence(spec, form),
                        rope=tables)
    return head(x, params, spec)

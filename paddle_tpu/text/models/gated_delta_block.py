"""A Gated DeltaNet hybrid decoder layer as pure functions of arrays:
Gated DeltaNet linear attention (Yang et al., arXiv:2412.06464, in the
published Qwen3-Next form) on most layers and a full-attention layer
among every few, of one of two kinds, which the spec's `full` names:

  - latent attention (`latent_block.BlockSpec`: `latent_block.py` with
    its GigaChat options on), sandwich norms and the FFN of
    `latent_block.ffn_sublayer` on every layer (GigaChat3.5);
  - gated GQA attention over ordinary K/V pages
    (`gated_attention_block.BlockSpec`), pre-norms and that module's
    expert FFN on every layer (Qwen3-Next).

ONE definition, called by the Layer's `forward` (`gigachat3_5.py`,
`qwen3_next.py`) and by the serving engine's programs
(`inference/layered.py`).

A linear layer (ZRMS(x; w) = RMS(x) (1 + w); every gain is zero-centred):

    h           = ZRMS(x; w_in)
    q,k | v | z = h W_qkvz          q, k: nk heads x dk;  v, z: nv heads x dv
    b | a       = h W_ba            nv each
    q,k,v       = silu(causal depthwise conv_K(q,k,v))   state: a slot's last K - 1 inputs
    q = l2norm(q) / sqrt(dk) ;  k = l2norm(k)           value head i uses key head i // (nv / nk)
    beta = sigmoid(b) ;  g = -exp(A_log) softplus(a + dt_bias) ;  alpha = exp(g)
    S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t S_{t-1}^T k_t))^T     S [dk, dv] a value head, float32
    o_t = S_t^T q_t
    o   = RMS(o; o_eps) (1 + w_o) gate_scale sigmoid(z)   a head (latent hybrids: `gate_sigmoid`)
        | RMS(o; o_eps) w_o silu(z)                       a head (GQA hybrids, a plain gain: `gate_silu`)
    x1  = x + ZRMS(o W_out; w_post_attn)                  sandwich (latent hybrids)
        | x + o W_out                                     pre-norm (GQA hybrids)

W_qkvz and W_ba are plain concatenations (latent hybrids: `split_plain`),
or (GQA hybrids, Qwen3-Next's checkpoint layout) grouped by key head: key
head g's columns are [q_g | k_g | v, z of its nv / nk value heads] and
[b | a of those heads] (`split_grouped`). The kind of the spec's `full`
layers decides all three (`_full`).

What differs between the callers is where the two states live (the conv's
last inputs and S), so a linear layer's `attend(mixed, b, a, lw) -> o`
is the caller's, as a full layer's is: it runs the conv over the
state's inputs and the new ones (`delta_inputs`), then the recurrence
from the state (`recurrent`: token by token; `chunked`: the WY form a
sub-chunk at a time, for prefill; on the chip a decode step is
`ops/pallas_gated_delta.gated_delta_decode`). A row that is padding takes
beta = 0 and g = 0: it leaves S as it found it.

A layer's kind is the pair (attention, FFN): `(LINEAR | FULL, DENSE |
EXPERTS)`; a linear layer keeps the `RECURRENT` state, a full one the
latent rows or the K/V pages of its kind (`cache_kind`).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ...ops.pallas_gated_delta import delta_step
from . import gated_attention_block as ga
from . import latent_block as lb

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
LINEAR = "linear_attention"
FULL = "full_attention"
#: the cache kind of a linear layer (paged_cache.LayeredKVCache)
RECURRENT = "recurrent"


@dataclass(frozen=True)
class BlockSpec:
    """The block's static sizes (hashable: it keys compiled programs).
    `full`: the full layers' spec (latent or gated GQA), whose
    `layer_types` are every layer's FFN kinds; `attn_types`: every
    layer's attention kind."""
    full: lb.BlockSpec | ga.BlockSpec
    attn_types: tuple
    num_k_heads: int
    num_v_heads: int
    k_dim: int
    v_dim: int
    conv_kernel: int
    o_eps: float
    #: the output gate's scale (`gate_sigmoid`: latent hybrids)
    gate_scale: float = 1.0
    #: tokens a step of the chunked (WY) form takes
    wy_chunk: int = 64

    @property
    def layer_types(self) -> tuple:
        return tuple(zip(self.attn_types, self.full.layer_types))

    @property
    def conv_dim(self) -> int:
        return 2 * self.num_k_heads * self.k_dim \
            + self.num_v_heads * self.v_dim

    @property
    def state_shape(self) -> tuple:
        """A slot's recurrent state in one layer: a matrix a value head."""
        return (self.num_v_heads, self.k_dim, self.v_dim)

    @property
    def conv_shape(self) -> tuple:
        """A slot's conv state in one layer: its last K - 1 inputs."""
        return (self.conv_kernel - 1, self.conv_dim)

    # what `ServingEngine` and the latent programs ask of the spec
    @property
    def eps(self) -> float:
        return self.full.eps

    @property
    def num_heads(self) -> int:
        return self.full.num_heads

    @property
    def num_kv_heads(self) -> int:
        return self.full.num_kv_heads

    @property
    def head_dim(self) -> int:
        return self.full.head_dim

    @property
    def rope_theta(self) -> float:
        return self.full.rope_theta


def _full(spec: BlockSpec):
    """The module of the spec's full layers: their block and FFN."""
    return lb if isinstance(spec.full, lb.BlockSpec) else ga


def cache_kind(kind, spec: BlockSpec) -> str:
    if kind[0] == LINEAR:
        return RECURRENT
    return _full(spec).cache_kind(kind[1])


def expert_layer(kind) -> bool:
    return lb.expert_layer(kind[1])


def rope_for(spec: BlockSpec, positions: int):
    return _full(spec).rope_for(spec.full, positions)


def head(x, params, spec: BlockSpec):
    return lb.head(x, params, spec.full)


# ------------------------------------------------------- linear attention

def delta_inputs(x_ext, b, a, lw, spec: BlockSpec):
    """(q, k, v, beta, g) float32 of T tokens: x_ext [..., K - 1 + T, C]
    (the K - 1 inputs before them, then theirs: q|k|v before the conv),
    b, a [..., T, nv]. q, k [..., T, nv, dk] (repeated to the value
    heads), v [..., T, nv, dv], beta, g [..., T, nv]."""
    nk, nv, dk, dv = spec.num_k_heads, spec.num_v_heads, spec.k_dim, \
        spec.v_dim
    w = lw["conv"].astype(_F32)                      # [K, C]
    taps, t = w.shape[0], x_ext.shape[-2] - w.shape[0] + 1
    with jax.named_scope("attn.linear.conv"):
        xf = x_ext.astype(_F32)
        acc = sum(jax.lax.slice_in_dim(xf, j, j + t, axis=-2) * w[j]
                  for j in range(taps))
        mixed = jax.nn.silu(acc)
    lead = mixed.shape[:-1]
    q = mixed[..., :nk * dk].reshape(lead + (nk, dk))
    k = mixed[..., nk * dk:2 * nk * dk].reshape(lead + (nk, dk))
    v = mixed[..., 2 * nk * dk:].reshape(lead + (nv, dv))

    def l2norm(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                 + 1e-6)

    rep = nv // nk
    q = jnp.repeat(l2norm(q) * (dk ** -0.5), rep, axis=-2)
    k = jnp.repeat(l2norm(k), rep, axis=-2)
    beta = jax.nn.sigmoid(b.astype(_F32))
    g = -jnp.exp(lw["A_log"].astype(_F32)) * jax.nn.softplus(
        a.astype(_F32) + lw["dt_bias"].astype(_F32))
    return q, k, v, beta, g


def recurrent(s, q, k, v, beta, g):
    """The rule token by token over T tokens from state s [nv, dk, dv]:
    (o [T, nv, dv], s_T). What a decode step does a token."""
    def one(s, xs):
        qt, kt, vt, bt, gt = xs
        o, s = delta_step(s, qt, kt, vt, bt, jnp.exp(gt))
        return s, o

    s, o = jax.lax.scan(one, s.astype(_F32), (q, k, v, beta, g))
    return o, s


def _unit_lower_inverse(a):
    """(I + A)^-1 for A [..., C, C] strictly lower triangular: A is
    nilpotent, so the inverse is (I - A)(I + A^2)(I + A^4)... to A^C, a
    product of log2(C) factors (exact, no series cut)."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    inv, p = eye - a, a
    n = 2
    while n < c:
        p = jnp.matmul(p, p, precision=_HIGHEST)
        inv = jnp.matmul(inv, eye + p, precision=_HIGHEST)
        n *= 2
    return inv


def chunked(s, q, k, v, beta, g, chunk: int):
    """The same rule over T tokens in the chunked WY form: within a
    sub-chunk of C tokens, with G_t the cumulative log decay since the
    sub-chunk's start and Gamma_t = exp(G_t),

        A[t, s] = beta_t (Gamma_t / Gamma_s) k_t . k_s      (s < t)
        U       = (I + A)^-1 (beta V - (beta Gamma K) S0)      the u_t rows
        O       = (Gamma Q) S0 + ((Q K^T) . Gamma_t / Gamma_s)_{s <= t} U
        S_C     = Gamma_C S0 + sum_s (Gamma_C / Gamma_s) k_s u_s^T

    the state carried from sub-chunk to sub-chunk (Qwen3-Next's
    `torch_chunk_gated_delta_rule`). Every product at HIGHEST precision:
    the state is float32. T is padded to whole sub-chunks with rows of
    beta = g = 0, which pass the state unchanged. Returns (o [T, nv, dv],
    s_T)."""
    t, nv, dk = k.shape
    dv = v.shape[-1]
    pad = -t % chunk
    q, k, v, beta, g = (jnp.pad(y, ((0, pad),) + ((0, 0),) * (y.ndim - 1))
                        for y in (q, k, v, beta, g))
    n = (t + pad) // chunk

    def split(x):                      # [T, H, ...] -> [n, H, C, ...]
        return x.reshape((n, chunk) + x.shape[1:]).swapaxes(1, 2)

    q, k, v, beta, g = (split(x) for x in (q, k, v, beta, g))
    cg = jnp.cumsum(g, axis=-1)                          # [n, H, C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = cg[..., :, None] - cg[..., None, :]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))    # s <= t
    strict = lower & ~jnp.eye(chunk, dtype=bool)

    def mm(eq, x, y):
        return jnp.einsum(eq, x, y, precision=_HIGHEST)

    kb = k * beta[..., None]
    a = jnp.where(strict, mm("...td,...sd->...ts", kb, k) * decay, 0.0)
    inv = _unit_lower_inverse(a)
    u0 = mm("...ts,...sd->...td", inv, v * beta[..., None])
    w = mm("...ts,...sd->...td", inv, kb * jnp.exp(cg)[..., None])
    qk = jnp.where(lower, mm("...td,...sd->...ts", q, k) * decay, 0.0)
    qg = q * jnp.exp(cg)[..., None]
    tail = jnp.exp(cg[..., -1:] - cg)                    # Gamma_C / Gamma_s

    def step(s, xs):
        u0_i, w_i, qk_i, qg_i, k_i, tail_i, last_i = xs
        u = u0_i - mm("htd,hdv->htv", w_i, s)
        o = mm("htd,hdv->htv", qg_i, s) + mm("hts,hsv->htv", qk_i, u)
        s = s * jnp.exp(last_i)[:, None, None] + mm(
            "hsd,hsv->hdv", k_i * tail_i[..., None], u)
        return s, o

    s, o = jax.lax.scan(step, s.astype(_F32),
                        (u0, w, qk, qg, k, tail, cg[..., -1]))
    return o.swapaxes(1, 2).reshape(t + pad, nv, dv)[:t], s


def split_plain(qkvz, ba, spec: BlockSpec):
    """(mixed, z, b, a) from projections that are plain concatenations:
    qkvz = [q | k | v | z], ba = [b | a]."""
    nv = spec.num_v_heads
    return (qkvz[:, :spec.conv_dim], qkvz[:, spec.conv_dim:], ba[:, :nv],
            ba[:, nv:])


def split_grouped(qkvz, ba, spec: BlockSpec):
    """(mixed [..., conv_dim] = q | k | v, z [..., nv dv], b, a [..., nv])
    from projections grouped by key head: key head g's columns of qkvz
    are [q_g (dk) | k_g (dk) | v (r dv) | z (r dv)] and of ba [b (r) | a
    (r)], r = nv / nk value heads of its own (Qwen3-Next's
    `fix_query_key_value_ordering`): value head g r + i is the i-th of
    key head g's."""
    nk, nv, dk, dv = spec.num_k_heads, spec.num_v_heads, spec.k_dim, \
        spec.v_dim
    r, lead = nv // nk, qkvz.shape[:-1]
    g = qkvz.reshape(lead + (nk, 2 * dk + 2 * r * dv))
    q, k = g[..., :dk], g[..., dk:2 * dk]
    v, z = g[..., 2 * dk:2 * dk + r * dv], g[..., 2 * dk + r * dv:]
    mixed = jnp.concatenate([y.reshape(lead + (-1,)) for y in (q, k, v)],
                            axis=-1)
    gba = ba.reshape(lead + (nk, 2 * r))
    return (mixed, z.reshape(lead + (nv * dv,)),
            gba[..., :r].reshape(lead + (nv,)),
            gba[..., r:].reshape(lead + (nv,)))


def gate_sigmoid(o, z, w, spec: BlockSpec):
    """The normed output o [T, nv, dv] float32 times (1 + w) gate_scale
    sigmoid(z) (z [T, nv dv])."""
    return o * (w.astype(_F32) + 1.0) * (spec.gate_scale * jax.nn.sigmoid(
        z.astype(_F32).reshape(o.shape)))


def gate_silu(o, z, w, spec: BlockSpec):
    """The normed output o [T, nv, dv] float32 times w silu(z) (z [T, nv
    dv]): a plain gain."""
    return o * w.astype(_F32) * jax.nn.silu(z.astype(_F32).reshape(o.shape))


def _add_mixer(x, out, lw, spec: BlockSpec):
    """x + a linear layer's output as the model's norms have it: through
    the post-attention norm (sandwich, latent hybrids) or as it is
    (pre-norm, GQA hybrids)."""
    if _full(spec) is ga:
        return (x.astype(_F32) + out.astype(_F32)).astype(x.dtype)
    return lb.sandwich_add(x, out, lb.gain(lw["ln_post_attn"], spec.full),
                           spec.eps)


def linear_attention(x, lw, spec: BlockSpec, attend):
    """The linear-attention sublayer: x [T, H] -> x1 [T, H]."""
    t = x.shape[0]
    nv, dv, eps = spec.num_v_heads, spec.v_dim, spec.eps
    h = lb.rms_norm(x, lb.gain(lw["ln_in"], spec.full), eps)
    with jax.named_scope("attn.linear.proj"):
        # float32 out: the recurrence and the output norm read these
        # projections, and the norm renormalises v - S^T k, a difference
        qkvz = jnp.dot(h, lw["qkvz"], preferred_element_type=_F32)
        ba = jnp.dot(h, lw["ba"], preferred_element_type=_F32)
    gqa = _full(spec) is ga
    mixed, z, b, a = (split_grouped if gqa else split_plain)(qkvz, ba, spec)
    with jax.named_scope("attn.linear"):
        o = attend(mixed, b, a, lw)                        # [T, nv, dv]
    with jax.named_scope("attn.linear.out"):
        o = o.astype(_F32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + spec.o_eps)
        o = (gate_silu if gqa else gate_sigmoid)(o, z, lw["o_norm"], spec)
        out = o.reshape(t, nv * dv).astype(x.dtype) @ lw["out"]
    return _add_mixer(x, out, lw, spec)


def block(x, lw, spec: BlockSpec, kind, attend, rope=None, valid=None):
    """One layer over a block of tokens x [T, H] (`latent_block.block`'s
    contract; `attend` the kind's: see the module's docstring)."""
    attn, ffn = kind
    mod = _full(spec)
    if attn == FULL:
        return mod.block(x, lw, spec.full, ffn, attend, rope=rope,
                         valid=valid)
    x1 = linear_attention(x, lw, spec, attend)
    return mod.ffn_sublayer(x1, lw, spec.full, ffn, valid)


def attend_linear_sequence(spec: BlockSpec, form=chunked):
    """`attend` of a linear layer for a whole sequence in hand (positions
    0..T-1, both states zero at its start)."""
    def attend(mixed, b, a, lw):
        x_ext = jnp.pad(mixed, ((spec.conv_kernel - 1, 0), (0, 0)))
        q, k, v, beta, g = delta_inputs(x_ext, b, a, lw, spec)
        s0 = jnp.zeros(spec.state_shape, _F32)
        if form is recurrent:
            return recurrent(s0, q, k, v, beta, g)[0]
        return chunked(s0, q, k, v, beta, g, spec.wy_chunk)[0]
    return attend


def forward_sequence(params, ids, spec: BlockSpec, form=chunked):
    """Logits [T, V] float32 of one whole sequence `ids` [T]: the plain
    forward pass (`form`: how the linear layers run their recurrence)."""
    x = params["embed"][ids]
    tables = tuple(jnp.asarray(y) for y in rope_for(spec, ids.shape[0]))
    for lw, kind in zip(params["layers"], spec.layer_types):
        attend = (attend_linear_sequence(spec, form) if kind[0] == LINEAR
                  else _full(spec).attend_sequence(spec.full))
        x, *_ = block(x, lw, spec, kind, attend, rope=tables)
    return head(x, params, spec)

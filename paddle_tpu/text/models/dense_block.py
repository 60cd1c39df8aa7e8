"""The dense decoder layer (LLaMA, GPT) as pure functions of arrays: ONE
definition of the block's mathematics, called by every dense program of
`text/generation.py` (the static engine), `inference/engine.py` (the
paged engine's stacked programs) and `inference/speculative.py` (the
draft's and the static verify's).

    llama   h = RMSNorm(x); q, k, v = rope(h Wq), rope(h Wk), h Wv
            x = x + attend(q, k, v) Wo
            x' = x + (silu(h2 Wgate) * h2 Wup) Wdown       h2 = RMSNorm(x)
    gpt     h = LN(x); q, k, v = the thirds of h Wqkv
            x = x + attend(q, k, v) Wo
            x' = x + gelu(h2 Wfc_in) Wfc_out               h2 = LN(x)

What differs between the callers is where the keys and values live — the
sequence in hand, a dense `[B, T, H_kv, D]` cache, a paged pool — and
that is the `attend` argument: `attend(q [..., nh, hd], k, v [..., nkv,
hd]) -> [..., nh, hd]`. It writes the new K/V into whatever it holds and
keeps the new cache for its caller (a local the layer scan's body
returns): the block never sees a cache. `parallel_block.py` is this
module's peer for the third architecture.

The masked softmax attention composed in XLA is here once a SHAPE
(`attend_causal`, `attend_one`, `attend_many`): a program's contraction,
ranks and dtypes are its site's, and the compiled programs depend on
them (the rank-3 seq-1 scores are what `tools/graft_lint.py`'s D4 decode
anchor matches; chunk and verify promise rank 4).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class _GenSpec:
    """Static configuration that keys the compiled dense programs."""
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    rms_eps: float
    max_new_tokens: int
    do_sample: bool
    top_k: int
    top_p: float
    temperature: float
    eos_token_id: int
    tie_embeddings: bool
    arch: str = "llama"  # "llama" (RMSNorm+RoPE+SwiGLU) | "gpt" (LN+wpe+GELU)
    # "none" | "int8" | "int4": weight-only per-output-channel quantization
    # on the layer matmuls + lm_head (≙ weight_only_linear's serving role) —
    # decode is HBM-bandwidth-bound, so shrinking weight bytes is the win;
    # activations stay bf16. int8 stores [K, N] int8 (XLA fuses the
    # int8->bf16 convert into the matmul tiles); int4 stores TRUE packed
    # [ceil(K/2), N] nibbles (ops/quantized.py) so the packed bytes are the
    # only HBM weight traffic — the Pallas fused dequant-matmul unpacks in
    # VMEM on TPU, the XLA take-bits composition everywhere else
    weight_quant: str = "none"


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) \
        * w


def _layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - m), axis=-1, keepdims=True)
    return ((xf - m) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def _rope(x, cos, sin):
    # x [..., D]; cos/sin broadcastable [..., D]
    x1, x2 = jnp.split(x, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


def _repeat_kv(x, rep, axis):
    return x if rep == 1 else jnp.repeat(x, rep, axis=axis)


def _mm(x, w):
    """x @ w where w is either a dense array or a weight-only pair
    (int8 [K,N] or packed int4 [ceil(K/2),N], scale f32 [N]) — the pair
    shape disambiguates, see ops/quantized.quant_matmul (the single shared
    dequant-matmul behind generation, weight_only_linear and the paged
    engine)."""
    if isinstance(w, tuple):
        from ...ops.quantized import quant_matmul

        return quant_matmul(x, w[0], w[1])
    return x @ w


def _logits(x, params, spec: _GenSpec):
    """x [B, H] -> [B, V]."""
    if spec.arch == "gpt":
        x = _layer_norm(x, params["final_ln"], params["final_ln_b"],
                        spec.rms_eps)
    else:
        x = _rms_norm(x, params["final_ln"], spec.rms_eps)
    if spec.tie_embeddings:
        return x.astype(jnp.float32) @ params["embed"].T.astype(jnp.float32)
    head = params["lm_head"]
    if isinstance(head, tuple):
        # f32 activations keep the historical logits numerics: for int8
        # this is exactly (x_f32 @ w8_f32) * ws_f32; int4 unpacks first
        return _mm(x.astype(jnp.float32), head)
    return x.astype(jnp.float32) @ head.astype(jnp.float32)


def num_positions(params, spec: _GenSpec) -> int:
    """Rows of the position table (`wpe`, or the rope tables)."""
    return (params["wpe"] if spec.arch == "gpt"
            else params["rope_cos"]).shape[0]


def embed(params, ids, pos, spec: _GenSpec):
    """(x, rope): the tokens' rows of the embedding, with their learned
    position rows added (gpt; rope is None) or beside the rope tables'
    rows (cos, sin) [..., 1, D] at their positions, one for all heads
    (llama). `pos` indexes the position table and broadcasts against
    `ids`: an int array, a scalar where all rows stand at one position, a
    slice for sequences in hand. The caller keeps it inside the table
    (`num_positions`)."""
    x = params["embed"][ids]
    if spec.arch == "gpt":
        return x + params["wpe"][pos], None
    return x, tuple(params[t][pos][..., None, :]
                    for t in ("rope_cos", "rope_sin"))


def block(x, lw, spec: _GenSpec, attend, rope=None):
    """One layer over x [..., H], any leading dimensions (the matmuls see
    them as rows). `lw`: the layer's arrays, named as `_extract_llama` /
    `_extract_gpt` (text/generation.py) name them, each a plain array or
    a weight-only pair (`_mm`); `rope`: `embed`'s (cos, sin) at the
    tokens' positions (llama). Returns x'."""
    h = x.shape[-1]
    nh, nkv, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    gpt = spec.arch == "gpt"
    if gpt:
        hn = _layer_norm(x, lw["ln1_w"], lw["ln1_b"], spec.rms_eps)
        qkv = _mm(hn.reshape(-1, h), lw["qkv"]).reshape(
            *x.shape[:-1], 3, nh, hd)
        q, k, v = (qkv[..., i, :, :] for i in range(3))
    else:
        hn = _rms_norm(x, lw["input_ln"], spec.rms_eps).reshape(-1, h)
        q = _mm(hn, lw["q"]).reshape(*x.shape[:-1], nh, hd)
        k = _mm(hn, lw["k"]).reshape(*x.shape[:-1], nkv, hd)
        v = _mm(hn, lw["v"]).reshape(*x.shape[:-1], nkv, hd)
        q = _rope(q, *rope)
        k = _rope(k, *rope)
    out = attend(q, k, v).reshape(-1, nh * hd)
    x = x + _mm(out, lw["o"]).reshape(x.shape)
    if gpt:
        hn = _layer_norm(x, lw["ln2_w"], lw["ln2_b"], spec.rms_eps)
        mlp = _mm(jax.nn.gelu(_mm(hn.reshape(-1, h), lw["fc_in"]),
                              approximate=False), lw["fc_out"])
    else:
        hn = _rms_norm(x, lw["post_ln"], spec.rms_eps).reshape(-1, h)
        mlp = _mm(jax.nn.silu(_mm(hn, lw["gate"])) * _mm(hn, lw["up"]),
                  lw["down"])
    return x + mlp.reshape(x.shape)


# ------------------------------------------- masked attention, by shape
# q has nh heads, k and v nkv: each KV head serves its group of nh // nkv
# query heads (heads are the axis before the last everywhere).

def _for_heads(q, k, v):
    rep = q.shape[-2] // k.shape[-2]
    return _repeat_kv(k, rep, k.ndim - 2), _repeat_kv(v, rep, v.ndim - 2)


def _masked_softmax(scores, mask, dtype):
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    return jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)


def attend_causal(q, k, v):
    """Sequences in hand: q [B, S, nh, hd], k, v [B, S, nkv, hd], key j
    visible from query i iff j <= i."""
    s = q.shape[1]
    k, v = _for_heads(q, k, v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = _masked_softmax(scores, mask[None, None], q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attend_one(q, k, v, valid):
    """Seq-1 queries q [B, nh, hd] over k, v [B, T, nkv, hd] where `valid`
    ([T], or [B, T] a row) is set. Scores stay rank 3 [B, nh, T]."""
    k, v = _for_heads(q, k, v)
    scores = jnp.einsum("bhd,bthd->bht", q, k) / math.sqrt(q.shape[-1])
    probs = _masked_softmax(scores, valid[..., None, :], q.dtype)
    return jnp.einsum("bht,bthd->bhd", probs, v)


def attend_many(q, k, v, mask):
    """C queries q [..., C, nh, hd] over k, v [..., T, nkv, hd] under mask
    [..., C, T], with or without a leading batch dimension. Scores are
    held rank 4 [B or 1, nh, C, T] either way: a prefill composition, not
    the rank-3 seq-1 decode shape."""
    k, v = _for_heads(q, k, v)
    scores = jnp.einsum("...chd,...thd->...hct", q, k) \
        * (1.0 / math.sqrt(q.shape[-1]))
    probs = _masked_softmax(scores.reshape(-1, *scores.shape[-3:]),
                            mask[..., None, :, :], q.dtype)
    return jnp.einsum("...hct,...thd->...chd", probs.reshape(scores.shape),
                      v)


def forward_sequence(params, ids, spec: _GenSpec):
    """Every layer over whole sequences ids [B, S] from position 0, each
    attending over the sequence in hand (the Pallas flash kernel on TPU
    at S >= 128, else `attend_causal`). Returns (x [B, S, H], ks, vs
    [L, B, S, H_kv, D]): the keys and values are what a prefill program
    puts into its cache."""
    from ...ops.pallas_attention import flash_attention_raw

    s = ids.shape[1]
    x, rope = embed(params, ids, slice(0, s), spec)

    def layer(xc, lw):
        kv = []

        def attend(q, k, v):
            kv.append((k, v))
            if jax.default_backend() == "tpu" and s >= 128:
                kr, vr = _for_heads(q, k, v)
                out = flash_attention_raw(
                    jnp.swapaxes(q, 1, 2), jnp.swapaxes(kr, 1, 2),
                    jnp.swapaxes(vr, 1, 2), causal=True)
                return jnp.swapaxes(out, 1, 2)
            return attend_causal(q, k, v)

        return block(xc, lw, spec, attend, rope), kv[0]

    x, (ks, vs) = jax.lax.scan(layer, x, params["layers"])
    return x, ks, vs

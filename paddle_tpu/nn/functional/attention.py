"""Attention kernels (≙ phi/kernels/fusion flash attention,
nn/functional/flash_attention.py:358-1139).

Layout convention follows paddle flash_attention: [batch, seqlen, heads, head_dim].
Two paths:
  - XLA path: jnp composition; XLA's TPU fusion handles the softmax(QK^T)V chain.
  - Pallas path: tiled flash kernel (paddle_tpu/ops/pallas_attention.py) used on
    real TPU for long sequences.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ...core.dispatch import op_call
from ...core.rng import next_key
from ...core.tensor import Tensor


def _xla_sdpa(q, k, v, mask, dropout_p, is_causal, dropout_key):
    # q,k,v: [B, S, H, D] -> compute in [B, H, S, D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    d = qt.shape[-1]
    # GQA: broadcast kv heads if fewer than q heads
    if kt.shape[1] != qt.shape[1]:
        rep = qt.shape[1] // kt.shape[1]
        kt = jnp.repeat(kt, rep, axis=1)
        vt = jnp.repeat(vt, rep, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / math.sqrt(d)
    if is_causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        else:
            scores = scores + mask
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(qt.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(probs.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)  # back to [B, S, H, D]


# force the Pallas flash path regardless of platform (tests set this to run
# the kernel in interpreter mode on CPU); None = auto (TPU + long seq)
FORCE_PALLAS: bool | None = None


def _pallas_available() -> bool:
    try:
        from ...ops import pallas_attention

        return pallas_attention.pltpu is not None
    except ImportError:
        return False


def _use_pallas(q):
    if FORCE_PALLAS is not None:
        return FORCE_PALLAS
    from ...ops._pallas_common import auto_partitioned

    return (jax.default_backend() == "tpu" and q.shape[1] >= 128
            and _pallas_available() and not auto_partitioned())


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True):
    dk = next_key() if (dropout_p > 0.0 and training) else None
    p = dropout_p if training else 0.0

    # context-parallel routing: inside a partitioned step whose
    # MeshConfig has sep > 1, the seq-sharded exchange rides the
    # ring/ulysses kernels (distributed/partitioner). The hook is one
    # list-peek when no partitioned step is active.
    from ...distributed.partitioner.api import _ACTIVE as _part_active

    if _part_active:
        from ...distributed.partitioner.api import maybe_sep_attention

        out = maybe_sep_attention(query, key, value, is_causal,
                                  attn_mask=attn_mask, dropout_p=p)
        if out is not None:
            return out

    if attn_mask is None and p == 0.0 and _use_pallas(query):
        from ...ops.pallas_attention import flash_attention_op

        return flash_attention_op(query, key, value, is_causal)

    def f(q, k, v, *m):
        return _xla_sdpa(q, k, v, m[0] if m else None, p, is_causal, dk)

    args = [query, key, value] + ([attn_mask] if attn_mask is not None else [])
    return op_call(f, *args, name="scaled_dot_product_attention", n_diff=3)

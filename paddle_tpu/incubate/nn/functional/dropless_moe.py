"""A dropless expert layer, told which experts it holds — pure functions
of arrays, called inside compiled programs.

The layer of "sigmoid top-k" mixture-of-experts decoders with shared
experts, as one chip of an expert-parallel deployment computes it:

    s      = sigmoid(h Wr)  in R^E            the router, in float32, over ALL E experts
    T      = the k largest of s ;  g_e = c * s_e / sum_{e' in T} s_e'     c: `routed_scale`, 1 unless the model says
    routed = sum_{e in T, e held here} g_e * Wd_e( silu(Wg_e h) * (Wu_e h) )
    shared = (1/S) * sum_j Wd'_j( silu(Wg'_j h) * (Wu'_j h) )

A model may clamp every SwiGLU (`swiglu_limit` L > 0: silu(min(Wg h, L))
* clip(Wu h, -L, L)); 0 leaves the products as written above.

No capacity factor and no token dropped: every pick that lands on a held
expert is computed. The weights `g_e` are normalised over all k picks,
wherever they land, so the parts that the ranks of a deployment compute
add up to the whole layer (the all-to-all and the sum across ranks are the
deployment's; on one chip the layer runs without them).

How the held experts are computed: a scan over them, each applied to the
whole block of tokens and weighted by its gate (zero where it was not
picked). Exact, and each expert's weights are read once a call; it costs
`n_local x tokens` rows of matmul where the picks need `~k x n_local / E`
of that. A grouped kernel over tokens sorted by expert is the optimisation
this leaves open (PERF.md).

`incubate/distributed/models/moe/moe_layer.py` is the GShard layer
(capacity, dropped tokens, biased GELU experts): another mechanism.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def route_sigmoid_topk(h, w_router, top_k: int, routed_scale: float = 1.0):
    """(idx [T, k] int32, gates [T, k] float32): the k highest sigmoid
    scores of each token over all experts, normalised over the k and
    multiplied by `routed_scale`. The router runs in float32 at full
    matmul precision: a pick that flips on rounding sends a token to
    another chip."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            h.astype(_F32), w_router.astype(_F32),
            precision=jax.lax.Precision.HIGHEST))
        top, idx = jax.lax.top_k(scores, top_k)
        gates = top / jnp.sum(top, axis=-1, keepdims=True)
        if routed_scale != 1.0:
            gates = gates * jnp.float32(routed_scale)
        return idx.astype(jnp.int32), gates


def local_gates(idx, gates, first_expert: int, num_local: int):
    """[T, num_local] float32: column e holds the token's weight for held
    expert `first_expert + e`, 0 where the token did not pick it."""
    held = first_expert + jnp.arange(num_local, dtype=jnp.int32)
    hit = (idx[:, :, None] == held[None, None, :]).astype(_F32)
    return jnp.einsum("tk,tke->te", gates, hit)


def local_load(gate_mat, valid=None):
    """(picks, max_load) int32 scalars: how many (token, held expert)
    picks the block made, and the most any one held expert got. `valid`
    [T] bool leaves padded rows out."""
    picked = gate_mat > 0
    if valid is not None:
        picked = picked & valid[:, None]
    per_expert = jnp.sum(picked.astype(jnp.int32), axis=0)
    return jnp.sum(per_expert), jnp.max(per_expert)


def clamp_gate(y, limit):
    """A SwiGLU's gate projection capped at `limit` (0: as it is)."""
    return jnp.minimum(y, limit) if limit else y


def clamp_up(y, limit):
    """A SwiGLU's up projection clipped to [-limit, limit] (0: as it is)."""
    return jnp.clip(y, -limit, limit) if limit else y


def local_experts(h, gate_mat, w_gate, w_up, w_down, limit=0.0):
    """sum_e gate_mat[:, e] * Wd_e(silu(Wg_e h) * (Wu_e h)) over the held
    experts, float32 [T, H]. Weights stacked [n, H, F], [n, H, F],
    [n, F, H]; `limit`: the SwiGLU clamp."""
    with jax.named_scope("moe.experts"):
        def one(acc, xs):
            wg, wu, wd, g = xs
            y = (jax.nn.silu(clamp_gate(h @ wg, limit))
                 * clamp_up(h @ wu, limit)) @ wd
            return acc + g[:, None] * y.astype(_F32), None

        acc0 = jnp.zeros((h.shape[0], w_down.shape[-1]), _F32)
        acc, _ = jax.lax.scan(one, acc0, (w_gate, w_up, w_down, gate_mat.T))
        return acc


def shared_experts_mean(h, w_gate, w_up, w_down, num_shared: int,
                        limit=0.0):
    """The mean of `num_shared` SwiGLU experts of equal width, stored side
    by side ([H, S*F], [H, S*F], [S*F, H]; expert j owns columns
    [j*F, (j+1)*F)): one wide SwiGLU gives their sum (the clamp acts on
    each column alone, so it holds for the sum too)."""
    with jax.named_scope("moe.shared"):
        y = (jax.nn.silu(clamp_gate(h @ w_gate, limit))
             * clamp_up(h @ w_up, limit)) @ w_down
        return y / num_shared


def moe_forward(h, lw, *, top_k, first_expert, num_local, num_shared,
                valid=None, h_router=None, routed_scale=1.0,
                swiglu_limit=0.0):
    """(routed + shared [T, H] float32, picks, max_load) for one layer's
    weights `lw` (keys `router`, `experts_gate|up|down`,
    `shared_gate|up|down`). `h_router`: the same activations before they
    were rounded to the matmuls' dtype, for the float32 router;
    `routed_scale`: the model's factor on the normalised gates (the
    shared experts are not scaled); `swiglu_limit`: the SwiGLU clamp of
    every expert, routed and shared."""
    idx, gates = route_sigmoid_topk(h if h_router is None else h_router,
                                    lw["router"], top_k, routed_scale)
    gate_mat = local_gates(idx, gates, first_expert, num_local)
    picks, max_load = local_load(gate_mat, valid)
    routed = local_experts(h, gate_mat, lw["experts_gate"],
                           lw["experts_up"], lw["experts_down"],
                           swiglu_limit)
    shared = shared_experts_mean(h, lw["shared_gate"], lw["shared_up"],
                                 lw["shared_down"], num_shared, swiglu_limit)
    return routed + shared.astype(_F32), picks, max_load

"""A dropless expert layer, told which experts it holds — pure functions
of arrays, called inside compiled programs.

The layer of "sigmoid top-k" mixture-of-experts decoders with shared
experts, as one chip of an expert-parallel deployment computes it:

    s      = sigmoid(h Wr)  in R^E            the router, in float32, over ALL E experts
    T      = the k largest of s ;  g_e = c * s_e / sum_{e' in T} s_e'     c: `routed_scale`, 1 unless the model says
    routed = sum_{e in T, e held here} g_e * Wd_e( silu(Wg_e h) * (Wu_e h) )
    shared = (1/S) * sum_j Wd'_j( silu(Wg'_j h) * (Wu'_j h) )

A model may clamp every SwiGLU (`swiglu_limit` L > 0: silu(min(Wg h, L))
* clip(Wu h, -L, L)); 0 leaves the products as written above. Two static
options of `moe_forward` give the "softmax top-k" layer with a gated
shared expert (Qwen3-Next):

    p      = softmax(h Wr) over ALL E experts, in float32
    T      = the k largest of p ;  g_e = p_e / sum_{e' in T} p_e'         `router="softmax"`
    shared = sigmoid(h w_sg) * Wd'( silu(Wg' h) * (Wu' h) )             `shared_gate=True`: one scalar a token

No capacity factor and no token dropped: every pick that lands on a held
expert is computed. The weights `g_e` are normalised over all k picks,
wherever they land, so the parts that the ranks of a deployment compute
add up to the whole layer (the all-to-all and the sum across ranks are the
deployment's; on one chip the layer runs without them).

How the held experts are computed: on the chip, one grouped kernel
(`ops/pallas_grouped_experts.py`) over the picks sorted by expert. It
fetches only the experts some live row picked, each once a call, and
computes only the picked rows, up to a tile: a decode tick reads the few
experts its rows picked, a chunk computes `~k x n_local / E` rows a token
where the scan computes `n_local`. It runs through ONE module-level
`jax.jit` function, so a program's expert layers trace it once a process
and lower it once a program (one private function, called a layer).
Elsewhere, and where `grouped_gate_reason` names a reason, a scan over the
held experts, each applied to the whole block of tokens and weighted by
its gate (zero where it was not picked): the kernel's oracle.

`incubate/distributed/models/moe/moe_layer.py` is the GShard layer
(capacity, dropped tokens, biased GELU experts): another mechanism.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ....ops import pallas_grouped_experts as pge
from ....ops.pallas_grouped_experts import clamp_gate, clamp_up

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


def route_softmax_topk(h, w_router, top_k: int):
    """(idx [T, k] int32, gates [T, k] float32): the k most probable
    experts of each token under a softmax over all experts, their
    probabilities normalised over the k (`norm_topk_prob`). In float32 at
    full matmul precision, as `route_sigmoid_topk`."""
    with jax.named_scope("moe.softmax_router"):
        probs = jax.nn.softmax(jnp.dot(
            h.astype(_F32), w_router.astype(_F32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        top, idx = jax.lax.top_k(probs, top_k)
        gates = top / jnp.sum(top, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), gates


def local_gates(idx, gates, first_expert: int, num_local: int):
    """[T, num_local] float32: column e holds the token's weight for held
    expert `first_expert + e`, 0 where the token did not pick it."""
    held = first_expert + jnp.arange(num_local, dtype=jnp.int32)
    hit = (idx[:, :, None] == held[None, None, :]).astype(_F32)
    return jnp.einsum("tk,tke->te", gates, hit)


def local_load(gate_mat, valid=None):
    """(picks, max_load, read) int32 scalars: how many (token, held
    expert) picks the block made, the most any one held expert got, and
    how many held experts got one (what the grouped kernel reads).
    `valid` [T] bool leaves padded rows out."""
    picked = gate_mat > 0
    if valid is not None:
        picked = picked & valid[:, None]
    per_expert = jnp.sum(picked.astype(jnp.int32), axis=0)
    return (jnp.sum(per_expert), jnp.max(per_expert),
            jnp.sum((per_expert > 0).astype(jnp.int32)))


def local_experts(h, gate_mat, w_gate, w_up, w_down, limit=0.0,
                  valid=None):
    """sum_e gate_mat[:, e] * Wd_e(silu(Wg_e h) * (Wu_e h)) over the held
    experts, float32 [T, H]. Weights stacked [n, H, F], [n, H, F],
    [n, F, H]; `limit`: the SwiGLU clamp; `valid` [T] bool: rows the
    grouped kernel leaves out (0 there; the scan computes every row)."""
    with jax.named_scope("moe.experts"):
        if pge.use_grouped_experts(h, w_gate):
            return pge.grouped_experts(h, gate_mat, w_gate, w_up, w_down,
                                       valid, limit)

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


def shared_expert_gate(h, w_gate):
    """sigmoid(h w_sg) [T, 1] float32: the shared expert's weight a token,
    at full matmul precision."""
    with jax.named_scope("moe.shared_gate"):
        return jax.nn.sigmoid(jnp.dot(
            h.astype(_F32), w_gate.astype(_F32),
            precision=jax.lax.Precision.HIGHEST))


def moe_forward(h, lw, *, top_k, first_expert, num_local, num_shared,
                valid=None, h_router=None, routed_scale=1.0,
                swiglu_limit=0.0, router="sigmoid", shared_gate=False):
    """(routed + shared [T, H] float32, picks, max_load, read) for one
    layer's weights `lw` (keys `router`, `experts_gate|up|down`,
    `shared_gate|up|down`). `h_router`: the same activations before they
    were rounded to the matmuls' dtype, for the float32 router;
    `routed_scale`: the model's factor on the normalised gates (the
    shared experts are not scaled); `swiglu_limit`: the SwiGLU clamp of
    every expert, routed and shared; `router`: "sigmoid" or "softmax"
    (`route_softmax_topk`, which takes no `routed_scale`); `shared_gate`:
    the shared expert scaled by `shared_expert_gate` (weights
    `shared_expert_gate` [H, 1]). `read`: the held experts whose weights
    the layer read (every one where the scan runs)."""
    h_r = h if h_router is None else h_router
    if router == "softmax":
        idx, gates = route_softmax_topk(h_r, lw["router"], top_k)
    else:
        idx, gates = route_sigmoid_topk(h_r, lw["router"], top_k,
                                        routed_scale)
    gate_mat = local_gates(idx, gates, first_expert, num_local)
    picks, max_load, read = local_load(gate_mat, valid)
    if not pge.use_grouped_experts(h, lw["experts_gate"]):
        read = jnp.int32(num_local)         # the scan reads every one
    routed = local_experts(h, gate_mat, lw["experts_gate"],
                           lw["experts_up"], lw["experts_down"],
                           swiglu_limit, valid)
    shared = shared_experts_mean(h, lw["shared_gate"], lw["shared_up"],
                                 lw["shared_down"], num_shared, swiglu_limit)
    if shared_gate:
        return (routed + shared.astype(_F32)
                * shared_expert_gate(h_r, lw["shared_expert_gate"]),
                picks, max_load, read)
    return routed + shared.astype(_F32), picks, max_load, read

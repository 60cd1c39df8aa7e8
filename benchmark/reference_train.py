"""The plain float32 trainer every train cell is held against: the
family's `reference_grads` (forward, loss, backward, a leaf at a time) and
AdamW written out, followed for TWO steps from the seed's weights.

Two steps need no optimizer state beyond the first gradient: with zero
moments, m1 = (1-b1) g1 and v1 = (1-b2) g1^2, so step 2's moments are
functions of g1 and g2. The reference therefore holds the parameters, g1
and one layer's gradients — it fits beside nothing else on the chip.
"""
from __future__ import annotations

import numpy as np


HEAD_ROWS = 2048


def blocked_head(rows_loss, x, ids, head_w: tuple):
    """The mean token loss over all rows of `x` [B, S, H] against `ids`
    [B, S], with its gradients, `HEAD_ROWS` rows at a time so that the
    float32 logits of a block are all that exists: `rows_loss(x_rows,
    id_rows, head_w)` is the SUM of a block's losses. Returns (loss, dx
    like x, gradients like head_w)."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0] * x.shape[1]
    xf, idf = x.reshape(n, -1), ids.reshape(n)
    vg = jax.jit(jax.value_and_grad(rows_loss, argnums=(0, 2)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    total, dxs, acc = 0.0, [], None
    for i in range(0, n, HEAD_ROWS):
        part, (dx, g) = vg(xf[i:i + HEAD_ROWS], idf[i:i + HEAD_ROWS], head_w)
        total = total + part
        dxs.append(dx)
        acc = g if acc is None else add(acc, g)
    scale = jax.jit(lambda t: jax.tree.map(lambda a: a / n, t))
    return (float(total) / n, scale(jnp.concatenate(dxs)).reshape(x.shape),
            scale(acc))


def token_losses(logits, id_rows):
    """Sum over rows of logsumexp(logits) - logits[target]."""
    import jax
    import jax.numpy as jnp

    picked = jnp.take_along_axis(logits, id_rows[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)


def run(family, cfg: dict, layers: int, weights: dict, batches: list,
        hp: dict, precision: str = "f32", half_batch: bool = False) -> dict:
    """{"losses": [l1, l2], "grad_norm": {leaf: |g1|},
    "delta_norm": {leaf: |p2 - p0|}} of the reference from `weights`
    (the seed's, in the served type) over the first two `batches`.
    `precision` other than "f32" is the control; `half_batch` the fault
    that leaves half of each batch out and takes the mean over the rest."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    lr, b1, b2 = hp["learning_rate"], hp["beta1"], hp["beta2"]
    eps, wd = hp["epsilon"], hp["weight_decay"]
    params = {k: v.astype(f32) for k, v in weights.items()}
    g1, grad_norm, delta_norm = {}, {}, {}
    norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))))

    @jax.jit
    def step1(p, g):
        return p * (1.0 - lr * wd) - lr * g / (jnp.abs(g) + eps)

    @jax.jit
    def step2(p, ga, gb, p0):
        m = b1 * (1 - b1) * ga + (1 - b1) * gb
        v = b2 * (1 - b2) * ga * ga + (1 - b2) * gb * gb
        mhat, vhat = m / (1 - b1 ** 2), v / (1 - b2 ** 2)
        new = p * (1.0 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
        return new, jnp.sqrt(jnp.sum(jnp.square(new - p0.astype(f32))))

    def feed(ids):
        ids = np.asarray(ids)
        return ids[: max(1, ids.shape[0] // 2)] if half_batch else ids

    def first(name, g):
        grad_norm[name] = float(norm(g))
        g1[name] = g

    def second(name, g):
        params[name], d = step2(params[name], g1.pop(name), g,
                                weights[name])
        delta_norm[name] = float(d)

    losses = [family.reference_grads(cfg, layers, params, feed(batches[0]),
                                     first, precision)]
    for name in list(params):
        params[name] = step1(params[name], g1[name])
    losses.append(family.reference_grads(cfg, layers, params,
                                         feed(batches[1]), second, precision))
    return {"losses": losses, "grad_norm": grad_norm,
            "delta_norm": delta_norm}


def gaps(prog: dict, ref: dict, worst: dict | None = None) -> dict:
    """The numbers a train cell compares, program against reference:
    each step's loss (relative), and by the worst leaf the gap between
    the two NORMS (not the norm of a difference) of the first gradient
    and of the parameters' change, against the reference's norm of that
    leaf or of the median leaf, whichever is larger. Leaves whose
    reference gradient is under a thousandth of the median leaf's move
    under Adam by round-off alone and are left out of the change."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss_step{i}_rel"] = abs(a - b) / abs(b)
    g_med = float(np.median(list(ref["grad_norm"].values())))
    d_med = float(np.median(list(ref["delta_norm"].values())))
    g_gap = {k: abs(prog["grad_norm"][k] - r) / max(r, g_med)
             for k, r in ref["grad_norm"].items()}
    d_gap = {k: abs(prog["delta_norm"][k] - r) / max(r, d_med)
             for k, r in ref["delta_norm"].items()
             if ref["grad_norm"][k] >= 1e-3 * g_med}
    out["grad_norm_worst_leaf"] = max(g_gap.values())
    out["delta_norm_worst_leaf"] = max(d_gap.values())
    if worst is not None:       # which leaves, for the progress line
        worst["grad_norm"] = max(g_gap, key=g_gap.get)
        worst["delta_norm"] = max(d_gap, key=d_gap.get)
    return out

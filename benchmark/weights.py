"""Weights made on the device from `--seed`, in one jitted call, in the
type they are served or trained in. The program's model is given them
(`assign`), and the reference is given the same dictionary made again: it
takes nothing the program has made."""
from __future__ import annotations

INIT_STD = 0.02


def make(spec: list, seed: int, dtype="bfloat16") -> dict:
    """{name: array} for a family's `weight_spec`, each leaf from its own
    fold of the seed's key."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    shapes = tuple((n, tuple(s), k) for n, s, k in spec)

    def gen(key):
        out = {}
        for i, (name, shape, kind) in enumerate(shapes):
            if kind == "ones":
                out[name] = jnp.ones(shape, dt)
            elif kind == "zeros":
                out[name] = jnp.zeros(shape, dt)
            else:
                out[name] = (INIT_STD * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)).astype(dt)
        return out

    # --seed may pass 2**31: fold its two halves into one key. The "rbg"
    # generator: threefry takes tens of seconds for 2 B normals on a v5e
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
                             seed >> 31)
    return jax.jit(gen)(key)


def assign(model, weights: dict) -> int:
    """Put `weights` into the program's model, leaf for leaf; every
    parameter must be given one of its own shape. Returns the count."""
    n = 0
    for name, p in model.named_parameters():
        w = weights[name]
        if tuple(p.shape) != tuple(w.shape):
            raise ValueError(f"{name}: model {tuple(p.shape)} vs made "
                             f"{tuple(w.shape)}")
        p._data = w
        n += int(w.size)
    extra = set(weights) - {n_ for n_, _ in model.named_parameters()}
    if extra:
        raise ValueError(f"weights the model has no parameter for: {extra}")
    return n

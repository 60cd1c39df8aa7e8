"""Global RNG state with trace-aware key threading.

Reference parity: paddle.seed + per-device generators
(python/paddle/framework/random.py). TPU-first: state is a counter-free jax
PRNG key held in a Tensor so that `to_static` capture machinery threads it
through compiled programs automatically (each traced step consumes and
rewrites the key — no stale-randomness, no recompilation).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .dispatch import current_trace
from .tensor import Tensor

_key_tensor: Tensor | None = None
#: keys handed out since import (`lazy_init.defer` counts an
#: initializer's draws with it)
_draws = 0


def seed(value: int):
    global _key_tensor
    _key_tensor = Tensor(jax.random.PRNGKey(value), _internal=True)
    return _key_tensor


def _state() -> Tensor:
    global _key_tensor
    if _key_tensor is None:
        seed(0)
    return _key_tensor


def next_key():
    """Split the global key; returns a raw jax key for immediate consumption."""
    global _draws
    _draws += 1
    kt = _state()
    tr = current_trace()
    if tr is not None:
        tr.on_read(kt)
        tr.on_mutate(kt)
    new, sub = jax.random.split(kt._data)
    kt._data = new
    return sub


def draw_count() -> int:
    return _draws


def get_rng_state():
    return [_state().numpy()]


def set_rng_state(state):
    global _key_tensor
    _key_tensor = Tensor(jnp.asarray(state[0]), _internal=True)

"""What a parameter made under ``paddle.LazyGuard`` holds in place of a
buffer: its shape, its dtype, its initializer, and the state the global
RNG had when the parameter was created. Nothing is allocated until the
parameter's data is first read (`Parameter._data`) or assigned; a read
runs the initializer from the recorded RNG state, so the values are those
an eager build of the same seed gives.
"""
from __future__ import annotations

import numpy as np

#: how many `LazyGuard` blocks are open (they nest)
_depth = 0


def active() -> bool:
    return _depth > 0


def enter() -> None:
    global _depth
    _depth += 1


def leave() -> None:
    global _depth
    _depth -= 1


class LazyInit:
    """A deferred initialisation. Quacks like an array for `shape`,
    `dtype` and `ndim`, which is all a parameter is asked before it has
    data."""

    __slots__ = ("shape", "dtype", "init", "rng_state")

    def __init__(self, shape, dtype, init, rng_state):
        self.shape = tuple(int(s) for s in shape)
        self.dtype = np.dtype(dtype)
        self.init = init
        #: the global key as it stood before this parameter's draws (host)
        self.rng_state = rng_state

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def materialize(self):
        """The initializer's array, drawn from the recorded RNG state;
        the global state is left as it was found."""
        import jax.numpy as jnp

        from . import rng
        from .dispatch import no_grad

        kt = rng._state()
        now = kt._data
        kt._data = jnp.asarray(self.rng_state)
        try:
            with no_grad():
                return self.init._generate(self.shape, self.dtype)
        finally:
            kt._data = now


def defer(shape, dtype, init) -> LazyInit:
    """Record `init` for a parameter of `shape`/`dtype` and advance the
    global RNG by as many draws as the initializer makes, without making
    the array (the draws are counted under `jax.eval_shape`)."""
    import jax

    from . import rng

    kt = rng._state()
    before = kt._data
    n0 = rng.draw_count()
    try:
        jax.eval_shape(lambda: init._generate(tuple(shape), np.dtype(dtype)))
    finally:
        kt._data = before               # the abstract run left tracers
    draws = rng.draw_count() - n0
    lazy = LazyInit(shape, dtype, init, np.asarray(before))
    for _ in range(draws):
        rng.next_key()
    return lazy

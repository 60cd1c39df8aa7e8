"""Shared plumbing for the Pallas TPU kernel modules
(pallas_attention.py, pallas_norm.py) — ONE copy of the subtle
platform/x64 rules so the sibling kernels can never drift apart.

paddle_tpu enables jax x64 globally, and Mosaic cannot legalize stray
i64/f64 values on real TPUs — so real-TPU traces run with x64 OFF. But
toggling x64 INSIDE an outer x64 jit trace desynchronizes jnp's internal
jitted helpers on CPU (jnp.pad's callee traced for i32 shape scalars while
the caller passes i64 — the seed's sdpa failure, round-8 triage), so
interpret-mode traces keep the caller's x64 setting.
"""
from __future__ import annotations

import contextlib

import jax
from jax.experimental.pallas import tpu as pltpu  # noqa: F401 — re-exported


def interpret() -> bool:
    """True off-TPU: kernels run in the Pallas interpreter (CPU tests)."""
    return jax.default_backend() != "tpu"


def x64_guard():
    """x64-off context for REAL-TPU traces only (see module docstring)."""
    return contextlib.nullcontext() if interpret() else jax.enable_x64(False)


def auto_partitioned() -> bool:
    """True while a `partition()` step over more than one device is
    traced or run. GSPMD cannot split a Mosaic kernel — the chip's
    compiler refuses the program with "Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map" — so
    the routers keep such a step on the XLA compositions, which GSPMD can
    partition. (The sep-axis ring/ulysses attention calls its kernel
    INSIDE a shard_map and is not affected.)"""
    from ..distributed.partitioner.api import active_config

    ctx = active_config()
    return ctx is not None and ctx[1].size > 1


def ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m

"""Shared helpers for the Pallas TPU kernels."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["idx32", "on_tpu"]


def on_tpu():
    """Whether the default backend is a TPU: what every kernel's
    ``interpret=None`` default and every ``impl="auto"`` dispatcher
    consults.  Call sites look it up through the module
    (``pallas_util.on_tpu()``), so the TPU-lowering gates that run on
    CPU patch this one name and reach every kernel."""
    return jax.default_backend() == "tpu"


def idx32(fn):
    """Wrap a BlockSpec index map so every returned index is int32.

    The package enables ``jax_enable_x64`` for float64 parity with the
    reference's mshadow type switch, and under x64 a Python int literal
    in an index map traces as a weak int64 constant.  Mosaic cannot
    legalize an i64 ``func.return`` (grid indices stay i32, so mixed
    tuples fail too) and TPU compilation of the kernel dies with
    "failed to legalize operation 'func.return'".  Casting every
    component restores the x64-independent contract.
    """
    @functools.wraps(fn)
    def wrapped(*g):
        return tuple(jnp.asarray(v, jnp.int32) for v in fn(*g))
    return wrapped

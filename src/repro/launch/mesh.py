"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis crosses DCN; batch shards over ("pod", "data").

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax initialization.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: shardings are propagated
    by the compiler, and plain ``jit``/``shard_map`` code needs no
    ``jax.set_mesh`` (``make_mesh`` defaults to ``Explicit`` axes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(multi_pod: bool) -> Tuple[str, ...]:
    return ("pod", "data") if multi_pod else ("data",)


def make_host_mesh(model: int = 1):
    """Degenerate mesh for CPU tests/examples (whatever devices exist)."""
    n = len(jax.devices())
    model = min(model, n)
    return make_mesh((n // model, model), ("data", "model"))

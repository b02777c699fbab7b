"""Public wrapper for the EmbeddingBag kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
from repro.kernels.tpu import interpret_mode


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def embedding_bag_pallas_op(table, ids, weights=None, *, mode: str = "sum",
                            interpret: bool | None = None):
    """table: [rows, dim]; ids: [n_bags, max_nnz]; weights optional (0 pads).
    -> [n_bags, dim]."""
    if interpret is None:
        interpret = interpret_mode()
    if weights is None:
        weights = jnp.ones(ids.shape, jnp.float32)
    return embedding_bag_pallas(table, ids.astype(jnp.int32),
                                weights.astype(jnp.float32), mode=mode,
                                interpret=interpret)

"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required for the smoke tests to see one
device while the dry-run sees 512 placeholders.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh

from repro.dist import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips, ``data`` x ``model``) or 2x16x16
    multi-pod (512 chips, ``pod`` x ``data`` x ``model``)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Single-device mesh for tests/examples."""
    return Mesh(jax.devices()[:1], ("data",))

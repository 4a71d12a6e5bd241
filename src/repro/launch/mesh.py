"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax device
state.  Single pod: (data=16, model=16) = 256 chips.  Multi-pod adds a
leading "pod" axis: (pod=2, data=16, model=16) = 512 chips.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)

"""Meshes.  Functions, not module-level constants — importing this module
never touches jax device state."""
from __future__ import annotations

import jax

from ..compat import make_mesh


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = data * model
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:n])

"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required so smoke tests see 1 CPU device while
the dry-run forces 512 host devices).

Topology (TPU v5e): a pod is a 16x16 mesh of 256 chips; multi-pod adds a
leading "pod" axis over the DCN/ICI-bridged pods. Elastic scaling: pass
``pods`` to grow the pod axis (2 -> N) without touching model code — the
"pod" axis only ever carries batch (and optionally pipeline stages), so
reshaping the fleet re-binds the same logical rules.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules place
    arrays through ``with_sharding_constraint`` and GSPMD, which only
    ``Auto`` axes accept (``make_mesh`` defaults to ``Explicit``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, pods: int = 2):
    shape = (pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Whatever devices exist locally (tests / examples)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return auto_mesh((n // model_axis, model_axis), ("data", "model"))

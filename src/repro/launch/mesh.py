"""Production mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before the first
jax initialisation.

Every mesh in the repo is built here with ``AxisType.Auto`` axes: the model
code places activations with ``with_sharding_constraint``, which reshards
under Auto axes but asserts under the ``Explicit`` axes that
``jax.make_mesh`` defaults to.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes, devices=None) -> Mesh:
    if devices is None:
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
    return Mesh(np.array(devices).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(n_devices: int | None = None, model_axis: int = 1,
                   devices=None) -> Mesh:
    """A small ("data", "model") mesh over whatever devices exist (tests,
    examples, the dry-run test script).

    ``devices`` pins an explicit device list *in that order* — the device-
    placement layer (``core/placement.py``) builds its cross-device
    reduction mesh this way so mesh order matches executor pin order (the
    rank-ordered psum must fold partials in executor order to stay
    bit-identical to the host left-fold).
    """
    n = len(devices) if devices is not None \
        else (n_devices or len(jax.devices()))
    if n % model_axis:
        raise ValueError(f"{n} devices do not split into model axis "
                         f"{model_axis}")
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"),
                      devices)

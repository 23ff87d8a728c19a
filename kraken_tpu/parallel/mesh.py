"""Device-mesh construction for the hash plane.

One axis -- ``pieces`` -- because the only parallel dimension SHA-256
admits is cross-piece (the 64-round chain serializes blocks within a
piece; SURVEY.md SS7 hard part #1). A 2-D mesh buys nothing here: there is
no second contraction axis, and digests are small enough that the gather
cost is noise.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def piece_mesh(
    n_devices: int | None = None, platform: str | None = None
) -> Mesh:
    """Build a 1-D ``pieces`` mesh over the first ``n_devices`` devices
    of ``platform`` (default: the default platform; all of its devices
    when ``n_devices`` is None).

    Asking for more devices than the platform has raises: a mesh that
    quietly moved to other devices (virtual CPU ones, say) would hash
    correctly and report nothing. Tests and the dry-run get their
    virtual CPU devices by running ON the CPU platform
    (``JAX_PLATFORMS=cpu`` + ``--xla_force_host_platform_device_count``).
    """
    devices = jax.devices() if platform is None else jax.devices(platform)
    n = n_devices if n_devices is not None else len(devices)
    if len(devices) < n:
        raise ValueError(
            f"need {n} {devices[0].platform} devices, have {len(devices)}"
        )
    return Mesh(np.asarray(devices[:n]), ("pieces",))

"""Force the CPU host platform to expose N devices (single owner of the
``--xla_force_host_platform_device_count`` XLA_FLAGS dance used by
`launch/serve --mesh`, the bench_serve mesh row, and the mesh
conformance tests).

jax-free on purpose: the flag is only honoured if it is in the
environment before jax's backend initializes, so callers import this
module and call :func:`force_host_device_count` *before* ``import jax``
(or build a child-process env with ``env=``).
"""
from __future__ import annotations

import os
from typing import MutableMapping, Optional


def force_host_device_count(
        n: int, env: Optional[MutableMapping[str, str]] = None) -> None:
    """Append the force-device-count flag to XLA_FLAGS in ``env``
    (default ``os.environ``), preserving any operator-set flags. A
    pre-existing ``--xla_force_host_platform_device_count`` wins — the
    caller must then cope with whatever device count comes up (e.g.
    ``launch.mesh.make_mesh``, which takes the first ``prod(shape)``
    devices, + an explicit count check)."""
    env = os.environ if env is None else env
    flags = env.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        return
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()

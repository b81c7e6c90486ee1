"""Device placement for the ops plane: one mesh-aware selection helper.

Before this module every launch site hardcoded ``jax.devices()[0]``
(``ops/engine.py``), which is exactly the
single-chip assumption ROADMAP item 3 calls the missing multiplier.
All device/mesh selection now routes through here:

* :func:`default_device` — the single-device engine's home chip.
  Env-overridable (``DRAGONBOAT_TPU_DEVICE=<index>``); defaults to
  device 0, i.e. exactly the old behavior.
* :func:`groups_mesh` — a 1-D ``jax.sharding.Mesh`` over the first N
  devices with the canonical ``"groups"`` axis name (SURVEY §2: the
  groups axis is the ONLY parallel axis).  ``DRAGONBOAT_TPU_MESH_DEVICES``
  selects N; unset/0/1 returns None (single-device mode).
* :func:`device_of_row` / :func:`rows_per_device` — the row-block
  placement contract shared by the sharded route tables
  (``route.build_route_tables_mesh``), the engine's striped row
  allocator and the balance plane's device coordinates: device ``d``
  owns the contiguous row block ``[d*Gl, (d+1)*Gl)``.

* :func:`configure_compile_cache` — the one compile-cache rule every
  entry point (``chip_smoke.py``, ``benchmark/run.py``, the tests)
  follows.

Keeping the block contract in ONE module matters: the shard_map'd
launch slices state by block, the route tables classify device
boundaries by block, and the engine reports ``device_coordinate`` by
block — three layers that silently corrupt cross-chip traffic if they
ever disagree.

One process per chip: a TPU belongs to the first process that
initialises the backend, and that process sees every chip of the host.
``DRAGONBOAT_TPU_DEVICE=<i>`` therefore picks among the devices ONE
process sees; it cannot give four processes a chip each (the first to
start holds all four).  A process-per-chip layout needs the chip made
visible per process from outside, before JAX starts.

Neither selector looks at ``.platform``: tests legitimately run the
engine on CPU devices.  An entry point that must run on the chip
(``chip_smoke.py``) checks the platform itself and fails otherwise.
"""
from __future__ import annotations

import os
from typing import Optional

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def configure_compile_cache(jax_module=None) -> str:
    """Place JAX's persistent compile cache; returns the directory used.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
    no directory is set in code.  Unset: ``<checkout>/.jax_cache`` — a
    fixed path, because a directory that moves between runs never hits.
    Call before the first compile.

    Every program is cached, however fast it compiled: the engines'
    warm set is ~90 executables of which JAX's default 1 s threshold
    kept 81 out on the v5e, and those cost 35.6 s of a cached start
    against 74.4 s cold (chip run, PR 21)."""
    if jax_module is None:
        import jax as jax_module
    jax_module.config.update(
        "jax_persistent_cache_min_compile_time_secs", 0.0
    )
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax_module.config.update("jax_compilation_cache_dir", path)
    return path


def default_device(jax_module=None):
    """The engine's home device.  ``DRAGONBOAT_TPU_DEVICE=<i>``
    overrides the index; the default (0) is byte-for-byte the old
    hardcoded ``jax.devices()[0]`` behavior."""
    if jax_module is None:
        import jax as jax_module
    devs = jax_module.devices()
    idx = int(os.environ.get("DRAGONBOAT_TPU_DEVICE", "0") or 0)
    if not 0 <= idx < len(devs):
        raise ValueError(
            f"DRAGONBOAT_TPU_DEVICE={idx} out of range: "
            f"{len(devs)} device(s) visible"
        )
    return devs[idx]


def groups_mesh(n_devices: Optional[int] = None, jax_module=None):
    """A 1-D mesh over the groups axis, or None for single-device mode.

    ``n_devices`` defaults to ``DRAGONBOAT_TPU_MESH_DEVICES`` (unset,
    0 or 1 → None, preserving current single-device behavior).
    """
    if jax_module is None:
        import jax as jax_module
    if n_devices is None:
        n_devices = int(
            os.environ.get("DRAGONBOAT_TPU_MESH_DEVICES", "0") or 0
        )
    if n_devices <= 1:
        return None
    from jax.sharding import Mesh
    import numpy as np

    devs = jax_module.devices()
    if len(devs) < n_devices:
        raise ValueError(
            f"mesh wants {n_devices} devices, only {len(devs)} visible"
        )
    return Mesh(np.asarray(devs[:n_devices]), ("groups",))


def rows_per_device(capacity: int, n_devices: int) -> int:
    """Block size of the row-block placement; capacity must divide."""
    if n_devices <= 0 or capacity % n_devices:
        raise ValueError(
            f"capacity {capacity} must divide over {n_devices} devices"
        )
    return capacity // n_devices


def device_of_row(g: int, capacity: int, n_devices: int) -> int:
    """Device coordinate hosting row ``g`` under the block contract."""
    return g // rows_per_device(capacity, n_devices)

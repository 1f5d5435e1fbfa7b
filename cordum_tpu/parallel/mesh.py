"""Device mesh + sharding helpers: the SPMD substrate for TPU workers.

The reference has no in-process parallelism (NATS/Redis control plane only;
SURVEY.md §2.4) — in the TPU-native design, every worker owns a slice and
runs jobs as SPMD computations over a ``jax.sharding.Mesh``.  These helpers
build meshes that match the physical slice, derive the topology string the
worker reports in heartbeats, and provide the standard axis vocabulary:

  * ``dp``   — data parallel (batch)
  * ``tp``   — tensor/model parallel (MXU-heavy dims, rides ICI)
  * ``sp``   — sequence/context parallel (long-context activations)
  * ``ep``   — expert parallel (MoE routing)
  * ``pp``   — pipeline parallel (layer stages)

Meshes are created over whatever devices JAX exposes (TPU slice in prod,
``xla_force_host_platform_device_count`` CPU devices in tests).
"""
from __future__ import annotations

import math
import os
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DP = "dp"
AXIS_TP = "tp"
AXIS_SP = "sp"
AXIS_EP = "ep"
AXIS_PP = "pp"


@dataclass
class MeshSpec:
    """Logical mesh shape; -1 on one axis means "absorb remaining devices"."""

    dp: int = -1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1

    def resolve(self, n_devices: int) -> dict[str, int]:
        if n_devices < 1:
            raise ValueError("need at least one device")
        sizes = {"dp": self.dp, "tp": self.tp, "sp": self.sp, "ep": self.ep, "pp": self.pp}
        bad = {k: v for k, v in sizes.items() if v != -1 and v < 1}
        if bad:
            # 0 / negative axes must fail loudly: a zero axis used to slip
            # through `prod(v for v in ... if v > 0)` and build a 0-sized
            # mesh dimension downstream
            raise ValueError(f"mesh axes must be -1 or >= 1, got {bad}")
        fixed = math.prod(v for v in sizes.values() if v > 0)
        free = [k for k, v in sizes.items() if v == -1]
        if len(free) > 1:
            raise ValueError("at most one mesh axis may be -1")
        if fixed > n_devices:
            raise ValueError(f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        if free:
            if n_devices % fixed:
                raise ValueError(f"{n_devices} devices not divisible by fixed axes {fixed}")
            sizes[free[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        return sizes


def build_mesh(
    spec: MeshSpec | None = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_names: Optional[Sequence[str]] = None,
) -> Mesh:
    """Build a named mesh over the devices.  Axes of size 1 are kept so the
    same PartitionSpecs work at every scale (XLA drops trivial collectives)."""
    devs = list(devices) if devices is not None else list(jax.devices())
    spec = spec or MeshSpec()
    sizes = spec.resolve(len(devs))
    names = list(axis_names) if axis_names else [AXIS_DP, AXIS_TP, AXIS_SP, AXIS_EP, AXIS_PP]
    shape = [sizes[n] for n in names]
    arr = np.array(devs).reshape(shape)
    return Mesh(arr, axis_names=tuple(names))


def simple_mesh(n_tp: int = 1, *, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """The common dp×tp mesh: tp fixed, dp absorbs the rest."""
    devs = list(devices) if devices is not None else list(jax.devices())
    n = len(devs)
    if n % n_tp:
        raise ValueError(f"{n} devices not divisible by tp={n_tp}")
    arr = np.array(devs).reshape(n // n_tp, n_tp)
    return Mesh(arr, axis_names=(AXIS_DP, AXIS_TP))


def slice_topology(devices: Optional[Sequence[jax.Device]] = None) -> str:
    """Physical topology string for heartbeats (e.g. ``2x2x1``); falls back
    to a flat ``N`` chip count when coords are unavailable (CPU backend)."""
    devs = list(devices) if devices is not None else list(jax.devices())
    coords = [getattr(d, "coords", None) for d in devs]
    if any(c is None for c in coords):
        return str(len(devs))
    dims = len(coords[0])
    extents = [len({c[i] for c in coords}) for i in range(dims)]
    return "x".join(str(e) for e in extents)


def device_kind(devices: Optional[Sequence[jax.Device]] = None) -> str:
    devs = list(devices) if devices is not None else list(jax.devices())
    return devs[0].device_kind if devs else ""


def hbm_stats(devices: Optional[Sequence[jax.Device]] = None) -> tuple[float, float]:
    """(used_gb, total_gb) summed over devices.  (0, 0) only for a backend
    that keeps no ``memory_stats`` (the CPU's returns None); a device that
    raises is a fault and propagates."""
    devs = list(devices) if devices is not None else list(jax.devices())
    used = total = 0.0
    for d in devs:
        st = d.memory_stats()
        if not st:
            return 0.0, 0.0
        used += st.get("bytes_in_use", 0) / 1e9
        total += st.get("bytes_limit", st.get("bytes_reservable_limit", 0)) / 1e9
    return used, total


#: where compiled programs persist when JAX_COMPILATION_CACHE_DIR is not set:
#: one fixed path inside the checkout (the path is part of the cache key, so
#: a directory that moves never hits)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile.  ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it
    itself and no other directory is set in code); otherwise the cache
    lives at :data:`DEFAULT_COMPILE_CACHE_DIR` — on an accelerator only:
    the CPU backend compiles test sizes in no time, and XLA's CPU loader
    logs a machine-feature warning on every cached read.  The minimum
    compile time drops to zero so the small page gather/scatter/copy
    programs persist too.  Returns the directory in use ("" = none)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        if jax.default_backend() == "cpu":
            return ""
        path = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def shard_batch(mesh: Mesh, batch, axes: Sequence[str] = (AXIS_DP,)):
    """Place a pytree of [B, ...] arrays with batch sharded over the given
    mesh axes and everything else replicated."""
    sharding = NamedSharding(mesh, P(tuple(axes) if len(axes) > 1 else axes[0]))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())

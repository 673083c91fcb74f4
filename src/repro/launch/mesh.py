"""Production mesh construction + multi-process bring-up.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state -- required for the dry-run's placeholder-device
bootstrap ordering, and for ``init_distributed``'s (flags, collectives,
``jax.distributed.initialize``) sequence, all of which must run before the
first backend-initializing call.

Launching multi-process runs (one process per host; CPU-portable, so CI and
laptops drill the exact same path as a real slice)::

    # terminal 1                                 # terminal 2
    python -m repro.launch.train \\
        --arch tinyllama-1.1b --smoke --vcycle \\
        --mesh 2x1 --coordinator 127.0.0.1:9876 \\
        --num-processes 2 --process-id 0 ...     # ... --process-id 1 ...

The ("data","model") mesh then spans all processes' devices; each process
feeds its own data shard, process 0 owns logging and the checkpoint manifest,
and every process writes only its addressable checkpoint shards (see
``repro.checkpoint``).

The same ``--mesh DxM`` flag (and the same axis names) drives the serving
side: ``launch/serve.py``'s ``make_server(cfg, mesh=...)`` places the paged
K/V page pool model-sharded along ``"model"`` with replicated block tables,
so a decode fleet reuses this module's mesh construction unchanged (see
launch/README.md, "Mesh-sharded paged decode").
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def parse_mesh_arg(spec: str) -> Tuple[int, ...]:
    """``"DxM"`` -> (data, model); ``"PxDxM"`` -> (pod, data, model).

    The 3-dim form adds a leading DCN "pod" axis (data parallelism across
    pods), which is what the hierarchical gradient-reduction strategies key
    on: dense within ("data",) ICI, compressed across "pod".
    """
    try:
        dims = tuple(int(p) for p in spec.lower().split("x"))
    except ValueError:
        raise ValueError(
            f"--mesh expects DxM or PxDxM (e.g. 2x4 or 2x2x1), "
            f"got {spec!r}") from None
    if len(dims) not in (2, 3) or any(d < 1 for d in dims):
        raise ValueError(
            f"--mesh expects 2 or 3 axes >= 1 (DxM or PxDxM), got {spec!r}")
    return dims


def _force_host_device_flag(n: int) -> None:
    """Env-only half of :func:`ensure_host_devices`: set (or raise) the
    ``--xla_force_host_platform_device_count`` flag without touching jax
    device state, so it can run before ``jax.distributed.initialize``."""
    flags = os.environ.get("XLA_FLAGS", "")
    marker = "--xla_force_host_platform_device_count="
    if n <= 1:
        return
    if marker in flags:
        # raise an existing, too-small count instead of refusing
        head, _, rest = flags.partition(marker)
        val, _, tail = rest.partition(" ")
        try:
            have_flag = int(val)
        except ValueError:
            have_flag = 0
        if have_flag < n:
            os.environ["XLA_FLAGS"] = f"{head}{marker}{n} {tail}".strip()
    else:
        os.environ["XLA_FLAGS"] = f"{flags} {marker}{n}".strip()


def ensure_host_devices(n: int) -> None:
    """Force the host (CPU) platform to expose >= ``n`` LOCAL devices.

    Must run before jax initializes its backends (i.e. before the first
    device-touching call -- the launcher calls it straight after arg parsing,
    which is why this module never creates device state at import time).
    A no-op when enough devices already exist (a real accelerator platform, or
    XLA_FLAGS already set by the caller); raises when the backend is already
    live with fewer devices than requested.
    """
    _force_host_device_flag(n)
    have = jax.local_device_count()
    if have < n:
        raise RuntimeError(
            f"mesh needs {n} local devices but jax sees {have} (backend "
            f"already initialized?); export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            f"launch")


def init_distributed(coordinator: str, num_processes: int, process_id: int,
                     *, local_devices: Optional[int] = None) -> None:
    """Bring up ``jax.distributed`` for a multi-process run (CPU-portable).

    Must run before ANY backend-initializing jax call.  Order matters and is
    encapsulated here: (1) force the host-platform device count this process
    must contribute (env only), (2) select the gloo CPU collectives
    implementation -- the default CPU backend refuses multi-process
    computations outright -- then (3) connect to the coordinator.  On an
    accelerator platform (2) is a harmless no-op: collectives ride the
    accelerator fabric and the forced CPU devices are never part of the mesh.

    Idempotent: a second call (e.g. a library test re-entering the launcher)
    is ignored once the distributed client is live.
    """
    from jax._src import distributed as _dist

    if getattr(_dist.global_state, "client", None) is not None:
        return
    if local_devices and local_devices > 1:
        _force_host_device_flag(local_devices)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """The one mesh constructor of the repo: every axis is ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which a gather or a
    ``with_sharding_constraint`` must spell out its output sharding.  The
    model code leaves propagation to GSPMD (``shard_l`` constraints, jit
    ``in_shardings``/``out_shardings``), which needs ``Auto`` axes.
    ``devices`` defaults to ``jax.devices()``; pass described devices (e.g. a
    TPU topology's) to compile for a chip that is not attached.
    """
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_cli_mesh(spec: str, *, num_processes: int = 1):
    """Mesh for the launcher's ``--mesh`` flag: ("data", "model") for ``DxM``,
    ("pod", "data", "model") for ``PxDxM`` (a leading DCN axis for the
    hierarchical gradient-reduction strategies).

    CPU-backed for tests/smoke: each process's host devices are forced to its
    d*m/num_processes share before the first backend initialization, so
    ``--mesh 2x4`` works on a laptop exactly like on a slice (the per-device
    arrays are just tiny).  With ``num_processes > 1`` the caller must have
    run :func:`init_distributed` first; the mesh then spans every process's
    devices (process-major device order, so a 2x1 mesh puts process 0 at data
    coordinate 0).
    """
    dims = parse_mesh_arg(spec)
    total = 1
    for d in dims:
        total *= d
    if total % num_processes:
        raise ValueError(
            f"--mesh {spec} has {total} devices, not divisible over "
            f"{num_processes} processes")
    ensure_host_devices(total // num_processes)
    if jax.device_count() < total:
        raise RuntimeError(
            f"mesh {spec} needs {total} devices but jax sees "
            f"{jax.device_count()} across {jax.process_count()} processes")
    axes = ("pod", "data", "model") if len(dims) == 3 else ("data", "model")
    return make_mesh(dims, axes)


def make_production_mesh(*, multi_pod: bool = False):
    """Target topology: one TPU v5e pod = 16x16 = 256 chips, ("data","model");
    two pods = (2,16,16) with a leading "pod" axis (DP across pods over DCN).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# hardware constants for the roofline (TPU v5e per chip)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link

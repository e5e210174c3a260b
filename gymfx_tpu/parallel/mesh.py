"""Device-mesh utilities: the distributed backbone.

The reference has no distributed backend (no NCCL/MPI — SURVEY.md
§2.9/§5.8); its only concurrency is one engine thread per env.  Here
scale-out is native JAX SPMD: pick a mesh, annotate shardings, let XLA
insert the collectives over ICI (psum for the learner all-reduce,
all-gathers for tensor-sharded layers).  Multi-host extends the same
mesh over DCN via ``jax.distributed.initialize`` (initialize_distributed).

Axes:
  data   env-batch data parallelism (rollout + gradient all-reduce)
  model  tensor parallelism for wide policy layers
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

shard_map = jax.shard_map


def pcast_varying(x, axis: str):
    """Mark a replicated value as device-varying over ``axis`` so e.g.
    fori_loop carry types match after a ``ppermute``."""
    return jax.lax.pcast(x, axis, to="varying")


def make_mesh(
    shape: Optional[Dict[str, int]] = None,
    *,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a Mesh; default: all devices on the 'data' axis.

    shape e.g. {"data": 4, "model": 2}; the product must divide the
    device count (extra devices are left unused, deterministically).
    """
    devices = list(devices if devices is not None else jax.devices())
    if not shape:
        shape = {"data": len(devices)}
    axis_names = tuple(shape.keys())
    sizes = tuple(int(v) for v in shape.values())
    need = int(np.prod(sizes))
    if need > len(devices):
        raise ValueError(
            f"mesh shape {shape} needs {need} devices, have {len(devices)}"
        )
    grid = np.array(devices[:need]).reshape(sizes)
    return Mesh(grid, axis_names)


def mesh_from_config(config: Dict) -> Optional[Mesh]:
    """Resolve the ``mesh_shape`` config key into a live Mesh (or None).

    Honor-or-reject: accepts a dict (config file) or a JSON string (CLI
    passthrough), validates axis names/sizes, and raises when the shape
    cannot be realized on the available devices — never silently ignores
    the field.  ``n_envs`` divisibility is validated by the trainers
    (they know their batch axis).

    ``elastic_exclude_devices`` (written by the elastic auto-resume
    controller, parallel/elastic.py) lists GLOBAL device indices lost to
    degrade events — the mesh forms over the survivors, not the first N
    devices, so a resume attempt never lands work back on a dead chip.
    """
    raw = config.get("mesh_shape")
    if raw is None or raw == "":
        return None
    if isinstance(raw, str):
        import json

        try:
            raw = json.loads(raw)
        except json.JSONDecodeError as exc:
            raise ValueError(
                f"mesh_shape must be a JSON object like "
                f'{{"data": 4, "model": 2}}; got {raw!r}'
            ) from exc
    if not isinstance(raw, dict) or not raw:
        raise ValueError(f"mesh_shape must be a non-empty mapping, got {raw!r}")
    shape: Dict[str, int] = {}
    for axis, size in raw.items():
        if not isinstance(axis, str) or not axis:
            raise ValueError(f"mesh_shape axis names must be strings, got {axis!r}")
        try:
            size_i = int(size)
            ok = size_i >= 1 and size_i == float(size)
        except (TypeError, ValueError):
            ok = False
        if not ok:
            raise ValueError(f"mesh_shape[{axis!r}] must be a positive int, got {size!r}")
        shape[axis] = size_i
    exclude = config.get("elastic_exclude_devices") or ()
    if exclude:
        dead = set()
        for idx in exclude:
            try:
                idx_i = int(idx)
            except (TypeError, ValueError):
                raise ValueError(
                    f"elastic_exclude_devices entries must be device "
                    f"indices, got {idx!r}"
                )
            if idx_i < 0:
                raise ValueError(
                    f"elastic_exclude_devices entries must be >= 0, "
                    f"got {idx_i}"
                )
            dead.add(idx_i)
        survivors = [d for i, d in enumerate(jax.devices()) if i not in dead]
        return make_mesh(shape, devices=survivors)
    return make_mesh(shape)


def validate_batch_axis(mesh: Optional[Mesh], n: int, what: str,
                        axis: str = "data") -> None:
    """Reject meshes missing the batch axis and batch sizes the mesh
    cannot shard evenly (either would otherwise surface as a cryptic
    sharding error deep inside XLA)."""
    if mesh is None:
        return
    if axis not in mesh.axis_names:
        raise ValueError(
            f"mesh_shape must include a {axis!r} axis (got axes "
            f"{list(mesh.axis_names)}): the trainers shard the env "
            f"batch over it"
        )
    k = mesh.shape[axis]
    if n % k != 0:
        raise ValueError(
            f"{what}={n} is not divisible by mesh axis {axis!r} size {k}; "
            f"choose {what} as a multiple of {k}"
        )


def validate_population_axis(mesh: Optional[Mesh], population: int,
                             axis: str = "data") -> None:
    """PBT shards its POPULATION (not the env batch) over the mesh
    ``axis``; honor-or-reject before XLA, same style as
    :func:`validate_batch_axis` — a population the mesh cannot split
    evenly would otherwise surface as a cryptic GSPMD error."""
    if mesh is None:
        return
    if axis not in mesh.axis_names:
        raise ValueError(
            f"mesh_shape must include a {axis!r} axis (got axes "
            f"{list(mesh.axis_names)}): PBT shards the population over it"
        )
    k = mesh.shape[axis]
    if population % k != 0:
        raise ValueError(
            f"pbt_population={population} is not divisible by mesh axis "
            f"{axis!r} size {k}; PBT shards the population over {axis!r} — "
            f"choose pbt_population as a multiple of {k}"
        )


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Leading-dim sharding for env batches."""
    return NamedSharding(mesh, PartitionSpec(axis))


class CoordinatorTimeoutError(TimeoutError):
    """Multi-host initialization exhausted its retry budget without
    reaching the coordinator — carries the address and attempt count so
    the launcher can tell "coordinator never came up" apart from a
    generic hang."""

    def __init__(self, coordinator_address: str, attempts: int,
                 cause: Optional[BaseException] = None):
        super().__init__(
            f"could not reach coordinator {coordinator_address!r} after "
            f"{attempts} attempt(s): {cause}"
        )
        self.coordinator_address = coordinator_address
        self.attempts = attempts
        self.cause = cause


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    *,
    retries: int = 3,
    backoff_s: float = 2.0,
    timeout_s: Optional[float] = None,
    _initialize=None,
    _sleep=None,
) -> None:
    """Multi-host (DCN) initialization; single-process no-op when no
    coordinator is configured.

    At pod scale the coordinator host routinely comes up seconds after
    its workers, so a bare ``jax.distributed.initialize`` races boot
    order.  The attempt is bounded: ``retries`` tries with linear
    ``backoff_s`` between them, each passing ``initialization_timeout``
    through, and the budget exhausting
    raises :class:`CoordinatorTimeoutError` instead of a raw
    RuntimeError, so launchers can distinguish "coordinator never came
    up" from a real init bug.  ``_initialize``/``_sleep`` are test
    seams (default: the real jax call / time.sleep).
    """
    if coordinator_address is None:
        return
    import time as _time

    init = _initialize if _initialize is not None else jax.distributed.initialize
    sleep = _sleep if _sleep is not None else _time.sleep
    attempts = max(1, int(retries))
    last: Optional[BaseException] = None
    for attempt in range(1, attempts + 1):
        kwargs = dict(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        try:
            if timeout_s is not None:
                kwargs["initialization_timeout"] = int(timeout_s)
            init(**kwargs)
            return
        except (RuntimeError, ConnectionError, TimeoutError) as exc:
            last = exc
            if attempt < attempts:
                sleep(backoff_s * attempt)
    raise CoordinatorTimeoutError(coordinator_address, attempts, last)

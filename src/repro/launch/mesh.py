"""Mesh builders for every execution placement (infrastructure, no direct
paper analogue — the paper simulates k workers on one device; these meshes
are where the reproduction's *sharded* placement puts them on hardware).

Axis convention (shared with ``repro.core.coordinator``):

- ``'pod'`` — hosts the paper's elastic *workers* under
  ``ElasticConfig.placement = "sharded"``: the (k, ...) worker axis of the
  trainer state is partitioned over it via ``shard_map``
  (k % pod_size == 0), one master reduction crossing it per round.
- ``'data'`` / ``'model'`` — ordinary GSPMD axes for sharding each worker's
  model replica *within* a pod. The sharded coordinator's ``shard_map`` is
  fully manual today, so each worker is replicated over these axes.

Every mesh built here has ``Auto`` axis types: on the installed jax 0.9.0
``jax.make_mesh`` defaults to ``Explicit`` axes, on which the logical
sharding rules' ``with_sharding_constraint`` (``repro.nn.sharding``) is
rejected.

Production: single pod (16, 16) = 256 chips, axes ('data', 'model');
multi-pod (2, 16, 16) = 512 chips, axes ('pod', 'data', 'model').

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests run with the
default single device).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The target hardware meshes (requires that many real/forced devices).

    ``multi_pod=False``: (16, 16) axes ('data', 'model') — one worker, the
    single-placement regime at scale. ``multi_pod=True``: (2, 16, 16) axes
    ('pod', 'data', 'model') — one elastic worker per pod, the mesh the
    sharded coordinator and ``launch/dryrun.py`` lower against.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(*, data: int = 1, model: int = 1, pod: int = 1) -> Mesh:
    """Small ('pod', 'data', 'model') mesh over the host's devices — for
    tests, CPU smoke runs and the sharded-placement default
    (``ElasticSession`` builds ``make_host_mesh(pod=jax.device_count())``).
    Always carries all three axes (size-1 axes are free) so host meshes and
    the multi-pod production mesh expose the same axis names; uses the
    first pod·data·model visible devices (emulate a multi-device CPU host
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before
    jax initializes — that exact spelling; jax reads no other env var for
    this).
    """
    return _auto_mesh((pod, data, model), ("pod", "data", "model"))


def make_distributed_mesh(*, coordinator_address=None, num_processes: int = 1,
                          process_id: int = 0, data: int = 1, model: int = 1,
                          pod: int = 0) -> Mesh:
    """Multi-process ('pod', 'data', 'model') mesh (ISSUE-10): one mesh
    spanning every process's devices, so the sharded coordinator's worker
    axis tiles across hosts instead of one host's forced device pool.

    With ``num_processes > 1`` this calls ``jax.distributed.initialize``
    (exactly once — safe to call when the runtime is already initialized)
    using the ``--coordinator-address/--num-processes/--process-id``
    plumbing from ``launch/train.py``; process 0 must host the coordinator
    at ``coordinator_address`` (``host:port``). After init, every process
    sees the *global* device list and builds the identical mesh over it.

    CPU caveat: jax's CPU backend supports distributed *initialization*
    (global device visibility, process_index, multihost utils) but not
    cross-process XLA computations ("Multiprocess computations aren't
    implemented on the CPU backend"), so on CPU each process falls back to
    a mesh over its **local** devices — the processes run the same
    deterministic program side by side (the 2-process CI smoke asserts
    they agree bit-for-bit on the final master). On TPU/GPU the mesh is
    genuinely global.

    ``pod = 0`` (default) sizes the pod axis to use every selected device:
    ``device_count // (data · model)``.
    """
    if num_processes > 1:
        if not coordinator_address:
            raise ValueError(
                "make_distributed_mesh: num_processes > 1 needs a "
                "coordinator_address (host:port of process 0)")
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
        except RuntimeError as e:  # already initialized: keep going
            if "already" not in str(e).lower():
                raise
    devices = list(jax.devices())
    if num_processes > 1 and jax.default_backend() == "cpu":
        print("[mesh] CPU backend: cross-process XLA computations are "
              "unsupported — falling back to a process-local mesh "
              f"({len(jax.local_devices())} local of {len(devices)} global "
              "devices)", flush=True)
        devices = list(jax.local_devices())
    if pod <= 0:
        pod = max(1, len(devices) // (data * model))
    n = pod * data * model
    if n > len(devices):
        raise ValueError(
            f"make_distributed_mesh: pod·data·model = {n} exceeds the "
            f"{len(devices)} available devices")
    grid = np.asarray(devices[:n]).reshape(pod, data, model)
    return Mesh(grid, ("pod", "data", "model"))

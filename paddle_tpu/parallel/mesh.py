"""Device mesh abstraction.

This replaces the reference's multi-device plumbing — ParallelExecutor's
per-GPU SSA graphs + NCCL rings (reference
paddle/fluid/framework/details/*, platform/nccl_helper.h) and the
go/pserver parameter-server topology — with the TPU-native model: one
logical ``jax.sharding.Mesh`` over all chips, shardings annotated on
values, XLA GSPMD inserting the collectives over ICI/DCN.

Axis conventions (used across the framework):
  dp — data parallel          tp — tensor (model) parallel
  pp — pipeline stages        sp — sequence/context parallel
  ep — expert parallel
"""
import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["DeviceMesh", "make_mesh", "PartitionSpec", "NamedSharding",
           "current_mesh", "mesh_scope", "init_distributed"]

P = PartitionSpec


class DeviceMesh:
    """A named mesh over the default backend's devices (or the given
    ``devices``); a mesh larger than that pool is an error."""

    def __init__(self, axes, devices=None):
        """axes: dict axis_name -> size (one size may be -1 to absorb the
        remaining devices)."""
        pool = list(jax.devices() if devices is None else devices)
        sizes = dict(axes)
        known = int(np.prod([s for s in sizes.values() if s != -1])) or 1
        for k, v in sizes.items():
            if v == -1:
                sizes[k] = len(pool) // known
        total = int(np.prod(list(sizes.values())))
        if not 0 < total <= len(pool):
            raise ValueError(
                f"mesh axes {axes} cannot be laid out over {len(pool)} "
                f"devices (resolved sizes {sizes} need {total})")
        arr = np.asarray(pool[:total]).reshape(list(sizes.values()))
        self.mesh = Mesh(arr, tuple(sizes.keys()))
        self.axes = sizes

    @property
    def axis_names(self):
        return self.mesh.axis_names

    def size(self, axis=None):
        if axis is None:
            return int(np.prod(list(self.axes.values())))
        return self.axes[axis]

    def sharding(self, *spec):
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self):
        return NamedSharding(self.mesh, P())

    def __enter__(self):
        self.mesh.__enter__()
        return self

    def __exit__(self, *a):
        return self.mesh.__exit__(*a)

    def __repr__(self):
        return f"DeviceMesh({self.axes})"


_current = None


def make_mesh(axes=None, devices=None):
    """Default: 1-D data-parallel mesh over every device."""
    if axes is None:
        axes = {"dp": -1}
    return DeviceMesh(axes, devices)


def current_mesh():
    return _current


import contextlib


@contextlib.contextmanager
def mesh_scope(mesh):
    global _current
    old = _current
    _current = mesh
    try:
        with mesh.mesh:
            yield mesh
    finally:
        _current = old


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None, local_device_ids=None):
    """Join a multi-host TPU pod slice (reference: the trainer/pserver
    bootstrap read from PADDLE_TRAINER_ID / PADDLE_TRAINERS /
    PADDLE_PSERVER_ENDPOINTS env, reference
    python/paddle/fluid/transpiler/distribute_transpiler.py usage).

    Wraps ``jax.distributed.initialize``: on Cloud TPU the arguments
    are discovered from the pod metadata, elsewhere they come from the
    fluid-style env vars as a fallback. After this, ``jax.devices()``
    spans every host's chips and a DeviceMesh built over them runs one
    SPMD program across the pod — collectives ride ICI within a slice
    and DCN across slices, with no pserver topology needed.

    ``PADDLE_TPU_CPU_COLLECTIVES=gloo`` selects the CPU collectives
    transport for multi-process bring-up on hosts without
    accelerators (docs/DISTRIBUTED.md).
    """
    import os
    if coordinator_address is None:
        eps = os.environ.get("PADDLE_PSERVER_ENDPOINTS") or \
            os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        coordinator_address = eps.split(",")[0] or None
    if num_processes is None and os.environ.get("PADDLE_TRAINERS"):
        num_processes = int(os.environ["PADDLE_TRAINERS"])
    if process_id is None and os.environ.get("PADDLE_TRAINER_ID"):
        process_id = int(os.environ["PADDLE_TRAINER_ID"])
    impl = os.environ.get("PADDLE_TPU_CPU_COLLECTIVES", "")
    if impl:
        # XLA:CPU's default collectives reject multiprocess programs
        # ("Multiprocess computations aren't implemented on the CPU
        # backend"); PADDLE_TPU_CPU_COLLECTIVES=gloo selects the
        # transport that implements them, which is what makes the
        # 2-process bring-up testable on a laptop
        # (tests/test_distributed_bringup.py). Opt-in by env because
        # it must be set before the CPU backend initializes and it
        # requires a live distributed client — flipping it in a
        # single-process run would break backend init.
        jax.config.update("jax_cpu_collectives_implementation", impl)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes, process_id=process_id,
        local_device_ids=local_device_ids)
    return len(jax.devices())

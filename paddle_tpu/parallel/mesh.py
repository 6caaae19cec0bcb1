"""Device mesh helpers.

The reference scales with NCCL allreduce (paddle/fluid/framework/details/
nccl_all_reduce_op_handle.cc) and pserver send/recv. TPU-native scaling is
declarative: build a jax.sharding.Mesh over the chips and annotate shardings;
XLA GSPMD inserts all-reduce/all-gather/reduce-scatter over ICI.

Axis conventions used across paddle_tpu:
  dp — data parallel (batch dim)
  mp — model/tensor parallel (hidden dims)
  sp — sequence/context parallel (long sequences; ring attention)
  pp — pipeline stages
"""
import numpy as np

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "data_parallel_mesh", "replicated", "batch_sharded",
           "vary", "Mesh", "NamedSharding", "P"]


def vary(x, axes):
    """Mark a constant as device-varying over `axes` so shard_map loop
    carries type-check. Shared by ring_attention and pipeline."""
    return lax.pcast(x, tuple(axes), to="varying")


def device_count():
    return len(jax.devices())


def make_mesh(axes, devices=None):
    """axes: dict axis_name -> size (use -1 once for 'remaining devices')."""
    devices = devices if devices is not None else jax.devices()
    import numbers
    try:
        sizes = {k: int(v) for k, v in dict(axes).items()
                 if isinstance(v, numbers.Integral)}
        ok = len(sizes) == len(dict(axes))
    except (TypeError, ValueError):
        ok = False
    if not ok:
        raise TypeError(
            "make_mesh expects {axis_name: size} (e.g. {'dp': -1} or "
            "{'dp': 4, 'mp': 2}), got %r" % (axes,))
    if any(s < 1 and s != -1 for s in sizes.values()) \
            or list(sizes.values()).count(-1) > 1:
        raise ValueError("make_mesh: axis sizes must be positive, with at "
                         "most one -1 wildcard; got %r" % (axes,))
    known = int(np.prod([s for s in sizes.values() if s != -1]))
    if any(v == -1 for v in sizes.values()) and known > len(devices):
        raise ValueError(
            "make_mesh: fixed axes in %r already need %d devices but only "
            "%d are available, leaving none for the -1 wildcard"
            % (axes, known, len(devices)))
    for k, v in sizes.items():
        if v == -1:
            sizes[k] = len(devices) // known
    names = tuple(sizes)
    shape = tuple(sizes[n] for n in names)
    total = int(np.prod(shape))
    if any(s < 1 for s in shape) or len(devices) < total:
        raise ValueError(
            "make_mesh: axes %r need %d devices but only %d are available "
            "(run under an n-device backend, e.g. XLA_FLAGS="
            "--xla_force_host_platform_device_count=%d with JAX_PLATFORMS=cpu)"
            % (dict(zip(names, shape)), total, len(devices), total))
    arr = np.asarray(devices[:total]).reshape(shape)
    return Mesh(arr, names)


def data_parallel_mesh(num_devices=None, devices=None):
    devices = devices if devices is not None else jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    return make_mesh({"dp": len(devices)}, devices)


def replicated(mesh):
    return NamedSharding(mesh, P())


def batch_sharded(mesh, ndim, axis_name="dp", batch_dim=0):
    spec = [None] * ndim
    spec[batch_dim] = axis_name
    return NamedSharding(mesh, P(*spec))

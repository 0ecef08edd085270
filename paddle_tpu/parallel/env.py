"""Parallel environment: device mesh bootstrap.

Reference parity: python/paddle/distributed/parallel.py:58 init_parallel_env
(env check -> KV bootstrap -> NCCLParallelContext::Init -> default ring) and
platform/collective_helper.h ring registry.  TPU-native design (SURVEY §5.8):
the ring_id-keyed NCCL comm world is replaced by ONE named-axis
jax.sharding.Mesh over ICI/DCN; "rings" become named mesh axes; bootstrap is
jax.distributed.initialize (coordination service) on multi-host.  Groups
(new_group) are sub-axes of the mesh rather than new communicators.
"""
import logging
import os
import threading

import numpy as np
import jax
from jax.sharding import Mesh, PartitionSpec, NamedSharding

_log = logging.getLogger("ptn.parallel")

_lock = threading.Lock()
_global_mesh = None
_initialized = False


class ParallelEnv:
    """Parity: fluid/dygraph/parallel.py ParallelEnv (PADDLE_* env)."""

    def __init__(self):
        self._rank = int(os.environ.get("PADDLE_TRAINER_ID", jax.process_index()))
        self._device_id = 0

    @property
    def rank(self):
        return self._rank

    @property
    def local_rank(self):
        return self._rank

    @property
    def world_size(self):
        return int(os.environ.get("PADDLE_TRAINERS_NUM", max(jax.device_count(), 1)))

    @property
    def nranks(self):
        return self.world_size

    @property
    def device_id(self):
        return self._device_id

    @property
    def trainer_endpoints(self):
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        return eps.split(",") if eps else []

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "")


def init_parallel_env(mesh_shape=None, axis_names=None):
    """Create the global device mesh (replaces NCCL ring-0 creation).

    On multi-host, callers should have run jax.distributed.initialize (the
    coordination-service analogue of c_gen_nccl_id's TCP bootstrap,
    gen_comm_id_helper.cc:297).
    """
    global _global_mesh, _initialized
    with _lock:
        devices = np.array(jax.devices())
        if mesh_shape is None:
            mesh_shape = (len(devices),)
            axis_names = axis_names or ("data",)
        devices = devices.reshape(mesh_shape)
        _global_mesh = Mesh(devices, axis_names)
        _initialized = True
    return ParallelEnv()


def is_initialized():
    return _initialized


def global_mesh():
    global _global_mesh
    if _global_mesh is None:
        init_parallel_env()
    return _global_mesh


def set_global_mesh(mesh):
    global _global_mesh, _initialized
    _global_mesh = mesh
    _initialized = True


def get_rank(group=None):
    return ParallelEnv().rank


def get_world_size(group=None):
    if group is not None and getattr(group, "nranks", None):
        return group.nranks
    return ParallelEnv().world_size


def build_mesh(shape_dict, dcn_shape_dict=None):
    """Build a named mesh, e.g. {'data': 2, 'model': 4} (hybrid topology).

    Axis order follows insertion order; total must divide available
    devices.  On real TPUs the device layout comes from
    jax.experimental.mesh_utils so trailing (fast-varying) axes land on
    ICI-adjacent chips; `dcn_shape_dict` (same keys, per-axis slice
    counts) places those factors across slices over DCN
    (create_hybrid_device_mesh) — the multi-slice recipe.  On CPU (the
    virtual test mesh) the layout is a plain reshape, byte-stable for
    the parity tests.
    """
    names = tuple(shape_dict.keys())
    sizes = tuple(int(v) for v in shape_dict.values())
    n = int(np.prod(sizes))
    devs = jax.devices()
    if n > len(devs):
        raise ValueError(
            f"mesh {dict(shape_dict)} needs {n} devices, "
            f"{len(devs)} visible")
    if n < len(devs):
        _log.info("build_mesh %s: using the first %d of %d devices",
                  dict(shape_dict), n, len(devs))
    if dcn_shape_dict is not None:
        unknown = set(dcn_shape_dict) - set(names)
        if unknown:
            raise ValueError(
                f"dcn_shape_dict keys {sorted(unknown)} are not mesh "
                f"axes {list(names)}")
        dcn_sizes = tuple(int(dcn_shape_dict.get(k, 1)) for k in names)
        for k, s, d in zip(names, sizes, dcn_sizes):
            if d <= 0 or s % d:
                raise ValueError(
                    f"DCN factor {d} does not divide axis {k!r} size {s}")
        ici_sizes = tuple(s // d for s, d in zip(sizes, dcn_sizes))
        if all(hasattr(d, "slice_index") for d in devs[:n]):
            from jax.experimental import mesh_utils

            devices = mesh_utils.create_hybrid_device_mesh(
                ici_sizes, dcn_sizes, devices=devs)
        else:
            # no slice topology (CPU test mesh / single slice): manual
            # slice-major layout — DCN factors are the slowest-varying
            # dims of each axis, the same placement the hybrid helper
            # produces modulo intra-slice ICI optimization
            arr = np.array(devs[:n]).reshape(dcn_sizes + ici_sizes)
            k = len(names)
            order = [i for pair in ((d, d + k) for d in range(k))
                     for i in pair]
            devices = arr.transpose(order).reshape(sizes)
        return Mesh(devices, names)
    if devs[0].platform == "tpu" and n == len(devs):
        from jax.experimental import mesh_utils

        # a topology mesh_utils cannot lay out raises: a plain reshape
        # would run, with axes that do not follow the ICI links
        devices = mesh_utils.create_device_mesh(sizes, devices=devs)
        return Mesh(devices, names)
    devices = np.array(devs[:n]).reshape(sizes)
    return Mesh(devices, names)


def tp_mesh(tp_degree=None, axis_name="model"):
    """A 1-D tensor-parallel mesh over the first `tp_degree` devices —
    the mesh the sharded generation engine takes (GenerationConfig.mesh;
    docs/GENERATION.md "Sharded decode").  Defaults to every visible
    device.  Goes through build_mesh, so on real TPUs the devices come
    ICI-ordered from mesh_utils and on CPU (the forced-host-device test
    mesh, ``--xla_force_host_platform_device_count=N``) it is a plain
    stable reshape."""
    n = len(jax.devices()) if tp_degree is None else int(tp_degree)
    if n < 1:
        raise ValueError(f"tp_degree must be >= 1, got {tp_degree}")
    if n > len(jax.devices()):
        raise ValueError(
            f"tp_degree={n} exceeds the {len(jax.devices())} visible "
            f"device(s)")
    return build_mesh({axis_name: n})

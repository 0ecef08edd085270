"""Device / Place abstraction.

Reference parity: paddle/fluid/platform/place.h:26-150 (CPUPlace/CUDAPlace/Place
tagged union) and device_context.h:109/805 (DeviceContext + pool).  TPU-native
design: a Place names a jax.Device; the "device context" role (stream + handle
ownership) is played by PJRT inside jax, so the pool here is just a thin registry
plus the current-device state used by tensor creation.
"""
import functools
import threading
import warnings

import jax

_state = threading.local()


class Place:
    """Device identity. device_type in {'cpu', 'tpu', 'gpu'}."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type, device_id=0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        devs = _devices_of_type(self.device_type)
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"{self!r}: device id out of range, {len(devs)} "
                f"device(s) visible")
        return devs[self.device_id]


def CPUPlace():
    return Place("cpu", 0)


def TPUPlace(device_id=0):
    return Place("tpu", device_id)


def CUDAPlace(device_id=0):  # accepted for API parity; maps to accelerator 0
    return Place("gpu", device_id)


def _devices_of_type(device_type):
    if device_type == "cpu":
        return jax.devices("cpu")
    # Any non-cpu type names the default backend's devices.  Reference
    # scripts say CUDAPlace/TPUPlace on accelerator-less hosts too (the
    # CPU test mesh), so that maps onto the CPU — said once, not silently.
    default = jax.devices()
    if default[0].platform == "cpu":
        _warn_accelerator_place_on_cpu(device_type)
    return default


@functools.cache
def _warn_accelerator_place_on_cpu(device_type):
    warnings.warn(
        f"a {device_type!r} place was asked for but JAX sees no "
        f"accelerator: it is placed on the CPU backend", stacklevel=4)


def _default_device_type():
    d = jax.devices()[0]
    return "cpu" if d.platform == "cpu" else "tpu"


def set_device(device):
    """paddle.set_device('tpu') / 'tpu:0' / 'cpu'."""
    if isinstance(device, Place):
        _state.place = device
        return device
    name, _, idx = device.partition(":")
    if name in ("gpu", "cuda", "xpu", "npu"):
        name = "tpu" if _default_device_type() == "tpu" else "cpu"
    place = Place(name, int(idx) if idx else 0)
    _state.place = place
    return place


def get_device():
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def current_place():
    if not hasattr(_state, "place"):
        _state.place = Place(_default_device_type(), 0)
    return _state.place


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return any(d.platform != "cpu" for d in jax.devices())


def device_count():
    return len(jax.devices())


def CUDAPinnedPlace():
    """Pinned host memory place (place.h:89); host arrays are already
    transfer-staged under PJRT, so this is the CPU place."""
    return Place("cpu", 0)


def XPUPlace(device_id=0):
    return Place("tpu", device_id)  # accelerator alias, like CUDAPlace


def NPUPlace(device_id=0):
    return Place("tpu", device_id)


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_rocm():
    return False


def get_cudnn_version():
    return None  # no cuDNN in a TPU build (API parity)

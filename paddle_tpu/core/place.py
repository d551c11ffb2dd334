"""Device / Place abstraction.

Reference parity: paddle/fluid/platform/place.h (CPUPlace/CUDAPlace/CUDAPinnedPlace)
and python/paddle/device.py (set_device/get_device). TPU-first: the accelerator
place is TPUPlace (alias XLAPlace); CUDAPlace maps onto it so unmodified scripts
using ``paddle.CUDAPlace(0)`` still target the accelerator.
"""
import jax


class Place:
    def __init__(self, device_id=0):
        self._device_id = int(device_id)

    def get_device_id(self):
        return self._device_id

    def __eq__(self, other):
        return type(self) is type(other) and self._device_id == other._device_id

    def __hash__(self):
        return hash((type(self).__name__, self._device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self._device_id})"


class CPUPlace(Place):
    def jax_device(self):
        return jax.devices('cpu')[self._device_id] if _has_platform('cpu') else None


class TPUPlace(Place):
    def jax_device(self):
        devs = jax.devices()
        if not 0 <= self._device_id < len(devs):
            raise ValueError(
                "%r: this process has %d device(s) (%s)"
                % (self, len(devs), devs[0].device_kind))
        return devs[self._device_id]


# Aliases so reference-era scripts run unmodified on TPU.
XLAPlace = TPUPlace
XPUPlace = TPUPlace
CUDAPlace = TPUPlace


class CUDAPinnedPlace(CPUPlace):
    pass


_current_device = ["auto"]


def _has_platform(name):
    try:
        return len(jax.devices(name)) > 0
    except RuntimeError:
        return False


def set_device(device):
    """device: 'cpu', 'tpu', 'tpu:0', 'gpu:0' (alias for tpu on this build)."""
    device = device.lower()
    _current_device[0] = device
    return get_place()


def get_device():
    if _current_device[0] == "auto":
        plat = jax.default_backend()
        return ("cpu" if plat == "cpu" else "tpu") + ":0"
    return _current_device[0]


def get_place():
    d = get_device()
    name, _, idx = d.partition(":")
    idx = int(idx or 0)
    return CPUPlace(idx) if name == "cpu" else TPUPlace(idx)


def default_jax_device():
    return get_place().jax_device()


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_tpu():
    return True


def device_count():
    return jax.device_count()

"""Device management. Parity: python/paddle/device.py."""
import jax

from ..core.place import (set_device, get_device, get_place, CPUPlace, TPUPlace,
                          XLAPlace, CUDAPlace, is_compiled_with_cuda,
                          is_compiled_with_tpu, device_count)

__all__ = ['set_device', 'get_device', 'get_place', 'CPUPlace', 'TPUPlace',
           'XLAPlace', 'CUDAPlace', 'is_compiled_with_cuda',
           'is_compiled_with_tpu', 'device_count', 'get_all_device_type',
           'get_available_device', 'synchronize', 'memory_stats']


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def synchronize(device=None):
    """Block until all queued device work completes."""
    (jax.device_put(0) + 0).block_until_ready()


def memory_stats(device=None):
    """Live/peak HBM bytes of ``device`` — a ``jax.Device``, a Place, or
    None for the current place's device (parity: fluid/memory stats). The
    CPU backend reports none: ``{}``."""
    if device is None:
        device = get_place()
    if hasattr(device, 'jax_device'):
        device = device.jax_device()
    return device.memory_stats() or {}


class cuda:
    """Namespace shim: paddle.device.cuda.* maps onto the TPU device."""

    @staticmethod
    def device_count():
        return jax.device_count()

    @staticmethod
    def synchronize(device=None):
        synchronize()


def get_cudnn_version():
    """No cuDNN on TPU: None (device.py get_cudnn_version for non-CUDA
    builds; same value as paddle.get_cudnn_version)."""
    return None


__all__ += ['get_cudnn_version']

"""paddle_tpu: a TPU-native deep learning framework with PaddlePaddle's API.

Compute path: JAX/XLA (+ Pallas kernels); eager dygraph semantics with a
vjp tape; whole-program XLA compilation for static graph & jitted train steps;
SPMD parallelism over jax.sharding meshes.
"""
import time as _time
# graftlint: disable=GL011 — a stamp, not a timing: where the span record
# `paddle_tpu.import` starts (observability.state.note_import, last line)
_IMPORT_T0_NS = _time.perf_counter_ns()

from .core.tensor import Tensor, Parameter, to_tensor
from .core import autograd
from .core.autograd import no_grad, enable_grad, grad, is_grad_enabled, set_grad_enabled
from .core.dtypes import (
    bool, uint8, int8, int16, int32, int64, float16, bfloat16, float32,
    float64, complex64, complex128, set_default_dtype, get_default_dtype)
from .core.place import (
    CPUPlace, TPUPlace, XLAPlace, CUDAPlace, CUDAPinnedPlace, set_device,
    get_device, is_compiled_with_cuda, is_compiled_with_tpu, is_compiled_with_xpu,
    device_count)
from .core.rng import seed, get_rng_state, set_rng_state, Generator

from .tensor import *  # noqa: F401,F403
from .tensor import creation, math, manipulation, linalg, logic, search, stat, random

from . import nn
from . import optimizer
from . import io
from . import metric
from . import distribution
from . import vision
from . import text
from . import rec
from . import distributed
from . import static
from . import jit
from . import amp
from . import incubate
from . import observability
from . import resilience
from . import engine
from . import utils
from . import dataset
from . import device
from . import inference
from . import interop
from . import reader
from . import slim
from . import serving
from . import regularizer
from . import sysconfig
from .framework import save, load, in_dynamic_mode, enable_static, disable_static, in_static_mode
from .hapi.model import Model
from .hapi.model_summary import summary
from .hapi import callbacks
from .nn.initializer import ParamAttr
from .utils.profiler import profiler
from . import version
from .utils.install_check import run_check
from .batch import batch
from . import fluid  # compat namespace

disable_signal_handler = lambda: None

__version__ = version.full_version
__git_commit__ = version.commit


def check_import_scipy(os_name):
    """Parity: python/paddle/check_import_scipy.py:16 — a Windows DLL
    diagnostic for scipy imports; non-Windows (this environment) is a
    no-op there too."""
    if os_name == 'nt':
        try:
            import scipy.io  # noqa: F401
        except ImportError as e:
            raise ImportError(
                str(e) + "\nscipy failed to import: on Windows check "
                "the VC++ redistributable installation")



def flops(net, input_size, custom_ops=None, print_detail=False):
    """Rough FLOPs estimator (parity: paddle.flops)."""
    from .hapi.model_summary import flops as _flops
    return _flops(net, input_size, custom_ops=custom_ops, print_detail=print_detail)

# -- 2.0-beta top-level alias tail (parity: python/paddle/__init__.py's
# #DEFINE_ALIAS block) ------------------------------------------------------
from .static.graph import Variable  # noqa: E402,F401
from .fluid.layers import (  # noqa: E402,F401
    create_parameter, create_global_var, crop_tensor, fill_constant,
    has_inf, has_nan, reduce_all, reduce_any, reduce_max, reduce_mean,
    reduce_min, reduce_prod, reduce_sum, sums, unique_with_counts)
from .fluid.lr_schedules import (  # noqa: E402,F401
    cosine_decay as _cosine_decay_fn,
    exponential_decay as _exp_decay_fn,
    inverse_time_decay as _inv_decay_fn,
    natural_exp_decay as _nat_decay_fn,
    polynomial_decay as _poly_decay_fn)
from .optimizer.lr import (NoamDecay, PiecewiseDecay)  # noqa: E402,F401
from .distributed import DataParallel  # noqa: E402,F401


def CosineDecay(learning_rate, step_each_epoch, epochs, **kw):
    """fluid.dygraph.CosineDecay-signature factory (2.0-beta alias)."""
    return _cosine_decay_fn(learning_rate, step_each_epoch, epochs)


def ExponentialDecay(learning_rate, decay_steps, decay_rate,
                     staircase=False, **kw):
    return _exp_decay_fn(learning_rate, decay_steps, decay_rate, staircase)


def InverseTimeDecay(learning_rate, decay_steps, decay_rate,
                     staircase=False, **kw):
    return _inv_decay_fn(learning_rate, decay_steps, decay_rate, staircase)


def NaturalExpDecay(learning_rate, decay_steps, decay_rate,
                    staircase=False, **kw):
    return _nat_decay_fn(learning_rate, decay_steps, decay_rate, staircase)


def PolynomialDecay(learning_rate, decay_steps, end_learning_rate=0.0001,
                    power=1.0, cycle=False, **kw):
    return _poly_decay_fn(learning_rate, decay_steps, end_learning_rate,
                          power, cycle)


def to_variable(value, name=None, zero_copy=None, dtype=None):
    """fluid.dygraph.to_variable alias."""
    return to_tensor(value, dtype=dtype)


def manual_seed(s):
    return seed(s)


def addcmul(input, tensor1, tensor2, value=1.0, name=None):
    """out = input + value * tensor1 * tensor2 (2.0-beta op)."""
    return input + tensor1 * tensor2 * value


def elementwise_sum(inputs, name=None):
    return sums(inputs)


def inverse(x, name=None):
    """Matrix inverse (2.0-beta top-level op)."""
    import jax.numpy as _jnp
    from .core.tensor import apply_op as _apply_op
    from .tensor._helpers import _t as _tt
    return _apply_op(lambda v: _jnp.linalg.inv(v), (_tt(x),))


def shuffle(x, name=None):
    """Random row shuffle (2.0-beta top-level op)."""
    import jax as _jax
    from .core.rng import next_key as _nk
    from .core.tensor import apply_op as _apply_op
    from .tensor._helpers import _t as _tt
    key = _nk()
    return _apply_op(
        lambda v: v[_jax.random.permutation(key, v.shape[0])], (_tt(x),))


def get_cuda_rng_state():
    """No CUDA here: returns the global generator state (the TPU/host RNG
    that actually drives sampling) for checkpoint symmetry."""
    from .core import rng as _rng
    return _rng.current_generator().get_state()


def set_cuda_rng_state(state):
    from .core import rng as _rng
    _rng.current_generator().set_state(state)


class SaveLoadConfig:
    """Config holder for jit.save/load (2.0-beta API)."""

    def __init__(self):
        self.output_spec = None
        self.model_filename = None
        self.params_filename = None
        self.separate_params = False
        self.keep_name_table = False


# -- 1.8 top-level compat tail (the last names the reference's
# python/paddle/__init__.py re-exports that have no 2.x home) --------------
from .fluid.lod_tensor import (LoDTensor, LoDTensorArray)  # noqa: E402,F401
from .static import data  # noqa: E402,F401

# the reference's ComplexVariable pairs two real tensors (incubate/complex);
# here complex64/128 are native Tensor dtypes, so the alias IS Tensor
ComplexTensor = Tensor


def get_cudnn_version():
    """No cuDNN on TPU: None, the reference's value for non-CUDA builds
    (python/paddle/device.py get_cudnn_version)."""
    return None


def get_tensor_from_selected_rows(x, name=None):
    """The reference densifies a SelectedRows gradient
    (operators/get_tensor_from_selected_rows_op.cc). Sparse gradients here
    are already dense (XLA scatter-add in the embedding vjp), so any
    tensor-like input passes through; true SelectedRows never exist."""
    return to_tensor(x)


def monkey_patch_math_varbase():
    """No-op: eager Tensor operators are installed at import
    (core/tensor.py), not lazily like the reference's VarBase patching."""


def monkey_patch_variable():
    """No-op: static Variable operators are installed at import
    (static/graph.py)."""


observability.state.note_import(_IMPORT_T0_NS)

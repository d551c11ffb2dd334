"""paddle_tpu.nn. Parity: python/paddle/nn/__init__.py."""
from .layer_base import Layer, functional_call, state_values, param_values, \
    buffer_values, load_state_values
from . import functional
from . import initializer
from .initializer import ParamAttr
from .clip import (ClipGradByValue, ClipGradByNorm, ClipGradByGlobalNorm,
                   GradientClipByValue, GradientClipByNorm,
                   GradientClipByGlobalNorm, clip_grad_norm_)
from .regularizer import L1Decay, L2Decay

from .layer.container import Sequential, LayerList, ParameterList, LayerDict
from .layer.common import (Identity, Linear, Embedding, Flatten, Dropout,
                           Dropout2D, Dropout3D, AlphaDropout, Upsample,
                           UpsamplingNearest2D, UpsamplingBilinear2D, Pad1D,
                           Pad2D, Pad3D, ZeroPad2D, CosineSimilarity,
                           PixelShuffle, PixelUnshuffle, Bilinear, Unfold, Fold)
from .layer.conv import (Conv1D, Conv2D, Conv3D, Conv1DTranspose,
                         Conv2DTranspose, Conv3DTranspose)
from .layer.pooling import (MaxPool1D, MaxPool2D, MaxPool3D, AvgPool1D,
                            AvgPool2D, AvgPool3D, AdaptiveAvgPool1D,
                            AdaptiveAvgPool2D, AdaptiveAvgPool3D,
                            AdaptiveMaxPool1D, AdaptiveMaxPool2D,
                            AdaptiveMaxPool3D)
from .layer.norm import (BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D,
                         SyncBatchNorm, LayerNorm, RMSNorm, GroupNorm,
                         InstanceNorm1D, InstanceNorm2D, InstanceNorm3D,
                         LocalResponseNorm, SpectralNorm)
from .layer.activation import (ReLU, ReLU6, LeakyReLU, PReLU, RReLU, ELU, CELU,
                               GELU, Sigmoid, Hardsigmoid, Hardswish,
                               Hardshrink, Hardtanh, Softplus, Softshrink,
                               Softsign, Swish, Silu, Mish, Tanh, Tanhshrink,
                               ThresholdedReLU, LogSigmoid, LogSoftmax, Softmax,
                               Maxout, GLU, SELU)
from .layer.loss import (CrossEntropyLoss, MSELoss, L1Loss, NLLLoss, BCELoss,
                         BCEWithLogitsLoss, KLDivLoss, SmoothL1Loss,
                         MarginRankingLoss, CTCLoss, HingeEmbeddingLoss,
                         CosineEmbeddingLoss, TripletMarginLoss)
from .layer.rnn import (RNNCellBase, SimpleRNNCell, LSTMCell, GRUCell, RNN,
                        BiRNN, SimpleRNN, LSTM, GRU)
from .decode import (Decoder, BeamSearchDecoder, dynamic_decode, DecodeHelper,
                     TrainingHelper, GreedyEmbeddingHelper,
                     SampleEmbeddingHelper, BasicDecoder)
from .layer.transformer import (MultiHeadAttention, TransformerEncoderLayer,
                                TransformerEncoder, TransformerDecoderLayer,
                                TransformerDecoder, Transformer)
from .layer.distance import PairwiseDistance
from .layer.linear_attention import (CausalSelfAttention, GatedDeltaNet,
                                     GroupedQueryAttention,
                                     KimiDeltaAttention, LatentAttention)
from .layer.moe import SwiGLU, SquaredReLU, SparseMoE
from .layer.state_space import Mamba2
from .utils import weight_norm, remove_weight_norm, spectral_norm

# -- 2.0-beta top-level nn surface tail --------------------------------------
# (parity: python/paddle/nn/__init__.py — the beta exported lowercase-`d`
# layer aliases, 1.8 holdover layers, the control-flow fns, and the layer
# submodules at nn top level)
from .layer import common, conv, norm, rnn, loss  # noqa: F401
from ..nn.functional import extension  # noqa: F401
from ..nn.functional import vision  # noqa: F401
from .layer.common import (Pad1D as ConstantPad1d,  # noqa: F401
                           Pad2D as ConstantPad2d,
                           Pad3D as ConstantPad3d,
                           ZeroPad2D as ZeroPad2d,
                           UpsamplingNearest2D as UpsamplingNearest2d,
                           UpsamplingBilinear2D as UpsamplingBilinear2d)
from ..fluid.layers import (beam_search, beam_search_decode,  # noqa: F401
                            gather_tree, cond, case, switch_case,
                            while_loop, clip_by_norm)
from . import utils as weight_norm_hook  # noqa: F401


def _pad_subclass(base, mode, fmt, name):
    """Mode-fixed pad layer CLASSES (isinstance/subclass must work)."""
    def __init__(self, padding, data_format=None, _name=None):
        base.__init__(self, padding, mode=mode,
                      data_format=data_format or fmt)
    return type(name, (base,), {'__init__': __init__})


ReflectionPad1d = _pad_subclass(Pad1D, 'reflect', 'NCL', 'ReflectionPad1d')
ReflectionPad2d = _pad_subclass(Pad2D, 'reflect', 'NCHW', 'ReflectionPad2d')
ReplicationPad1d = _pad_subclass(Pad1D, 'replicate', 'NCL',
                                 'ReplicationPad1d')
ReplicationPad2d = _pad_subclass(Pad2D, 'replicate', 'NCHW',
                                 'ReplicationPad2d')
ReplicationPad3d = _pad_subclass(Pad3D, 'replicate', 'NCDHW',
                                 'ReplicationPad3d')

# lowercase-d beta aliases
Conv1d, Conv2d, Conv3d = Conv1D, Conv2D, Conv3D
ConvTranspose1d = Conv1DTranspose
ConvTranspose2d = Conv2DTranspose
ConvTranspose3d = Conv3DTranspose
BatchNorm1d, BatchNorm2d, BatchNorm3d = BatchNorm1D, BatchNorm2D, BatchNorm3D
InstanceNorm1d, InstanceNorm2d, InstanceNorm3d = (InstanceNorm1D,
                                                  InstanceNorm2D,
                                                  InstanceNorm3D)
MaxPool1d, MaxPool2d, MaxPool3d = MaxPool1D, MaxPool2D, MaxPool3D
AvgPool1d, AvgPool2d, AvgPool3d = AvgPool1D, AvgPool2D, AvgPool3D
AdaptiveMaxPool1d = AdaptiveMaxPool1D
AdaptiveMaxPool2d = AdaptiveMaxPool2D
AdaptiveMaxPool3d = AdaptiveMaxPool3D
AdaptiveAvgPool1d = AdaptiveAvgPool1D
AdaptiveAvgPool2d = AdaptiveAvgPool2D
AdaptiveAvgPool3d = AdaptiveAvgPool3D
Dropout2d, Dropout3d = Dropout2D, Dropout3D

# 1.8 holdover layers — lazy: fluid.dygraph imports jit which imports nn,
# so a module-level import here would close an import cycle
def __getattr__(name):
    if name in ('BilinearTensorProduct', 'InstanceNorm'):
        from ..fluid import dygraph as _D
        return getattr(_D, name)
    raise AttributeError(f"module 'paddle.nn' has no attribute {name!r}")


class Pool2D(Layer):
    """1.8 dygraph.Pool2D: pool_type/pool_size signature."""

    def __init__(self, pool_size=-1, pool_type="max", pool_stride=1,
                 pool_padding=0, global_pooling=False, use_cudnn=True,
                 ceil_mode=False, exclusive=True, data_format="NCHW"):
        super().__init__()
        self._kw = dict(pool_size=pool_size, pool_type=pool_type,
                        pool_stride=pool_stride, pool_padding=pool_padding,
                        global_pooling=global_pooling, ceil_mode=ceil_mode,
                        exclusive=exclusive, data_format=data_format)

    def forward(self, input):
        from ..fluid.layers import pool2d
        return pool2d(input, **self._kw)


class HSigmoid(Layer):
    """1.8 hierarchical-sigmoid layer over the functional hsigmoid."""

    def __init__(self, feature_size, num_classes, param_attr=None,
                 bias_attr=None, is_custom=False, is_sparse=False):
        super().__init__()
        from ..fluid.layers_tail import _op_param
        from .initializer import XavierUniform, Constant
        n_nodes = max(num_classes - 1, 1)
        self.weight = _op_param([n_nodes, feature_size], param_attr,
                                XavierUniform(), 'hsigmoid_w')
        self.bias = _op_param([n_nodes], bias_attr, Constant(0.0),
                              'hsigmoid_b')
        self._num_classes = num_classes
        self._is_custom = is_custom

    def forward(self, input, label, path_table=None, path_code=None):
        # inject this layer's persistent weight/bias by rebuilding the
        # functional loss against them
        import jax.numpy as jnp
        import math as _math
        from ..core.tensor import apply_op
        from ..tensor._helpers import _t
        num_classes = self._num_classes
        n_nodes = max(num_classes - 1, 1)
        depth = max(int(_math.ceil(_math.log2(max(num_classes, 2)))), 1)
        if self._is_custom:
            def fn(xv, lv, wv, bv, ptv, pcv):
                nodes = ptv.astype(jnp.int32)
                codes = pcv.astype(xv.dtype)
                valid = (nodes >= 0)
                nid = jnp.maximum(nodes, 0)
                s = jnp.einsum('bd,bkd->bk', xv, wv[nid]) + bv[nid]
                z = (1.0 - 2.0 * codes) * s
                sp = jnp.maximum(-z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z)))
                return jnp.where(valid, sp, 0.0).sum(axis=1, keepdims=True)
            return apply_op(fn, (_t(input), _t(label), self.weight,
                                 self.bias, _t(path_table), _t(path_code)))

        def fn(xv, lv, wv, bv):
            leaf = lv.astype(jnp.int32).reshape(-1) + num_classes
            losses = jnp.zeros((xv.shape[0],), xv.dtype)
            node = leaf
            for _ in range(depth):
                code = (node % 2).astype(xv.dtype)
                parent = node // 2
                valid = parent >= 1
                nid = jnp.clip(parent - 1, 0, n_nodes - 1)
                s = jnp.einsum('bd,bd->b', xv, wv[nid]) + bv[nid]
                z = (1.0 - 2.0 * code) * s
                sp = jnp.maximum(-z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z)))
                losses = losses + jnp.where(valid, sp, 0.0)
                node = parent
            return losses[:, None]
        return apply_op(fn, (_t(input), _t(label), self.weight, self.bias))


class RowConv(Layer):
    """1.8 lookahead row convolution layer."""

    def __init__(self, num_channels, future_context_size, param_attr=None,
                 act=None):
        super().__init__()
        from ..fluid.layers_tail import _op_param
        from .initializer import XavierUniform
        self.weight = _op_param([future_context_size + 1, num_channels],
                                param_attr, XavierUniform(), 'row_conv_w')
        self._act = act
        self._k = future_context_size + 1

    def forward(self, input):
        import jax.numpy as jnp
        from ..core.tensor import apply_op
        from ..tensor._helpers import _t
        k = self._k

        def fn(v, wv):
            pad = jnp.pad(v, ((0, 0), (0, k - 1), (0, 0)))
            out = pad[:, 0:v.shape[1], :] * wv[0]
            for i in range(1, k):
                out = out + pad[:, i:i + v.shape[1], :] * wv[i]
            return out

        out = apply_op(fn, (_t(input), self.weight))
        if self._act:
            out = getattr(functional, self._act)(out)
        return out

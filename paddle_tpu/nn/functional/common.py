"""Common functionals: linear/embedding/dropout/pad/interpolate/...

Parity: python/paddle/nn/functional/common.py + input.py.
"""
import numbers
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ...core.tensor import Tensor, apply_op
from ...core import rng as _rng
from ...core.dtypes import convert_dtype
from ...kernels._common import keep_mask, rows_first
from ...tensor._helpers import _t

__all__ = ['linear', 'embedding', 'one_hot', 'label_smooth', 'dropout',
           'dropout2d', 'dropout3d', 'alpha_dropout', 'pad', 'zeropad2d',
           'interpolate', 'upsample', 'bilinear', 'cosine_similarity',
           'pixel_shuffle', 'pixel_unshuffle', 'unfold', 'fold', 'class_center_sample']


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b, W shape (in, out) — parity: F.linear.

    Under amp.auto_cast, x/W are cast to the amp dtype (bf16 on TPU) so the
    matmul hits the MXU at low precision while the bias add stays fused.
    """
    from ...amp import maybe_cast_for

    def mm(v, w, *b):
        v, w = maybe_cast_for('matmul', v, w)
        out = jnp.matmul(v, w)
        if b:
            out = out + b[0].astype(out.dtype)
        return out
    if bias is None:
        return apply_op(mm, (_t(x), _t(weight)))
    return apply_op(mm, (_t(x), _t(weight), _t(bias)))


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Gather rows; padding_idx rows get zero gradient (zeroed lookup).

    TPU-first: 'sparse' grads become dense gathers — XLA scatter-add handles
    the backward; sharded vocab lives in distributed.sharded_embedding.
    """
    x, weight = _t(x), _t(weight)
    def fn(i, w):
        out = jnp.take(w, i, axis=0)
        if padding_idx is not None:
            mask = (i == padding_idx)[..., None]
            out = jnp.where(mask, jnp.zeros_like(out), out)
        return out
    return apply_op(fn, (x, weight))


def one_hot(x, num_classes, name=None):
    x = _t(x)
    return apply_op(lambda i: jax.nn.one_hot(i, num_classes, dtype=jnp.float32),
                    (x,), differentiable=False)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    label = _t(label)
    if prior_dist is not None:
        return apply_op(lambda l, p: (1 - epsilon) * l + epsilon * p,
                        (label, _t(prior_dist)))
    def fn(l):
        k = l.shape[-1]
        return (1 - epsilon) * l + epsilon / k
    return apply_op(fn, (label,))


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    x = _t(x)
    if not training or p == 0:
        if mode == "downscale_in_infer" and not training:
            return apply_op(lambda v: v * (1 - p), (x,))
        return x
    if p == 1:
        return apply_op(lambda v: jnp.zeros_like(v), (x,))
    key = _rng.next_key()
    axes = None
    if axis is not None:
        axes = [axis] if isinstance(axis, numbers.Integral) else list(axis)
    def fn(v):
        if axes is None:
            # the leading dim is the batch: in a sharded step each device
            # draws the mask of its own rows
            keep = keep_mask(key, 1.0 - p, v.shape, rows_first(v.ndim))
        else:
            shape = tuple(v.shape[i] if i in axes else 1 for i in range(v.ndim))
            keep = jax.random.bernoulli(key, 1.0 - p, shape)
        if mode == "upscale_in_train":
            return jnp.where(keep, v / (1.0 - p), jnp.zeros_like(v))
        return jnp.where(keep, v, jnp.zeros_like(v))

    # test-mode variant for Program.clone(for_test=True)
    if mode == "upscale_in_train":
        eval_fn = lambda v: v  # noqa: E731
    else:
        eval_fn = lambda v: v * (1 - p)  # noqa: E731
    return apply_op(fn, (x,), eval_fn=eval_fn)


def dropout2d(x, p=0.5, training=True, data_format='NCHW', name=None):
    axis = [0, 1] if data_format == 'NCHW' else [0, 3]
    return dropout(x, p=p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format='NCDHW', name=None):
    axis = [0, 1] if data_format == 'NCDHW' else [0, 4]
    return dropout(x, p=p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    x = _t(x)
    if not training or p == 0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    a = ((1 - p) * (1 + p * alpha_p ** 2)) ** -0.5
    b = -a * alpha_p * p
    key = _rng.next_key()
    def fn(v):
        keep = jax.random.bernoulli(key, 1.0 - p, v.shape)
        return a * jnp.where(keep, v, alpha_p) + b
    return apply_op(fn, (x,))


def _pad_pairs(pad, ndim, data_format):
    """Convert paddle pad spec (last-dim-first pairs) to jnp.pad pairs."""
    if len(pad) == 2 * ndim:
        pairs = [(int(pad[2 * i]), int(pad[2 * i + 1])) for i in range(ndim)]
        return pairs
    n_spatial = len(pad) // 2
    pairs_spatial = [(int(pad[2 * i]), int(pad[2 * i + 1]))
                     for i in range(n_spatial)]
    pairs = [(0, 0)] * ndim
    if data_format.startswith('NC'):
        for i, pr in enumerate(pairs_spatial):
            pairs[ndim - 1 - i] = pr
    else:  # NHWC-style: spatial dims are 1..ndim-2
        for i, pr in enumerate(pairs_spatial):
            pairs[ndim - 2 - i] = pr
    return pairs


def pad(x, pad, mode='constant', value=0.0, data_format="NCHW", name=None):
    x = _t(x)
    if isinstance(pad, Tensor):
        pad = pad.numpy().tolist()
    pad = list(pad)
    nd = x.ndim
    pairs = _pad_pairs(pad, nd, data_format)
    jmode = {'constant': 'constant', 'reflect': 'reflect', 'replicate': 'edge',
             'edge': 'edge', 'circular': 'wrap'}[mode]
    def fn(v):
        if jmode == 'constant':
            return jnp.pad(v, pairs, mode='constant', constant_values=value)
        return jnp.pad(v, pairs, mode=jmode)
    return apply_op(fn, (x,))


def zeropad2d(x, padding, data_format="NCHW", name=None):
    return pad(x, padding, mode='constant', value=0.0, data_format=data_format)


def _resize_axis_coords(out_size, in_size, align_corners, align_mode, scale=None):
    if align_corners:
        if out_size == 1:
            return jnp.zeros((1,))
        return jnp.arange(out_size) * ((in_size - 1) / (out_size - 1))
    ratio = (in_size / out_size) if scale is None else (1.0 / scale)
    if align_mode == 0:
        return jnp.maximum((jnp.arange(out_size) + 0.5) * ratio - 0.5, 0)
    return jnp.arange(out_size) * ratio


def interpolate(x, size=None, scale_factor=None, mode='nearest',
                align_corners=False, align_mode=0, data_format='NCHW', name=None):
    """Parity: F.interpolate (nearest/bilinear/bicubic/trilinear/area/linear)."""
    x = _t(x)
    nd = x.ndim
    channel_last = not data_format.startswith('NC')
    spatial_axes = list(range(1, nd - 1)) if channel_last else list(range(2, nd))

    in_sizes = [x.shape[a] for a in spatial_axes]
    if size is not None:
        if isinstance(size, Tensor):
            size = size.numpy().tolist()
        out_sizes = [int(s.item()) if isinstance(s, Tensor) else int(s) for s in
                     (size if isinstance(size, (list, tuple)) else [size])]
        scales = [None] * len(out_sizes)
    else:
        if isinstance(scale_factor, (int, float)):
            scale_factor = [scale_factor] * len(in_sizes)
        out_sizes = [int(s * f) for s, f in zip(in_sizes, scale_factor)]
        scales = list(scale_factor)

    method = {'nearest': 'nearest', 'bilinear': 'linear', 'linear': 'linear',
              'trilinear': 'linear', 'bicubic': 'cubic', 'area': 'linear'}[mode]

    if method == 'nearest' or (not align_corners and align_mode == 1 and
                               method == 'linear' and False):
        def fn(v):
            out = v
            for ax, (osz, isz) in zip(spatial_axes, zip(out_sizes, in_sizes)):
                idx = jnp.clip(jnp.floor(jnp.arange(osz) * (isz / osz)), 0,
                               isz - 1).astype(jnp.int32)
                out = jnp.take(out, idx, axis=ax)
            return out
        return apply_op(fn, (x,))

    if method == 'cubic':
        def fn(v):
            shape = list(v.shape)
            for a, s in zip(spatial_axes, out_sizes):
                shape[a] = s
            return jax.image.resize(v, shape, method='cubic')
        return apply_op(fn, (x,))

    # linear/bilinear/trilinear with paddle's align semantics via gather+lerp
    def fn(v):
        out = v
        for ax, (osz, isz, sc) in zip(spatial_axes,
                                      zip(out_sizes, in_sizes, scales)):
            coords = _resize_axis_coords(osz, isz, align_corners, align_mode, sc)
            lo = jnp.clip(jnp.floor(coords).astype(jnp.int32), 0, isz - 1)
            hi = jnp.clip(lo + 1, 0, isz - 1)
            w = (coords - lo).astype(v.dtype)
            shape_w = [1] * out.ndim
            shape_w[ax] = osz
            w = w.reshape(shape_w)
            out = (1 - w) * jnp.take(out, lo, axis=ax) + w * jnp.take(out, hi, axis=ax)
        return out
    return apply_op(fn, (x,))


def upsample(x, size=None, scale_factor=None, mode='nearest', align_corners=False,
             align_mode=0, data_format='NCHW', name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode,
                       data_format)


def bilinear(x1, x2, weight, bias=None, name=None):
    """y_k = x1 W_k x2^T (+ b). weight: (out, in1, in2)."""
    if bias is None:
        return apply_op(lambda a, b, w: jnp.einsum('bi,oij,bj->bo', a, w, b),
                        (_t(x1), _t(x2), _t(weight)))
    return apply_op(lambda a, b, w, bb: jnp.einsum('bi,oij,bj->bo', a, w, b) + bb,
                    (_t(x1), _t(x2), _t(weight), _t(bias)))


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    def fn(a, b):
        dot = jnp.sum(a * b, axis=axis)
        na = jnp.sqrt(jnp.sum(a * a, axis=axis))
        nb = jnp.sqrt(jnp.sum(b * b, axis=axis))
        return dot / jnp.maximum(na * nb, eps)
    return apply_op(fn, (_t(x1), _t(x2)))


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    x = _t(x)
    r = upscale_factor
    def fn(v):
        if data_format == "NCHW":
            n, c, h, w = v.shape
            v = v.reshape(n, c // (r * r), r, r, h, w)
            v = v.transpose(0, 1, 4, 2, 5, 3)
            return v.reshape(n, c // (r * r), h * r, w * r)
        n, h, w, c = v.shape
        v = v.reshape(n, h, w, r, r, c // (r * r))
        v = v.transpose(0, 1, 3, 2, 4, 5)
        return v.reshape(n, h * r, w * r, c // (r * r))
    return apply_op(fn, (x,))


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    x = _t(x)
    r = downscale_factor
    def fn(v):
        if data_format == "NCHW":
            n, c, h, w = v.shape
            v = v.reshape(n, c, h // r, r, w // r, r)
            v = v.transpose(0, 1, 3, 5, 2, 4)
            return v.reshape(n, c * r * r, h // r, w // r)
        n, h, w, c = v.shape
        v = v.reshape(n, h // r, r, w // r, r, c)
        v = v.transpose(0, 1, 3, 2, 4, 5)
        return v.reshape(n, h // r, w // r, c * r * r)
    return apply_op(fn, (x,))


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col. x: (N, C, H, W) -> (N, C*kh*kw, L)."""
    x = _t(x)
    def norm2(v):
        return [v, v] if isinstance(v, int) else list(v)
    kh, kw = norm2(kernel_sizes)
    sh, sw = norm2(strides)
    dh, dw = norm2(dilations)
    p = norm2(paddings)
    if len(p) == 2:
        pt, pb, pl, pr = p[0], p[0], p[1], p[1]
    else:
        pt, pb, pl, pr = p
    def fn(v):
        n, c, h, w = v.shape
        v = jnp.pad(v, [(0, 0), (0, 0), (pt, pb), (pl, pr)])
        hh, ww = v.shape[2], v.shape[3]
        oh = (hh - (dh * (kh - 1) + 1)) // sh + 1
        ow = (ww - (dw * (kw - 1) + 1)) // sw + 1
        patches = lax.conv_general_dilated_patches(
            v, (kh, kw), (sh, sw), 'VALID', rhs_dilation=(dh, dw),
            dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
        return patches.reshape(n, c * kh * kw, oh * ow)
    return apply_op(fn, (x,))


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """col2im — inverse of unfold via scatter-add."""
    x = _t(x)
    def norm2(v):
        return [v, v] if isinstance(v, int) else list(v)
    oh, ow = norm2(output_sizes)
    kh, kw = norm2(kernel_sizes)
    sh, sw = norm2(strides)
    dh, dw = norm2(dilations)
    p = norm2(paddings)
    if len(p) == 2:
        pt, pb, pl, pr = p[0], p[0], p[1], p[1]
    else:
        pt, pb, pl, pr = p
    def fn(v):
        n, ckk, L = v.shape
        c = ckk // (kh * kw)
        hh, ww = oh + pt + pb, ow + pl + pr
        nh = (hh - (dh * (kh - 1) + 1)) // sh + 1
        nw = (ww - (dw * (kw - 1) + 1)) // sw + 1
        v = v.reshape(n, c, kh, kw, nh, nw)
        out = jnp.zeros((n, c, hh, ww), v.dtype)
        for i in range(kh):
            for j in range(kw):
                hi = i * dh
                wj = j * dw
                out = out.at[:, :, hi:hi + nh * sh:sh, wj:wj + nw * sw:sw].add(
                    v[:, :, i, j])
        return out[:, :, pt:pt + oh, pl:pl + ow]
    return apply_op(fn, (x,))


def class_center_sample(label, num_classes, num_samples, group=None):
    """Sample ``num_samples`` class centers always containing the positives.

    For margin-softmax / partial-FC large-class training: the classification
    layer only materializes the sampled columns. Returns
    ``(remapped_label, sampled_class_center)``:
    - sampled_class_center: [num_samples] sorted ascending class ids — every
      class present in ``label`` (while they fit), topped up with uniformly
      random negatives;
    - remapped_label: [N] index of each label within sampled_class_center.

    TPU-first fixed-shape design: one jit-compatible top-k over a random
    priority vector (positives keyed into [0,1), negatives into [1,2)) —
    no host-side set arithmetic, fully static [num_samples] output. If more
    than num_samples distinct positive classes exist, a uniform subset is
    kept and the dropped ones remap to -1.
    """
    label = _t(label)
    if num_samples > num_classes:
        raise ValueError(
            "class_center_sample: num_samples (%d) must be <= num_classes "
            "(%d)" % (num_samples, num_classes))
    if num_samples == num_classes:
        # degenerate: keep every class, identity remap (shape stays
        # [num_samples] as documented)
        def fn_all(lv):
            sampled = jnp.arange(num_classes, dtype=lv.dtype)
            return lv, sampled
        return apply_op(fn_all, (label,), n_outputs=2,
                        differentiable=False)
    key = _rng.next_key()

    def fn(lv):
        lab = lv.reshape(-1).astype(jnp.int32)
        pos = jnp.zeros((num_classes,), jnp.bool_).at[lab].set(True)
        u = jax.random.uniform(key, (num_classes,))
        # positives sort strictly before any negative
        priority = jnp.where(pos, u, u + 1.0)
        _, sampled = jax.lax.top_k(-priority, num_samples)
        sampled = jnp.sort(sampled).astype(lv.dtype)
        table = jnp.full((num_classes,), -1, jnp.int32) \
            .at[sampled].set(jnp.arange(num_samples, dtype=jnp.int32))
        remapped = table[lab].reshape(lv.shape).astype(lv.dtype)
        return remapped, sampled

    return apply_op(fn, (label,), n_outputs=2, differentiable=False)

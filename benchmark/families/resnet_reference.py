"""Plain reference for the `resnet` family: ResNet training, written from the
papers, in `jax.numpy` and float32. It imports nothing of the program
(`harness.rounding` is the benchmark's own).

- He et al. 2015 (arXiv:1512.03385) section 3 and table 1: a 7x7/2 stem, a
  3x3/2 max pool, four stages of bottleneck blocks (1x1, 3x3, 1x1, the
  widths x4 on the way out), projection shortcuts where the shape changes,
  global average pooling and a fully connected layer; batch normalisation
  (Ioffe & Szegedy 2015, arXiv:1502.03167, algorithm 1: the batch's mean
  and biased variance) after every convolution, ReLU after it but for the
  last of a block, which comes after the addition. "v1.5": the stride of a
  downsampling block sits on its 3x3 convolution.
- The update is SGD with momentum as that paper's section 3.4 uses it
  (v <- mu v + g, w <- w - lr v), the gradient including the L2 penalty's
  weight_decay * w on every parameter.

Images come in as the program gets them, uint8 [N, H, W, 3]; they are scaled
to [0, 1], normalised with the configuration's per-channel mean and standard
deviation and put channels first. Convolution weights are [out, in, kh, kw],
the fully connected weight is [in, out].

`precision` is 'float32' (the reference) or 'float8' (the CONTROL:
convolution and matmul operands rounded to 4 exponent and 3 mantissa bits
(float8 e4m3) with a per-tensor scale).
"""
import functools

import jax
import jax.numpy as jnp

from harness.rounding import leaf_norms, round_to

BN_EPS = 1e-5


def _conv(x, w, stride, precision):
    pad = (w.shape[2] - 1) // 2
    return jax.lax.conv_general_dilated(
        round_to(x, precision), round_to(w, precision),
        window_strides=(stride, stride), padding=((pad, pad), (pad, pad)),
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'),
        precision=jax.lax.Precision.HIGHEST)


def _bn(x, p, name):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    g = p[name + '.weight'].reshape(1, -1, 1, 1)
    b = p[name + '.bias'].reshape(1, -1, 1, 1)
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * g + b


def _bottleneck(precision, stride, project, x, p):
    out = jax.nn.relu(_bn(_conv(x, p['conv1.weight'], 1, precision), p, 'bn1'))
    out = jax.nn.relu(_bn(_conv(out, p['conv2.weight'], stride, precision),
                          p, 'bn2'))
    out = _bn(_conv(out, p['conv3.weight'], 1, precision), p, 'bn3')
    if project:
        x = _bn(_conv(x, p['downsample.0.weight'], stride, precision),
                p, 'downsample.1')
    return jax.nn.relu(out + x)


def loss_fn(params, batch, *, cfg, precision):
    (images,), (labels,) = batch
    mean = jnp.asarray(cfg['pixel_mean'], jnp.float32)
    std = jnp.asarray(cfg['pixel_std'], jnp.float32)
    x = (images.astype(jnp.float32) / 255.0 - mean) / std
    x = x.transpose(0, 3, 1, 2)
    pre = cfg['param_prefix']
    p = {k[len(pre):]: v for k, v in params.items()}
    x = jax.nn.relu(_bn(_conv(x, p['conv1.weight'], 2, precision), p, 'bn1'))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), ((0, 0), (0, 0), (1, 1), (1, 1)))
    for stage, blocks in enumerate(cfg['stage_blocks'], 1):
        for b in range(blocks):
            name = 'layer%d.%d.' % (stage, b)
            sub = {k[len(name):]: v for k, v in p.items()
                   if k.startswith(name)}
            stride = 2 if (b == 0 and stage > 1) else 1
            block = jax.checkpoint(functools.partial(
                _bottleneck, precision, stride, b == 0))
            x = block(x, sub)
    x = jnp.mean(x, axis=(2, 3))
    logits = jnp.matmul(round_to(x, precision),
                        round_to(p['fc.weight'], precision),
                        precision=jax.lax.Precision.HIGHEST) + p['fc.bias']
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels.reshape(-1, 1),
                                         axis=-1))


def momentum_update(params, grads, velocity, *, lr, momentum, weight_decay):
    new_p, new_v = {}, {}
    for k, p in params.items():
        v = momentum * velocity[k] + grads[k] + weight_decay * p
        new_p[k] = p - lr * v
        new_v[k] = v
    return new_p, new_v


def follow_steps(cfg, optim, params, batches, precision='float32'):
    """Follow the first len(batches) optimizer steps from `params` on the
    host batches the program was fed, each ((uint8 images,), (labels,)).
    Returns {'losses', 'first_gradient', 'change_norms'}; the first
    gradient (every leaf, on the host) is the loss's own, without the L2
    term."""
    wd = optim['weight_decay']
    grad = jax.jit(jax.value_and_grad(functools.partial(
        loss_fn, cfg=cfg, precision=precision)))

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def update(p, g, v):
        return momentum_update(p, g, v, lr=optim['learning_rate'],
                               momentum=optim['momentum'], weight_decay=wd)

    change = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))
    start = params
    p = jax.tree_util.tree_map(jnp.copy, params)
    velocity = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = {'losses': []}
    for t, batch in enumerate(batches, 1):
        loss, g = grad(p, batch)
        out['losses'].append(float(loss))
        if t == 1:
            out['first_gradient'] = jax.device_get(g)
        p, velocity = update(p, g, velocity)
    out['change_norms'] = jax.device_get(change(p, start))
    return out

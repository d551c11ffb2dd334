"""Family `resnet`: ImageNet-shaped ResNet training through the program's
engine. See `bert.py` for what a family file holds; the plain reference is
`resnet_reference.py`, beside this file.
"""
import numpy as np

REFERENCE = 'resnet_reference'


def _convs(cfg):
    """Every convolution: (name, in, out, kernel, stride, output side)."""
    side = cfg['image_size'] // 2
    yield 'conv1', 3, cfg['stem_width'], 7, 2, side
    side //= 2                                   # the max pool
    inplanes = cfg['stem_width']
    for stage, (blocks, planes) in enumerate(
            zip(cfg['stage_blocks'], cfg['stage_widths']), 1):
        for b in range(blocks):
            name = 'layer%d.%d.' % (stage, b)
            stride = 2 if (b == 0 and stage > 1) else 1
            out = planes * cfg['expansion']
            yield name + 'conv1', inplanes, planes, 1, 1, side
            side //= stride
            yield name + 'conv2', planes, planes, 3, stride, side
            yield name + 'conv3', planes, out, 1, 1, side
            if b == 0:
                yield name + 'downsample.0', inplanes, out, 1, stride, side
            inplanes = out


def _norm_of(conv):
    if conv.endswith('downsample.0'):
        return conv[:-1] + '1'
    return conv.replace('conv', 'bn')


def param_spec(cfg):
    pre = cfg['param_prefix']
    spec = {}
    for name, cin, cout, k, _, _ in _convs(cfg):
        # He et al. 2015b (arXiv:1502.01852): std = sqrt(2 / fan_out)
        spec[pre + name + '.weight'] = (
            (cout, cin, k, k), 'normal:%r' % float(np.sqrt(2.0 / (cout * k * k))))
        # Goyal et al. 2017 (arXiv:1706.02677) section 5.1: the last batch
        # norm of a residual block starts at 0, so every block starts as the
        # identity. Without it the seeded net is 16 blocks of exploding
        # gradient deep, and bf16's rounding alone moves a leaf's gradient
        # norm by 16-18% (PERF.md section 6): nothing could be compared
        last = name.endswith('conv3')
        spec[pre + _norm_of(name) + '.weight'] = (
            (cout,), 'zeros' if last else 'ones')
        spec[pre + _norm_of(name) + '.bias'] = ((cout,), 'zeros')
    width = cfg['stage_widths'][-1] * cfg['expansion']
    spec[pre + 'fc.weight'] = ((width, cfg['num_classes']), 'normal:0.01')
    spec[pre + 'fc.bias'] = ((cfg['num_classes'],), 'zeros')
    return spec


def buffer_spec(cfg):
    pre = cfg['param_prefix']
    spec = {}
    for name, _, cout, _, _, _ in _convs(cfg):
        spec[pre + _norm_of(name) + '._mean'] = ((cout,), 'zeros')
        spec[pre + _norm_of(name) + '._variance'] = ((cout,), 'ones')
    return spec


def build(cfg, deterministic=False):
    """The program's (net, loss, optimizer). The net is the model behind a
    layer that takes the uint8 images as they come off the host, scales and
    normalises them on the device and puts the channels first."""
    from paddle_tpu import nn, optimizer
    from paddle_tpu.vision.models import resnet as zoo
    mean = np.asarray(cfg['pixel_mean'], np.float32) * 255.0
    scale = 1.0 / (np.asarray(cfg['pixel_std'], np.float32) * 255.0)

    class FromPixels(nn.Layer):
        def __init__(self):
            super().__init__()
            self.net = getattr(zoo, cfg['model'])(
                num_classes=cfg['num_classes'])

        def forward(self, images):
            x = (images.astype('float32') - mean) * scale
            return self.net(x.transpose([0, 3, 1, 2]))

    assert cfg['param_prefix'] == 'net.'
    net = FromPixels()
    net.train()
    o = cfg['optimizer']
    opt = optimizer.Momentum(learning_rate=o['learning_rate'],
                             momentum=o['momentum'],
                             weight_decay=o['weight_decay'])

    def loss(logits, labels):
        return nn.functional.cross_entropy(logits, labels.reshape([-1]))
    return net, loss, opt


def stochastic(cfg):
    return False


def first_gradient(cfg, slots, start):
    """The loss's gradient on the first step: from zero, the velocity after
    one step is what Momentum was handed, the gradient plus the L2 term
    weight_decay * start. The L2 term is taken out again: it is the same on
    both sides, and with it every leaf that the identity blocks leave
    without a gradient would compare equal and hide the others."""
    return slots['velocity'] - cfg['optimizer']['weight_decay'] * start


def make_pool(cfg, traffic, seed, batches, rows):
    """`batches` host batches ((uint8 images [rows, S, S, 3],), (labels,)).
    An image is uniform noise around a pattern of its class (a coarse 8x8
    colour grid), so the labels can be learned."""
    rs = np.random.default_rng([int(seed), 0x2E50])
    size, classes = cfg['image_size'], cfg['num_classes']
    coarse = 8
    patterns = rs.integers(48, 208, (classes, coarse, coarse, 3), np.int16)
    out = []
    for _ in range(batches):
        labels = rs.integers(0, classes, rows).astype(np.int32)
        base = patterns[labels].repeat(size // coarse, 1).repeat(
            size // coarse, 2)
        noise = rs.integers(-48, 48, base.shape, np.int16)
        out.append((((base + noise).astype(np.uint8),), (labels,)))
    return out


def augment(traffic, batch, rs):
    """Random horizontal flip of each image, on the host."""
    (images,), by = batch
    flip = np.flatnonzero(rs.random(images.shape[0]) < traffic['flip_prob'])
    out = images.copy()
    out[flip] = images[flip, :, ::-1]
    return ((out,), by)


def flops_per_sample(cfg, traffic):
    """Operations one image's forward and backward passes require: 2 per
    multiply-add of every convolution and of the classifier, times three
    passes. Batch norm, ReLU, pooling and the optimizer do not count. (The
    stem's backward needs no gradient for the image; at 0.12 GFLOP of 24.6
    it is counted as the convention has it.)"""
    macs = sum(cin * cout * k * k * side * side
               for _, cin, cout, k, _, side in _convs(cfg))
    macs += cfg['stage_widths'][-1] * cfg['expansion'] * cfg['num_classes']
    return 6.0 * macs

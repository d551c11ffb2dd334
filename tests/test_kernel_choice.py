"""Which implementation each `nn.functional` kernel site takes, and that its
program is the one it was.

The rule that picks a Pallas kernel or its XLA form lives with the kernel
(`kernels/flash_attention.py`, `fused_norm.py`, `fused_dropout_norm.py`,
`short_conv.py`, `rotary.py`): backend, the shape's tiling, what the kernel
can express, whether the step is sharded over a mesh (the rotation), and two
size rules (sequence length 512 for attention, 4096 rows for dropout + add +
norm).
Every outcome runs under `_common.took`, which bumps
`kernels.<kernel>.<path>` and names the scope `<kernel>.<path>`: a trace of
a step proves which one it ran. Here the sites are traced through
`nn.functional` (the short convolution, which `nn.KimiDeltaAttention` calls
on values, through its entry; the rotation, which the attention layers call
on values, likewise) with `jax.default_backend` patched; nothing is lowered.
"""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.core import rng
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn import functional as F

BF16, F32 = jnp.bfloat16, jnp.float32


def _key():
    return jax.random.key(0, impl='rbg')


def _call(fn, arrays, key, **kw):
    """One site, called as a layer inside a traced step calls it: Tensors
    around the tracers, the dropout key from the scope the engine opens."""
    with rng.key_scope(key):
        return fn(*(Tensor(a) for a in arrays), **kw)._value


def attention(q_shape, mask_shape=None, p=0.0, k_len=None, causal=False,
              dtype=BF16):
    """BERT's call: (B, L, H, D) operands, an additive float mask."""
    b, lq, h, d = q_shape
    kv = (b, k_len or lq, h, d)
    shapes = [(q_shape, dtype), (kv, dtype), (kv, dtype)]
    if mask_shape is not None:
        shapes.append((mask_shape, F32))

    def site(key, q, k, v, mask=None):
        return _call(F.scaled_dot_product_attention, (q, k, v), key,
                     attn_mask=None if mask is None else Tensor(mask),
                     dropout_p=p, is_causal=causal, training=True)
    return site, shapes


def layer_norm(x_shape, normalized=None, dtype=BF16):
    normalized = list(normalized or x_shape[-1:])
    w = (tuple(normalized), F32)

    def site(key, x, w, b):
        return _call(lambda x, w, b: F.layer_norm(x, normalized, w, b, 1e-12),
                     (x, w, b), key)
    return site, [(x_shape, dtype), w, w]


def rms_norm(x_shape, dtype=BF16):
    def site(key, x, w):
        return F.rms_norm_values(x, w, 1e-6)
    return site, [(x_shape, dtype), (x_shape[-1:], F32)]


def rms_norm_tensor(x_shape):
    def site(key, x, w):
        return _call(F.rms_norm, (x, w), key)
    return site, [(x_shape, BF16), (x_shape[-1:], F32)]


def dropout_add_norm(x_shape, p=0.1, dtype=BF16):
    w = (x_shape[-1:], F32)

    def site(key, x, res, w, b):
        return _call(F.fused_dropout_add_layer_norm, (x, res, w, b), key,
                     dropout_p=p, epsilon=1e-12, training=True)
    return site, [(x_shape, dtype), (x_shape, dtype), w, w]


def short_conv(y_shape, head_dim=None, taps=4, dtype=BF16):
    """`nn.KimiDeltaAttention`'s call: the projection's output as the
    matmul wrote it, the taps, the row's document numbers."""
    from paddle_tpu.kernels.short_conv import short_conv as entry

    def site(key, y, w, seg):
        return entry(y, w, seg, head_dim)
    return site, [(y_shape, dtype), ((taps, y_shape[-1]), F32),
                  (y_shape[:2], jnp.int32)]


def rotary(x_shape, turned=None, dtype=BF16, sharded=False):
    """The attention layers' call: a projection's output seen by head
    (B, T, H, d) and the packed row's positions; the half turn over the whole
    head (`nn.GroupedQueryAttention`), or with `turned` the pair turn over a
    head's last channels (`nn.LatentAttention`). `sharded`: traced inside
    `kernel_mesh`, as a step whose operands are split over a mesh is."""
    from paddle_tpu.kernels import rotary as entry
    from paddle_tpu.kernels._common import kernel_mesh
    from paddle_tpu.nn.layer.linear_attention import rope_inv_freq
    d = x_shape[-1]

    def turn(x, at):
        if turned is None:
            return entry.rotary_halves(
                x, at, rope_inv_freq(500000, d).astype(np.float32), 1.25)
        return entry.rotary_pairs(x, at, 32e6, turned)

    def site(key, x, at):
        if not sharded:
            return turn(x, at)
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ('data',))
        with kernel_mesh(mesh, ('data',)):
            return turn(x, at)
    return site, [(x_shape, dtype), (x_shape[:2], jnp.int32)]


def _structs(shapes):
    return [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]


# (id, kernel, backend, the site, the path it takes)
_CHOICES = [
    # attention: BERT's two cells stand on either side of the size rule
    ('attention-seq128-cell', 'flash_attention', 'tpu',
     attention((64, 128, 16, 64), (64, 1, 1, 128), 0.1), 'xla'),
    ('attention-seq512-cell', 'flash_attention', 'tpu',
     attention((16, 512, 16, 64), (16, 1, 1, 512), 0.1), 'pallas'),
    ('attention-seq256-under-the-size-rule', 'flash_attention', 'tpu',
     attention((2, 256, 4, 64)), 'xla'),
    ('attention-seq512-no-mask', 'flash_attention', 'tpu',
     attention((2, 512, 4, 64)), 'pallas'),
    ('attention-seq1024-causal', 'flash_attention', 'tpu',
     attention((2, 1024, 4, 64), causal=True), 'pallas'),
    ('attention-seq512-one-mask-row-for-all', 'flash_attention', 'tpu',
     attention((2, 512, 4, 64), (1, 1, 1, 512)), 'pallas'),
    ('attention-seq640-does-not-tile', 'flash_attention', 'tpu',
     attention((2, 640, 4, 64)), 'xla'),
    ('attention-full-mask', 'flash_attention', 'tpu',
     attention((2, 512, 4, 64), (2, 4, 512, 512)), 'xla'),
    ('attention-mask-per-query', 'flash_attention', 'tpu',
     attention((2, 512, 4, 64), (2, 1, 512, 512)), 'xla'),
    ('attention-lq-is-not-lk', 'flash_attention', 'tpu',
     attention((2, 512, 4, 64), k_len=1024), 'xla'),
    ('attention-seq512-off-the-tpu', 'flash_attention', 'cpu',
     attention((2, 512, 4, 64), (2, 1, 1, 512), 0.1), 'xla'),
    # layer norm
    ('layer-norm-cell', 'fused_layer_norm', 'tpu',
     layer_norm((64, 128, 1024)), 'pallas'),
    ('layer-norm-rows-do-not-tile', 'fused_layer_norm', 'tpu',
     layer_norm((4095, 1024)), 'xla'),
    ('layer-norm-lanes-do-not-tile', 'fused_layer_norm', 'tpu',
     layer_norm((64, 200)), 'xla'),
    ('layer-norm-over-two-axes', 'fused_layer_norm', 'tpu',
     layer_norm((8, 16, 128), normalized=(16, 128)), 'xla'),
    ('layer-norm-off-the-tpu', 'fused_layer_norm', 'cpu',
     layer_norm((64, 128, 1024)), 'xla'),
    # RMS norm, over values (a layer's own traced function) and Tensors
    ('rms-norm-cell', 'fused_rms_norm', 'tpu',
     rms_norm((2, 8192, 2304)), 'pallas'),
    ('rms-norm-tensor', 'fused_rms_norm', 'tpu',
     rms_norm_tensor((16, 256)), 'pallas'),
    ('rms-norm-rows-do-not-tile', 'fused_rms_norm', 'tpu',
     rms_norm((13, 256)), 'xla'),
    ('rms-norm-lanes-do-not-tile', 'fused_rms_norm', 'tpu',
     rms_norm((16, 200)), 'xla'),
    ('rms-norm-off-the-tpu', 'fused_rms_norm', 'cpu',
     rms_norm((2, 8192, 2304)), 'xla'),
    # dropout + add + layer norm: both sides of the size rule
    ('dropout-add-norm-cell', 'fused_dropout_norm', 'tpu',
     dropout_add_norm((64, 128, 1024)), 'pallas'),
    ('dropout-add-norm-4096-rows', 'fused_dropout_norm', 'tpu',
     dropout_add_norm((4096, 1024)), 'pallas'),
    ('dropout-add-norm-4095-rows', 'fused_dropout_norm', 'tpu',
     dropout_add_norm((4095, 1024)), 'xla'),
    ('dropout-add-norm-4088-rows-tile-under-the-size-rule',
     'fused_dropout_norm', 'tpu', dropout_add_norm((4088, 1024)), 'xla'),
    # ... where the norm by itself is still the layer-norm kernel: what
    # BERT's step holds at fewer than 4096 rows
    ('dropout-add-norm-4088-rows-norm-keeps-its-kernel',
     'fused_layer_norm', 'tpu', dropout_add_norm((4088, 1024)), 'pallas'),
    ('dropout-add-norm-4100-rows-do-not-tile', 'fused_dropout_norm', 'tpu',
     dropout_add_norm((4100, 1024)), 'xla'),
    ('dropout-add-norm-lanes-do-not-tile', 'fused_dropout_norm', 'tpu',
     dropout_add_norm((8192, 200)), 'xla'),
    ('dropout-add-norm-no-dropout', 'fused_dropout_norm', 'tpu',
     dropout_add_norm((8192, 1024), p=0.0), 'pallas'),
    ('dropout-add-norm-off-the-tpu', 'fused_dropout_norm', 'cpu',
     dropout_add_norm((8192, 1024)), 'xla'),
    # the short convolution: q and k of the Kimi cell (normed heads), its v
    ('short-conv-cell-q', 'short_conv', 'tpu',
     short_conv((1, 8192, 4096), head_dim=128), 'pallas'),
    ('short-conv-cell-v', 'short_conv', 'tpu',
     short_conv((1, 8192, 4096)), 'pallas'),
    ('short-conv-float32-rows-of-48', 'short_conv', 'tpu',
     short_conv((2, 48, 256), head_dim=256, dtype=F32), 'pallas'),
    ('short-conv-rows-do-not-tile', 'short_conv', 'tpu',
     short_conv((1, 8200, 4096), head_dim=128), 'xla'),
    ('short-conv-lanes-do-not-tile', 'short_conv', 'tpu',
     short_conv((1, 8192, 4000)), 'xla'),
    ('short-conv-a-normed-head-of-half-a-column', 'short_conv', 'tpu',
     short_conv((1, 8192, 4096), head_dim=64), 'xla'),
    ('short-conv-heads-of-half-a-column-not-normed', 'short_conv', 'tpu',
     short_conv((1, 8192, 128)), 'pallas'),
    ('short-conv-ten-taps', 'short_conv', 'tpu',
     short_conv((1, 8192, 4096), head_dim=128, taps=10), 'xla'),
    ('short-conv-off-the-tpu', 'short_conv', 'cpu',
     short_conv((1, 8192, 4096), head_dim=128), 'xla'),
    # the rotation: Mellum2's q and k (half turn, heads of 128), JoyAI's q
    # (pair turn over the last 64 of 128 + 64)
    ('rotary-mellum2-cell-q', 'rotary', 'tpu',
     rotary((2, 8192, 32, 128)), 'pallas'),
    ('rotary-mellum2-cell-k', 'rotary', 'tpu',
     rotary((2, 8192, 4, 128)), 'pallas'),
    ('rotary-joyai-cell-q', 'rotary', 'tpu',
     rotary((2, 8192, 32, 192), turned=64), 'pallas'),
    ('rotary-float32-rows-of-48', 'rotary', 'tpu',
     rotary((2, 48, 2, 128), dtype=F32), 'pallas'),
    ('rotary-rows-do-not-tile', 'rotary', 'tpu',
     rotary((2, 8200, 32, 128)), 'xla'),
    ('rotary-half-turn-of-half-a-register', 'rotary', 'tpu',
     rotary((2, 8192, 32, 64)), 'xla'),
    ('rotary-pair-turn-of-half-a-register', 'rotary', 'tpu',
     rotary((2, 8192, 32, 64), turned=64), 'pallas'),
    ('rotary-three-heads-of-192-fill-no-registers', 'rotary', 'tpu',
     rotary((2, 8192, 3, 192), turned=64), 'xla'),
    ('rotary-in-a-sharded-step', 'rotary', 'tpu',
     rotary((2, 8192, 32, 128), sharded=True), 'xla'),
    ('rotary-off-the-tpu', 'rotary', 'cpu',
     rotary((2, 8192, 32, 128)), 'xla'),
]


@pytest.fixture
def telemetry():
    was = obs.enabled()
    obs.enable()
    yield
    if not was:
        obs.disable()


def _taken(kernel):
    return {path: obs.counter('kernels.%s.%s' % (kernel, path)).value
            for path in ('pallas', 'xla')}


@pytest.mark.parametrize('kernel,backend,site,path',
                         [c[1:] for c in _CHOICES],
                         ids=[c[0] for c in _CHOICES])
def test_the_site_takes(monkeypatch, telemetry, kernel, backend, site, path):
    """The one decision of the trace is counted under the path it took, and
    the ops sit under that path's scope."""
    monkeypatch.setattr(jax, 'default_backend', lambda: backend)
    fn, shapes = site
    before = _taken(kernel)
    jaxpr = jax.make_jaxpr(fn)(_key(), *_structs(shapes))
    after = _taken(kernel)
    other = 'xla' if path == 'pallas' else 'pallas'
    assert (after[path] - before[path], after[other] - before[other]) == \
        (1, 0)
    text = jaxpr.pretty_print(name_stack=True)
    assert '%s.%s' % (kernel, path) in text
    assert '%s.%s' % (kernel, other) not in text
    assert path == 'xla' or 'pallas_call' in text


# Forward-and-gradient jaxprs of commit 31e439e at the cells' own shapes,
# source locations taken out (name stacks are not printed): the programs
# the benchmark's steps hold. The rotation's two are PR 44's, which brought
# the kernel: Mellum2's q and k, JoyAI's q.
_PROGRAMS = [
    ('attention-seq128', [attention((64, 128, 16, 64), (64, 1, 1, 128), 0.1)],
     '9728593572e4a028a0a00d326eb749ea0c1af8b8d46acba637181253fb2b6c6e'),
    ('attention-seq512', [attention((16, 512, 16, 64), (16, 1, 1, 512), 0.1)],
     'fb9b738a13cf138e00f4fff7152b25cf15f2319a498152d550f346242893e7b7'),
    ('layer-norm-1024', [layer_norm((64, 128, 1024)),
                         layer_norm((16, 512, 1024)),
                         layer_norm((1280, 1024), dtype=F32)],
     '0d563db57167cc3b23e0aa470079daf25e9cacf15503d8d8ec6a481c2e931b28'),
    ('dropout-add-norm-8192-rows', [dropout_add_norm((64, 128, 1024)),
                                    dropout_add_norm((16, 512, 1024)),
                                    dropout_add_norm((8192, 1024), dtype=F32)],
     '3f0cf7d174723a6dbae21fbc117c07e648f06c0e019dab5aff859b9c318624b8'),
    ('dropout-add-norm-4095-rows', [dropout_add_norm((4095, 1024)),
                                    dropout_add_norm((8, 128, 1024))],
     'ad8713ac00a1bbad50501b7e8c2c50f2fb85ff57180ebccc76b6606506e1d476'),
    ('rms-norm-2304', [rms_norm((2, 8192, 2304)),
                       rms_norm((2, 8192, 2304), dtype=F32)],
     '7cf430396d2a57a067805e8d4458460c4c93f9aeda0258dca33baf34bdd80c67'),
    ('rotary-halves-mellum2', [rotary((2, 8192, 32, 128)),
                               rotary((2, 8192, 4, 128))],
     'e42ea1f50fb1d7d01ba638c6a6a3c5414e64589c8c8f60f02dc86db61d25f087'),
    ('rotary-pairs-joyai', [rotary((2, 8192, 32, 192), turned=64)],
     '54acccbb58419b2bb8fce5b10b69cd4ea09cab9296d5866e6ccc452f6e71d60c'),
]


def program_digest(sites):
    texts = []
    for fn, shapes in sites:
        def loss(key, *args):
            return jnp.sum(fn(key, *args).astype(F32))
        floats = tuple(i + 1 for i, (_, dt) in enumerate(shapes)
                       if jnp.issubdtype(dt, jnp.floating))
        texts.append(str(jax.make_jaxpr(jax.grad(loss, argnums=floats))(
            _key(), *_structs(shapes))))
    text = re.sub(r'/[\w/.\-]+\.py:\d+', 'SRC', '\n'.join(texts))
    text = re.sub(r'at SRC|SRC', '', text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize('sites,digest', [p[1:] for p in _PROGRAMS],
                         ids=[p[0] for p in _PROGRAMS])
def test_the_cells_programs_are_unchanged(monkeypatch, sites, digest):
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert program_digest(sites) == digest


def functional_dropout(x_shape, p=0.1, dtype=BF16):
    def site(key, x):
        return _call(F.dropout, (x,), key, p=p, training=True)
    return site, [(x_shape, dtype)]


# The XLA-path masks go through `kernels._common.keep_mask` since PR 45 so
# that a SHARDED step draws them per device. On one device, outside
# `kernel_mesh` or inside one that splits nothing, the trace is what
# `jax.random.bernoulli` wrote in the site's own lines before.
_MASK_SITES = [
    ('attention-seq128-cell', 'tpu',
     attention((64, 128, 16, 64), (64, 1, 1, 128), 0.1)),
    ('attention-seq512-off-the-tpu-reference', 'cpu',
     attention((2, 512, 4, 64), (2, 1, 1, 512), 0.1)),
    ('dropout-add-norm-4095-rows', 'tpu', dropout_add_norm((4095, 1024))),
    ('dropout-add-norm-off-the-tpu-reference', 'cpu',
     dropout_add_norm((8192, 1024))),
    ('functional-dropout-embeddings', 'tpu',
     functional_dropout((64, 128, 1024))),
]


@pytest.mark.parametrize('scope', ['no-scope', 'one-device-mesh'])
@pytest.mark.parametrize('backend,site', [c[1:] for c in _MASK_SITES],
                         ids=[c[0] for c in _MASK_SITES])
def test_the_one_device_mask_draw_is_jax_random_bernoulli(monkeypatch, backend,
                                                          site, scope):
    import contextlib
    from paddle_tpu.kernels import (_common, flash_attention,
                                    fused_dropout_norm)
    from paddle_tpu.nn.functional import common
    monkeypatch.setattr(jax, 'default_backend', lambda: backend)
    fn, shapes = site
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ('data',))

    def text():
        with _common.kernel_mesh(mesh, ('data',)) \
                if scope == 'one-device-mesh' else contextlib.nullcontext():
            return str(jax.make_jaxpr(fn)(_key(), *_structs(shapes)))

    got = text()
    for module in (flash_attention, fused_dropout_norm, common):
        monkeypatch.setattr(
            module, 'keep_mask',
            lambda key, keep_prob, shape, dims: jax.random.bernoulli(
                key, keep_prob, shape))
    assert 'random_bits' in got and 'shard_map' not in got
    assert got == text()


# The attention layers that rotate nothing (Kimi's `LatentAttention` with no
# `rope_theta`, Nemotron's `GroupedQueryAttention` with no table) share their
# code with the two that do: forward and gradient under bf16 autocast with the
# block's norm in front and recomputation, they trace to the jaxprs of commit
# 89fd2c6, the parent of the rotary kernel's PR, on the kernels' path.
_LAYERS_THAT_DO_NOT_ROTATE = [
    ('grouped-query-no-table',
     lambda nn: nn.GroupedQueryAttention(256, 8, 2, 128, None),
     'f1c43132cdefc18ca44a30cb684032591c3bb7adf7db04687f942d23bf8779b6'),
    ('grouped-query-no-table-window',
     lambda nn: nn.GroupedQueryAttention(256, 8, 2, 128, None, window=256),
     '3642ff6f77ed9ebc4f2e694786da403e89974d98a8c058a47e6b55bf23f5c0c7'),
    ('latent-no-theta',
     lambda nn: nn.LatentAttention(256, 2, 128, 64, 128, 128),
     '71e829e68cbd547617311b47196f3961e9fc70e130b56248425d0036e823d39c'),
    ('latent-low-rank-no-theta',
     lambda nn: nn.LatentAttention(256, 2, 128, 64, 128, 128,
                                   q_lora_rank=64),
     '560662a017a54059784143db7ddeb79c58e7580c413bba246f9db8d546adf19c'),
]


def layer_digest(make):
    import paddle_tpu as paddle
    from paddle_tpu import amp, nn
    from paddle_tpu.nn.layer_base import functional_call
    paddle.seed(0)
    layer = make(nn)
    names = [n for n, _ in layer.named_parameters()]
    weights = [p._value for _, p in layer.named_parameters()]
    norm = nn.RMSNorm(256, epsilon=1e-5)

    def loss(x, seg, *ws):
        with amp.auto_cast(dtype='bfloat16'):
            y, _ = functional_call(layer, dict(zip(names, ws)), Tensor(x),
                                   Tensor(seg), norm, True)
        return jnp.sum(y._value.astype(F32))
    text = str(jax.make_jaxpr(jax.grad(
        loss, argnums=(0,) + tuple(range(2, 2 + len(weights)))))(
            jnp.zeros((2, 1024, 256), F32), jnp.zeros((2, 1024), jnp.int32),
            *weights))
    text = re.sub(r'/[\w/.\-]+\.py:\d+', 'SRC', text)
    text = re.sub(r'at SRC|SRC', '', text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize('make,digest',
                         [c[1:] for c in _LAYERS_THAT_DO_NOT_ROTATE],
                         ids=[c[0] for c in _LAYERS_THAT_DO_NOT_ROTATE])
def test_layers_that_do_not_rotate_trace_to_the_parents_program(
        monkeypatch, make, digest):
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert layer_digest(make) == digest

"""Kernel sites under a partitioned jit (``kernels._common.spmd_kernel``).

The TPU compiler refuses a Pallas kernel in a GSPMD-partitioned program,
so under ``kernel_mesh`` each site becomes a ``shard_map``. Here on the
8-device CPU mesh, in interpret mode: the partitioned result equals the
one-device result, the kernel really runs per shard, and the shard offsets
the dropout tile ids are built from are the global ones. (The TPU
compiler's side of it is held by tests/test_chip_compile.py.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.kernels import _common
from paddle_tpu.kernels.flash_attention import flash_attention_bhld
from paddle_tpu.kernels.fused_dropout_norm import fused_dropout_add_layer_norm
from paddle_tpu.kernels.fused_norm import fused_layer_norm, fused_rms_norm


def _mesh(shape, names):
    n = int(np.prod(shape))
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), names)


def _ln(x, w, b):
    return fused_layer_norm(x, w, b, interpret=True)


def _rms(x, w, b):
    return fused_rms_norm(x, w, interpret=True)


def _fdln(x, w, b):
    return fused_dropout_add_layer_norm(x, x * 0.5, w, b, interpret=True)


@pytest.mark.parametrize('fn', [_ln, _rms, _fdln],
                         ids=['layer_norm', 'rms_norm', 'dropout_add_norm'])
def test_row_kernels_partition_over_the_batch_axes(fn):
    mesh = _mesh((4, 2), ('data', 'model'))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 128), jnp.float32)
    w = jnp.linspace(0.5, 1.5, 128)
    b = jnp.linspace(-1.0, 1.0, 128)

    def loss(x, w, b):
        return jnp.sum(fn(x, w, b) ** 2 * jnp.arange(128.0))

    def traced(x, w, b):
        with _common.kernel_mesh(mesh, ('data',), ('model',)):
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)

    rows, rep = NamedSharding(mesh, P('data')), NamedSharding(mesh, P())
    step = jax.jit(traced, in_shardings=(rows, rep, rep))
    assert 'shard_map' in str(step.trace(x, w, b).jaxpr)
    got_v, got_g = step(x, w, b)
    want_v, want_g = jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-5)
    for g, wnt in zip(got_g, want_g):
        np.testing.assert_allclose(g, wnt, rtol=1e-4, atol=1e-3)
    assert got_g[0].sharding.spec[0] == 'data'   # nothing was gathered


@pytest.mark.parametrize('causal,kpad,packed', [
    (True, False, False), (False, True, False), (True, False, True)],
    ids=['causal', 'key_padding', 'packed'])
def test_flash_partitions_over_batch_and_heads(causal, kpad, packed):
    mesh = _mesh((2, 2), ('data', 'model'))
    B, H, L, D = 4, 4, 128, 32
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, H, L, D),
                                 jnp.float32) for i in range(3))
    bias = start = None
    if kpad:
        bias = jnp.where(jnp.arange(L)[None, :] < jnp.array(
            [[128], [96], [64], [32]]), 0.0, -1e4).astype(jnp.float32)
    if packed:      # every row its own documents: each device's rows take
        at = jnp.arange(L)[None, :]     # their own tile bounds with them
        start = jnp.where(at >= jnp.array([[128], [64], [100], [32]]),
                          jnp.array([[128], [64], [100], [32]]), 0
                          ).astype(jnp.int32)
    block = 32 if packed else 64

    def loss(q, k, v):
        o = flash_attention_bhld(q, k, v, causal=causal, kpad_bias=bias,
                                 doc_start=start, block_q=block,
                                 block_k=block, interpret=True)
        return jnp.sum(o * o)

    def traced(q, k, v):
        with _common.kernel_mesh(mesh, ('data',), ('model',)):
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    bh = NamedSharding(mesh, P('data', 'model'))
    step = jax.jit(traced, in_shardings=(bh, bh, bh))
    assert str(step.trace(q, k, v).jaxpr).count('shard_map') >= 2
    got_v, got_g = step(q, k, v)
    want_v, want_g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-4)
    for g, wnt in zip(got_g, want_g):
        np.testing.assert_allclose(g, wnt, rtol=1e-3, atol=1e-4)
    assert tuple(got_g[0].sharding.spec[:2]) == ('data', 'model')


def test_shard_offsets_are_global_and_uneven_parts_stay_whole():
    """``shard[f] = (start, total)``: start is where the device's part
    begins in the whole array (what the dropout tile ids are built from);
    a factor whose parts would not be a multiple of ``granule`` is not
    split."""
    mesh = _mesh((4,), ('data',))

    def impl(x, shard):
        start, total = shard['n']
        return x + start.astype(x.dtype), jnp.full(x.shape[:1], total)

    site = _common.spmd_kernel(impl, [('n', 'd')], [('n', 'd'), ('n',)],
                               {'n': 'batch'}, granule=8)

    def traced(x):
        with _common.kernel_mesh(mesh, ('data',)):
            return site(x)

    x = jnp.zeros((64, 4))
    starts, totals = jax.jit(traced)(x)
    np.testing.assert_array_equal(starts[:, 0], np.repeat([0, 16, 32, 48],
                                                          16))
    np.testing.assert_array_equal(totals, 64)
    # 24 rows over 4 devices = 6 each: not a multiple of 8 -> whole
    starts, _ = jax.jit(traced)(jnp.zeros((24, 4)))
    np.testing.assert_array_equal(starts, 0)
    # outside the scope the site is the plain call
    starts, totals = jax.jit(site)(x)
    np.testing.assert_array_equal(starts, 0)
    np.testing.assert_array_equal(totals, 64)


def test_site_inside_a_manual_shard_map_is_called_as_it_is():
    """Ring attention, the pipeline and sync-BN call kernels from inside
    their own ``shard_map``: the site must not open a second one over the
    same axes."""
    mesh = _mesh((4,), ('data',))
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    w = jnp.ones((128,))

    def body(x):
        with _common.kernel_mesh(mesh, ('data',)):
            return fused_layer_norm(x, w, w * 0, interpret=True)

    got = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P('data'),
                                out_specs=P('data'), check_vma=False))(x)
    np.testing.assert_allclose(got, fused_layer_norm(x, w, w * 0,
                                                     interpret=True),
                               rtol=1e-5, atol=1e-5)


# --- the dropout masks that XLA ops draw (``_common.keep_mask``) -----------
# Each maker below returns, for a batch: site(key, x) -> the keep mask the
# site applied, as its output shows it; x; the dropout rate; the mask's shape.

def _attention_mask_site(batch):
    """`attention_blhd`'s XLA form: with q = 0 the scores are uniform, and
    with v the identity (D = L) the output IS keep / (L (1 - p))."""
    from paddle_tpu.kernels.flash_attention import attention_blhd
    H, L = 2, 32

    def site(key, q):
        v = jnp.broadcast_to(jnp.eye(L, dtype=q.dtype)[None, :, None, :],
                             q.shape)
        out = attention_blhd(q, q, v, None, False, 0.1, key)   # (B, L, H, L)
        return jnp.swapaxes(out, 1, 2) > 0

    return site, jnp.zeros((batch, L, H, L), jnp.float32), 0.1, \
        (batch, H, L, L)


def _reference_attention_mask_site(batch):
    from paddle_tpu.kernels.flash_attention import _attn_reference
    H, L = 2, 32

    def site(key, q):
        v = jnp.broadcast_to(jnp.eye(L, dtype=q.dtype), q.shape)
        return _attn_reference(q, q, v, False, 1.0, dropout_p=0.1,
                               dropout_key=key) > 0

    return site, jnp.zeros((batch, H, L, L), jnp.float32), 0.1, \
        (batch, H, L, L)


def _functional_dropout_site(batch):
    from paddle_tpu.core import rng
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn import functional as F

    def site(key, x):
        with rng.key_scope(key):
            return F.dropout(Tensor(x), 0.25)._value > 0

    return site, jnp.ones((batch, 16, 64), jnp.float32), 0.25, \
        (batch, 16, 64)


def _dropout_add_norm_site(batch):
    """Off the TPU `dropout_add_layer_norm` is dropout and add as XLA ops
    and a layer norm: of a row of ones, the kept stand above the row's
    mean and the dropped under it."""
    from paddle_tpu.kernels.fused_dropout_norm import dropout_add_layer_norm

    def site(key, x):
        return dropout_add_layer_norm(x, jnp.zeros_like(x), None, None,
                                      0.5, 1e-5, key) > 0

    return site, jnp.ones((batch, 4, 128), jnp.float32), 0.5, (batch, 4, 128)


def _kernel_reference_site(batch):
    """`fused_dropout_add_layer_norm` where its rows do not tile (7 a
    device is no multiple of 8, neither is 7 x batch): `_xla_reference`."""
    def site(key, x):
        seed = jax.random.randint(key, (1, 1), 0, 2**31 - 1).astype(jnp.int32)
        return fused_dropout_add_layer_norm(
            x, jnp.zeros_like(x), None, None, 0.5, 1e-5, seed) > 0

    return site, jnp.ones((batch, 7, 128), jnp.float32), 0.5, (batch, 7, 128)


_MASK_SITES = [_attention_mask_site, _reference_attention_mask_site,
               _functional_dropout_site, _dropout_add_norm_site,
               _kernel_reference_site]
_MASK_IDS = ['attention_blhd', 'attn_reference', 'functional_dropout',
             'dropout_add_layer_norm', 'fused_dropout_norm_reference']


def _rbg_key(seed=0):
    from paddle_tpu.core.rng import _make_key
    return _make_key(seed)


def _u32_elements(text):
    """Element counts of the `u32` arrays of a compiled program's text (the
    host backend spells the generator out as flat arrays, so shapes say
    little and counts say it all)."""
    import re
    return [int(np.prod([int(n) for n in dims.split(',') if n] or [1]))
            for dims in re.findall(r'u32\[([\d,]*)\]', text)]


@pytest.mark.parametrize('make', _MASK_SITES, ids=_MASK_IDS)
def test_xla_dropout_masks_are_drawn_per_shard(make):
    """Under `kernel_mesh` every device draws the mask of its own rows at
    their shape: the per-device program holds no array of the GLOBAL
    mask's size and slices no random bits (XLA's partitioner cannot split
    an `rng-bit-generator`: a draw at the global shape is made whole on
    every device and cut), the draws are still Bernoulli(1 - p), and no
    two devices share a stream."""
    mesh = _mesh((4,), ('data',))
    site, x, p, mask_shape = make(16)
    whole, part = int(np.prod(mask_shape)), int(np.prod(mask_shape)) // 4

    def traced(key, x):
        with _common.kernel_mesh(mesh, ('data',)):
            return site(key, x)

    step = jax.jit(traced, in_shardings=(None, NamedSharding(mesh, P('data'))))
    key = _rbg_key(3)
    assert 'shard_map' in str(step.trace(key, x).jaxpr)
    text = step.lower(key, x).compile().as_text()
    counts = _u32_elements(text)
    assert part in counts                   # the draw is there, at a part's
    assert max(counts) < whole              # size, and nothing larger is
    assert 'dynamic-slice' not in text
    keep = step(key, x)
    assert keep.shape == mask_shape and keep.sharding.spec[0] == 'data'
    keep = np.asarray(keep)
    rate, err = keep.mean(), np.sqrt(p * (1 - p) / keep.size)
    assert abs(rate - (1 - p)) < 3 * err, (rate, err)
    parts = keep.reshape((4, -1))
    for i in range(4):
        for j in range(i):
            agree = (parts[i] == parts[j]).mean()
            assert agree < p * p + (1 - p) * (1 - p) + 0.05, (i, j, agree)


@pytest.mark.parametrize('make', _MASK_SITES[:4], ids=_MASK_IDS[:4])
def test_without_the_scope_every_device_draws_the_global_mask(make):
    """What the scope is for, and that the test above can see it: left to
    XLA's partitioner, an `rbg` draw at a sharded shape is made whole on
    every device (the fifth site's key is a threefry key, which splits)."""
    mesh = _mesh((4,), ('data',))
    site, x, p, mask_shape = make(16)
    loose = jax.jit(site, in_shardings=(None, NamedSharding(mesh, P('data'))))
    text = loose.lower(_rbg_key(3), x).compile().as_text()
    assert max(_u32_elements(text)) >= int(np.prod(mask_shape))


@pytest.mark.parametrize('make', _MASK_SITES, ids=_MASK_IDS)
def test_xla_dropout_masks_stay_whole_where_nothing_splits(make):
    """A batch whose parts would not be whole, and a site inside a
    `shard_map` that is manual over the axis already, take the one draw of
    `jax.random.bernoulli` at the shape they see."""
    from paddle_tpu import observability as obs
    mesh = _mesh((4,), ('data',))
    key = _rbg_key(5)
    was = obs.enabled()
    obs.enable()
    try:
        counts = [obs.counter('kernels.dropout_mask.' + path)
                  for path in ('whole', 'shard')]
        before = [c.value for c in counts]
        site, x, p, mask_shape = make(6)        # 6 rows over 4 devices

        def traced(key, x):
            with _common.kernel_mesh(mesh, ('data',)):
                return site(key, x)

        assert 'shard_map' not in str(jax.make_jaxpr(traced)(key, x))
        assert [c.value - b for c, b in zip(counts, before)] == [1, 0]

        site, x, p, mask_shape = make(16)
        manual = jax.shard_map(traced, mesh=mesh, in_specs=(P(), P('data')),
                               out_specs=P('data'), check_vma=False)
        assert str(jax.make_jaxpr(manual)(key, x)).count('shard_map') == 1
        assert [c.value - b for c, b in zip(counts, before)] == [2, 0]
        # the same key on every device, the same draw: the manual caller's
        # business, as it was
        keep = np.asarray(jax.jit(manual)(key, x)).reshape((4, -1))
        assert (keep[0] == keep[1]).all()
        jax.make_jaxpr(traced)(key, x)
        assert [c.value - b for c, b in zip(counts, before)] == [2, 1]
    finally:
        if not was:
            obs.disable()


def test_mask_splits_over_batch_and_heads_and_is_traced_once(monkeypatch):
    """The scores' mask splits over both factors, every part from its own
    stream; the sites of one shape share ONE trace of the draw."""
    mesh = _mesh((2, 2), ('data', 'model'))
    shape, dims = (4, 4, 16, 32), ('b', 'h', None, None)
    traces = []
    real = jax.random.bernoulli

    def counted(*a, **kw):
        traces.append(1)
        return real(*a, **kw)

    def traced(key):
        with _common.kernel_mesh(mesh, ('data',), ('model',)):
            return [_common.keep_mask(jax.random.fold_in(key, i), 0.5, shape,
                                      dims) for i in range(3)]

    _common._shard_keep_mask.clear_cache()
    monkeypatch.setattr(jax.random, 'bernoulli', counted)
    masks = jax.jit(traced)(_rbg_key(7))
    assert len(traces) == 1
    for m in masks:
        assert tuple(m.sharding.spec[:2]) == ('data', 'model')
    m = np.asarray(masks[0])
    blocks = [m[b:b + 2, h:h + 2].ravel() for b in (0, 2) for h in (0, 2)]
    for i in range(4):
        for j in range(i):
            assert 0.4 < (blocks[i] == blocks[j]).mean() < 0.6
    assert not (np.asarray(masks[0]) == np.asarray(masks[1])).all()

"""The short convolution's Pallas kernels in interpret mode on the CPU, at
the real head size (128) and toy lengths: the output and the gradients of
the input and of the four taps against the XLA ops the layer ran before
(`causal_conv`, SiLU, the per-head l2norm). Rows of 96 positions take tiles
of 32, so the documents below begin at every place a tile's halo (before it
in the forward, after it in the backward) can be cut."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.kernels import short_conv as sc
from paddle_tpu.kernels.short_conv import short_conv
from paddle_tpu.nn.functional.delta_rule import causal_conv

T, W, D, TAPS = 96, 256, 128, 4
# where the documents of a row begin; the second tile is rows 32..63
LAYOUTS = {
    'one_document': [0],
    'at_a_tiles_first_row': [0, 32],
    'at_its_second_row': [0, 33],
    'at_its_fourth_row': [0, 35],
    'three_rows_before_its_end': [0, 61],
    'two_rows_before_its_end': [0, 62],
    'one_row_before_its_end': [0, 63],
    'short_documents_across_tiles': [0, 2, 30, 31, 34, 64, 65, 67, 93]}


def reference(y, w, seg, head_dim):
    x = jax.nn.silu(causal_conv(y.astype(jnp.float32), w, seg))
    if head_dim is not None:
        x = x.reshape(x.shape[:2] + (-1, head_dim))
        x = x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    return x.reshape(y.shape)


@functools.lru_cache(maxsize=None)
def _graded(which, head_dim):
    """The output and the gradients of one loss through the named form,
    jitted once for every layout."""
    fn = {'reference': reference,
          'kernel': functools.partial(short_conv, interpret=True)}[which]

    def run(y, w, seg, weight):
        out, vjp = jax.vjp(lambda y, w: fn(y, w, seg, head_dim), y, w)
        return (out,) + vjp(weight)
    return jax.jit(run)


def _case(layout, dtype, rows=1):
    rs = np.random.default_rng(5)
    y = jnp.asarray(rs.normal(size=(rows, T, W)), dtype)
    w = jnp.asarray(0.5 * rs.normal(size=(TAPS, W)), jnp.float32)
    weight = jnp.asarray(rs.normal(size=(rows, T, W)), jnp.float32)
    seg = np.searchsorted(LAYOUTS[layout], np.arange(T), side='right')
    return y, w, jnp.asarray(np.tile(seg, (rows, 1)), jnp.int32), weight


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('head_dim', [D, None], ids=['l2norm', 'no_norm'])
@pytest.mark.parametrize('layout', list(LAYOUTS))
def test_kernels_follow_the_xla_form(layout, head_dim, dtype):
    """float32 arithmetic from the first tap to the norm on both sides: the
    output and the taps' gradient agree to float32 rounding, the input's
    gradient to the rounding of its own dtype."""
    assert sc._row_tile(T) == 32
    args = _case(layout, dtype)
    want = _graded('reference', head_dim)(*args)
    got = _graded('kernel', head_dim)(*args)
    assert got[0].dtype == jnp.float32 and got[1].dtype == dtype
    for name, a, b, rel in zip(('out', 'dy', 'dw'), want, got, (
            1e-6, 1e-5 if dtype == jnp.float32 else 2.0 ** -8, 1e-5)):
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        assert np.all(np.isfinite(b)), name
        np.testing.assert_allclose(b, a, atol=rel * np.abs(a).max(),
                                   rtol=rel, err_msg=name)


def test_rows_and_column_groups_are_independent(monkeypatch):
    """Two rows with different documents, the columns in two groups (as a
    width over 2048 is cut): every grid step reads its own marks and halo,
    the taps' gradient adds up over rows and tiles."""
    monkeypatch.setattr(sc, '_col_block', lambda width, strip: 128)
    sc._forward.clear_cache(), sc._backward.clear_cache()
    try:
        y, w, seg, weight = _case('short_documents_across_tiles',
                                  jnp.bfloat16, rows=2)
        seg = seg.at[1].set(seg[1, ::-1].max() - seg[1, ::-1])
        want = reference(y, w, seg, D)
        got = short_conv(y, w, seg, D, interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-6)
        g_want, g_got = (jax.grad(
            lambda y, w: jnp.sum(weight * f(y, w)), argnums=(0, 1))(y, w)
            for f in (lambda y, w: reference(y, w, seg, D),
                      lambda y, w: short_conv(y, w, seg, D, interpret=True)))
        np.testing.assert_allclose(g_got[1], g_want[1], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(g_got[0].astype(jnp.float32),
                                   g_want[0].astype(jnp.float32), atol=2e-2)
    finally:
        sc._forward.clear_cache(), sc._backward.clear_cache()


@pytest.mark.parametrize('shape,head_dim', [
    ((1, 100, 256), 128), ((1, 96, 200), None), ((1, 96, 256), 64),
    ((1, 8, 256), 128)],
    ids=['rows_do_not_tile', 'lanes_do_not_tile', 'a_head_of_half_a_column',
         'a_row_shorter_than_a_tile'])
def test_shapes_that_do_not_tile_take_the_xla_form_and_say_so(shape,
                                                              head_dim):
    """The XLA ops' numbers to the bit with interpret mode asked for, and
    the choice counted under `kernels.short_conv.xla`."""
    rs = np.random.default_rng(3)
    y = jnp.asarray(rs.normal(size=shape), jnp.bfloat16)
    w = jnp.asarray(rs.normal(size=(TAPS, shape[2])), jnp.float32)
    seg = jnp.asarray((np.arange(shape[1]) >= 5)[None], jnp.int32)
    was = obs.enabled()
    obs.enable()
    try:
        xla, pallas = (obs.counter('kernels.short_conv.' + p)
                       for p in ('xla', 'pallas'))
        before = xla.value, pallas.value
        got = short_conv(y, w, seg, head_dim, interpret=True)
        assert (xla.value, pallas.value) == (before[0] + 1, before[1])
    finally:
        if not was:
            obs.disable()
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(reference(y, w, seg, head_dim)))


@pytest.mark.parametrize('broken', [None, 'y', 'w'],
                         ids=['sound', 'dy_off', 'dw_off'])
def test_directional_check_holds_the_backward_to_its_forward(monkeypatch,
                                                             broken):
    """`checks.check_short_conv_backward` (the chip runs it in
    `chip_smoke.py`'s `kernels` phase; here interpret mode, three tiles): the
    kernels' gradients pass, a backward with one gradient 5% off is
    refused."""
    from paddle_tpu.kernels import checks

    def check():
        return checks.check_short_conv_backward((1, T, W), interpret=True)
    if not broken:
        got = check()
        assert set(got) == {'y', 'w', 'y_no_norm', 'w_no_norm'}
        for fd, an in got.values():
            assert abs(fd - an) < 5e-3 * abs(an)
    else:
        sound = sc._backward
        monkeypatch.setattr(sc, '_backward', lambda *args, **kw: tuple(
            g * 1.05 if n == broken else g
            for n, g in zip(('y', 'w'), sound(*args, **kw))))
        with pytest.raises(AssertionError, match='d%s along' % broken):
            check()


def test_kernel_partitions_over_rows_and_heads():
    """Under `kernel_mesh` (a sharded step) the site becomes a `shard_map`:
    rows over `data`, heads over `model`, nothing gathered, the numbers of
    one device; the taps' gradient is summed over the rows' devices."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.kernels import _common
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ('data', 'model'))
    y, w, seg, weight = _case('short_documents_across_tiles', jnp.float32,
                              rows=2)

    def loss(y, w):
        return jnp.sum(weight * short_conv(y, w, seg, D, interpret=True))

    def traced(*args):
        with _common.kernel_mesh(mesh, ('data',), ('model',)):
            return jax.value_and_grad(loss, argnums=(0, 1))(*args)

    step = jax.jit(traced, in_shardings=(
        NamedSharding(mesh, P('data', None, 'model')),
        NamedSharding(mesh, P(None, 'model'))))
    assert 'shard_map' in str(step.trace(y, w).jaxpr)
    got_v, got_g = step(y, w)
    want_v, want_g = jax.value_and_grad(loss, argnums=(0, 1))(y, w)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert tuple(got_g[0].sharding.spec) == ('data', None, 'model')

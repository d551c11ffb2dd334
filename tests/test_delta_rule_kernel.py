"""The delta rule's Pallas kernels in interpret mode on the CPU, at the real
head size (K = V = 128) and toy lengths: `o` and the five gradients against
the token-by-token recurrence and against the XLA chunk-wise form."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.delta_rule import delta_rule
from paddle_tpu.nn.functional.delta_rule import delta_rule_chunked
from test_kimi_linear import delta_rule_recurrent

H, K, V = 2, 128, 128
# where the documents of a row begin
LAYOUTS = {
    'boundary_inside_a_sub_block': (256, [0, 70, 197]),
    'document_spans_chunks': (256, [0, 30, 230]),
    'boundary_at_a_chunk_edge': (256, [0, 64, 128]),
    'row_of_one_chunk': (64, [0, 21])}
DECAYS = {'weak': (1.0, -5.0), 'strong': (0.5, 3.0)}


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


@functools.lru_cache(maxsize=None)
def _graded(which, dtype):
    """value_and_grad of one loss through the named form, jitted once for
    every case that shares its shapes."""
    fn = {'recurrent': delta_rule_recurrent,
          'xla': functools.partial(delta_rule_chunked, dtype=dtype),
          'kernel': functools.partial(delta_rule, dtype=dtype,
                                      interpret=True)}[which]

    def loss(q, k, v, g, beta, seg):
        return jnp.sum(jnp.sin(fn(_unit(q), _unit(k), v, g, beta, seg,
                                  K ** -0.5)))
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))


def _case(layout, decay):
    T, starts = LAYOUTS[layout]
    rs = np.random.default_rng(11)
    q, k, g = (jnp.asarray(rs.normal(size=(1, T, H, K)), jnp.float32)
               for _ in range(3))
    v = jnp.asarray(rs.normal(size=(1, T, H, V)), jnp.float32)
    spread, shift = DECAYS[decay]
    g = -jnp.exp(spread * g + shift)
    beta = jax.nn.sigmoid(jnp.asarray(rs.normal(size=(1, T, H)),
                                      jnp.float32))
    seg = jnp.asarray(np.searchsorted(starts, np.arange(T), side='right')
                      [None], jnp.int32)
    return q, k, v, g, beta, seg


@pytest.mark.parametrize('dtype', [None, 'bfloat16'])
@pytest.mark.parametrize('decay', list(DECAYS))
@pytest.mark.parametrize('layout', list(LAYOUTS))
def test_kernel_follows_the_recurrence_and_the_xla_form(layout, decay, dtype):
    """float32 operands: the recurrence's numbers to float32 rounding;
    bfloat16 operands in the three large products: to their rounding, and as
    close to the recurrence as the XLA form with the same operands is."""
    args = _case(layout, decay)
    want = _graded('recurrent', None)(*args)
    xla = _graded('xla', dtype)(*args)
    got = _graded('kernel', dtype)(*args)
    rel = 5e-5 if dtype is None else 2e-2
    assert abs(float(got[0]) - float(want[0])) < rel * 40
    for name, a, x, b in zip('q k v g beta'.split(), want[1], xla[1], got[1]):
        assert np.all(np.isfinite(b)), name
        top = float(jnp.max(jnp.abs(a)))
        np.testing.assert_allclose(b, a, atol=rel * top + 1e-6, err_msg=name)
        np.testing.assert_allclose(b, x, atol=rel * top + 1e-6, err_msg=name)


def test_shapes_that_do_not_tile_take_the_xla_form():
    """A head of 64 lanes, or a row that is no multiple of the chunk: the
    XLA form's numbers to the bit, with interpret mode asked for."""
    rs = np.random.default_rng(2)
    T = 64
    q, k, g, v = (jnp.asarray(rs.normal(size=(1, T, 2, 64)), jnp.float32)
                  for _ in range(4))
    g = -jnp.exp(g - 1.0)
    beta = jnp.full((1, T, 2), 0.5, jnp.float32)
    seg = jnp.zeros((1, T), jnp.int32)
    want = delta_rule_chunked(q, k, v, g, beta, seg, 0.125)
    got = delta_rule(q, k, v, g, beta, seg, 0.125, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize('broken', [None, 'g', 'beta'],
                         ids=['sound', 'dg_off', 'dbeta_off'])
def test_directional_check_holds_the_backward_to_its_forward(monkeypatch,
                                                             broken):
    """`checks.check_delta_rule_backward` (the chip runs it in
    `chip_smoke.py`'s `kernels` phase; here interpret mode, two chunks): the
    kernels' gradients pass, a backward with one gradient 5% off is
    refused."""
    from paddle_tpu.kernels import checks, delta_rule as dr

    def check():
        return checks.check_delta_rule_backward((1, 128, 1, 128),
                                                interpret=True)
    if not broken:
        got = check()
        assert set(got) == {'q', 'k', 'v', 'g', 'beta'}
        for fd, an in got.values():
            assert abs(fd - an) < 5e-3 * abs(an)
    else:
        sound = dr._backward
        monkeypatch.setattr(dr, '_backward', lambda *args, **kw: tuple(
            g * 1.05 if n == broken else g
            for n, g in zip(('q', 'k', 'v', 'g', 'beta'),
                            sound(*args, **kw))))
        with pytest.raises(AssertionError, match='d%s along' % broken):
            check()


def test_kernel_partitions_over_rows_and_heads():
    """Under `kernel_mesh` (a sharded step) the site becomes a `shard_map`:
    rows over `data`, heads over `model`, nothing gathered, the numbers of
    one device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.kernels import _common
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ('data', 'model'))
    q, k, v, g, beta, seg = (jnp.concatenate([x, x[:, ::-1]])
                             for x in _case('row_of_one_chunk', 'weak'))
    seg = jnp.sort(seg, axis=1)

    def loss(q, k, v, g, beta):
        return jnp.sum(jnp.sin(delta_rule(_unit(q), _unit(k), v, g, beta, seg,
                                          K ** -0.5, interpret=True)))

    def traced(*args):
        with _common.kernel_mesh(mesh, ('data',), ('model',)):
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*args)

    wide = NamedSharding(mesh, P('data', None, 'model'))
    step = jax.jit(traced, in_shardings=(wide,) * 5)
    assert 'shard_map' in str(step.trace(q, k, v, g, beta).jaxpr)
    got_v, got_g = step(q, k, v, g, beta)
    want_v, want_g = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
        q, k, v, g, beta)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    assert tuple(got_g[0].sharding.spec) == ('data', None, 'model')

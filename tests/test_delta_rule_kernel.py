"""The delta rule's Pallas kernels in interpret mode on the CPU, at the real
head size (K = V = 128) and toy lengths: `o` and the five gradients against
the token-by-token recurrence and against the XLA chunk-wise form; the
hand-written chunk backward against `jax.vjp` of the chunk; the forward
against the program and the numbers it had before the backward was written
by hand."""
import functools
import hashlib
import re

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


# One chunk of 64 in sub-blocks of 16 for `_chunk_backward`: K, V, how many
# of each are zero channels (as `on_lanes` lays 96 / 192 on 128 / 256), one
# decay a channel or K equal ones, the largest beta, where documents begin,
# the document of the position before the chunk (-1: none, so `cont` is all
# zero), and whether a cotangent comes back from the chunks behind.
CHUNKS = {
    'kimi_head': (128, 128, 0, 0, 'channel', 1.0, [0], 0, True),
    'olmo_head_on_lanes': (128, 256, 32, 64, 'head', 2.0, [0], 0, True),
    'boundary_inside_a_sub_block': (128, 128, 0, 0, 'channel', 1.0,
                                    [0, 21, 39], 0, True),
    'cont_all_zero': (128, 128, 0, 0, 'channel', 1.0, [0, 48], -1, True),
    'tail_partly_zero_on_lanes': (128, 256, 32, 64, 'head', 2.0, [0, 37],
                                  0, True),
    'strong_decay_last_chunk': (128, 128, 0, 0, 'strong', 1.0, [0, 5], 0,
                                False)}


def _chunk_case(K, V, zero_k, zero_v, decay, beta_max, starts, before,
                dstate):
    C = 64
    rs = np.random.default_rng(5)

    def normal(*shape, zero=0):
        x = rs.normal(size=shape).astype(np.float32)
        if zero:
            x[..., -zero:] = 0.0
        return jnp.asarray(x)

    q, k = _unit(normal(C, K, zero=zero_k)), _unit(normal(C, K, zero=zero_k))
    v = normal(C, V, zero=zero_v)
    if decay == 'head':
        g = jnp.broadcast_to(-jnp.exp(normal(C, 1) - 2.0), (C, K))
    else:
        spread, shift = (0.5, 3.0) if decay == 'strong' else (1.0, -3.0)
        g = -jnp.exp(spread * normal(C, K) + shift)
    if zero_k:
        g = g.at[:, -zero_k:].set(0.0)
    beta = beta_max * jax.nn.sigmoid(normal(C, 1))

    def of_the_state(x):        # (V, K): zero on the zero channels of both
        x = np.array(x)
        x[:, K - zero_k:], x[V - zero_v:] = 0.0, 0.0
        return jnp.asarray(x)

    state = of_the_state(0.3 * normal(V, K))
    seg = jnp.asarray(np.searchsorted(starts, np.arange(C), side='right'),
                      jnp.float32) - 1.0
    cont = (seg == before).astype(jnp.float32)[:, None]
    tail = (seg == seg[-1]).astype(jnp.float32)[:, None]
    do = normal(C, V, zero=zero_v)
    ds = of_the_state(0.2 * normal(V, K) if dstate else np.zeros((V, K)))
    return ((q, k, v, g, beta, state), (seg[:, None], seg[None], cont, tail),
            (do, ds))


@pytest.mark.parametrize('case', list(CHUNKS))
def test_chunk_backward_is_the_vjp_of_the_chunk(case):
    """`_chunk_backward`, from the chunk's equations and what `_chunk` keeps,
    against `jax.vjp` of `_chunk` on the same inputs: float32, every product
    at the highest precision, each gradient to 1e-5 of its norm (in float64
    the two agree to 1e-13). Zero channels keep zero gradients."""
    from paddle_tpu.kernels import delta_rule as dr
    K, _, zero_k, zero_v = CHUNKS[case][:4]
    args, marks, cts = _chunk_case(*CHUNKS[case])
    static = dict(scale=(K - zero_k) ** -0.5, sub=16, dtype=None)
    with jax.default_matmul_precision('highest'):
        (_, _, kept), vjp = jax.vjp(
            lambda *x: dr._chunk(*x, *marks, **static), *args)
        want = vjp((*cts, jax.tree.map(jnp.zeros_like, kept)))
        got = jax.jit(functools.partial(dr._chunk_backward, **static))(
            *args, *marks, *kept, *cts)
    for name, a, b in zip('q k v g beta state'.split(), want, got):
        norm = float(jnp.sqrt(jnp.sum(a * a)))
        # nothing of a chunk whose `cont` is all zero reaches its start state
        assert norm > 0 or (name == 'state' and case == 'cont_all_zero')
        # under the strong decay dg is what is left of terms 1e4 times its
        # size: both forms read 1.5e-5 of its norm off a float64 run
        rel = 1e-4 if (name, CHUNKS[case][4]) == ('g', 'strong') else 1e-5
        assert float(jnp.max(jnp.abs(a - b))) <= rel * norm, name
    if zero_k:
        for name, b in zip('q k g'.split(), (got[0], got[1], got[3])):
            assert not np.any(np.asarray(b)[:, -zero_k:]), name
        assert not np.any(np.asarray(got[5])[:, -zero_k:])
    if zero_v:
        assert not np.any(np.asarray(got[2])[:, -zero_v:])
        assert not np.any(np.asarray(got[5])[-zero_v:])


# `_chunk`'s o and end state at commit 6a3f9be (before the backward was
# written by hand), as jaxprs with the source locations taken out: K, V,
# dtype -> sha256
_CHUNK_FORWARD = [
    (128, 128, 'bfloat16',
     '934bc03411f6f66b9caf574e42452de9b5c0e0b6bbde745b8633e2975db2aae2'),
    (128, 256, None,
     '888f479d2588f95aa005b31ab99bb2efedbcbde71722597f25a14c3f4b5e154e')]


@pytest.mark.parametrize('K,V,dtype,digest', _CHUNK_FORWARD,
                         ids=['kimi_head', 'olmo_head_on_lanes'])
def test_the_chunks_forward_program_is_unchanged(K, V, dtype, digest):
    """The backward reads more of what the chunk's forward makes (U, A, B
    beside the inverse) and `_chunk` returns it: the equations that make `o`
    and the state the chunk ends with are the ones they were, one for one."""
    from paddle_tpu.kernels import delta_rule as dr
    C = 64
    shapes = [(C, K), (C, K), (C, V), (C, K), (C, 1), (V, K), (C, 1), (1, C),
              (C, 1), (C, 1)]
    text = str(jax.make_jaxpr(lambda *x: dr._chunk(
        *x, scale=K ** -0.5, sub=16, dtype=dtype)[:2])(
        *[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]))
    text = re.sub(r'/[\w/.\-]+\.py:\d+', 'SRC', text)
    text = re.sub(r'at SRC|SRC', '', text)
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_forward_that_saves_is_the_forward_element_for_element():
    """Interpret mode, a row of four chunks with documents that end inside
    them: the forward that keeps what the backward reads writes the `o` of
    the forward that keeps nothing, element for element; both write what
    `_chunk` gives chunk after chunk, and the kept start states, inverses, U
    and scores are that chain's."""
    from paddle_tpu.kernels import delta_rule as dr
    q, k, v, g, beta, seg = _case('boundary_inside_a_sub_block', 'weak')
    q, k = _unit(q), _unit(k)
    T, C = q.shape[1], 64
    static = dict(scale=K ** -0.5, chunk=C, sub=16, dtype='bfloat16', heads=2,
                  interpret=True)
    wide = [x.reshape(1, T, -1) for x in (q, k, v, g)]
    per_head = jnp.moveaxis(beta, 1, 2).reshape(1, H, T // C, 1, C)
    marks = dr._marks(seg, C)
    (plain,) = dr._forward(*wide, per_head, marks, save=False, **static)
    o, starts, inverses, us, scores = dr._forward(*wide, per_head, marks,
                                                  save=True, **static)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(plain))

    one_chunk = jax.jit(functools.partial(dr._chunk, scale=K ** -0.5, sub=16,
                                          dtype='bfloat16'))
    for h in range(H):
        state = jnp.zeros((V, K), jnp.float32)
        for n in range(T // C):
            rows, m = slice(n * C, (n + 1) * C), marks[0, n]
            np.testing.assert_array_equal(np.asarray(starts[0, h, n]),
                                          np.asarray(state))
            want, state, (inverse, U, A, Bm) = one_chunk(
                q[0, rows, h], k[0, rows, h], v[0, rows, h], g[0, rows, h],
                beta[0, rows, h, None], state, m[0][:, None], m[0][None],
                m[1][:, None], m[2][:, None])
            for got, kept in ((o[0, rows, h * V:(h + 1) * V], want),
                              (inverses[0, h, n], inverse),
                              (us[0, rows, h * V:(h + 1) * V], U),
                              (scores[0, h, n], jnp.concatenate([A, Bm], 1))):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(kept))


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

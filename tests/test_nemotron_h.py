"""The Nemotron-H-pattern decoder through the train engine, at a test size on
the CPU: the chunk-scan kernels (interpret mode) against the XLA chunk form
and against the recurrence token by token, the convolution's bias, the
ungated experts at a width that is no whole register, the sixteen-way share,
and the program against the benchmark's plain reference (float32 on both
sides, so what is held is that both do the same mathematics; the chip holds
the stated bf16 precision to the cell's limits).
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, 'benchmark', 'tests'))

import _tiny  # noqa: E402  (puts benchmark/ on the path)
from harness import check  # noqa: E402
# how a run's set-up drives the program's first steps, and the reference
from test_kimi_linear import (program_readings,  # noqa: E402
                              reference_readings)

from paddle_tpu import nn, observability as obs  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.kernels import flash_attention, short_conv, ssd  # noqa: E402
from paddle_tpu.nn.functional import moe  # noqa: E402
from paddle_tpu.nn.functional.ssd import ssd_chunked  # noqa: E402
from paddle_tpu.nn.layer import state_space  # noqa: E402
from paddle_tpu.text import nemotron_h  # noqa: E402

# program against reference in float32 (my CPU runs, PR 43, seed 7, the eight
# layers ME*MEM*E): loss_gap at most 4.2e-6 (a float32 step of a loss of 5.5
# after two updates; 0 on the first), first_gradient_gap 1.7e-6,
# first_gradient_difference 1.5e-6, change_gap 1.8e-4. The float8 control
# reads loss_gap 8e-4 to 1e-2, 0.25, 0.46 and 0.052. The limits are the
# Mellum tests': each 100 times and more over the sound reading, but
# loss_gap, which stands 2.4 times over the third step's.
LIMITS = {'loss_gap': 1e-5, 'first_gradient_gap': 5e-3,
          'first_gradient_difference': 1e-3, 'change_gap': 2e-2,
          'loss_fall': -1e9}

REFERENCE = _tiny.harness_run.load_module('families',
                                          'nemotron_h_reference')


@pytest.fixture
def telemetry():
    was = obs.enabled()
    obs.enable()
    yield
    if not was:
        obs.disable()


def taken(kernel):
    """How often a trace took each path of `kernel` so far (`_common.took`
    counts once a trace, with telemetry on)."""
    return np.array([obs.counter('kernels.%s.%s' % (kernel, path)).value
                     for path in ('pallas', 'xla')])


# ------------------------------------------------------- the chunk scan

def recurrence(x, dt, A, Bm, Cm, D, seg):
    """The rule as it is written, token by token: what both chunk forms are
    held to. x (B, T, H, P); dt (B, T, H); A, D (H,); Bm, Cm (B, T, G, N)."""
    H, G = x.shape[2], Bm.shape[2]
    Bh, Ch = (jnp.repeat(a, H // G, axis=2) for a in (Bm, Cm))
    first = jnp.concatenate([jnp.ones_like(seg[:, :1], bool),
                             seg[:, 1:] != seg[:, :-1]], axis=1)

    def token(state, xs):
        x, dt, b, c, first = xs
        state = jnp.where(first[:, None, None, None], 0.0, state)
        state = jnp.exp(dt * A)[..., None, None] * state \
            + (dt[..., None] * x)[..., None] * b[:, :, None, :]
        return state, jnp.einsum('bhpn,bhn->bhp', state, c) + D[:, None] * x

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + Bm.shape[3:])
    _, y = jax.lax.scan(token, start, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, Bh, Ch, first)))
    return jnp.moveaxis(y, 0, 1)


def scan_operands(T=256, H=4, P=64, G=2, N=128):
    """Two rows of two chunks of 128: a document that ends INSIDE the first
    chunk (at 100) and one that ends ON its edge (at 128), one that runs
    over the edge (0..129), steps from 0.02 to 4 and rates from 0.2 to 5."""
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    t = jnp.arange(T)
    seg = jnp.stack([jnp.where(t < 100, 0, jnp.where(t < 128, 1, 2)),
                     jnp.where(t < 130, 0, 1)]).astype(jnp.int32)
    return (jax.random.normal(k[0], (2, T, H, P)),
            jax.nn.softplus(2 * jax.random.normal(k[1], (2, T, H))),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            0.3 * jax.random.normal(k[3], (2, T, G, N)),
            0.3 * jax.random.normal(k[4], (2, T, G, N)),
            jax.random.normal(k[5], (H,))), seg, \
        jax.random.normal(k[6], (2, T, H, P))


_SCANS = {}


def scan_run(form):
    """(y, gradients by x, dt, A, B, C, D) of one form on `scan_operands`,
    computed once."""
    if form not in _SCANS:
        operands, seg, cot = scan_operands()
        fn = {'recurrence': lambda *a: recurrence(*a, seg),
              'xla': lambda *a: ssd_chunked(*a, seg, chunk=128),
              'kernels': lambda *a: ssd.ssd(*a, seg, chunk=128,
                                            interpret=True)}[form]
        _SCANS[form] = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * cot), argnums=tuple(range(6)),
            has_aux=False))(*operands), jax.jit(fn)(*operands)
    return _SCANS[form]


def close(got, want, rel):
    np.testing.assert_allclose(got, want, atol=rel * float(
        jnp.max(jnp.abs(want))) + 1e-6, rtol=0)


@pytest.mark.parametrize('form,held_to,rel', [
    ('xla', 'recurrence', 3e-5), ('kernels', 'xla', 3e-5),
    ('kernels', 'recurrence', 3e-5)])
def test_the_chunk_scan_follows(form, held_to, rel):
    """Forward and all six gradients: the XLA chunk form against the
    recurrence (they share nothing but the rule), the kernels in interpret
    mode against the XLA form and against the recurrence (the three make
    the running sum of dt A in three orders: a few float32 steps of a sum
    that reaches hundreds); a document boundary inside a chunk, on a
    chunk's edge, and a document that carries its state over the edge."""
    (_, got_grads), got = scan_run(form)
    (_, want_grads), want = scan_run(held_to)
    close(got, want, rel)
    for a, b in zip(got_grads, want_grads):
        close(a, b, rel)


def test_the_scan_takes_the_kernels_only_where_the_shapes_tile(monkeypatch,
                                                               telemetry):
    """`ssd` counts which form a trace took (`kernels.ssd.pallas` / `.xla`);
    on the TPU the cell's shapes take the kernels, a head of 96, a row of
    100 tokens or a group of 1.5 registers the XLA form; off it, all do."""
    def marks(T, H, P, G, N, chunk):
        args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
            (1, T, H, P), (1, T, H), (H,), (1, T, G, N), (1, T, G, N), (H,))]
        before = taken('ssd')
        jax.make_jaxpr(lambda *a: ssd.ssd(
            *a, jnp.zeros((1, T), jnp.int32), chunk=chunk))(*args)
        return tuple(taken('ssd') - before)
    assert marks(256, 64, 64, 8, 128, 128) == (0, 1)
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert marks(256, 64, 64, 8, 128, 128) == (1, 0)
    assert marks(256, 4, 96, 2, 128, 128) == (0, 1)
    assert marks(100, 4, 64, 2, 128, 100) == (0, 1)
    assert marks(256, 3, 64, 1, 128, 128) == (0, 1)         # 1.5 registers


# ------------------------------------------------- the convolution's bias

@pytest.mark.parametrize('head_dim,norm', [(None, True), (128, True)],
                         ids=['plain', 'l2norm'])
def test_the_convolutions_bias_and_its_gradient(head_dim, norm):
    """silu(conv(y) + b): the kernels (interpret mode), which take the bias
    as one more row of the taps, against the XLA form; a document boundary
    at a tile's edge and inside one. A call without a bias is the old one."""
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    T, W = 64, 256
    y, w, b, cot = (jax.random.normal(k[0], (2, T, W)),
                    jax.random.normal(k[1], (4, W)),
                    jax.random.normal(k[2], (W,)),
                    jax.random.normal(k[3], (2, T, W)))
    t = jnp.arange(T)
    seg = jnp.stack([jnp.where(t < 30, 0, 1), jnp.where(
        t < 16, 0, jnp.where(t < 33, 1, 2))]).astype(jnp.int32)

    def kernels(y, w, b):
        return jnp.sum(short_conv.short_conv(
            y, w, seg, head_dim, interpret=True, norm=norm, bias=b) * cot)

    def plain(y, w, b):
        return jnp.sum(short_conv._xla(y, w, seg, head_dim, b) * cot)

    got = jax.value_and_grad(kernels, argnums=(0, 1, 2))(y, w, b)
    want = jax.value_and_grad(plain, argnums=(0, 1, 2))(y, w, b)
    assert float(got[0]) == pytest.approx(float(want[0]), abs=2e-4)
    for a, c in zip(got[1], want[1]):
        np.testing.assert_allclose(a, c, atol=2e-5)
    assert float(jnp.max(jnp.abs(want[1][2]))) > 0.1      # the bias counts
    none = short_conv.short_conv(y, w, seg, head_dim, interpret=True,
                                 norm=norm)
    zero = short_conv.short_conv(y, w, seg, head_dim, interpret=True,
                                 norm=norm, bias=jnp.zeros((W,)))
    np.testing.assert_allclose(none, zero, atol=1e-6)


# ----------------------------------------------------------- expert layer

def relu2_layer(held, experts=16, top_k=3, hidden=32, width=13):
    return nn.SparseMoE(hidden, width, experts, top_k, experts_held=held,
                        shared_size=2 * width, scaling=2.5,
                        initializer_range=0.3, router='sigmoid',
                        activation='relu2')


def reference_layer(layer, held, x):
    """The reference's expert layer on `layer`'s weights, holding `held`."""
    w = {'router': layer.router._value,
         'experts_up': layer.experts_up._value,
         'experts_down': layer.experts_down._value,
         'shared.up_proj': layer.shared.up_proj._value,
         'shared.down_proj': layer.shared.down_proj._value}
    cfg = {'experts_held': list(held), 'num_experts_per_tok': layer.top_k,
           'routed_scaling_factor': layer.scaling}
    return REFERENCE._moe(cfg, w, x, 'float32')


def test_the_ungated_layer_has_no_gate_matrix_and_follows_the_reference():
    """Experts 4..7 of 16 held, ungated at width 13 with a shared expert of
    26: output and the gradients by the input, the router and the four
    matrices against the reference's; the gated layer keeps its three."""
    rs = np.random.default_rng(3)
    layer = relu2_layer((4, 8))
    names = ['router', 'experts_up', 'experts_down']
    assert not hasattr(layer, 'experts_gate')
    assert sorted(n for n, _ in layer.named_parameters()) == sorted(
        names + ['shared.up_proj', 'shared.down_proj'])
    assert hasattr(nn.SparseMoE(32, 16, 16, 3), 'experts_gate')
    with pytest.raises(ValueError):
        nn.SparseMoE(32, 16, 16, 3, activation='gelu')
    x = jnp.asarray(rs.normal(size=(2, 24, 32)), jnp.float32)
    cot = jnp.asarray(rs.normal(size=(2, 24, 32)), jnp.float32)

    def program(x, *ws):
        for name, w in zip(names, ws):
            getattr(layer, name)._value = w
        y, counters = layer(Tensor(x))
        return jnp.sum(y._value * cot), counters._value

    def plain(x, *ws):
        for name, w in zip(names, ws):
            getattr(layer, name)._value = w
        return jnp.sum(reference_layer(layer, (4, 8), x) * cot)

    ws = [getattr(layer, n)._value for n in names]
    (got, counters), got_grads = jax.value_and_grad(
        program, argnums=tuple(range(4)), has_aux=True)(x, *ws)
    want, want_grads = jax.value_and_grad(
        plain, argnums=tuple(range(4)))(x, *ws)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(want_grads, got_grads):
        np.testing.assert_allclose(b, a, atol=1e-5 + 1e-4 * float(
            jnp.max(jnp.abs(a))))
    c = dict(zip(moe.COUNTERS, np.asarray(counters)))
    assert c['assignments'] == 2 * 24 * 3 and c['dropped'] == 0
    assert 0 < c['assignments_held'] < c['assignments']


def test_a_width_that_is_no_whole_register_stays_on_the_kernels(telemetry):
    """Experts of 72 columns (the cell's are 1856: 14.5 registers) through
    the grouped-product kernels in interpret mode, laid on 128 behind zero
    columns, against the plain dense products at 72: the same numbers and
    the same gradients at the UNPADDED shapes, and no trace takes
    `grouped_matmul.xla`."""
    rs = np.random.default_rng(5)
    T, H, F, G, E, k = 64, 128, 72, 2, 8, 2
    x = jnp.asarray(rs.normal(size=(T, H)), jnp.float32)
    up = jnp.asarray(rs.normal(size=(G, H, F)) * 0.1, jnp.float32)
    down = jnp.asarray(rs.normal(size=(G, F, H)) * 0.1, jnp.float32)
    idx = jnp.asarray(np.stack([rs.permutation(E)[:k] for _ in range(T)]),
                      jnp.int32)
    weights = jnp.asarray(rs.random((T, k)), jnp.float32)
    cot = jnp.asarray(rs.normal(size=(T, H)), jnp.float32)

    def kernels(x, up, down):
        return jnp.sum(moe.expert_share(x, idx, weights, None, up, down,
                                        (2, 4), E, tile=8,
                                        interpret=True)[0] * cot)

    def plain(x, up, down):
        y = 0.0
        for e in range(2, 4):
            share = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
            y = y + share[:, None] * moe.relu2_mlp(x, up[e - 2], down[e - 2])
        return jnp.sum(y * cot)

    before = taken('grouped_matmul')
    got = jax.value_and_grad(kernels, argnums=(0, 1, 2))(x, up, down)
    pallas, xla = taken('grouped_matmul') - before
    assert pallas > 0 and xla == 0
    want = jax.value_and_grad(plain, argnums=(0, 1, 2))(x, up, down)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, b in zip(got[1], want[1]):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5 + 1e-4 * float(
            jnp.max(jnp.abs(b))))


def test_bfloat16_rows_whose_pairs_fill_no_register_are_gathered_as_words(
        telemetry):
    """A hidden size of 384 (the cell's 2688 is 10.5 registers of bfloat16
    pairs, this one 1.5): `gather_rows` moves the rows as float32 words
    through the kernels (interpret mode) and casts behind them; the rows and
    the gradient by x are those of the plain gather."""
    from paddle_tpu.kernels import row_permute
    rs = np.random.default_rng(8)
    tokens, width, tile = 64, 384, 16
    x = jnp.asarray(rs.normal(size=(tokens, width)), jnp.bfloat16)
    held = jnp.asarray([16, 9, 3, 0], jnp.int32)
    tok = jnp.asarray(rs.integers(0, tokens, 4 * tile), jnp.int32)
    cot = jnp.asarray(rs.normal(size=(4 * tile, width)), jnp.float32)
    valid = row_permute.rows_valid(held, tile)[:, None]

    def kernels(x):
        rows = row_permute.gather_rows(x, tok, held, interpret=True)
        return jnp.sum(rows.astype(jnp.float32) * cot), rows

    def plain(x):
        rows = jnp.where(valid, x[tok], 0)
        return jnp.sum(rows.astype(jnp.float32) * cot), rows

    before = taken('row_permute')
    (_, got), dgot = jax.value_and_grad(kernels, has_aux=True)(x)
    assert tuple(taken('row_permute') - before) == (1, 0)
    (_, want), dwant = jax.value_and_grad(plain, has_aux=True)(x)
    assert got.dtype == jnp.bfloat16 and dgot.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_allclose(np.asarray(dgot, np.float32),
                               np.asarray(dwant, np.float32), atol=0.07)


def test_the_sixteen_shares_add_up_to_the_uncut_references_layer():
    """The cut of the cell at a tiny size: sixteen chips share a layer of 16
    experts (the cell: 128, 8 a chip) of which a token picks 3. The routed
    sums the program's sixteen shares give, with the shared expert, which
    every chip computes alike, counted ONCE, add up to what the REFERENCE
    gives for the layer that holds every expert."""
    rs = np.random.default_rng(4)
    whole = relu2_layer((0, 16))
    x = jnp.asarray(rs.normal(size=(2, 24, 32)), jnp.float32)
    want = np.asarray(reference_layer(whole, (0, 16), x))
    shared = whole.shared(Tensor(x)).numpy()
    assert float(np.abs(shared).max()) > 1e-2
    total, held_sum = shared.copy(), 0.0
    for lo in range(16):
        share = relu2_layer((lo, lo + 1))
        share.router.set_value(whole.router)
        for name in ('experts_up', 'experts_down'):
            getattr(share, name).set_value(
                getattr(whole, name).numpy()[lo:lo + 1])
        for name in ('up_proj', 'down_proj'):
            getattr(share.shared, name).set_value(
                getattr(whole.shared, name))
        y, c = share(Tensor(x))
        total += y.numpy() - shared             # the routed part alone
        held_sum += float(c.numpy()[0])
        assert float(c.numpy()[4]) == 0.0                  # dropped
    assert held_sum == 2 * 24 * 3
    np.testing.assert_allclose(total, want, atol=5e-5)


# -------------------------------------------------- the layers' contracts

def test_the_pattern_names_every_layer_and_refuses_a_dense_one():
    assert nemotron_h.layer_kinds('ME*', None) == ['mamba', 'experts',
                                                   'attention']
    whole = nemotron_h.NemotronHConfig().layer_kinds
    assert (len(whole), whole.count('mamba'), whole.count('experts'),
            whole.count('attention')) == (52, 23, 23, 6)
    assert nemotron_h.NemotronHConfig(num_hidden_layers=9).layer_kinds == [
        nemotron_h.KINDS[c] for c in 'MEMEM*EME']
    with pytest.raises(ValueError, match='dense feed-forward'):
        nemotron_h.layer_kinds('ME-M')
    with pytest.raises(ValueError, match='not M'):
        nemotron_h.layer_kinds('MEX')
    with pytest.raises(ValueError, match='names 3 layers'):
        nemotron_h.layer_kinds('ME*', 4)
    with pytest.raises(ValueError, match='relu'):
        nemotron_h.NemotronHConfig(mlp_hidden_act='silu')


def test_attention_without_a_table_rotates_nothing():
    """`inv_freq=None`: no cosine in the trace (the rotation and its scope
    `attn.rope` are not entered), and the layer is the rotary layer at
    position 0 everywhere (a table of zeros)."""
    rs = np.random.default_rng(6)
    plain = nn.GroupedQueryAttention(32, 4, 2, 8, None,
                                     initializer_range=0.3)
    turned = nn.GroupedQueryAttention(32, 4, 2, 8, np.zeros(4),
                                      initializer_range=0.3)
    for name in ('q_proj', 'k_proj', 'v_proj', 'o_proj'):
        getattr(turned, name).set_value(getattr(plain, name))
    x = Tensor(jnp.asarray(rs.normal(size=(2, 16, 32)), jnp.float32))
    seg = Tensor(jnp.asarray(np.sort(rs.integers(0, 3, (2, 16)), axis=1),
                             jnp.int32))
    np.testing.assert_allclose(plain(x, seg).numpy(), turned(x, seg).numpy(),
                               atol=1e-6)

    def trace(layer):
        return str(jax.make_jaxpr(
            lambda v: layer(Tensor(v), seg)._value)(x._value))
    assert ' cos ' in trace(turned) and ' cos ' not in trace(plain)
    assert plain.inv_freq is None


def test_the_layers_own_initialisation_is_the_published_one():
    """`nn.Mamba2` draws A in [1, 16], the step log-uniform in [0.001, 0.1]
    and D = 1 (the benchmark's weights are the harness's normal draws)."""
    layer = nn.Mamba2(32, 64, 8, 2, 16)
    A = np.exp(layer.A_log.numpy())
    dt = np.log1p(np.exp(layer.dt_bias.numpy()))
    assert 1.0 <= A.min() and A.max() <= 16.0 and A.max() - A.min() > 5
    assert 0.001 <= dt.min() * 1.001 and dt.max() <= 0.1001
    assert np.all(layer.D.numpy() == 1.0)
    assert layer.in_proj.shape == [32, 2 * 512 + 2 * 32 + 64]
    assert layer.conv_weight.shape == [4, 512 + 64]
    assert nn.Mamba2(32, 64, 8, 2, 16, conv_bias=False).conv_bias is None


# ---------------------------------------------- program against reference

def tiny(cut):
    """The test configuration: 'whole' (the eight layers ME*MEM*E) or its
    first three, 'trio' (one of each letter)."""
    config = _tiny.load('nemotron-h-tiny')
    traffic = _tiny.load('train-pack-tiny')
    if cut == 'trio':
        config['num_hidden_layers'] = 3
    return config, traffic


_SOUND = {}


def sound_run(cut):
    """(program's readings, batches, reference's readings, its routing) of
    seed 7 at the test size, computed once."""
    if cut not in _SOUND:
        config, traffic = tiny(cut)
        readings, batches = program_readings(config, traffic, seed=7)
        routing = []
        sound = reference_readings(config, traffic, 7, batches,
                                   routing=routing)
        _SOUND[cut] = (readings, batches, sound, routing)
    return _SOUND[cut]


@pytest.mark.parametrize('cut', ['trio', 'whole'])
def test_program_follows_the_reference(cut):
    """Loss, first gradient leaf by leaf and the change of three AdamW
    steps: one layer of each letter, and the eight layers; experts 4..7 of
    16 held, 2 groups of 2 heads of 64, experts 13 wide."""
    readings, _, sound, routing = sound_run(cut)
    rows, ok = check.compare(readings, sound, LIMITS)
    assert ok, [r for r in rows if not r[3]]
    assert set(readings['first_gradient']) == set(sound['first_gradient'])
    config, _ = tiny(cut)
    letters = config['hybrid_override_pattern'][:config['num_hidden_layers']]
    assert [r.shape for r in routing] == [(2, 64, 3)] * letters.count('E')
    assert all(np.all(np.diff(r, axis=-1) > 0) for r in routing)
    leaves = set(readings['first_gradient'])
    assert 'layers.0.mixer.conv_bias' in leaves
    assert 'layers.1.mixer.shared.down_proj' in leaves
    assert not any('experts_gate' in leaf for leaf in leaves)


def test_lower_precision_control_is_not_correct():
    """The reference computed in float8, put in the program's place."""
    config, traffic = tiny('trio')
    _, batches, sound, _ = sound_run('trio')
    control = reference_readings(config, traffic, 7, batches,
                                 precision='float8')
    rows, ok = check.compare(control, sound, LIMITS)
    assert not ok, rows
    assert 'first_gradient_difference' in {r[0] for r in rows if not r[3]}


def _scan_call(monkeypatch, change):
    """`change(real, x, dt, A, Bm, Cm, D, seg, **kw)` in the place of the
    Mamba-2 layers' call of `ssd`."""
    real = state_space.ssd
    monkeypatch.setattr(state_space, 'ssd',
                        lambda *a, **kw: change(real, *a, **kw))


def _the_state_is_not_reset_at_a_document(monkeypatch):
    _scan_call(monkeypatch, lambda real, x, dt, A, Bm, Cm, D, seg, **kw: real(
        x, dt, A, Bm, Cm, D, jnp.zeros_like(seg), **kw))


def _a_head_reads_b_and_c_of_the_wrong_group(monkeypatch):
    _scan_call(monkeypatch, lambda real, x, dt, A, Bm, Cm, D, seg, **kw: real(
        x, dt, A, jnp.roll(Bm, 1, axis=2), jnp.roll(Cm, 1, axis=2), D, seg,
        **kw))


def _the_step_does_not_scale_the_input(monkeypatch):
    """S_t = exp(dt A) S + x B^T: the rule on x / dt, its skip put right."""
    def change(real, x, dt, A, Bm, Cm, D, seg, **kw):
        plain = x / dt[..., None]
        return real(plain, dt, A, Bm, Cm, D, seg, **kw) \
            + D[:, None] * (x - plain)
    _scan_call(monkeypatch, change)


def _the_gate_comes_behind_the_norm(monkeypatch):
    def behind(y, z, scale, groups, eps):
        B, T, W = y.shape
        y = y.reshape(B, T, groups, W // groups)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return y.reshape(B, T, W) * scale * jax.nn.silu(z)
    monkeypatch.setattr(state_space, 'gated_group_norm', behind)


def _a_query_head_reads_the_next_kv_head(monkeypatch):
    real = flash_attention.flash_attention_bhld
    monkeypatch.setattr(
        flash_attention, 'flash_attention_bhld',
        lambda q, k, v, **kw: real(q, jnp.roll(k, 1, axis=1),
                                   jnp.roll(v, 1, axis=1), **kw))


def _the_convolution_drops_its_bias(monkeypatch):
    real = state_space.short_conv
    monkeypatch.setattr(state_space, 'short_conv',
                        lambda y, w, seg, bias=None: real(y, w, seg))


@pytest.mark.parametrize('fault', [
    _the_state_is_not_reset_at_a_document,
    _a_head_reads_b_and_c_of_the_wrong_group,
    _the_step_does_not_scale_the_input, _the_gate_comes_behind_the_norm,
    _a_query_head_reads_the_next_kv_head, _the_convolution_drops_its_bias])
def test_a_planted_fault_fails_the_limits(fault, monkeypatch):
    """The program with one thing wrong, on the batches and against the
    reference of the sound run of one layer of each letter."""
    _, _, sound, _ = sound_run('trio')
    fault(monkeypatch)
    readings, _ = program_readings(*tiny('trio'), seed=7)
    rows, ok = check.compare(readings, sound, LIMITS)
    assert not ok, rows


# ------------------------------------------------------ the committed cell

def test_the_cell_states_the_published_widths():
    """The committed configuration against the catalog row the driver drew
    (`Nemotron-Labs-TwoTower-30B-A3B-Base-BF16`): every width as published,
    the three cuts named, inside the floors; the family's count of
    parameters and of required operations; nothing of the denoiser."""
    with open(os.path.join(ROOT, 'benchmark', 'configs',
                           'nemotron-labs-twotower-30b-a3b.json')) as f:
        config = json.load(f)
    published = dict(
        attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
        head_dim=128, hidden_size=2688, intermediate_size=1856,
        layer_norm_epsilon=1e-5, mamba_head_dim=64, mamba_hidden_act='silu',
        mamba_num_heads=64, mamba_proj_bias=False,
        max_position_embeddings=262144, mlp_bias=False,
        mlp_hidden_act='relu2', model_type='nemotron_h',
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        n_group=1, n_groups=8, n_shared_experts=1, norm_eps=1e-5,
        norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=6,
        num_key_value_heads=2, partial_rotary_factor=1,
        rescale_prenorm_residual=True, residual_in_fp32=False,
        rope_theta=10000, routed_scaling_factor=2.5, sliding_window=None,
        ssm_state_size=128, tie_word_embeddings=False,
        time_step_floor=1e-4, time_step_limit=[0, None], time_step_max=0.1,
        time_step_min=0.001, topk_group=1, use_bias=False,
        use_conv_bias=True, use_mamba_kernels=True)
    assert {k: config[k] for k in published} == published
    pattern = config['hybrid_override_pattern']
    assert pattern == 'MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME'
    assert (len(pattern), pattern.count('M'), pattern.count('E'),
            pattern.count('*')) == (52, 23, 23, 6)
    assert config['reduced'] == ['num_hidden_layers', 'n_routed_experts',
                                 'vocab_size']
    assert set(config['reduced_from']) == set(config['reduced'])
    assert (config['num_hidden_layers'], config['n_routed_experts'],
            config['vocab_size']) == (9, 8, 16384)      # of 52, 128, 131072
    assert pattern[:9] == 'MEMEM*EME' and set(pattern[:9]) == set(pattern)
    assert config['num_experts_total'] == 128
    assert config['experts_held'] == [0, 8]
    assert config['n_routed_experts'] >= 8
    assert config['vocab_size'] * 8 >= 131072
    for key in ('towers', 'block', 'mamba', 'attention', 'intermediate_size',
                'router', 'experts', 'optimizer', 'weights',
                'initializer_range', 'precision'):
        assert config['assumed'][key]
    assert 'NOTHING of it is built' in config['assumed']['towers']
    family = _tiny.harness_run.load_module('families', config['family'])
    spec = family.param_spec(config)
    assert not any('gate' in name or 'adaln' in name.lower()
                   for name in spec)
    assert sorted(family.buffer_spec(config)) == [
        'layers.%d.mixer.e_score_correction_bias' % i for i in (1, 3, 6, 8)]
    count = sum(int(np.prod(shape)) for shape, _ in spec.values())
    assert count == 666962944                  # ISSUE 43's 667.0M: 10.67 GB
    assert spec['layers.0.mixer.in_proj'][0] == (2688, 4096 + 6144 + 64)
    assert spec['layers.0.mixer.conv_bias'][0] == (6144,)
    assert spec['layers.1.mixer.experts_up'][0] == (8, 2688, 1856)
    assert spec['layers.1.mixer.shared.up_proj'][0] == (2688, 3712)
    assert spec['layers.5.mixer.k_proj'][0] == (2688, 2 * 128)
    with open(os.path.join(ROOT, 'benchmark', 'traffic',
                           'train-pack8k.json')) as f:
        traffic = json.load(f)
    # 33.0 TFLOP a step of two rows (ISSUE 43: 11.0 forward, 33 in all)
    flops = family.flops_per_sample(config, traffic)
    assert 16.4e12 < flops < 16.6e12
    one = dict(config, num_hidden_layers=1)        # a Mamba-2 layer alone
    none = dict(config, num_hidden_layers=0)
    mamba = (family.flops_per_sample(one, traffic)
             - family.flops_per_sample(none, traffic)) / 6 / 8192
    assert mamba == 2688 * 10304 + 4096 * 2688 + 8 * 128 * 128 \
        + 64 * (128 * 64 + 2 * 64 * 128)         # 38.7M + 1.70M a token
    pool = family.make_pool(dict(config), dict(traffic, seq_len=512,
                                               doc_len_clip=[8, 512],
                                               doc_len_median=64), 3, 1, 2)
    (ids, seg, labels), _ = pool[0]
    assert max(ids.max(), labels.max()) < config['vocab_size']

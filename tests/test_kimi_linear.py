"""Kimi-Linear through the train engine, at a test size on the CPU: each new
layer against its plain form, the expert layer's share contract, and the
program against the benchmark's plain reference (float32 on both sides, so
what is held is that both do the same mathematics; the chip holds the stated
bf16 precision to the cell's limits).
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
from harness import check, params  # noqa: E402
from harness.spans import Spans  # noqa: E402

from paddle_tpu import nn  # noqa: E402
from paddle_tpu.kernels.flash_attention import flash_attention_bhld  # noqa: E402
from paddle_tpu.nn.functional import delta_rule, moe  # noqa: E402

# program against reference in float32, my CPU runs, PR 27: loss_gap under
# 2e-6, first_gradient_gap under 3e-4, first_gradient_difference under 1e-5,
# change_gap under 2e-3; the float8 control: first_gradient_difference 0.03
# and more
LIMITS = {'loss_gap': 1e-4, 'first_gradient_gap': 5e-3,
          'first_gradient_difference': 1e-3, 'change_gap': 2e-2,
          'loss_fall': -1e9}


def rows_with_documents(rs, rows, seq, documents):
    return jnp.asarray(np.sort(rs.integers(0, documents, (rows, seq)),
                               axis=1), jnp.int32)


# ------------------------------------------------------------ delta rule

def delta_rule_recurrent(q, k, v, g, beta, seg, scale):
    """The recurrence as the paper writes it, token by token: what the
    program's chunk-wise form is held to. q, k, g (B, T, H, K);
    v (B, T, H, V); beta (B, T, H); seg (B, T) -> o (B, T, H, V), float32."""
    B, T, H, K = q.shape
    high = jax.lax.Precision.HIGHEST
    first = jnp.concatenate([jnp.ones((B, 1), bool),
                             seg[:, 1:] != seg[:, :-1]], axis=1)

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t, new = xs
        S = jnp.where(new[:, None, None, None], 0.0, S)
        S = S * jnp.exp(g_t)[..., None]
        read = jnp.einsum('bhkv,bhk->bhv', S, k_t, precision=high)
        S = S + jnp.einsum('bhk,bhv->bhkv', k_t,
                           b_t[..., None] * (v_t - read), precision=high)
        return S, jnp.einsum('bhkv,bhk->bhv', S, q_t, precision=high)

    f32 = jnp.float32
    xs = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (q, k, v, g, beta)
               ) + (jnp.moveaxis(first, 1, 0),)
    _, o = jax.lax.scan(step, jnp.zeros((B, H, K, v.shape[-1]), f32), xs)
    return jnp.moveaxis(o, 0, 1) * scale


@pytest.mark.parametrize('chunk,sub,decay', [
    (32, 8, 'mixed'), (16, 16, 'mixed'), (32, 8, 'strong'), (64, 16, 'weak')])
def test_chunked_delta_rule_follows_the_recurrence(chunk, sub, decay):
    """Forward and gradient, with document boundaries inside chunks and at
    their edges, under decays from none to exp(-30) a token."""
    rs = np.random.default_rng(3)
    B, T, H, K, V = 2, 128, 2, 16, 8
    q, k, g = (jnp.asarray(rs.normal(size=(B, T, H, K)), jnp.float32)
               for _ in range(3))
    v = jnp.asarray(rs.normal(size=(B, T, H, V)), jnp.float32)
    g = -jnp.exp({'mixed': 2.0, 'strong': 0.5, 'weak': 1.0}[decay] * g
                 + {'mixed': -1.0, 'strong': 3.0, 'weak': -5.0}[decay])
    beta = jax.nn.sigmoid(jnp.asarray(rs.normal(size=(B, T, H)), jnp.float32))
    seg = rows_with_documents(rs, B, T, 4)
    seg = seg.at[0, 64:].set(seg[0, 64:] + 4)      # one at a chunk's edge

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def plain(q, k, v, g, beta):
        return jnp.sum(jnp.sin(delta_rule_recurrent(
            unit(q), unit(k), v, g, beta, seg, K ** -0.5)))

    def chunked(q, k, v, g, beta):
        return jnp.sum(jnp.sin(delta_rule.delta_rule_chunked(
            unit(q), unit(k), v, g, beta, seg, K ** -0.5, chunk=chunk,
            sub=sub)))

    want = jax.value_and_grad(plain, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    got = jax.jit(jax.value_and_grad(chunked, argnums=(0, 1, 2, 3, 4)))(
        q, k, v, g, beta)
    assert abs(float(want[0]) - float(got[0])) < 1e-4
    for a, b in zip(want[1], got[1]):
        assert np.all(np.isfinite(b))
        np.testing.assert_allclose(b, a, atol=2e-5 * float(jnp.max(jnp.abs(a)))
                                   + 1e-6)


def test_causal_conv_stops_at_document_boundaries():
    rs = np.random.default_rng(0)
    x = jnp.asarray(rs.normal(size=(1, 12, 3)), jnp.float32)
    w = jnp.asarray(rs.normal(size=(4, 3)), jnp.float32)
    seg = jnp.asarray([[0] * 5 + [1] * 7], jnp.int32)
    y = np.asarray(delta_rule.causal_conv(x, w, seg))
    for t in range(12):
        want = sum(np.asarray(w)[3 - b] * np.asarray(x)[0, t - b]
                   for b in range(4)
                   if t - b >= 0 and seg[0, t - b] == seg[0, t])
        np.testing.assert_allclose(y[0, t], want, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- latent attention

@pytest.mark.parametrize('path', ['pallas-interpret', 'xla'])
def test_attention_with_two_head_sizes_and_a_document_mask(path):
    """q and k 48 wide, v 32 wide (the shape of 192 / 128), packed documents:
    the kernels (interpret mode) and the XLA path against plain attention
    written out here, forward and gradient."""
    rs = np.random.default_rng(1)
    B, H, L, D, DV = 2, 2, 256, 48, 32
    q, k = (jnp.asarray(rs.normal(size=(B, H, L, D)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rs.normal(size=(B, H, L, DV)), jnp.float32)
    seg = rows_with_documents(rs, B, L, 5)
    start = nn.layer.linear_attention.doc_starts(seg)
    sees = (seg[:, :, None] == seg[:, None, :]) \
        & (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])

    def plain(q, k, v):
        s = jnp.einsum('bhld,bhmd->bhlm', q, k) * D ** -0.5
        p = jax.nn.softmax(jnp.where(sees[:, None], s, -1e30), axis=-1)
        return jnp.sum(jnp.sin(jnp.einsum('bhlm,bhmd->bhld', p, v)))

    def program(q, k, v):
        return jnp.sum(jnp.sin(flash_attention_bhld(
            q, k, v, causal=True, doc_start=start, block_q=64, block_k=64,
            interpret=path == 'pallas-interpret')))

    want = jax.value_and_grad(plain, argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(program, argnums=(0, 1, 2))(q, k, v)
    assert abs(float(want[0]) - float(got[0])) < 1e-3
    for a, b in zip(want[1], got[1]):
        np.testing.assert_allclose(b, a, atol=1e-4)
    with pytest.raises(ValueError):
        flash_attention_bhld(q, k, v, causal=False, doc_start=start)


# ----------------------------------------------------------- expert layer

def expert_layer(held, experts=16, top_k=4, hidden=16, width=8):
    return nn.SparseMoE(hidden, width, experts, top_k, experts_held=held,
                        shared_size=width, scaling=2.446,
                        initializer_range=0.3)


@pytest.mark.parametrize('per_share', [1, 4])
def test_the_shares_add_up_to_the_uncut_layer(per_share):
    """The routed parts of all the shares, plus the shared expert once,
    equal the layer that holds every expert."""
    rs = np.random.default_rng(2)
    experts = 16
    whole = expert_layer((0, experts))
    x = nn.functional.dropout(jnp.asarray(rs.normal(size=(2, 24, 16)),
                                          jnp.float32), p=0.0)
    want, counters = whole(x)
    assert float(counters.numpy()[0]) == 2 * 24 * 4       # all held
    shared = whole.shared(x).numpy()
    total = np.zeros_like(want.numpy())
    held_sum = 0.0
    for lo in range(0, experts, per_share):
        share = expert_layer((lo, lo + per_share))
        share.router.set_value(whole.router)
        for name in ('experts_gate', 'experts_up', 'experts_down'):
            getattr(share, name).set_value(
                getattr(whole, name).numpy()[lo:lo + per_share])
        for name in ('gate_proj', 'up_proj', 'down_proj'):
            getattr(share.shared, name).set_value(getattr(whole.shared, name))
        y, c = share(x)
        total += y.numpy() - shared
        held_sum += float(c.numpy()[0])
        assert float(c.numpy()[4]) == 0.0                  # dropped
    assert held_sum == 2 * 24 * 4
    np.testing.assert_allclose(total + shared, want.numpy(), atol=2e-5)


def per_expert_loop(x, idx, w, gate, up, down, held):
    """The routed sum as it is written: every held expert over every token,
    masked, in float32."""
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(*held):
        m = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)[:, None]
        y = y + m * moe.swiglu(x, gate[e - held[0]], up[e - held[0]],
                               down[e - held[0]])
    return y


def counted(c):
    return dict(zip(moe.COUNTERS, np.asarray(c).tolist()))


@pytest.mark.parametrize('held,buffer,rounds', [
    ((1, 2), 'large', 2), ((0, 2), 'large', 1), ((0, 4), 'small', 1)])
def test_no_token_is_dropped_when_every_token_goes_to_one_expert(
        held, buffer, rounds):
    """A router that sends every token to the same held expert. The row
    buffer is twice an even router's share, or four times where that is too
    small: a chip that holds four experts of 16 takes the smaller one, one
    that holds two the larger one, and for one that holds this expert alone
    the rows are twice the larger buffer, so the same product runs a second
    round. Either way every assignment is computed."""
    rs = np.random.default_rng(4)
    T, H, F, E, k, G = 96, 16, 8, 16, 2, held[1] - held[0]
    x = jnp.asarray(rs.normal(size=(T, H)), jnp.float32)
    gate, up = (jnp.asarray(rs.normal(size=(G, H, F)) * 0.3, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rs.normal(size=(G, F, H)) * 0.3, jnp.float32)
    bias = jnp.zeros((E,)).at[jnp.asarray([1, 9])].set(10.0)
    idx, w = moe.route_sigmoid_topk(x, jnp.zeros((H, E)), bias, k, 2.446)
    assert set(np.unique(np.asarray(idx))) == {1, 9}
    tile = moe.row_tile(T, k, E)
    small, large = (n * tile for n in moe.buffer_tiles(T, k, G, E, tile))
    assert (T <= small, T <= large) == (buffer == 'small', rounds == 1)
    y, c = jax.jit(lambda *a: moe.expert_share(*a, held, E))(
        x, idx, w, gate, up, down)
    at = 1 - held[0]
    want = jnp.sum(jnp.where(idx == 1, w, 0.0), 1)[:, None] \
        * moe.swiglu(x, gate[at], up[at], down[at])
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert counted(c) == {
        'assignments_held': T, 'assignments': T * k, 'expert_rows_max': T,
        'expert_rows_mean': T / G, 'dropped': 0.0, 'rows_computed': T,
        'rounds': rounds, 'rows_moved': T}


def _picks(T, k, experts, rows):
    """idx (T, k): `rows[e]` tokens pick held expert e (the first rows[e]
    tokens, so a token may pick several), every other pick goes to the
    experts from 8 on, which nobody holds."""
    idx = np.tile(np.arange(8, 8 + k), (T, 1))
    for slot, (e, n) in enumerate(sorted(rows.items())):
        idx[:n, slot] = e
    assert idx.max() < experts and all(len(set(r)) == k for r in idx)
    return jnp.asarray(idx, jnp.int32)


# T 64, top 4 of 32, experts (0, 4) held: tiles of 8 rows, buffers of 8 and
# of 16 tiles (64 and 128 rows)
_ROUTINGS = {
    'an expert with zero rows': ({0: 20, 1: 0, 2: 7, 3: 0}, 1),
    'every row to the last held expert': ({3: 64}, 1),
    'the rows exactly fill the smaller buffer': ({0: 32, 2: 32}, 1),
    'one row more than the smaller buffer': ({0: 32, 1: 1, 2: 32}, 1),
    'the rows exactly fill the larger buffer': ({0: 64, 2: 64}, 1),
    'one row more than the larger buffer': ({0: 64, 1: 1, 2: 64}, 2),
    'no row at all': ({}, 1),
}


@pytest.mark.parametrize('path', ['xla', 'pallas-interpret'])
@pytest.mark.parametrize('routing', sorted(_ROUTINGS))
def test_the_routed_product_equals_the_per_expert_loop(routing, path):
    """Output, and the gradients of x, of the routing weights and of the
    three weight stacks, against every held expert over every token in
    float32; the rows computed stay within a tile an expert of the rows
    held, none dropped, and a second round exactly when the padded rows
    pass the larger buffer. The kernels run in interpret mode at widths of
    whole lane registers."""
    rows, rounds = _ROUTINGS[routing]
    T, E, k, held = 64, 32, 4, (0, 4)
    H, F = (128, 256) if path != 'xla' else (16, 8)
    rs = np.random.default_rng(3)
    x = jnp.asarray(rs.normal(size=(T, H)), jnp.float32)
    gate, up = (jnp.asarray(rs.normal(size=(4, H, F)) * H ** -0.5,
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rs.normal(size=(4, F, H)) * F ** -0.5, jnp.float32)
    idx = _picks(T, k, E, rows)
    w = jnp.asarray(rs.uniform(0.2, 1.0, size=(T, k)), jnp.float32)
    cot = jnp.asarray(rs.normal(size=(T, H)), jnp.float32)
    tile = moe.row_tile(T, k, E)
    assert (tile, moe.buffer_tiles(T, k, 4, E, tile)) == (8, (8, 16))

    def program(*a):
        y, c = moe.expert_share(a[0], idx, *a[1:], held, E,
                                interpret=path != 'xla')
        return jnp.sum(y * cot), (y, c)

    def plain(*a):
        y = per_expert_loop(a[0], idx, *a[1:], held)
        return jnp.sum(y * cot), y
    every = (0, 1, 2, 3, 4)
    (_, (y, c)), got = jax.jit(jax.value_and_grad(
        program, argnums=every, has_aux=True))(x, w, gate, up, down)
    (_, want_y), want = jax.jit(jax.value_and_grad(
        plain, argnums=every, has_aux=True))(x, w, gate, up, down)
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)
    if any(rows.values()):
        assert all(float(jnp.max(jnp.abs(g))) > 0 for g in got)
    c, n_held = counted(c), sum(rows.values())
    assert c['assignments_held'] == n_held and c['dropped'] == 0.0
    assert c['rounds'] == rounds
    assert n_held <= c['rows_computed'] <= n_held + 4 * tile
    assert c['rows_computed'] == sum(-(-n // tile) * tile
                                     for n in rows.values())


# ---------------------------------------------- program against reference

def tiny(layers):
    """The test configuration with only the named layers: 'kda+dense',
    'kda+moe', 'mla+moe' or 'whole' (KDA + dense, KDA + experts, MLA +
    experts)."""
    config = _tiny.load('kimi-linear-tiny')
    traffic = _tiny.load('train-pack-tiny')
    if layers != 'whole':
        attention, ffn = layers.split('+')
        config['num_hidden_layers'] = 1
        config['first_k_dense_replace'] = int(ffn == 'dense')
        config['linear_attn_config'].update(
            kda_layers=[1] if attention == 'kda' else [],
            full_attn_layers=[1] if attention == 'mla' else [])
    return config, traffic


def modules(config, traffic):
    run = _tiny.harness_run
    family = run.load_module('families', config['family'])
    return (family, run.load_module('families', family.REFERENCE),
            run.load_module('jobs', traffic['job']))


def program_readings(config, traffic, seed):
    """The first three steps of the program's compiled step, driven as a
    run's set-up drives them -> (readings, the batches it was fed)."""
    family, _, job = modules(config, traffic)
    step, make_state, spec = job.build_step(
        family, config, traffic, jax.devices()[:1], deterministic=False)
    feed = job.Feed(family, traffic, family.make_pool(
        config, traffic, seed, traffic['pool_batches'],
        traffic['batch_per_chip']), seed, remember=job.CHECK_STEPS)
    feed_iter = job.prefetcher(step, feed)
    try:
        _, readings = job.checked_steps(
            job.Caller(feed_iter, Spans(), config['compute_dtype']), step,
            make_state(seed), family, config,
            lambda: params.make(spec, seed))
    finally:
        feed_iter.close()
    return readings, feed.first


def reference_readings(config, traffic, seed, batches, **kw):
    family, reference, _ = modules(config, traffic)
    return reference.follow_steps(
        config, config['optimizer'],
        params.make(family.param_spec(config), seed), batches, **kw)


_SOUND = {}


def sound_run(layers):
    """(program's readings, batches, reference's readings, its routing) of
    seed 7 at the test size, computed once for the tests that share them."""
    if layers not in _SOUND:
        config, traffic = tiny(layers)
        readings, batches = program_readings(config, traffic, seed=7)
        routing = []
        sound = reference_readings(config, traffic, 7, batches,
                                   routing=routing)
        _SOUND[layers] = (readings, batches, sound, routing)
    return _SOUND[layers]


@pytest.mark.parametrize('layers', ['kda+dense', 'kda+moe', 'mla+moe',
                                    'whole'])
def test_program_follows_the_reference(layers):
    """Loss, first gradient leaf by leaf and the change of three AdamW
    steps, for each layer type alone and for the whole net."""
    readings, _, sound, _ = sound_run(layers)
    rows, ok = check.compare(readings, sound, LIMITS)
    assert ok, [r for r in rows if not r[3]]
    assert set(readings['first_gradient']) == set(sound['first_gradient'])


def test_lower_precision_control_is_not_correct():
    """The reference computed in float8, put in the program's place."""
    config, traffic = tiny('whole')
    _, batches, sound, _ = sound_run('whole')
    control = reference_readings(config, traffic, 7, batches,
                                 precision='float8')
    rows, ok = check.compare(control, sound, LIMITS)
    assert not ok, rows
    assert 'first_gradient_difference' in {r[0] for r in rows if not r[3]}


def _state_leaks_across_documents(monkeypatch):
    real = delta_rule.delta_rule_chunked
    monkeypatch.setattr(
        nn.layer.linear_attention, 'delta_rule',
        lambda q, k, v, g, beta, seg, *a, **kw: real(
            q, k, v, g, beta, jnp.zeros_like(seg), *a, **kw))


def _attention_sees_other_documents(monkeypatch):
    monkeypatch.setattr(nn.layer.linear_attention, 'doc_starts',
                        lambda seg: jnp.zeros_like(seg))


def _routed_weights_are_not_scaled(monkeypatch):
    real = moe.route_sigmoid_topk
    monkeypatch.setattr(
        moe, 'route_sigmoid_topk',
        lambda x, w, b, k, scaling: real(x, w, b, k, 1.0))


def _one_held_expert_is_left_out(monkeypatch):
    real = moe.expert_share

    def share(x, idx, weights, gate, up, down, held, experts, **kw):
        return real(x, jnp.where(idx == held[0], experts, idx), weights,
                    gate, up, down, held, experts, **kw)
    monkeypatch.setattr(moe, 'expert_share', share)


@pytest.mark.parametrize('fault,layers', [
    (_state_leaks_across_documents, 'kda+dense'),
    (_attention_sees_other_documents, 'mla+moe'),
    (_routed_weights_are_not_scaled, 'kda+moe'),
    (_one_held_expert_is_left_out, 'kda+moe')])
def test_a_planted_fault_fails_the_limits(fault, layers, monkeypatch):
    """The program with one thing wrong, on the batches and against the
    reference of the sound run of the layer the fault lies in."""
    _, _, sound, _ = sound_run(layers)
    fault(monkeypatch)
    readings, _ = program_readings(*tiny(layers), seed=7)
    rows, ok = check.compare(readings, sound, LIMITS)
    assert not ok, rows


def test_the_reference_reports_its_routing():
    routing = sound_run('whole')[3]
    assert [r.shape for r in routing] == [(2, 64, 4)] * 2
    assert all(np.all(np.diff(r, axis=-1) > 0) for r in routing)


def test_the_cell_states_the_published_widths():
    """The committed configuration against the catalog's numbers this PR
    was drawn: every width as published, the three cuts named."""
    with open(os.path.join(ROOT, 'benchmark', 'configs',
                           'kimi-linear-48b-a3b.json')) as f:
        config = json.load(f)
    assert (config['hidden_size'], config['intermediate_size'],
            config['moe_intermediate_size'], config['kv_lora_rank'],
            config['qk_nope_head_dim'], config['qk_rope_head_dim'],
            config['v_head_dim'], config['num_experts_per_token'],
            config['num_experts_total']) == (2304, 9216, 1024, 512, 128, 64,
                                             128, 8, 256)
    assert config['reduced'] == ['num_hidden_layers', 'num_experts',
                                 'vocab_size']
    family = _tiny.harness_run.load_module('families', config['family'])
    spec = family.param_spec(config)
    count = sum(int(np.prod(shape)) for shape, _ in spec.values())
    assert 600e6 < count < 605e6
    with open(os.path.join(ROOT, 'benchmark', 'traffic',
                           'train-pack8k.json')) as f:
        traffic = json.load(f)
    per_token = family.flops_per_sample(config, traffic) / traffic['seq_len']
    assert 2.0e9 < per_token < 2.4e9
    pool = family.make_pool(dict(config), dict(traffic, seq_len=512,
                                               doc_len_clip=[8, 512],
                                               doc_len_median=64), 3, 1, 2)
    (ids, seg, labels), _ = pool[0]
    assert ids.max() < config['vocab_size'] and labels.max() < \
        config['vocab_size']
    inside = seg[:, 1:] == seg[:, :-1]
    assert np.all((labels[:, :-1] >= 0) == inside)
    assert np.all(labels[:, :-1][inside] == ids[:, 1:][inside])


def test_berts_flash_attention_program_is_unchanged(monkeypatch):
    """The kernels took a second head size and a document mask by shape and
    by an optional operand: a call that has neither (BERT's: key-padding
    bias, in-kernel dropout; the decoders' causal one) traces to the program
    it traced to before PR 27, forward and backward. The digest is of the
    jaxprs of commit 848c652 at these shapes, source locations taken out."""
    import hashlib
    import re
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    texts = []
    for causal, kpad, p in [(False, True, 0.1), (False, False, 0.1),
                            (True, False, 0.0), (False, True, 0.0)]:
        def loss(q, k, v, bias, seed):
            o = flash_attention_bhld(
                q, k, v, causal=causal, kpad_bias=bias if kpad else None,
                dropout_p=p, dropout_seed=seed if p else None)
            return jnp.sum(o.astype(jnp.float32))
        q = jnp.zeros((16, 16, 512, 64), jnp.bfloat16)
        texts.append(str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
            q, q, q, jnp.zeros((16, 512), jnp.float32),
            jnp.zeros((1, 1), jnp.int32))))
    text = re.sub(r'/[\w/.\-]+\.py:\d+', 'SRC', '\n'.join(texts))
    text = re.sub(r'at SRC|SRC', '', text)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        '8a989b639155b4349508935f072c7fba57ec3970a4b9a0e4b1e179c0c2329f34'

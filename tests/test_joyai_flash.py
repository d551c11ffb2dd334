"""JoyAI-LLM-Flash through the train engine, at a test size on the CPU: the
rotary low-rank latent layer on packed rows, the prediction module's labels,
masks and shared gradients, the expert layer's 16-way share, and the program
against the benchmark's plain reference (float32 on both sides, so what is
held is that both do the same mathematics; the chip holds the stated bf16
precision to the cell's limits).
"""
import hashlib
import json
import os
import re
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
from test_kimi_linear import (counted, program_readings,  # noqa: E402
                              reference_readings)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import nn  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.nn.functional import moe  # noqa: E402
from paddle_tpu.nn.layer.linear_attention import rotate_pairs  # noqa: E402
from paddle_tpu.nn.layer_base import functional_call, param_values  # noqa: E402
from paddle_tpu.text.joyai_flash import (JoyAIFlashConfig,  # noqa: E402
                                         JoyAIFlashForCausalLM)

# program against reference in float32 (my CPU runs, PR 31, seed 7): loss_gap
# at most 4e-8 (a float32 step of a loss of 7.3), first_gradient_gap 1.6e-7
# (the worst leaf's NORM: the order of the sums), first_gradient_difference
# 4e-7 (the median leaf's difference), change_gap 5e-6 (AdamW's normalised
# step magnifies a leaf's smallest gradients). The float8 control reads
# loss_gap 7e-4 to 2e-3, 0.055, 0.127 and 0.033, and each planted fault
# loss_gap 1e-3 and more, 0.1-0.19, 0.19-0.32, 0.05-0.12. Each limit stands
# 100 times and more over the sound reading and under the control's and the
# faults'.
LIMITS = {'loss_gap': 1e-5, 'first_gradient_gap': 5e-3,
          'first_gradient_difference': 1e-3, 'change_gap': 2e-2,
          'loss_fall': -1e9}


def tiny_net():
    paddle.seed(5)
    net = JoyAIFlashForCausalLM(JoyAIFlashConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=48, moe_intermediate_size=16,
        num_experts=16, num_experts_per_token=4, q_lora_rank=24,
        kv_lora_rank=8, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=10000.0, experts_held=(4, 8), moe_block=8,
        initializer_range=0.2))
    net.train()
    return net


def packed_batch(rs, rows=2, seq=48, documents=4, vocab=64):
    """ids, document numbers, next-token labels and labels two ahead, as the
    family's pool makes them."""
    ids = rs.integers(0, vocab, (rows, seq)).astype(np.int32)
    seg = np.sort(rs.integers(0, documents, (rows, seq)), axis=1) \
        .astype(np.int32)
    labels = np.full((rows, seq), -1, np.int32)
    labels[:, :-1] = np.where(seg[:, 1:] == seg[:, :-1], ids[:, 1:], -1)
    ahead = np.full((rows, seq), -1, np.int32)
    ahead[:, :-2] = np.where(seg[:, 2:] == seg[:, :-2], ids[:, 2:], -1)
    return ids, seg, labels, ahead


def loss_and_counters(net, weights, batch):
    (loss, counters), _ = functional_call(
        net, weights, *(Tensor(jnp.asarray(a)) for a in batch))
    return loss._value, counters._value


# ------------------------------------------------------- rotary attention

def rotary_layer():
    paddle.seed(3)
    return nn.LatentAttention(32, 2, 16, 8, 16, 8, epsilon=1e-6,
                              initializer_range=0.2, q_lora_rank=24,
                              rope_theta=10000.0)


def test_rotation_turns_adjacent_pairs_by_the_published_angles():
    rs = np.random.default_rng(0)
    x = rs.normal(size=(1, 5, 3, 8)).astype(np.float32)
    pos = np.array([[0, 1, 2, 0, 7]], np.int32)
    got = np.asarray(rotate_pairs(jnp.asarray(x), jnp.asarray(pos), 32e6))
    for t in range(5):
        for j in range(4):
            angle = pos[0, t] * 32e6 ** (-2 * j / 8)
            z = (x[0, t, :, 2 * j] + 1j * x[0, t, :, 2 * j + 1]) \
                * np.exp(1j * angle)
            np.testing.assert_allclose(got[0, t, :, 2 * j], z.real, atol=1e-5)
            np.testing.assert_allclose(got[0, t, :, 2 * j + 1], z.imag,
                                       atol=1e-5)


def test_a_packed_row_equals_each_document_run_alone():
    """Positions restart at a document's first token and the mask holds: the
    layer's output over a packed row, and its gradient by the input, are
    those of each document given to the layer as a row of its own."""
    rs = np.random.default_rng(1)
    layer = rotary_layer()
    T, cuts = 40, [0, 7, 8, 29, 40]
    seg = np.repeat(np.arange(4), np.diff(cuts))[None].astype(np.int32)
    x = jnp.asarray(rs.normal(size=(1, T, 32)), jnp.float32)
    probe = jnp.asarray(rs.normal(size=(1, T, 32)), jnp.float32)

    @jax.jit
    def run(x, seg, probe):
        """-> (the layer's output, its pull-back of `probe` to x)."""
        out, pull = jax.vjp(lambda x: layer(Tensor(x), Tensor(seg))._value, x)
        return out, pull(probe)[0]

    whole, whole_grad = run(x, jnp.asarray(seg), probe)
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        alone, alone_grad = run(x[:, lo:hi], jnp.zeros((1, hi - lo), jnp.int32),
                                probe[:, lo:hi])
        np.testing.assert_allclose(whole[:, lo:hi], alone, atol=2e-5)
        np.testing.assert_allclose(whole_grad[:, lo:hi], alone_grad,
                                   atol=2e-5)
    # and global positions give the same scores: a score depends on the
    # difference of the two positions alone
    glob = jnp.arange(T, dtype=jnp.int32)[None]
    q = jnp.asarray(rs.normal(size=(1, T, 8)), jnp.float32)
    k = jnp.asarray(rs.normal(size=(1, T, 8)), jnp.float32)
    local = glob - jnp.asarray(np.repeat(cuts[:-1], np.diff(cuts)))[None]
    s_global = jnp.einsum('btd,bsd->bts', rotate_pairs(q, glob, 1e4),
                          rotate_pairs(k, glob, 1e4))
    s_local = jnp.einsum('btd,bsd->bts', rotate_pairs(q, local, 1e4),
                         rotate_pairs(k, local, 1e4))
    same = seg[0][:, None] == seg[0][None, :]
    np.testing.assert_allclose(np.asarray(s_global)[0][same],
                               np.asarray(s_local)[0][same], atol=1e-4)


def test_the_layer_without_the_new_arguments_traces_to_the_parents_program(
        monkeypatch):
    """`q_lora_rank` and `rope_theta` left at None: the layer of the Kimi
    configuration, its parameter names and, forward and gradient under bf16
    autocast with the block's norm in front and recomputation, the jaxpr it
    traced to at commit aa857e7 (digests of that commit's text at these
    shapes, source locations taken out), on the kernels' path and off it.
    PR 32 changed the kernels' program on packed rows (their loops take two
    more operands, the tile bounds made from `doc_start`): the 'tpu' digest
    is that commit's layer with PR 32's kernels, the 'cpu' one aa857e7's."""
    from paddle_tpu import amp
    paddle.seed(0)
    layer = nn.LatentAttention(256, 2, 128, 64, 128, 128, epsilon=1e-5)
    norm = nn.RMSNorm(256, epsilon=1e-5)
    names = [n for n, _ in layer.named_parameters()]
    assert names == ['q_proj', 'kv_a_proj', 'kv_a_norm', 'kv_b_proj',
                     'o_proj']
    weights = [p._value for _, p in layer.named_parameters()]

    def loss(x, seg, scale, *ws):
        norm.weight._value = scale
        with amp.auto_cast(dtype='bfloat16'):
            y, _ = functional_call(layer, dict(zip(names, ws)), Tensor(x),
                                   Tensor(seg), norm, True)
        return jnp.sum(y._value.astype(jnp.float32))

    x = jnp.zeros((2, 1024, 256), jnp.float32)
    seg = jnp.zeros((2, 1024), jnp.int32)
    for backend, digest in [
            ('tpu', '454c4399c50684e60c7f707b6fb326d94eaa5343eb434d97eda35e27c'
                    'cc14be3'),
            ('cpu', '733f06177ae1dfd8dfbcac97d8a4658f4593b1841ab299d9e8d26ffc'
                    'd7eae55f')]:
        monkeypatch.setattr(jax, 'default_backend', lambda b=backend: b)
        text = str(jax.make_jaxpr(jax.grad(
            loss, argnums=(0, 2) + tuple(range(3, 3 + len(weights)))))(
                x, seg, norm.weight._value, *weights))
        text = re.sub(r'/[\w/.\-]+\.py:\d+', 'SRC', text)
        text = re.sub(r'at SRC|SRC', '', text)
        assert hashlib.sha256(text.encode()).hexdigest() == digest, backend


# ----------------------------------------------------- prediction module

def test_the_modules_loss_stops_at_document_ends():
    """A position whose id two ahead lies in the next document (or past the
    row) adds nothing to L_mtp and receives no gradient from it: neither what
    the main model hands the module there nor the embedding one ahead (which,
    for a document's last position, is another document's first id)."""
    from paddle_tpu.text.decoder_block import packed_head_loss
    rs = np.random.default_rng(2)
    net = tiny_net()
    ids, seg, labels, ahead = packed_batch(rs)
    ends = np.concatenate([seg[:, 1:] != seg[:, :-1],
                           np.ones((seg.shape[0], 1), bool)], axis=1)
    near_end = ends | np.concatenate(
        [ends[:, 1:], np.ones((seg.shape[0], 1), bool)], axis=1)
    assert np.all((ahead == -1) == near_end)

    def mtp_loss(h, e, ahead):
        g, _ = net.mtp(Tensor(h), Tensor(e), Tensor(jnp.asarray(seg)))
        return packed_head_loss(g, Tensor(jnp.asarray(ahead)),
                                net.lm_head)._value

    h, e = (jnp.asarray(rs.normal(size=ids.shape + (32,)), jnp.float32)
            for _ in range(2))
    mtp_loss = jax.jit(mtp_loss)
    value, (gh, ge) = jax.jit(jax.value_and_grad(mtp_loss, argnums=(0, 1)))(
        h, e, ahead)
    for grad in (gh, ge):
        assert float(jnp.max(jnp.abs(grad[jnp.asarray(near_end)]))) == 0.0
        assert float(jnp.min(jnp.max(jnp.abs(grad), axis=-1)[
            jnp.asarray(~near_end)])) > 0.0
    # and the mean is over the labelled positions alone: with one of them
    # taken out, the sum of the others' losses stays
    fewer = ahead.copy()
    r, t = np.argwhere(ahead >= 0)[3]
    fewer[r, t] = -1
    n = int(np.sum(ahead >= 0))
    alone = ahead.copy()
    alone[:] = -1
    alone[r, t] = ahead[r, t]
    assert float(value) * n == pytest.approx(
        float(mtp_loss(h, e, fewer)) * (n - 1)
        + float(mtp_loss(h, e, alone)), rel=1e-5)


def test_embedding_and_head_get_the_sum_of_the_two_losses_gradients():
    """L = L_main + lambda L_mtp: the shared table's and the shared head's
    gradients are the main loss's own plus lambda times the module's own,
    and every leaf of the module gets lambda times L_mtp's gradient."""
    rs = np.random.default_rng(3)
    net = tiny_net()
    weights = param_values(net)
    batch = packed_batch(rs)
    lam = net.config.mtp_loss_weight

    def three(w):
        loss, counters = loss_and_counters(net, w, batch)
        return jnp.stack([loss, counters[-2], counters[-1]]), counters

    def a_row_each(w):      # (the grouped product has no batching rule)
        _, back, counters = jax.vjp(three, w, has_aux=True)
        return [back(jnp.eye(3)[i])[0] for i in range(3)], counters

    (whole, main, mtp), counters = jax.jit(a_row_each)(weights)
    for leaf in whole:
        want = main[leaf] + lam * mtp[leaf]
        np.testing.assert_allclose(whole[leaf], want, atol=1e-6
                                   + 1e-5 * float(jnp.max(jnp.abs(want))))
    for leaf in ('embed_tokens.weight', 'lm_head'):
        assert float(jnp.max(jnp.abs(main[leaf]))) > 0
        assert float(jnp.max(jnp.abs(mtp[leaf]))) > 0
    for leaf in whole:
        if leaf.startswith('mtp.'):
            assert float(jnp.max(jnp.abs(main[leaf]))) == 0.0, leaf
    # the counters: every expert layer's assignments, the module's too
    names = dict(zip(net.step_counter_names, np.asarray(counters)))
    assert names['moe.assignments'] == 2 * 2 * 48 * 4      # 2 expert layers
    assert names['moe.dropped'] == 0.0
    assert set(net.step_counter_sums) <= set(net.step_counter_names)


# ----------------------------------------------------------- expert layer

def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The cut of the cell at a tiny size: 16 chips share a layer of 256
    experts of which a token picks 8; the routed parts of the 16 shares, plus
    the shared expert once, equal the layer that holds every expert."""
    rs = np.random.default_rng(4)

    def layer(held):
        return nn.SparseMoE(16, 8, 256, 8, experts_held=held, shared_size=8,
                            scaling=2.5, initializer_range=0.3)
    whole = layer((0, 256))
    x = Tensor(jnp.asarray(rs.normal(size=(2, 24, 16)), jnp.float32))
    want, counters = whole(x)
    assert float(counters.numpy()[0]) == 2 * 24 * 8
    shared = whole.shared(x).numpy()
    total, held_sum = np.zeros_like(want.numpy()), 0.0
    for lo in range(0, 256, 16):
        share = layer((lo, lo + 16))
        share.router.set_value(whole.router)
        for name in ('experts_gate', 'experts_up', 'experts_down'):
            getattr(share, name).set_value(
                getattr(whole, name).numpy()[lo:lo + 16])
        for name in ('gate_proj', 'up_proj', 'down_proj'):
            getattr(share.shared, name).set_value(getattr(whole.shared, name))
        y, c = share(x)
        total += y.numpy() - shared
        held_sum += float(c.numpy()[0])
        assert float(c.numpy()[4]) == 0.0                  # dropped
    assert held_sum == 2 * 24 * 8
    np.testing.assert_allclose(total + shared, want.numpy(), atol=2e-5)


def test_the_cells_row_buffers():
    """What `expert_share` works out from the shapes of the two cells that
    run it (16384 tokens a step, top 8 of 256): tiles of 256 rows, half an
    expert's even share, and buffers of twice and four times the share
    held."""
    assert moe.row_tile(16384, 8, 256) == 256
    assert moe.buffer_tiles(16384, 8, 16, 256, 256) == (64, 128)   # JoyAI
    assert moe.buffer_tiles(16384, 8, 8, 256, 256) == (32, 64)     # Kimi
    # never more than every token to every held expert it can pick
    assert moe.buffer_tiles(64, 8, 2, 4, 8) == (64 * 2 // 8 + 2,) * 2


@pytest.mark.parametrize('dtype', [None, jnp.bfloat16])
def test_the_kernels_follow_the_xla_form_at_the_cells_cut(dtype):
    """16 of 256 experts held, top 8, a seeded router: the grouped-product
    kernels (interpret mode), in float32 and with bfloat16 operands, against
    `ragged_dot` on the same layout in float32: output and the five
    gradients; what the products ran over is the rows held plus less than a
    tile an expert."""
    rs = np.random.default_rng(5)
    T, H, F, E, k, held = 256, 128, 128, 256, 8, (32, 48)
    x = jnp.asarray(rs.normal(size=(T, H)), jnp.float32)
    gate, up = (jnp.asarray(rs.normal(size=(16, H, F)) * H ** -0.5,
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rs.normal(size=(16, F, H)) * F ** -0.5, jnp.float32)
    idx, w = moe.route_sigmoid_topk(
        x, jnp.asarray(rs.normal(size=(H, E)) * 0.3, jnp.float32),
        jnp.zeros((E,)), k, 2.5)
    cot = jnp.asarray(rs.normal(size=(T, H)), jnp.float32)
    tile = moe.row_tile(T, k, E) * (2 if dtype is not None else 1)

    def run(dtype, interpret):
        def loss(*a):
            y, c = moe.expert_share(a[0], idx, *a[1:], held, E, tile=tile,
                                    dtype=dtype, interpret=interpret)
            return jnp.sum(y * cot), (y, c)
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                x, w, gate, up, down)
    (_, (y, c)), got = run(dtype, True)
    (_, (want_y, want_c)), want = run(None, False)
    for a, b in zip((y,) + got, (want_y,) + want):
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert gap < (1e-5 if dtype is None else 2e-2), gap
    np.testing.assert_array_equal(c, want_c)
    c = counted(c)
    assert c['assignments_held'] > 0 and c['dropped'] == 0
    # tiles of 16 rows (bfloat16's sublanes) for experts of ~10: the padded
    # rows pass the smaller buffer and the step takes the larger one
    small = tile * moe.buffer_tiles(T, k, 16, E, tile)[0]
    assert (c['rows_computed'] <= small) == (dtype is None)
    assert c['rounds'] == 1
    assert c['rows_computed'] < c['assignments_held'] + 16 * tile


# ---------------------------------------------- program against reference

def tiny(cut=None):
    config = _tiny.load('joyai-flash-tiny')
    traffic = _tiny.load('train-pack-tiny')
    if cut == 'dense':
        config['num_hidden_layers'] = 1
    return config, traffic


_SOUND = {}


def sound_run(cut=None):
    """(program's readings, batches, reference's readings, its routing, its
    two losses a step) of seed 7 at the test size, computed once."""
    if cut not in _SOUND:
        config, traffic = tiny(cut)
        readings, batches = program_readings(config, traffic, seed=7)
        routing, parts = [], []
        sound = reference_readings(config, traffic, 7, batches,
                                   routing=routing, parts=parts)
        _SOUND[cut] = (readings, batches, sound, routing, parts)
    return _SOUND[cut]


@pytest.mark.parametrize('cut', ['dense', None])
def test_program_follows_the_reference(cut):
    """Loss, first gradient leaf by leaf and the change of three AdamW
    steps: the dense layer with the module, and the whole test net."""
    readings, _, sound, routing, parts = sound_run(cut)
    rows, ok = check.compare(readings, sound, LIMITS)
    assert ok, [r for r in rows if not r[3]]
    assert set(readings['first_gradient']) == set(sound['first_gradient'])
    assert all(np.all(np.diff(r, axis=-1) > 0) for r in routing)
    assert len(routing) == (1 if cut else 2)       # the module's is the last
    for loss, (main, mtp) in zip(sound['losses'], parts):
        assert loss == pytest.approx(main + 0.3 * mtp, rel=1e-6)


def test_lower_precision_control_is_not_correct():
    """The reference computed in float8, put in the program's place."""
    config, traffic = tiny()
    _, batches, sound, _, _ = sound_run()
    control = reference_readings(config, traffic, 7, batches,
                                 precision='float8')
    rows, ok = check.compare(control, sound, LIMITS)
    assert not ok, rows
    assert 'first_gradient_difference' in {r[0] for r in rows if not r[3]}


def _keys_are_not_rotated(monkeypatch):
    real = nn.layer.linear_attention.rotate_pairs
    monkeypatch.setattr(
        nn.layer.linear_attention, 'rotate_pairs',
        lambda x, at, theta: real(x, at * (x.shape[2] != 1), theta))


def _attention_sees_other_documents(monkeypatch):
    from paddle_tpu.kernels import flash_attention
    real = flash_attention.flash_attention_bhld
    monkeypatch.setattr(
        flash_attention, 'flash_attention_bhld',
        lambda q, k, v, doc_start=None, **kw: real(
            q, k, v, doc_start=jnp.zeros_like(doc_start), **kw))


def _the_module_is_trained_on_labels_one_ahead(monkeypatch):
    real = JoyAIFlashForCausalLM.forward
    monkeypatch.setattr(
        JoyAIFlashForCausalLM, 'forward',
        lambda self, ids, seg, labels, ahead, *a: real(
            self, ids, seg, labels, labels, *a))


def _the_module_is_given_the_current_id(monkeypatch):
    monkeypatch.setattr(jnp, 'roll', lambda x, shift, axis=None: x)


@pytest.mark.parametrize('fault', [
    _keys_are_not_rotated, _attention_sees_other_documents,
    _the_module_is_trained_on_labels_one_ahead,
    _the_module_is_given_the_current_id])
def test_a_planted_fault_fails_the_limits(fault, monkeypatch):
    """The program with one thing wrong, on the batches and against the
    reference of the sound run."""
    _, _, sound, _, _ = sound_run()
    fault(monkeypatch)
    readings, _ = program_readings(*tiny(), seed=7)
    rows, ok = check.compare(readings, sound, LIMITS)
    assert not ok, rows


# ------------------------------------------------------ the committed cell

def test_the_cell_states_the_published_widths():
    """The committed configuration against the catalog row the driver drew:
    every width as published, the three cuts named, inside the floors."""
    with open(os.path.join(ROOT, 'benchmark', 'configs',
                           'joyai-llm-flash.json')) as f:
        config = json.load(f)
    published = dict(
        hidden_size=2048, num_attention_heads=32, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, q_lora_rank=1536,
        kv_lora_rank=512, intermediate_size=7168, moe_intermediate_size=768,
        num_experts_total=256, num_experts_per_tok=8, n_shared_experts=1,
        routed_scaling_factor=2.5, rope_theta=32000000,
        num_nextn_predict_layers=1, first_k_dense_replace=1,
        rms_norm_eps=1e-6, n_group=1, topk_group=1)
    assert {k: config[k] for k in published} == published
    assert config['reduced'] == ['num_hidden_layers', 'n_routed_experts',
                                 'vocab_size']
    assert set(config['reduced_from']) == set(config['reduced'])
    assert config['num_hidden_layers'] - config['first_k_dense_replace'] >= 4
    assert config['n_routed_experts'] >= 8
    assert config['vocab_size'] * 8 >= 129280
    assert config['assumed_values']['mtp_loss_weight'] == 0.3
    family = _tiny.harness_run.load_module('families', config['family'])
    spec = family.param_spec(config)
    count = sum(int(np.prod(shape)) for shape, _ in spec.values())
    assert 679e6 < count < 682e6            # ISSUE 31's 680.5M
    with open(os.path.join(ROOT, 'benchmark', 'traffic',
                           'train-pack8k.json')) as f:
        traffic = json.load(f)
    per_token = family.flops_per_sample(config, traffic) / traffic['seq_len']
    assert 395e6 * 6 < per_token < 397e6 * 6        # 396M multiply-adds
    pool = family.make_pool(dict(config), dict(traffic, seq_len=512,
                                               doc_len_clip=[8, 512],
                                               doc_len_median=64), 3, 1, 2)
    (ids, seg, labels, ahead), _ = pool[0]
    assert max(ids.max(), labels.max(), ahead.max()) < config['vocab_size']
    two = seg[:, 2:] == seg[:, :-2]
    assert np.all((ahead[:, :-2] >= 0) == two) and np.all(ahead[:, -2:] == -1)
    assert np.all(ahead[:, :-2][two] == ids[:, 2:][two])
    assert np.all(labels[ahead >= 0] >= 0)

"""The `laguna` family's own statements, at the test size: its parameters
against the net the program builds, the operations a sample requires and
what it tells the kernels' roofline readers they count, each against a count
written out by hand (a file of its own: a `model_config` PR adds files to
the benchmark and edits none)."""
import numpy as np
import pytest

import _tiny
from test_harness import causal_pairs, kernel_ctx


def test_the_familys_statement_of_its_net_and_its_operations():
    """`param_spec` and `buffer_spec` against the net `build` makes (the job
    refuses a difference), and `flops_per_sample` against a count by hand:
    heads by layer, the gate's projection, the dense layer, the shared
    expert on every token, 2 x 4 / 8 = 1 held expert a token by
    expectation."""
    import jax
    run = _tiny.harness_run
    config, traffic = _tiny.load('laguna-tiny'), _tiny.load('train-pack-tiny')
    family = run.load_module('families', 'laguna')
    job = run.load_module('jobs', traffic['job'])
    _, _, spec = job.build_step(family, config, traffic, jax.devices()[:1],
                                deterministic=False)
    assert sum('g_proj' in k for k in spec) == 5
    assert sum('shared.' in k for k in spec) == 12
    assert sorted(family.buffer_spec(config)) == [
        'layers.%d.mlp.e_score_correction_bias' % i for i in (1, 2, 3, 4)]
    full = 2 * 64 * 128 + 2 * 64 * 64 + 64 * 4 + 12.0 * 4 * 64
    window = 2 * 64 * 192 + 2 * 64 * 64 + 64 * 6 + 4.0 * 6 * 64
    sparse = 64 * 8 + 3 * 64 * 24 + 3 * 64 * 16
    per_token = 2 * full + 3 * window + 3 * 64 * 96 + 4 * sparse + 64 * 64
    assert family.flops_per_sample(config, traffic) == 6.0 * 64 * per_token


# (reader, held rows or None, operations, bytes): float32 cells, so an
# element of the compute type is 4 bytes
REQUIRED = [
    # what TURNS: 2 full layers x (4 + 2) heads x 16 of a head's 32, 3 window
    # layers x (6 + 2) heads x 32; 12 operations, 4 x 4 bytes a channel
    ('rope_roofline', None,
     (2 * 96 + 3 * 256) * 128 * 12, (2 * 96 + 3 * 256) * 128 * 16),
    # 100 held rows, gate + up + down x 3 passes at 64 x 16; 4 sparse layers
    # x 4 held experts of 64 x 16 a matrix; 64 + 16 elements a row and pass
    ('moe.experts_roofline', 100,
     100 * 9 * 2 * 64 * 16, 9 * (4 * 4 * 64 * 16 + 100 * 80) * 4),
    # 100 rows of 64 read and written by four moves
    ('moe.permute_roofline', 100, 100 * 64 * 4, 100 * 64 * 2 * 16),
]


@pytest.mark.parametrize('name,held,flops,bytes_', REQUIRED)
def test_a_kernels_required_count(name, held, flops, bytes_):
    reader = _tiny.harness_run.load_module('layer_metrics', name)
    ctx = kernel_ctx('laguna-tiny')
    got = reader.required(ctx) if held is None \
        else reader.required(ctx, held)
    assert got == (flops, bytes_)


def test_packed_attentions_required_count():
    """Each layer at ITS query heads: 4 in the two full layers, 6 under a
    window of 8 keys in the three window layers, on 2 K/V heads of 32."""
    reader = _tiny.harness_run.load_module(
        'layer_metrics', 'flash_attention_packed_roofline')
    ctx = kernel_ctx('laguna-tiny')
    layers = [(None, 4)] + [(8, 6)] * 3 + [(None, 4)]
    flops = elements = 0
    for window, heads in layers:
        flops += 2 * heads * causal_pairs(ctx['traffic'], window) * 12 * 32
        elements += 128 * (heads + 2) * 6 * 32
    got = reader.required(ctx)
    assert got == (pytest.approx(flops, rel=1e-12), elements * 4)
    assert not np.isnan(got[0])


def test_the_gates_reader_reads_its_scope_and_nothing_without_one():
    reader = _tiny.harness_run.load_module('layer_metrics', 'attn.gate_ms')
    ctx = {'layer_scopes': {'per_step_ms': {'attn.gate': 12.5}}}
    assert reader.read(ctx) == 12.5
    assert reader.read({'layer_scopes': {'per_step_ms': {}}}) is None
    assert reader.read({'layer_scopes': None}) is None

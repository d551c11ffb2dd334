"""The main path's Pallas kernels, compiled for a described TPU v5e chip.

No chip is attached here: the TPU compiler that is installed with JAX
compiles for a ``v5e:2x2`` topology that is described, so what the chip's
compiler would refuse (a slice not aligned to the tiling, too much VMEM) is
refused here at no chip time. Real widths: BERT-large head shapes
``[8, 16, L, 64]`` bf16 and ``[B*L, 1024]`` rows. A compile that passes is
not a chip run — ``chip_smoke.py`` is.

Rules this file keeps (on-chip-measurement guide, section 2): the topology
is described inside a module-scoped fixture that skips when it cannot be —
never at import, in a ``skipif`` or in a ``parametrize`` argument; compiles
run in the test's own process (the worker that loaded libtpu keeps its
lock); JAX's persistent cache is off around them (an entry written for a
described chip cannot be read back without one).
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from paddle_tpu.kernels import delta_rule as dr
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import fused_dropout_norm as fdn
from paddle_tpu.kernels import fused_norm as fn
from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels import rotary
from paddle_tpu.kernels import row_permute as rp
from paddle_tpu.kernels import short_conv as sc
from paddle_tpu.kernels import ssd as ssd_kernels
from paddle_tpu.kernels._common import kernel_mesh

B, H, D = 8, 16, 64            # BERT-large attention: 16 heads of 64
HIDDEN = 1024
ROWS = 8192                    # B*L: 64x128 (seq-128 batch) = 16x512


@pytest.fixture(scope='module')
def topo():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:   # no TPU compiler here: nothing to hold to
        pytest.skip('no v5e:2x2 topology can be described here: %r' % (e,))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    yield desc
    jax.config.update('jax_enable_compilation_cache', was)
    cc.reset_cache()


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn_, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn_).lower(*args).compile()
    text = compiled.as_text()
    assert 'tpu_custom_call' in text
    return text


def _tilings(seq, has_kpad):
    """The tilings `flash_attention_bhld` can be asked for (`block_q`,
    `block_k`); with a key-padding bias block_k is pinned to the full row
    (the kernel streams the whole bias), so only block_q varies."""
    bs = [b for b in (128, 256, 512, 1024) if seq % b == 0 and b <= seq]
    if has_kpad:
        return [(bq, seq) for bq in bs]
    return [(bq, bk) for bq in bs for bk in bs]


# (causal, has_kpad, dropout_p): decoder attention; BERT's padded batches
# with attention dropout; BERT pretraining without a mask (bench/smoke)
_FLASH_VARIANTS = [(True, False, 0.0), (False, True, 0.1), (False, False, 0.1)]
_FLASH_CASES = [
    (seq, causal, kpad, p, bq, bk)
    for seq in (512, 1024)
    for causal, kpad, p in _FLASH_VARIANTS
    for bq, bk in _tilings(seq, kpad)]


@pytest.mark.parametrize(
    'seq,causal,kpad,p,bq,bk', _FLASH_CASES,
    ids=['l%d_c%d_m%d_p%d_%dx%d' % (s, c, m, p > 0, bq, bk)
         for s, c, m, p, bq, bk in _FLASH_CASES])
def test_flash_tiling_compiles_fwd_bwd(one_chip, seq, causal, kpad, p, bq,
                                       bk):
    """Every tiling a caller can ask for compiles, forward and backward."""
    scale = D ** -0.5

    def loss(q, k, v, bias, seed):
        out = fa._flash(q, k, v, bias if kpad else None, seed, None, causal,
                        scale, bq, bk, p, False)
        return jnp.sum(out.astype(jnp.float32))

    qkv = ((B, H, seq, D), jnp.bfloat16)
    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, qkv, qkv, qkv,
             ((B, seq), jnp.float32), ((1, 1), jnp.int32))


def test_flash_latent_attention_shapes_compile_fwd_bwd(one_chip):
    """Kimi-Linear's full-attention layer at its real shapes: rows of 8192,
    32 heads, q and k 192 wide, v 128 wide, packed documents. A head's whole
    K and V (forward), Q, O, dO and dQ (backward) stay in VMEM: past the 16
    MiB a kernel gets unasked, so both ask for their own limit."""
    rows, heads, seq = 2, 32, 8192

    def loss(q, k, v, start):
        doc = (start,) + fa.doc_tile_bounds(start, 512, 512)
        out = fa._flash(q, k, v, None, jnp.zeros((1, 1), jnp.int32), doc,
                        True, 192 ** -0.5, 512, 512, 0.0, False)
        return jnp.sum(out.astype(jnp.float32))

    qk = ((rows, heads, seq, 192), jnp.bfloat16)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, qk, qk,
                    ((rows, heads, seq, 128), jnp.bfloat16),
                    ((rows, seq), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert fa._fwd_vmem_limit(seq, 192, 128, 512, 2, 2) > (16 << 20)
    # BERT's calls are inside what a kernel gets unasked: no limit is set
    assert fa._fwd_vmem_limit(512, 64, 64, 512, 2, 1) is None
    assert fa._bwd_vmem_limit(512, 64, 512, 512, 2) is None


def test_flash_grouped_query_shapes_compile_fwd_bwd(one_chip):
    """Mellum's attention at its real shapes: rows of 8192, 32 query heads
    on 4 K/V heads of 128, packed documents, with the window of 1024 and
    without. The backward keeps a K/V head's whole dK and dV in VMEM, in
    float32, over its 8 query heads and its K tiles: 16.8 MB more than the
    ungrouped call asks for."""
    rows, heads, kv_heads, seq = 2, 32, 4, 8192

    def loss(window):
        def fn(q, k, v, doc_start):
            start = fa.row_starts(doc_start, window)
            doc = (start,) + fa.doc_tile_bounds(start, 512, 512)
            out = fa._flash(q, k, v, None, jnp.zeros((1, 1), jnp.int32), doc,
                            True, 128 ** -0.5, 512, 512, 0.0, False)
            return jnp.sum(out.astype(jnp.float32))
        return jax.grad(fn, argnums=(0, 1, 2))

    for window in (1024, None):
        text = _compile(loss(window), one_chip,
                        ((rows, heads, seq, 128), jnp.bfloat16),
                        ((rows, kv_heads, seq, 128), jnp.bfloat16),
                        ((rows, kv_heads, seq, 128), jnp.bfloat16),
                        ((rows, seq), jnp.int32))
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        assert 'f32[8,8192,128]' in text        # dK, dV: 2 rows x 4 heads
    plain = fa._bwd_vmem_limit(seq, 128, 512, 512, 2, 128, True)
    grouped = fa._bwd_vmem_limit(seq, 128, 512, 512, 2, 128, True,
                                 grouped=True)
    assert grouped - plain == 2 * seq * 256 * 4 * 3 // 2
    assert grouped < (128 << 20)
    # the Nemotron-H attention layer: 32 query heads on TWO K/V heads, no
    # window; a group of 16 asks for no more VMEM than a group of 8 (a K/V
    # head's dK and dV stay over however many query heads share it)
    text = _compile(loss(None), one_chip,
                    ((rows, heads, seq, 128), jnp.bfloat16),
                    ((rows, 2, seq, 128), jnp.bfloat16),
                    ((rows, 2, seq, 128), jnp.bfloat16),
                    ((rows, seq), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert 'f32[4,8192,128]' in text            # dK, dV: 2 rows x 2 heads


@pytest.mark.parametrize('heads,window', [(48, None), (64, 512)],
                         ids=['full-48-on-8', 'window-512-64-on-8'])
def test_flash_two_head_counts_compile_fwd_bwd(one_chip, heads, window):
    """Laguna's two kinds of layer at their real shapes: rows of 8192, 48
    query heads (a group of 6) without a window and 64 (a group of 8, q 8192
    wide) under a window of 512, ONE tile, on 8 K/V heads of 128, packed
    documents. dK and dV come back at the K/V heads."""
    def fn(q, k, v, doc_start):
        start = fa.row_starts(doc_start, window)
        doc = (start,) + fa.doc_tile_bounds(start, 512, 512)
        out = fa._flash(q, k, v, None, jnp.zeros((1, 1), jnp.int32), doc,
                        True, 128 ** -0.5, 512, 512, 0.0, False)
        return jnp.sum(out.astype(jnp.float32))
    text = _compile(jax.grad(fn, argnums=(0, 1, 2)), one_chip,
                    ((2, heads, 8192, 128), jnp.bfloat16),
                    ((2, 8, 8192, 128), jnp.bfloat16),
                    ((2, 8, 8192, 128), jnp.bfloat16),
                    ((2, 8192), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert 'f32[16,8192,128]' in text           # dK, dV: 2 rows x 8 heads


def test_delta_rule_compiles_fwd_bwd_at_the_cells_shape(one_chip,
                                                        monkeypatch):
    """One row of Kimi-Linear's delta rule as the cell runs it: 8192
    tokens, 32 heads of 128, chunks of 64 in sub-blocks of 16, float32
    operands with bfloat16 in the three large products. The `HIGHEST`
    float32 products, the `a^T b` state update and the chunk's gradient
    written out in the backward kernel (the sub-block scores' transposes on
    slices of 16, 32 and 48 keys, a score column spread over the lanes) all
    have to lower: one custom call forward, two (the forward that saves what
    the backward reads of every chunk, the backward) under `jax.grad`."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    T, heads, width = 8192, 32, 128

    def forward(q, k, v, g, beta, seg):
        return dr.delta_rule(q, k, v, g, beta, seg, width ** -0.5,
                             dtype=jnp.bfloat16)

    def loss(*args):
        return jnp.sum(jnp.sin(forward(*args)))

    wide = ((1, T, heads, width), jnp.float32)
    shapes = (wide, wide, wide, wide, ((1, T, heads), jnp.float32),
              ((1, T), jnp.int32))
    text = _compile(forward, one_chip, *shapes)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), one_chip,
                    *shapes)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert 'delta_rule.pallas' in text and 'delta_rule.xla' not in text


@pytest.mark.parametrize('head_dim', [128, None], ids=['l2norm', 'no_norm'])
def test_short_conv_compiles_fwd_bwd_at_the_cells_shape(one_chip,
                                                        monkeypatch,
                                                        head_dim):
    """One row of a KDA projection as the cell runs it: 8192 positions, 32
    heads of 128, the matmul's bfloat16 output in, float32 out; q and k with
    the l2norm, v without. The shifts by `roll`, the halo block read in
    front of a tile and the taps' gradient accumulated over a row's tiles
    all have to lower: one custom call forward, two (the forward, whose
    output the loss reads, and the backward, which keeps nothing of the
    forward but its operands) under `jax.grad`."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    T, width = 8192, 4096

    def forward(y, w, seg):
        return sc.short_conv(y, w, seg, head_dim)

    def loss(*args):
        return jnp.sum(jnp.sin(forward(*args)))

    shapes = (((1, T, width), jnp.bfloat16), ((4, width), jnp.float32),
              ((1, T), jnp.int32))
    text = _compile(forward, one_chip, *shapes)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    text = _compile(jax.grad(loss, argnums=(0, 1)), one_chip, *shapes)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert 'short_conv.pallas' in text and 'short_conv.xla' not in text


def test_ssd_compiles_fwd_bwd_at_the_cells_shape(one_chip, monkeypatch):
    """One row of a Mamba-2 layer's rule as the Nemotron-H cell runs it: 8192
    tokens in chunks of 128, 64 heads of 64 in 8 groups, a state of 128,
    float32 operands with bfloat16 in the products. The single-lane slices
    of the column pack spread over a tile, the 0/1 products at `HIGHEST`,
    the `(C, 2C) x (2C, 128)` product of a register of heads and the
    `a^T b` state update all have to lower: one custom call forward, two
    (the forward that saves every chunk's start state, the backward) under
    `jax.grad`."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    T, heads, width, groups, state = 8192, 64, 64, 8, 128

    def forward(x, dt, A, Bm, Cm, D, seg):
        return ssd_kernels.ssd(x, dt, A, Bm, Cm, D, seg, chunk=128,
                               dtype=jnp.bfloat16)

    def loss(*args):
        return jnp.sum(jnp.sin(forward(*args)))

    shared = ((1, T, groups, state), jnp.float32)
    shapes = (((1, T, heads, width), jnp.float32),
              ((1, T, heads), jnp.float32), ((heads,), jnp.float32),
              shared, shared, ((heads,), jnp.float32), ((1, T), jnp.int32))
    text = _compile(forward, one_chip, *shapes)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    text = _compile(jax.grad(loss, argnums=tuple(range(6))), one_chip,
                    *shapes)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert 'ssd.pallas' in text and 'ssd.xla' not in text
    assert 'f32[1,8,64,512,128]' in text    # the chunks' start states


def test_short_conv_with_a_bias_compiles_fwd_bwd(one_chip, monkeypatch):
    """The convolution in front of the Mamba-2 rule, on B's (or C's) 1024
    columns of a row of 8192, with the bias as a fifth row of the taps and
    its gradient as that row's."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    T, width = 8192, 1024

    def loss(y, w, b, seg):
        return jnp.sum(jnp.sin(sc.short_conv(y, w, seg, bias=b)))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    ((1, T, width), jnp.bfloat16), ((4, width), jnp.float32),
                    ((width,), jnp.float32), ((1, T), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert 'short_conv.pallas' in text and 'short_conv.xla' not in text
    assert 'f32[1,5,1024]' in text          # the taps' and the bias's rows


_X = ((ROWS, HIDDEN), jnp.bfloat16)
_W = ((HIDDEN,), jnp.bfloat16)
_SEED = ((1, 1), jnp.int32)


@pytest.mark.parametrize('hidden,width,held,experts', [
    (2048, 768, 16, 256), (2304, 1024, 8, 256), (2304, 896, 16, 64),
    (2688, 1920, 8, 128)],
    ids=['joyai-llm-flash', 'kimi-linear', 'mellum2', 'nemotron-h'])
def test_grouped_matmul_compiles_fwd_bwd_at_the_cells_shapes(
        one_chip, hidden, width, held, experts):
    """The routed experts' three products and their backward at the three
    cells' shapes: 16384 tokens top 8 of 256 (of 64: experts of 7 x 128
    lanes, a buffer of 131072 rows, every assignment there can be), the
    larger of the two buffers (four times the even share) in tiles of 256
    rows, bf16 rows against float32 weight stacks whose whole (K, N) matrix
    a group is one block in VMEM. The Nemotron-H experts' 1856 columns come
    laid on 1920 (15 registers) behind zeros, as `moe._round` hands them
    over (their rows are a sixth of the others': 8 held of 128, top 6)."""
    from paddle_tpu.nn.functional import moe
    tile = moe.row_tile(16384, 8, experts)
    tiles = moe.buffer_tiles(16384, 8, held, experts, tile)[1]

    def loss(rows, gate, up, down, tile_group, active):
        def product(lhs, rhs, out=None):
            return gm.grouped_matmul(lhs, rhs, tile_group, active, out)
        h = jax.nn.silu(product(rows, gate)) * product(rows, up)
        return jnp.sum(product(h, down, jnp.float32) ** 2)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, 'default_backend', lambda: 'tpu')
        text = _compile(
            jax.grad(loss, argnums=(0, 1, 2, 3)), one_chip,
            ((tiles * tile, hidden), jnp.bfloat16),
            ((held, hidden, width), jnp.float32),
            ((held, hidden, width), jnp.float32),
            ((held, width, hidden), jnp.float32),
            ((tiles,), jnp.int32), ((1,), jnp.int32))
    calls = [c for c in _CUSTOM_CALL.findall(text)
             if 'grouped_matmul.pallas' in c]
    # three products forward, then d lhs and d rhs of each
    assert len(calls) == 9, calls
    assert 'ragged-dot' not in text


@pytest.mark.parametrize('hidden,held,experts', [
    (2048, 16, 256), (2304, 8, 256), (2304, 16, 64), (2688, 8, 128)],
    ids=['joyai-llm-flash', 'kimi-linear', 'mellum2', 'nemotron-h'])
def test_row_permute_compiles_fwd_bwd_at_the_cells_shapes(
        one_chip, hidden, held, experts):
    """The routed experts' rows into the buffer and back at the three cells'
    shapes (16384 tokens, the larger buffer in tiles of 256 rows, bfloat16
    rows in, float32 rows back): the gather with its backward (the combine
    without weights) and the combine with its backward (the gather with the
    weights and the dots), the token side whole in VMEM a column chunk at a
    time: 72 MiB of the chip's 128. A hidden size of 2688 is 10.5 registers
    of bfloat16 pairs: its rows are gathered as float32 words (three column
    chunks of 7 registers), still by the kernels."""
    from paddle_tpu.nn.functional import moe
    tokens = 16384
    tile = moe.row_tile(tokens, 8, experts)
    tiles = moe.buffer_tiles(tokens, 8, held, experts, tile)[1]

    def loss(x, out, scale, tok, held_rows):
        rows = rp.gather_rows(x, tok, held_rows)
        y = rp.combine_rows(out + rows.astype(jnp.float32), scale, tok,
                            held_rows, tokens)
        return jnp.sum(y ** 2)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, 'default_backend', lambda: 'tpu')
        text = _compile(
            jax.grad(loss, argnums=(0, 1, 2)), one_chip,
            ((tokens, hidden), jnp.bfloat16),
            ((tiles * tile, hidden), jnp.float32),
            ((tiles * tile,), jnp.float32),
            ((tiles * tile,), jnp.int32), ((tiles,), jnp.int32))
    calls = [c for c in _CUSTOM_CALL.findall(text)
             if 'row_permute.pallas' in c]
    # gather and combine forward, then each one's backward
    assert len(calls) == 4, calls
    assert not re.search(r' (scatter|gather)\(', text)


@pytest.mark.parametrize('heads,d,form,turned', [
    (32, 128, 'halves', 128), (4, 128, 'halves', 128),
    (32, 192, 'pairs', 64), (48, 128, 'halves', 64), (8, 128, 'halves', 64),
    (64, 128, 'halves', 128)],
    ids=['mellum2-q', 'mellum2-k', 'joyai-llm-flash-q', 'laguna-full-q',
         'laguna-full-k', 'laguna-window-q'])
def test_rotary_compiles_fwd_bwd_at_the_cells_shapes(one_chip, heads, d,
                                                     form, turned):
    """The rotation of two packed rows of 8192 at the three rotary cells'
    published head shapes, bfloat16: the half turn over heads of 128 (32 and
    64 query heads, 4 K/V heads), the half turn INSIDE the first 64 lanes of
    heads of 128 (48 query heads, 8 K/V heads: two rolls and a select), and
    the pair turn over the last 64 lanes of heads of 128 + 64, whose block
    is two heads (384 lanes) and whose second head starts mid-register. One
    custom call forward and one backward, the same kernel with its
    BlockSpecs exchanged, and no transpose left beside them."""
    from paddle_tpu.nn.layer.linear_attention import rope_inv_freq

    def loss(x, at):
        if form == 'halves':
            y = rotary.rotary_halves(
                x, at, rope_inv_freq(500000, turned).astype(np.float32),
                1.25)
        else:
            y = rotary.rotary_pairs(x, at, 32e6, turned)
        assert y.shape == (2, heads, 8192, d)
        return jnp.sum(y.astype(jnp.float32) ** 2)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, 'default_backend', lambda: 'tpu')
        text = _compile(jax.grad(loss), one_chip,
                        ((2, 8192, heads, d), jnp.bfloat16),
                        ((2, 8192), jnp.int32))
    calls = [c for c in _CUSTOM_CALL.findall(text)
             if 'rotary.pallas' in c]
    assert len(calls) == 2, calls
    assert not re.search(r' transpose\(', text)


def test_fused_layer_norm_compiles_fwd_bwd(one_chip):
    def loss(x, w, b):
        y = fn._fused_layer_norm2d(x, w, b, 1e-5, False)
        return jnp.sum(y.astype(jnp.float32))
    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, _X, _W, _W)


def test_fused_rms_norm_compiles_fwd_bwd(one_chip):
    def loss(x, w):
        y = fn._fused_rms_norm2d(x, w, 1e-6, False)
        return jnp.sum(y.astype(jnp.float32))
    _compile(jax.grad(loss, argnums=(0, 1)), one_chip, _X, _W)


@pytest.mark.parametrize('p', [0.0, 0.1], ids=['p0', 'p0.1'])
def test_fused_dropout_norm_compiles_fwd_bwd(one_chip, p):
    def loss(x, res, w, b, seed):
        y = fdn._fdln(x, res, w, b, seed, 1e-5, p, False)
        return jnp.sum(y.astype(jnp.float32))
    _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), one_chip, _X, _X, _W, _W,
             _SEED)


def _partitioned(topo, monkeypatch, f, specs, shapes, axes=('data',),
                 mesh_shape=(4,)):
    """Compile ``f`` for the four chips of the 2x2 topology with operands
    sharded as ``specs``, traced as the engine traces a sharded step
    (``kernel_mesh``); return the per-device program text."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    mesh = Mesh(np.asarray(topo.devices).reshape(mesh_shape), axes)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=NamedSharding(mesh, spec))
            for (s, dt), spec in zip(shapes, specs)]
    with pytest.raises(NotImplementedError, match='partition'):
        jax.jit(f).lower(*args)          # no scope: refused, and loudly

    def traced(*a):
        with kernel_mesh(mesh, axes[:1], axes[1:]):
            return f(*a)
    return jax.jit(traced).lower(*args).compile().as_text()


def test_partitioned_norms_keep_their_kernels(topo, monkeypatch):
    """Rows sharded over the four chips: the TPU compiler refuses to
    partition a Mosaic kernel by itself ("Mosaic kernels cannot be
    automatically partitioned"), so under ``kernel_mesh`` every kernel
    site partitions itself (``_common.spmd_kernel``) — each chip runs the
    kernel on its rows, and no operand is gathered."""
    def loss(x, res, w, b, seed):
        y = fdn.fused_dropout_add_layer_norm(x, res, w, b, 0.1,
                                             dropout_seed=seed)
        y = fn.fused_rms_norm(fn.fused_layer_norm(y, w, b), w)
        return jnp.sum(y.astype(jnp.float32))
    rows, rep = P('data', None), P()
    text = _partitioned(topo, monkeypatch,
                        jax.grad(loss, argnums=(0, 1, 2, 3)),
                        [rows, rows, rep, rep, rep],
                        [_X, _X, _W, _W, _SEED])
    # fwd of three kernels + the dropout-mask backward kernel
    assert text.count('tpu_custom_call') >= 4
    assert '.xla' not in text
    assert 'all-gather' not in text
    assert 'bf16[2048,1024]' in text        # a quarter of the 8192 rows


@pytest.mark.parametrize('spec,mesh_shape,axes,local', [
    (P('data'), (4,), ('data',), '[2,16,512,64]'),
    (P('data', 'model'), (2, 2), ('data', 'model'), '[4,8,512,64]'),
], ids=['batch', 'batch+heads'])
def test_partitioned_flash_keeps_its_kernels(topo, monkeypatch, spec,
                                             mesh_shape, axes, local):
    """Flash attention with batch (FSDP) and batch x heads (FSDP x TP)
    sharded over the 2x2 chips: the forward kernel and the one backward
    kernel (dQ, dK and dV from one pass over the score tile) on each chip's
    (batch, heads) block, key-padding bias and in-kernel dropout included,
    both named after their scope (a device trace is read by that name)."""
    L = 512
    qkv = ((B, H, L, D), jnp.bfloat16)

    def loss(q, k, v, bias, seed):
        o = fa.flash_attention_bhld(q, k, v, kpad_bias=bias, dropout_p=0.1,
                                    dropout_seed=seed)
        return jnp.sum(o.astype(jnp.float32))
    text = _partitioned(
        topo, monkeypatch, jax.grad(loss, argnums=(0, 1, 2)),
        [spec, spec, spec, P(spec[0]), P()],
        [qkv, qkv, qkv, ((B, L), jnp.float32), _SEED],
        axes=axes, mesh_shape=mesh_shape)
    calls = _CUSTOM_CALL.findall(text)
    assert len(calls) == 2, calls
    assert all(c.startswith('flash_attention.pallas') for c in calls)
    assert 'flash_attention.xla' not in text
    assert 'all-gather' not in text
    assert 'bf16%s' % local in text


# ---------------------------------------------------------------------------
# the whole train step at a small width: its phases and its kernels' names
# ---------------------------------------------------------------------------

_STEP_SEQ, _STEP_BATCH, _STEP_MASKED = 512, 8, 76
_KERNEL_SCOPES = ('flash_attention.pallas', 'fused_dropout_norm.pallas',
                  'fused_layer_norm.pallas')
_CUSTOM_CALL = re.compile(
    r'^\s+%(\S+) = .*custom_call_target="tpu_custom_call"', re.M)
_OP_NAMES = re.compile(r'op_name="([^"]*)"')


def _small_bert_step_text(topo, monkeypatch, sharded, lowered=False):
    """One layer of BERT at hidden 128 (2 heads of 64), seq 512 so that
    attention takes the flash kernels, through ``engine.build_train_step``
    under bf16 autocast as the benchmark calls it, compiled for one chip of
    the described topology or (``sharded``) FSDP over its four: the
    program's text. Nothing can be put on a described device, so the state
    is shapes carrying the shardings."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, engine, optimizer
    from paddle_tpu.distributed.strategy import ShardingConfig
    from paddle_tpu.nn.layer_base import buffer_values, param_values
    from paddle_tpu.text.bert import BertConfig, BertForPretraining
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')

    def shapes(tree, shardings):
        return jax.tree_util.tree_map(
            lambda v, s: jax.ShapeDtypeStruct(np.shape(v), v.dtype,
                                              sharding=s), tree, shardings)
    paddle.seed(0)
    net = BertForPretraining(BertConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=256,
        max_position_embeddings=_STEP_SEQ, hidden_dropout_prob=0.1,
        attention_probs_dropout_prob=0.1))
    net.train()
    sharding = None
    if sharded:
        sharding = ShardingConfig(
            mesh=Mesh(np.asarray(topo.devices).reshape((4,)), ('data',)))
        monkeypatch.setattr(
            ShardingConfig, 'device_put_state',
            lambda self, state, shardings=None: shapes(state, shardings))
    step = engine.build_train_step(
        net=net, loss=net.pretraining_loss, sharding=sharding,
        optimizer=optimizer.AdamW(learning_rate=1e-3, weight_decay=0.01))
    state = step.init_state(param_values(net), buffer_values(net))
    if sharded:
        batch_sh, key_sh = step._batch_sharding, sharding.replicated()
    else:
        batch_sh = key_sh = SingleDeviceSharding(topo.devices[0])
        state = shapes(state, jax.tree_util.tree_map(lambda v: batch_sh,
                                                     state))
    rows = [(_STEP_BATCH, _STEP_SEQ)] * 3 + [(_STEP_BATCH, _STEP_MASKED)] * 2 \
        + [(_STEP_BATCH, 1)]
    feed = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=batch_sh)
            for s in rows]
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=key_sh)
    with amp.auto_cast(dtype='bfloat16'):
        low = step._jit.lower(state, (tuple(feed[:4]), tuple(feed[4:])), key)
        if lowered:
            return low.as_text(debug_info=True)
        return low.compile().as_text()


def _without_provenance(text):
    """The module's text with what only says where an instruction came
    from taken out: `metadata={...}`, the header's tables of files and
    stack frames, and the instructions' own names (the compiler names a
    custom call after its innermost scope), numbered by first appearance."""
    blocks = [b for b in text.split('\n\n') if b.split('\n', 1)[0] not in (
        'FileNames', 'FunctionNames', 'FileLocations', 'StackFrames')]
    text = re.sub(r',? ?metadata=\{[^}]*\}', '', '\n\n'.join(blocks))
    names = {}
    return re.sub(r'%[\w.\-]+', lambda m: names.setdefault(
        m.group(0), '%%n%d' % len(names)), text)


def test_step_keeps_its_phases_and_its_kernels_names(topo, monkeypatch):
    """The step's scopes reach the chip's compiler: `forward`, its transpose
    and `update` stand in the instructions' `op_name`, the Pallas custom
    calls are named after their kernels, and the scopes changed nothing
    else: with provenance taken out the program is the unscoped one's."""
    import contextlib
    from paddle_tpu.engine import builder
    from paddle_tpu.observability import costs
    text = _small_bert_step_text(topo, monkeypatch, sharded=False)
    op_names = _OP_NAMES.findall(text)
    for scope in ('/jvp(forward)/', '/transpose(jvp(forward))/', '/update/'):
        assert any(scope in n for n in op_names), scope
    calls = _CUSTOM_CALL.findall(text)
    # forward and backward of the flash and dropout-norm kernels, the
    # embedding and head layer norms
    assert len(calls) >= 7
    assert all(any(c.startswith(s) for s in _KERNEL_SCOPES) for c in calls)
    assert {s for s in _KERNEL_SCOPES
            if any(c.startswith(s) for c in calls)} == set(_KERNEL_SCOPES)
    phases = costs.instruction_phases(text)
    assert {phases[c] for c in calls} == {'forward', 'backward'}
    # a layer's attention: the forward kernel and ONE backward kernel
    assert sorted(phases[c] for c in calls
                  if c.startswith('flash_attention.pallas')) == [
                      'backward', 'forward']
    assert 'backward+update' in phases.values()     # XLA fuses them: said

    class NoScopes:                 # the builder's scopes alone, taken out
        named_scope = staticmethod(lambda name: contextlib.nullcontext())

        def __getattr__(self, name):
            return getattr(jax, name)
    monkeypatch.setattr(builder, 'jax', NoScopes())
    unscoped = _small_bert_step_text(topo, monkeypatch, sharded=False)
    assert not any('jvp(forward)' in n for n in _OP_NAMES.findall(unscoped))
    assert _without_provenance(text) == _without_provenance(unscoped)


@pytest.mark.parametrize('sharded', [False, True], ids=['one_chip', '2x2'])
def test_telemetry_leaves_the_chips_program_alone(topo, monkeypatch, sharded):
    """ISSUE 39's spans (the build, the state's making and placing, the
    first dispatch's `first`) and the records of JAX's compile path are the
    host's: with telemetry on the chip's compiler is handed the program it
    is handed with telemetry off."""
    from paddle_tpu import observability as obs
    off = _small_bert_step_text(topo, monkeypatch, sharded=sharded)
    obs.enable()
    try:
        on = _small_bert_step_text(topo, monkeypatch, sharded=sharded)
        assert [e for e in obs.trace_events()
                if e['name'] == 'engine.init_state']
    finally:
        obs.disable()
        obs.reset()
    assert _without_provenance(on) == _without_provenance(off)


def test_partitioned_step_names_its_kernels_and_its_gathers(topo,
                                                            monkeypatch):
    """FSDP over the four chips: inside `shard_map` the custom calls keep
    their kernels' names (a device trace is read by them), and the
    use-time gathers of the sharded parameters carry `fsdp.gather`. The
    carry's reshard is in the traced program (`fsdp.reshard`) and compiles
    to no instruction of its own: replicated to sharded is a slice, which
    the partitioner folds into the update that reads it."""
    text = _small_bert_step_text(topo, monkeypatch, sharded=True)
    calls = _CUSTOM_CALL.findall(text)
    assert len(calls) >= 7
    assert all(any(c.startswith(s) for s in _KERNEL_SCOPES) for c in calls)
    assert not re.search(r'^\s+%shard_map[.\d]* = ', text, re.M)
    gathers = re.findall(r'^\s+%\S+ = \S+ all-gather(?:-start)?\(.*', text,
                         re.M)
    named = [g for g in gathers if 'fsdp.gather/' in g]
    assert len(named) >= 10, len(gathers)
    lowered = _small_bert_step_text(topo, monkeypatch, sharded=True,
                                    lowered=True)
    assert 'fsdp.reshard' in lowered and 'fsdp.gather' in lowered


def _holds_one_routed_product(text, calls, under, phases):
    """The expert layers of a compiled step: the grouped-product kernels,
    forward and backward, every one under `moe.experts` (which so still
    names its instructions); no second form (no XLA stand-in, and no plain
    product left under the scope: the dense branch went with PR 38; the one
    `conditional` picks the buffer's size), and the rounds past the buffer
    as loops."""
    grouped = [c for c in calls if c.startswith('grouped_matmul.pallas')]
    assert grouped and all('moe.experts' in under[c] for c in grouped)
    assert {phases[c] for c in grouped} == {'forward', 'backward'}
    assert 'grouped_matmul.xla' not in text and 'ragged-dot' not in text
    # the rows' way in and out: the kernels, forward and backward, and no
    # gather or scatter of rows left beside them
    moved = [c for c in calls if c.startswith('row_permute.pallas')]
    assert moved and all('moe.experts' in under[c] for c in moved)
    assert {phases[c] for c in moved} == {'forward', 'backward'}
    assert 'row_permute.xla' not in text
    lines = {m.group(1): m.group(2) for m in re.finditer(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(', text, re.M)}
    kinds = {lines.get(name) for name, scopes in under.items()
             if 'moe.experts' in scopes}
    assert 'while' in kinds and not kinds & {'dot', 'convolution'}, kinds


def test_hybrid_step_holds_its_kernels_and_its_layers_scopes(topo,
                                                             monkeypatch):
    """Kimi-Linear at a small width (heads of the real sizes: 128 for the
    delta rule, 192 / 128 for latent attention; rows of 1024 so that
    attention takes the flash kernels) through `engine.build_train_step`
    under bf16 autocast with per-block recomputation, compiled for one
    described chip: the flash and RMS-norm kernels are in it, and every
    layer scope the benchmark reads names instructions of the compiled
    module (`observability.costs.instruction_scopes`)."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, engine, optimizer
    from paddle_tpu.nn.layer_base import buffer_values, param_values
    from paddle_tpu.observability import costs
    from paddle_tpu.text.kimi_linear import (KimiLinearConfig,
                                             KimiLinearForCausalLM)
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    paddle.seed(0)
    net = KimiLinearForCausalLM(KimiLinearConfig(
        vocab_size=1024, hidden_size=256, num_hidden_layers=5,
        num_attention_heads=2, head_dim=128, intermediate_size=512,
        moe_intermediate_size=128, num_experts=16, num_experts_per_token=4,
        experts_held=(0, 4), kv_lora_rank=128, recompute=True))
    net.train()
    step = engine.build_train_step(
        net=net, loss=net.training_loss,
        optimizer=optimizer.AdamW(learning_rate=1e-4, weight_decay=0.1))
    one = SingleDeviceSharding(topo.devices[0])
    state = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v), v.dtype, sharding=one),
        step.init_state(param_values(net), buffer_values(net)))
    feed = tuple(jax.ShapeDtypeStruct((2, 1024), jnp.int32, sharding=one)
                 for _ in range(3))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    with amp.auto_cast(dtype='bfloat16'):
        text = step._jit.lower(state, (feed, ()), key).compile().as_text()
    calls = _CUSTOM_CALL.findall(text)
    assert any(c.startswith('flash_attention.pallas') for c in calls)
    assert any(c.startswith('fused_rms_norm.pallas') for c in calls)
    assert 'flash_attention.xla' not in text and 'delta_rule.xla' not in text
    assert 'short_conv.xla' not in text
    under = costs.instruction_scopes(text)
    found = {scope for scopes in under.values() for scope in scopes}
    assert found >= {'kda.scan', 'kda.proj', 'mla.attention', 'moe.route',
                     'moe.experts', 'moe.shared', 'lm_head',
                     'fused_rms_norm.pallas', 'delta_rule.pallas',
                     'short_conv.pallas', 'grouped_matmul.pallas', 'update'}
    _holds_one_routed_product(text, calls, under,
                              costs.instruction_phases(text))
    assert all('fused_rms_norm.pallas' in under[c] for c in calls
               if c.startswith('fused_rms_norm.pallas'))
    # the delta rule's kernels: a KDA layer maps its rows, so each of the
    # four holds one forward kernel in the forward pass and, in the
    # backward pass, the forward again (it saves the chunks' start states)
    # and the backward kernel
    delta = [c for c in calls if c.startswith('delta_rule.pallas')]
    assert len(delta) == 12, delta
    assert all('kda.scan' in under[c] for c in delta)
    assert all('mla.attention' in under[c] for c in calls
               if c.startswith('flash_attention.pallas'))
    # the short convolutions of q, k and v: per KDA layer three forward
    # kernels in the forward pass and, in the backward pass, the three
    # again (the recomputation) and their three backward kernels
    short = [c for c in calls if c.startswith('short_conv.pallas')]
    assert len(short) == 36, short
    assert all('kda.proj' in under[c] for c in short)
    phases = costs.instruction_phases(text)
    for kernel in ('flash_attention.pallas', 'delta_rule.pallas',
                   'short_conv.pallas'):
        assert {phases[c] for c in calls if c.startswith(kernel)} == {
            'forward', 'backward'}


def test_rotary_decoder_step_holds_its_scopes(topo, monkeypatch):
    """JoyAI-LLM-Flash at a small width (heads of the real sizes, 128 + 64 /
    128; rows of 1024 so that attention takes the flash kernels) through
    `engine.build_train_step` under bf16 autocast with per-half
    recomputation, compiled for one described chip: every block's attention
    is the flash kernels (the prediction module's too), the rotation and
    the module name instructions of the compiled module, and the module's
    attention lies under `mtp`, `mla.attention` and, beside it, `mla.rope`
    alike."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, engine, optimizer
    from paddle_tpu.nn.layer_base import buffer_values, param_values
    from paddle_tpu.observability import costs
    from paddle_tpu.text.joyai_flash import (JoyAIFlashConfig,
                                             JoyAIFlashForCausalLM)
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    paddle.seed(0)
    net = JoyAIFlashForCausalLM(JoyAIFlashConfig(
        vocab_size=1024, hidden_size=256, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=512,
        moe_intermediate_size=128, num_experts=16, num_experts_per_token=4,
        experts_held=(0, 4), q_lora_rank=128, kv_lora_rank=128,
        recompute=True))
    net.train()
    step = engine.build_train_step(
        net=net, loss=net.training_loss,
        optimizer=optimizer.AdamW(learning_rate=1e-4, weight_decay=0.1))
    one = SingleDeviceSharding(topo.devices[0])
    state = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v), v.dtype, sharding=one),
        step.init_state(param_values(net), buffer_values(net)))
    feed = tuple(jax.ShapeDtypeStruct((2, 1024), jnp.int32, sharding=one)
                 for _ in range(4))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    with amp.auto_cast(dtype='bfloat16'):
        text = step._jit.lower(state, (feed, ()), key).compile().as_text()
    calls = _CUSTOM_CALL.findall(text)
    under = costs.instruction_scopes(text)
    found = {scope for scopes in under.values() for scope in scopes}
    assert found >= {'mla.attention', 'mla.rope', 'mtp', 'moe.route',
                     'moe.experts', 'moe.shared', 'lm_head',
                     'fused_rms_norm.pallas', 'update'}
    assert 'flash_attention.xla' not in text
    _holds_one_routed_product(text, calls, under,
                              costs.instruction_phases(text))
    # three blocks (two and the module's), each the forward kernel, the
    # forward again in the recomputation and the one backward kernel
    flash = [c for c in calls if c.startswith('flash_attention.pallas')]
    assert len(flash) == 9, flash
    assert all('mla.attention' in under[c] for c in flash)
    assert sum('mtp' in under[c] for c in flash) == 3
    both = [n for n, scopes in under.items()
            if {'mtp', 'mla.rope'} <= set(scopes)]
    assert both and all('mla.attention' in under[n] for n in both)
    # the queries' rotation is the kernel, under `mla.rope` in the forward
    # pass, the recomputation and the backward pass of each block (else
    # `mla.rope_ms` would fall because the work left the scope)
    turned = [c for c in calls if c.startswith('rotary.pallas')]
    assert len(turned) == 9 and 'rotary.xla' not in text, turned
    assert all({'mla.attention', 'mla.rope'} <= set(under[c])
               for c in turned)
    assert sum('mtp' in under[c] for c in turned) == 3
    phases = costs.instruction_phases(text)
    assert sorted(phases[c] for c in turned) == \
        ['backward'] * 6 + ['forward'] * 3
    assert any({'mtp', 'lm_head'} <= set(scopes) for scopes in under.values())


def test_head_share_step_holds_its_kernels_and_its_layers_scopes(
        topo, monkeypatch):
    """Olmo-Hybrid at a small depth and width with the REAL head sizes (keys
    of 96, values of 192, attention heads of 128; 3 of 6 heads held; one
    row of 1024 so that attention takes the flash kernels) through
    `engine.build_train_step` under bf16 autocast with per-half
    recomputation, compiled for one described chip: heads off the lane grid
    take the delta-rule and short-convolution KERNELS (laid on 128 / 256
    lanes), the q/k norms and the norms behind the sublayers the RMS-norm
    kernel, no site its XLA form, and the four layer scopes the new cell's
    readers read name instructions of the compiled module."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, engine, optimizer
    from paddle_tpu.nn.layer_base import buffer_values, param_values
    from paddle_tpu.observability import costs
    from paddle_tpu.text.olmo_hybrid import (OlmoHybridConfig,
                                             OlmoHybridForCausalLM)
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    paddle.seed(0)
    net = OlmoHybridForCausalLM(OlmoHybridConfig(
        vocab_size=1024, hidden_size=256, num_hidden_layers=4,
        num_attention_heads=6, linear_num_heads=6, intermediate_size=512,
        heads_held=(3, 3), recompute=True))
    net.train()
    step = engine.build_train_step(
        net=net, loss=net.training_loss,
        optimizer=optimizer.AdamW(learning_rate=1e-4, weight_decay=0.1))
    one = SingleDeviceSharding(topo.devices[0])
    state = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v), v.dtype, sharding=one),
        step.init_state(param_values(net), buffer_values(net)))
    feed = tuple(jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one)
                 for _ in range(3))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    with amp.auto_cast(dtype='bfloat16'):
        text = step._jit.lower(state, (feed, ()), key).compile().as_text()
    calls = _CUSTOM_CALL.findall(text)
    for kernel in ('delta_rule', 'short_conv', 'flash_attention',
                   'fused_rms_norm'):
        assert any(c.startswith(kernel + '.pallas') for c in calls), kernel
        assert kernel + '.xla' not in text, kernel
    under = costs.instruction_scopes(text)
    found = {scope for scopes in under.values() for scope in scopes}
    assert found >= {'gdn.scan', 'gdn.proj', 'attn.full', 'ffn.dense',
                     'lm_head', 'fused_rms_norm.pallas', 'delta_rule.pallas',
                     'short_conv.pallas', 'flash_attention.pallas', 'update'}
    # three linear layers of one row: the forward kernel, the forward again
    # in the recomputation (it saves the chunks' start states) and the
    # backward kernel; q, k and v as many times each
    delta = [c for c in calls if c.startswith('delta_rule.pallas')]
    assert len(delta) == 9, delta
    assert all('gdn.scan' in under[c] for c in delta)
    short = [c for c in calls if c.startswith('short_conv.pallas')]
    assert len(short) == 27, short
    assert all('gdn.proj' in under[c] for c in short)
    flash = [c for c in calls if c.startswith('flash_attention.pallas')]
    assert len(flash) == 3, flash
    assert all('attn.full' in under[c] for c in flash)
    # the q and k norms of the one full layer lie under its scope; the
    # eight norms behind the sublayers and the final one do not
    norms = [c for c in calls if c.startswith('fused_rms_norm.pallas')]
    assert sum('attn.full' in under[c] for c in norms) >= 4
    assert len(norms) >= 4 + 2 * 8 + 1


def test_grouped_query_decoder_step_holds_its_kernels_and_its_scopes(
        topo, monkeypatch):
    """Mellum at a small width (heads of the real size, 128; 8 query heads
    on 2 K/V heads; rows of 1024 under a window of 256, so that attention
    takes the flash kernels and the window binds) through
    `engine.build_train_step` under bf16 autocast with per-half
    recomputation, compiled for one described chip: one period of the
    pattern, three window layers and a full one. Flash attention, the
    grouped product and the fused norm are the Pallas kernels under the new
    scopes, no site took its XLA form, and K and V (dK and dV too) enter
    and leave the flash kernels at 2 heads, never at 8."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, engine, optimizer
    from paddle_tpu.nn.layer_base import buffer_values, param_values
    from paddle_tpu.observability import costs
    from paddle_tpu.text.mellum import MellumConfig, MellumForCausalLM
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    paddle.seed(0)
    net = MellumForCausalLM(MellumConfig(
        vocab_size=1024, hidden_size=256, num_hidden_layers=4,
        num_attention_heads=8, num_key_value_heads=2, head_dim=128,
        sliding_window=256, moe_intermediate_size=128, num_experts=16,
        num_experts_per_token=4, experts_held=(4, 8), recompute=True))
    net.train()
    assert not buffer_values(net)           # the softmax router has no bias
    step = engine.build_train_step(
        net=net, loss=net.training_loss,
        optimizer=optimizer.AdamW(learning_rate=1e-4, weight_decay=0.1))
    one = SingleDeviceSharding(topo.devices[0])
    state = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v), v.dtype, sharding=one),
        step.init_state(param_values(net), buffer_values(net)))
    feed = tuple(jax.ShapeDtypeStruct((2, 1024), jnp.int32, sharding=one)
                 for _ in range(3))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    with amp.auto_cast(dtype='bfloat16'):
        text = step._jit.lower(state, (feed, ()), key).compile().as_text()
    calls = _CUSTOM_CALL.findall(text)
    under = costs.instruction_scopes(text)
    found = {scope for scopes in under.values() for scope in scopes}
    assert found >= {'attn.window', 'attn.full', 'attn.rope', 'moe.route',
                     'moe.experts', 'lm_head', 'flash_attention.pallas',
                     'fused_rms_norm.pallas', 'grouped_matmul.pallas',
                     'update'}
    assert not re.findall(r'[\w.]+\.xla\b', text)
    _holds_one_routed_product(text, calls, under,
                              costs.instruction_phases(text))
    assert any(c.startswith('fused_rms_norm.pallas') for c in calls)
    # four blocks, each the forward kernel, the forward again in the
    # recomputation and the one backward kernel; three of them window layers
    flash = [c for c in calls if c.startswith('flash_attention.pallas')]
    assert len(flash) == 12, flash
    assert sum('attn.window' in under[c] for c in flash) == 9
    assert sum('attn.full' in under[c] for c in flash) == 3
    # the rotation lies inside either kind of layer
    turned = [set(s) for s in under.values() if 'attn.rope' in s]
    assert any('attn.window' in s for s in turned)
    assert any('attn.full' in s for s in turned)
    assert all(s & {'attn.window', 'attn.full'} for s in turned)
    # and is the kernel, for q and for k, in the forward pass, the
    # recomputation and the backward pass of each block, every call under
    # `attn.rope` (else `attn.rope_ms` would fall because the work left it)
    rope = [c for c in calls if c.startswith('rotary.pallas')]
    assert len(rope) == 24, rope
    assert all('attn.rope' in under[c] for c in rope)
    phases = costs.instruction_phases(text)
    assert sorted(phases[c] for c in rope) == \
        ['backward'] * 16 + ['forward'] * 8
    # operands and results of the flash kernels by their leading size: 2 rows
    # x 8 query heads = 16 (forward q, o; backward q, o, dO, dQ) and 2 rows x
    # 2 K/V heads = 4 (forward k, v; backward k, v, dK, dV)
    seen = []
    for line in text.splitlines():
        m = re.match(r'\s*%?(flash_attention\.pallas[\w.\-]*) = ', line)
        if m and 'custom-call(' in line:
            head = line.split('frontend_attributes')[0]
            sizes = re.findall(r'(?:bf16|f32)\[(\d+),1024,128\]', head)
            seen.append(sorted(sizes.count(n) for n in ('16', '4')))
            assert set(sizes) == {'16', '4'}, line[:300]
    assert sorted(seen) == [[2, 2]] * 8 + [[4, 4]] * 4, seen


def test_gated_decoder_step_holds_its_kernels_and_its_scopes(topo,
                                                             monkeypatch):
    """Laguna at a small width (heads of the real size, 128, of which a full
    layer turns 64; 6 query heads in the full layers and 8 in the window
    layers on ONE K/V head; rows of 1024 under a window of 256) through
    `engine.build_train_step` under bf16 autocast with per-half
    recomputation, compiled for one described chip: the dense layer and a
    whole period behind it. The gate, the dense layer and the shared expert
    name instructions under their scopes, no site took its XLA form, the
    rotation is the kernel in both kinds of layer, and the flash kernels
    take q at each kind's own head count."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, engine, optimizer
    from paddle_tpu.nn.layer_base import buffer_values, param_values
    from paddle_tpu.observability import costs
    from paddle_tpu.text.laguna import LagunaConfig, LagunaForCausalLM
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    paddle.seed(0)
    net = LagunaForCausalLM(LagunaConfig(
        vocab_size=1024, hidden_size=256, num_hidden_layers=5,
        num_attention_heads=6, num_attention_heads_per_layer=[6, 8, 8, 8, 6],
        num_key_value_heads=1, sliding_window=256, intermediate_size=512,
        moe_intermediate_size=128, shared_expert_intermediate_size=128,
        num_experts=16, num_experts_per_token=4, experts_held=(4, 8),
        recompute=True))
    net.train()
    assert len(buffer_values(net)) == 4     # the sparse layers' zero biases
    step = engine.build_train_step(
        net=net, loss=net.training_loss,
        optimizer=optimizer.AdamW(learning_rate=1e-4, weight_decay=0.1))
    one = SingleDeviceSharding(topo.devices[0])
    state = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v), v.dtype, sharding=one),
        step.init_state(param_values(net), buffer_values(net)))
    feed = tuple(jax.ShapeDtypeStruct((2, 1024), jnp.int32, sharding=one)
                 for _ in range(3))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    with amp.auto_cast(dtype='bfloat16'):
        text = step._jit.lower(state, (feed, ()), key).compile().as_text()
    calls = _CUSTOM_CALL.findall(text)
    under = costs.instruction_scopes(text)
    found = {scope for scopes in under.values() for scope in scopes}
    assert found >= {'attn.window', 'attn.full', 'attn.rope', 'attn.gate',
                     'ffn.dense', 'moe.route', 'moe.experts', 'moe.shared',
                     'lm_head', 'flash_attention.pallas', 'rotary.pallas',
                     'fused_rms_norm.pallas', 'grouped_matmul.pallas',
                     'update'}
    assert not re.findall(r'[\w.]+\.xla\b', text)
    # the gate lies inside either kind of layer, never outside one
    gated = [set(s) for s in under.values() if 'attn.gate' in s]
    assert any('attn.window' in s for s in gated)
    assert any('attn.full' in s for s in gated)
    assert all(s & {'attn.window', 'attn.full'} for s in gated)
    # five blocks: the forward kernel, the forward again in the
    # recomputation and the one backward kernel; three of them window layers
    flash = [c for c in calls if c.startswith('flash_attention.pallas')]
    assert len(flash) == 15, flash
    assert sum('attn.window' in under[c] for c in flash) == 9
    assert sum('attn.full' in under[c] for c in flash) == 6
    # the rotation is the kernel for q and k in the forward pass, the
    # recomputation and the backward pass of each block, partial rule and
    # whole head alike
    rope = [c for c in calls if c.startswith('rotary.pallas')]
    assert len(rope) == 30, rope
    assert all('attn.rope' in under[c] for c in rope)
    # q enters the flash kernels at 2 rows x 6 heads in a full layer and at
    # 2 rows x 8 in a window layer; k and v at 2 rows x 1
    seen = set()
    for line in text.splitlines():
        m = re.match(r'\s*%?(flash_attention\.pallas[\w.\-]*) = ', line)
        if m and 'custom-call(' in line:
            head = line.split('frontend_attributes')[0]
            sizes = set(re.findall(r'(?:bf16|f32)\[(\d+),1024,128\]', head))
            kind = 'attn.window' if 'attn.window' in under[m.group(1)] \
                else 'attn.full'
            seen.add((kind,) + tuple(sorted(sizes, key=int)))
    assert seen == {('attn.full', '2', '12'), ('attn.window', '2', '16')}


def test_state_space_decoder_step_holds_its_kernels_and_its_scopes(
        topo, monkeypatch):
    """The Nemotron-H decoder at a small hidden size with the REAL inner
    sizes (Mamba-2 heads of 64 with 8 to a group and a state of 128; 32
    query heads on 2 K/V heads of 128; experts of 1856 columns and a shared
    one of 3712; two rows of 1024 so that attention takes the flash kernels)
    through `engine.build_train_step` under bf16 autocast with
    recomputation, compiled for one described chip: one layer of each
    letter, M E *. The rule is the `ssd.pallas` kernels under `ssm.scan`,
    the three convolutions the short-convolution kernels under `ssm.conv`
    inside `ssm.proj`, the experts' 1856 columns go through the grouped
    product's kernels, no site took its XLA form, nothing is rotated, and K
    and V (dK and dV too) enter and leave the flash kernels at 2 heads."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, engine, optimizer
    from paddle_tpu.nn.layer_base import buffer_values, param_values
    from paddle_tpu.observability import costs
    from paddle_tpu.text.nemotron_h import (NemotronHConfig,
                                            NemotronHForCausalLM)
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    paddle.seed(0)
    net = NemotronHForCausalLM(NemotronHConfig(
        vocab_size=1024, hidden_size=256, num_hidden_layers=3,
        hybrid_override_pattern='ME*', mamba_num_heads=16, n_groups=2,
        n_routed_experts=16, num_experts_per_tok=4, experts_held=(4, 8),
        recompute=True))
    net.train()
    assert sorted(buffer_values(net)) == [
        'layers.1.mixer.e_score_correction_bias']
    step = engine.build_train_step(
        net=net, loss=net.training_loss,
        optimizer=optimizer.AdamW(learning_rate=1e-4, weight_decay=0.1))
    one = SingleDeviceSharding(topo.devices[0])
    state = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v), v.dtype, sharding=one),
        step.init_state(param_values(net), buffer_values(net)))
    feed = tuple(jax.ShapeDtypeStruct((2, 1024), jnp.int32, sharding=one)
                 for _ in range(3))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    with amp.auto_cast(dtype='bfloat16'):
        text = step._jit.lower(state, (feed, ()), key).compile().as_text()
    calls = _CUSTOM_CALL.findall(text)
    under = costs.instruction_scopes(text)
    found = {scope for scopes in under.values() for scope in scopes}
    assert found >= {'ssm.proj', 'ssm.conv', 'ssm.scan', 'ssd.pallas',
                     'short_conv.pallas', 'attn.full', 'moe.route',
                     'moe.experts', 'moe.shared', 'lm_head',
                     'flash_attention.pallas', 'fused_rms_norm.pallas',
                     'grouped_matmul.pallas', 'row_permute.pallas', 'update'}
    assert 'attn.rope' not in found
    assert not re.findall(r'[\w.]+\.xla\b', text)
    _holds_one_routed_product(text, calls, under,
                              costs.instruction_phases(text))
    # one Mamba-2 layer, its two rows taken one at a time inside a loop: the
    # forward kernel, the forward again in the recomputation (it saves the
    # chunks' start states) and the backward kernel; x, B and C as many
    # convolutions each
    scan = [c for c in calls if c.startswith('ssd.pallas')]
    assert len(scan) == 3, scan
    assert all('ssm.scan' in under[c] for c in scan)
    short = [c for c in calls if c.startswith('short_conv.pallas')]
    assert len(short) == 9, short
    assert all({'ssm.proj', 'ssm.conv'} <= set(under[c]) for c in short)
    flash = [c for c in calls if c.startswith('flash_attention.pallas')]
    assert len(flash) == 3, flash
    assert all('attn.full' in under[c] for c in flash)
    # operands and results of the flash kernels by their leading size: 2 rows
    # x 32 query heads = 64 and 2 rows x 2 K/V heads = 4
    for line in text.splitlines():
        m = re.match(r'\s*%?(flash_attention\.pallas[\w.\-]*) = ', line)
        if m and 'custom-call(' in line:
            head = line.split('frontend_attributes')[0]
            sizes = re.findall(r'(?:bf16|f32)\[(\d+),1024,128\]', head)
            assert set(sizes) == {'64', '4'}, line[:300]

"""Flash attention Pallas kernels vs plain-XLA reference (interpret mode).

Runs the real kernel bodies through Pallas interpret mode on the CPU backend,
so forward AND backward tiling/masking logic is validated without a TPU.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels.flash_attention import (_attn_reference,
                                                flash_attention_bhld)

B, H, L, D = 2, 3, 128, 16
BQ = BK = 64


def _inputs(seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, H, L, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, H, L, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, H, L, D), jnp.float32)
    return q, k, v


def _kpad(seed=1):
    rs = np.random.RandomState(seed)
    lengths = rs.randint(L // 2, L + 1, size=B)
    bias = np.zeros((B, L), np.float32)
    for i, n in enumerate(lengths):
        bias[i, n:] = -1e9
    return jnp.asarray(bias)


@pytest.mark.skipif(jax.default_backend() == "tpu",
                    reason="interpret emulation is CPU-validation only")
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_bias", [False, True])
def test_flash_forward_parity(causal, with_bias):
    q, k, v = _inputs()
    bias = _kpad() if with_bias else None
    out = flash_attention_bhld(q, k, v, causal=causal, kpad_bias=bias,
                               block_q=BQ, block_k=BK, interpret=True)
    ref = _attn_reference(q, k, v, causal, 1.0 / np.sqrt(D), bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# (block_q, block_k) at L = 128: the one fused backward kernel writes dQ
# straight out when there is one K tile (nk == 1; a key-padding bias pins
# block_k to L, so every biased case is one) and sums it over the grid's K
# axis in an fp32 scratch otherwise
_BWD_BLOCKS = [(128, 128), (64, 128), (64, 64), (32, 64)]
_BWD_IDS = ['nq1_nk1', 'nq2_nk1', 'nq2_nk2', 'nq4_nk2']


def _grad_close(got, want, dtype, name):
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name} mismatch")
    else:   # bf16 operands into the MXU and a bf16 result, fp32 between
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 2e-2, f"d{name} rel diff {rel}"


@pytest.mark.skipif(jax.default_backend() == "tpu",
                    reason="interpret emulation is CPU-validation only")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=['fp32', 'bf16'])
@pytest.mark.parametrize("blocks", _BWD_BLOCKS, ids=_BWD_IDS)
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_bias", [False, True])
def test_flash_backward_parity(causal, with_bias, blocks, dtype):
    q, k, v = (t.astype(dtype) for t in _inputs(2))
    bias = _kpad(3) if with_bias else None
    bq, bk = blocks

    def flash_loss(q, k, v):
        o = flash_attention_bhld(q, k, v, causal=causal, kpad_bias=bias,
                                 block_q=bq, block_k=bk, interpret=True)
        o = o.astype(jnp.float32)
        return jnp.sum(o * jnp.cos(o))  # non-trivial cotangent

    def ref_loss(q, k, v):
        o = _attn_reference(q, k, v, causal, 1.0 / np.sqrt(D), bias)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(
        *(t.astype(jnp.float32) for t in (q, k, v)))
    for a, b, name in zip(g_flash, g_ref, 'qkv'):
        assert a.dtype == dtype
        _grad_close(a, b, dtype, name)


def test_flash_uneven_blocks_falls_back():
    # L=100 doesn't tile into 64-blocks -> silently uses the XLA reference
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 2, 100, 16), jnp.float32)
    out = flash_attention_bhld(q, q, q, causal=True, block_q=64, block_k=64,
                               interpret=True)
    ref = _attn_reference(q, q, q, True, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=['fp32', 'bf16'])
@pytest.mark.parametrize("block_q", [128, 64], ids=['nq1', 'nq2'])
@pytest.mark.parametrize("fill", [-1e9, -np.inf],
                         ids=['minus_1e9', 'minus_inf'])
def test_flash_fully_masked_rows_zero_grads(fill, block_q, dtype):
    """Batch entry 0 has ALL its keys masked. With a finite bias its rows
    are a softmax over equal scores; with -inf the forward leaves
    ``LSE_EMPTY`` for them and the backward's probabilities are exp(-inf):
    output and gradients of that entry are 0, never NaN, and the other
    entry's still equal the reference's."""
    q, k, v = (t.astype(dtype) for t in _inputs(4))
    bias = np.zeros((B, L), np.float32)
    bias[0, :] = fill
    bias[1, L - 40:] = fill
    bias = jnp.asarray(bias)

    def loss(q, k, v):
        o = flash_attention_bhld(q, k, v, causal=False, kpad_bias=bias,
                                 block_q=block_q, block_k=BK, interpret=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert np.isfinite(float(val))
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g.astype(jnp.float32))))

    def ref_loss(q, k, v):   # the entry that has keys left
        o = _attn_reference(q, k, v, False, 1.0 / np.sqrt(D), bias[1:])
        return jnp.sum(o ** 2)

    want = jax.grad(ref_loss, argnums=(0, 1, 2))(
        *(t[1:].astype(jnp.float32) for t in (q, k, v)))
    for g, w, name in zip(grads, want, 'qkv'):
        _grad_close(g[1:], w, dtype, name)
        if fill == -np.inf:
            assert not np.asarray(g[0].astype(jnp.float32)).any()


@pytest.mark.parametrize("broken", [None, 'q', 'k', 'v'],
                         ids=['sound', 'dq_off', 'dk_off', 'dv_off'])
def test_directional_check_holds_the_backward_to_its_forward(monkeypatch,
                                                             broken):
    """``checks.check_flash_dropout_backward`` (the chip runs it with the
    hardware PRNG's dropout on; here dropout off, interpret mode): the
    kernels' gradients pass, a backward with one gradient 5% off is
    refused."""
    from paddle_tpu.kernels import checks, flash_attention as fa

    def check():
        return checks.check_flash_dropout_backward(
            (2, 2, 128, 16), dropout_p=0.0, interpret=True)
    if not broken:
        got = check()
        assert set(got) == set('qkv')
        for fd, an in got.values():
            assert abs(fd - an) < 2e-3 * abs(an)
    else:
        sound = fa._flash_backward
        monkeypatch.setattr(fa, '_flash_backward', lambda *args: tuple(
            g * 1.05 if n == broken else g
            for n, g in zip('qkv', sound(*args))))
        with pytest.raises(AssertionError, match='d%s along' % broken):
            check()


@pytest.mark.skipif(jax.default_backend() != 'tpu',
                    reason="in-kernel PRNG dropout needs real TPU hardware "
                           "(interpret-mode prng_random_bits is a zero stub)")
class TestFlashDropoutTPU:
    def test_flash_dropout_deterministic_and_varies(self):
        q, k, v = _inputs(5)
        seed = jnp.array([[1234]], jnp.int32)
        f = jax.jit(lambda s: flash_attention_bhld(
            q, k, v, causal=False, dropout_p=0.3, dropout_seed=s,
            block_q=BQ, block_k=BK))
        o1, o2, o3 = f(seed), f(seed), f(jnp.array([[77]], jnp.int32))
        assert bool(jnp.allclose(o1, o2))
        assert not bool(jnp.allclose(o1, o3))

    def test_flash_dropout_grads_match_same_mask_reference(self):
        """Extract the implied keep-mask via identity-V probes, then check
        analytic grads against a dense reference using that exact mask.
        Highest matmul precision so the XLA reference (bf16 MXU passes by
        default) doesn't dominate the comparison error."""
        with jax.default_matmul_precision('highest'):
            self._dropout_grad_check()

    def _dropout_grad_check(self):
        p_drop, scale = 0.3, 1.0 / np.sqrt(D)
        q, k, v = _inputs(6)
        seed = jnp.array([[42]], jnp.int32)

        def flash(q, k, v):
            return flash_attention_bhld(q, k, v, causal=True,
                                        dropout_p=p_drop, dropout_seed=seed,
                                        block_q=BQ, block_k=BK)

        chunks = []
        for c in range(L // D):
            E = jnp.zeros((L, D), jnp.float32).at[c * D:(c + 1) * D, :].set(
                jnp.eye(D))
            chunks.append(np.asarray(jax.jit(flash)(
                q, k, jnp.broadcast_to(E, (B, H, L, D)))))
        M = np.concatenate(chunks, axis=-1)          # D∘P, shape (B,H,L,L)

        s = np.einsum('bhld,bhmd->bhlm', np.asarray(q), np.asarray(k)) * scale
        s = np.where(np.tril(np.ones((L, L), bool)), s, -1e30)
        P = np.exp(s - s.max(-1, keepdims=True))
        P /= P.sum(-1, keepdims=True)
        Dm = np.where(P > 1e-12, M / np.maximum(P, 1e-12), 0.0)
        Dm = jnp.asarray(np.round(Dm * (1 - p_drop)) / (1 - p_drop))

        def ref_loss(q, k, v):
            s = jnp.einsum('bhld,bhmd->bhlm', q, k) * scale
            s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -1e30)
            o = jnp.einsum('bhlm,bhmd->bhld', jax.nn.softmax(s, -1) * Dm, v)
            return jnp.sum(o * jnp.sin(o))

        def flash_loss(q, k, v):
            o = flash(q, k, v)
            return jnp.sum(o * jnp.sin(o))

        gf = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
        for a, b, n in zip(gf, gr, 'qkv'):
            a, b = np.asarray(a), np.asarray(b)
            rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-9)
            assert rel < 5e-3, f"d{n} rel diff {rel}"


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="real Mosaic kernel needs TPU hardware")
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_bias", [False, True])
def test_flash_real_kernel_parity_tpu(causal, with_bias):
    """The compiled (non-interpret) kernels vs an f32-precision reference —
    validates the two-phase causal loop and bias streaming on hardware."""
    q, k, v = _inputs(5)
    bias = _kpad(6) if with_bias else None
    with jax.default_matmul_precision("float32"):
        out = flash_attention_bhld(q, k, v, causal=causal, kpad_bias=bias,
                                   block_q=BQ, block_k=BK)
        ref = _attn_reference(q, k, v, causal, 1.0 / np.sqrt(D), bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

        def flash_loss(q, k, v):
            o = flash_attention_bhld(q, k, v, causal=causal, kpad_bias=bias,
                                     block_q=BQ, block_k=BK)
            return jnp.sum(o * jnp.cos(o))

        def ref_loss(q, k, v):
            o = _attn_reference(q, k, v, causal, 1.0 / np.sqrt(D), bias)
            return jnp.sum(o * jnp.cos(o))

        g1 = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# packed rows: the tile loops' bounds come from doc_start
# ---------------------------------------------------------------------------

def _starts(L, cuts):
    """doc_start of one row whose documents begin at 0 and at `cuts`."""
    s = np.zeros(L, np.int32)
    for at in sorted(cuts):
        s[at:] = at
    return s


def _bound_rows(kind, L, block):
    rs = np.random.RandomState(7)
    if kind == 'random':
        return np.stack([_starts(L, rs.choice(np.arange(1, L), n, False))
                         for n in (1, 3, 6, 11)])
    if kind == 'one_document':
        return _starts(L, [])[None]
    if kind == 'documents_of_64':
        return _starts(L, range(64, L, 64))[None]
    if kind == 'on_and_off_a_tile_edge':
        return np.stack([_starts(L, [block * 2]),
                         _starts(L, [block * 2 - 1]),
                         _starts(L, [block * 2 + 1]),
                         _starts(L, [block, block * 3 - 1, block * 3])])
    assert kind == 'not_monotone'   # nothing a packed row gives; any caller's
    return np.minimum(rs.randint(0, L, (4, L)), np.arange(L)).astype(np.int32)


def _needed(start, bq, bk):
    """(nq, nk) bool, by brute force: does any (row, key) of the tile pair
    pass the causal and the document mask."""
    L = start.shape[0]
    rows, cols = np.arange(L)[:, None], np.arange(L)[None, :]
    seen = (cols <= rows) & (cols >= start[:, None])
    return seen.reshape(L // bq, bq, L // bk, bk).any(axis=(1, 3))


@pytest.mark.parametrize('blocks', [(64, 64), (128, 128), (64, 128),
                                    (128, 64)],
                         ids=['64x64', '128x128', '64x128', '128x64'])
@pytest.mark.parametrize('kind', ['random', 'one_document', 'documents_of_64',
                                  'on_and_off_a_tile_edge', 'not_monotone'])
def test_doc_tile_bounds_against_brute_force(kind, blocks):
    """Never a needed tile pair outside the bounds; on monotone rows (what a
    packed row gives) the forward's first tile is itself needed, and so is
    the backward's last; one document skips nothing."""
    from paddle_tpu.kernels.flash_attention import (doc_tile_bounds,
                                                    doc_tile_counts)
    L, (bq, bk) = 1024, blocks
    starts = _bound_rows(kind, L, max(blocks))
    lo, hi = (np.asarray(a) for a in doc_tile_bounds(jnp.asarray(starts),
                                                     bq, bk))
    assert lo.shape == (len(starts), L // bq) and lo.dtype == np.int32
    assert hi.shape == (len(starts), L // bk) and hi.dtype == np.int32
    swept = 0
    for start, lo_b, hi_b in zip(starts, lo, hi):
        need = _needed(start, bq, bk)
        for i in range(L // bq):
            assert not need[i, :lo_b[i]].any()
            if kind != 'not_monotone':
                assert need[i, lo_b[i]]
            swept += (i * bq + bq + bk - 1) // bk - lo_b[i]
        for j in range(L // bk):
            assert not need[hi_b[j]:, j].any()
            if kind != 'not_monotone':
                assert need[hi_b[j] - 1, j]
    if kind == 'one_document':
        assert not lo.any() and (hi == L // bq).all()
    got, causal = doc_tile_counts(jnp.asarray(starts), bq, bk)
    assert float(got) == swept
    assert float(causal) == len(starts) * sum(
        (i * bq + bq + bk - 1) // bk for i in range(L // bq))


def test_the_decoders_step_counters_end_with_the_tile_pairs():
    """`decoder_block.merge_counters`: after the expert layers' counters,
    the tile pairs one latent layer's forward visits on the step's rows
    (tiles of 512), with the document bounds and without."""
    from paddle_tpu.text import decoder_block as db
    assert db.STEP_COUNTER_NAMES[-2:] == ('flash.tiles_swept',
                                          'flash.tiles_causal')
    assert set(db.STEP_COUNTER_SUMS) <= set(db.STEP_COUNTER_NAMES)
    seg = np.zeros((3, 2048), np.int32)
    seg[0, 1024:] = 1           # the row's last two Q tiles start at tile 2
    seg[1, 1500:] = 1           # its last Q tile starts at tile 1500 // 512
    got = dict(zip(db.STEP_COUNTER_NAMES, np.asarray(
        db.merge_counters([], jnp.asarray(seg))._value)))
    assert got['flash.tiles_causal'] == 3 * (1 + 2 + 3 + 4)
    assert got['flash.tiles_swept'] == (10 - 4) + (10 - 2) + 10
    assert not any(v for k, v in got.items() if k.startswith('moe.'))


def test_doc_tile_counts_of_rows_that_do_not_tile_are_zero():
    from paddle_tpu.kernels.flash_attention import doc_tile_counts
    swept, causal = doc_tile_counts(jnp.zeros((2, 100), jnp.int32), 64, 64)
    assert float(swept) == 0 and float(causal) == 0


@pytest.mark.skipif(jax.default_backend() == "tpu",
                    reason="interpret emulation is CPU-validation only")
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['fp32', 'bf16'])
@pytest.mark.parametrize('block', [64, 128])
def test_document_bounds_give_the_full_sweeps_numbers(block, dtype):
    """O, lse, dQ, dK and dV with the loops bounded by the documents equal
    those of the sweep of every tile under the diagonal element for element
    (the bounds are an operand of the internal calls): a skipped tile pair
    contributes exact zeros. Latent attention's head sizes, 192 / 128."""
    from paddle_tpu.kernels import flash_attention as fa
    b, h, L, d, dv = 2, 2, 512, 192, 128
    rs = np.random.RandomState(11)
    q, k = (jnp.asarray(rs.randn(b, h, L, d), dtype) for _ in range(2))
    v, g = (jnp.asarray(rs.randn(b, h, L, dv), dtype) for _ in range(2))
    start = jnp.asarray(np.stack([_starts(L, [100, 128, 300]),
                                  _starts(L, [64, 257, 448])]))
    bounded = (start,) + fa.doc_tile_bounds(start, block, block)
    n = L // block
    full = (start, jnp.zeros((b, n), jnp.int32), jnp.full((b, n), n,
                                                          jnp.int32))
    assert not all(np.array_equal(x, y) for x, y in zip(bounded[1:],
                                                        full[1:]))
    seed = jnp.zeros((1, 1), jnp.int32)
    rest = (True, d ** -0.5, block, block, 0.0, True)
    o, lse = fa._flash_forward(q, k, v, None, seed, bounded, *rest)
    o_full, lse_full = fa._flash_forward(q, k, v, None, seed, full, *rest)
    assert np.array_equal(o, o_full) and np.array_equal(lse, lse_full)
    grads = fa._flash_backward(q, k, v, o, lse, None, seed, bounded, g,
                               *rest)
    grads_full = fa._flash_backward(q, k, v, o, lse, None, seed, full, g,
                                    *rest)
    for name, got, want in zip('qkv', grads, grads_full):
        assert got.dtype == dtype
        assert np.array_equal(got, want), 'd' + name
    # and they are the masked attention's
    ref = _attn_reference(*(t.astype(jnp.float32) for t in (q, k, v)), True,
                          d ** -0.5, doc_start=start)
    np.testing.assert_allclose(
        np.asarray(o.astype(jnp.float32)), np.asarray(ref),
        atol=2e-5 if dtype == jnp.float32 else 3e-2)


# forward-and-gradient jaxprs of commit 06cc413 (before the document bounds),
# source locations taken out: (causal, key-padding bias, dropout, q's shape,
# v's head size)
_UNBOUNDED_PROGRAMS = [
    ('berts_key_padding_with_dropout', (False, True, 0.1, (16, 16, 512, 64),
                                        64),
     '906927ec451263921c0bd171f18093fb146e4672942099e2861d0fac8f5fe10f'),
    ('causal_without_documents', (True, False, 0.0, (2, 4, 1024, 192), 128),
     '207f05f45f873b8bedf6eb1bf3fab0403caa64bab583e87539a03d86817c941f'),
]


@pytest.mark.parametrize('call,digest', [p[1:] for p in _UNBOUNDED_PROGRAMS],
                         ids=[p[0] for p in _UNBOUNDED_PROGRAMS])
def test_a_call_without_doc_start_traces_to_the_parents_program(
        monkeypatch, call, digest):
    """The loops' bounds adapt on one thing the call observes, `doc_start`:
    without it the kernels keep their constant bounds and the call traces to
    the program it traced to before they could follow documents."""
    import hashlib
    import re
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    causal, kpad, p, shape, dv = call

    def loss(q, k, v, bias, seed):
        o = flash_attention_bhld(
            q, k, v, causal=causal, kpad_bias=bias if kpad else None,
            dropout_p=p, dropout_seed=seed if p else None)
        return jnp.sum(o.astype(jnp.float32))
    q = jnp.zeros(shape, jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        q, q, jnp.zeros(shape[:3] + (dv,), jnp.bfloat16),
        jnp.zeros((shape[0], shape[2]), jnp.float32),
        jnp.zeros((1, 1), jnp.int32)))
    text = re.sub(r'/[\w/.\-]+\.py:\d+', 'SRC', text)
    text = re.sub(r'at SRC|SRC', '', text)
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_packed_check_holds_the_kernels_to_the_reference(monkeypatch):
    """``checks.check_flash_packed`` at a small size in interpret mode: the
    kernels pass, and kernels whose forward starts one tile late (a needed
    tile pair skipped) are refused."""
    from paddle_tpu.kernels import checks, flash_attention as fa

    def check():
        return checks.check_flash_packed((1, 2, 1024, 48), v_dim=32, seed=5,
                                         block=128, interpret=True)
    errs = check()
    assert set(errs) == {'o', 'dq', 'dk', 'dv', 'tiles_swept_share'}
    assert 0.3 < errs['tiles_swept_share'] < 0.7
    sound = fa.doc_tile_bounds
    monkeypatch.setattr(fa, 'doc_tile_bounds', lambda *a: tuple(
        t + 1 if at == 0 else t for at, t in enumerate(sound(*a))))
    with pytest.raises(AssertionError, match='packed flash'):
        check()


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="real Mosaic kernel needs TPU hardware")
def test_flash_packed_real_kernel_parity_tpu():
    """``checks.check_flash_packed`` (``chip_smoke.py`` runs it): the
    compiled kernels on a packed row of 8192 at 192 / 128, documents as the
    benchmark's traffic draws them, against the XLA reference."""
    from paddle_tpu.kernels import checks
    errs = checks.check_flash_packed()
    assert errs['tiles_swept_share'] < 1.0

"""Attention autotune harness (CPU-testable parts; the flash candidates
themselves only run on TPU hardware)."""
import numpy as np
import pytest

from paddle_tpu.kernels import autotune as at


@pytest.fixture(autouse=True)
def isolated_cache():
    at.clear_cache()
    yield
    at.clear_cache()


def test_candidate_blocks_divisibility():
    cands = at._candidate_blocks(512, has_kpad=False)
    assert (512, 512) in cands and (256, 128) in cands
    assert all(512 % bq == 0 and 512 % bk == 0 for bq, bk in cands)
    # kpad pins block_k to the full row
    kcands = at._candidate_blocks(512, has_kpad=True)
    assert kcands and all(bk == 512 for _, bk in kcands)
    # non-power-of-two seq: only divisors survive
    assert at._candidate_blocks(384, has_kpad=False) == [(128, 128)]


def test_autotune_records_and_caches():
    dec = at.autotune_attention(2, 2, 128, 16, dtype='float32',
                                budget_s=30.0)
    assert dec is not None and dec['mode'] in ('xla', 'flash')
    sig = at.attention_signature(2, 2, 128, 16, False, False, 0.0,
                                 dtype='float32')
    assert at._CACHE[sig] == dec
    assert at.lookup(2, 2, 128, 16, False, False, 0.0,
                     dtype='float32') == dec
    # in-process only: a cleared cache (a fresh process) is untuned, so no
    # file outside the checkout can decide which kernel runs
    at.clear_cache()
    assert at.lookup(2, 2, 128, 16, False, False, 0.0,
                     dtype='float32') is None


def test_refused_candidate_raises(monkeypatch):
    """A candidate that fails to compile/run is a bug to surface, not a
    slow candidate to drop in silence."""
    def boom(*a, **k):
        raise RuntimeError('Mosaic refused the tiling')
    monkeypatch.setattr(at, '_time_step', boom)
    with pytest.raises(RuntimeError, match='refused'):
        at.autotune_attention(1, 1, 128, 8, dtype='float32', budget_s=30.0)


def test_lookup_none_when_untuned():
    assert at.lookup(1, 1, 64, 8, False, False, 0.0) is None


def test_second_call_does_no_timing_work(monkeypatch):
    at.autotune_attention(1, 1, 128, 8, dtype='float32', budget_s=30.0)
    timed = []
    monkeypatch.setattr(at, '_time_step',
                        lambda *a, **k: timed.append(1) or 0.0)
    at.autotune_attention(1, 1, 128, 8, dtype='float32', budget_s=30.0)
    assert timed == []   # pure cache hit, no candidates re-timed


def test_dispatch_skips_lookup_when_ineligible(monkeypatch):
    calls = []
    real_lookup = at.lookup

    def spy(*args, **kw):
        calls.append(args)
        return real_lookup(*args, **kw)

    import paddle_tpu.nn.functional.transformer as tr
    monkeypatch.setattr('paddle_tpu.kernels.autotune.lookup', spy)
    import paddle_tpu as paddle
    q = paddle.to_tensor(np.ones((2, 64, 2, 8), 'float32'))
    out = tr.scaled_dot_product_attention(q, q, q)
    assert tuple(out.shape) == (2, 64, 2, 8)
    # on CPU flash is never eligible, so lookup is skipped entirely
    assert calls == []


class TestDispatchOverride:
    """Force flash-eligibility on CPU (stub backend + stub kernel) and
    check the tuned decision really drives the dispatch."""

    @pytest.fixture
    def flashable(self, monkeypatch):
        import paddle_tpu.nn.functional.transformer as tr
        import paddle_tpu.kernels.flash_attention as fa
        import jax.numpy as jnp
        monkeypatch.setattr(tr.jax, 'default_backend', lambda: 'tpu')
        kernel_calls = []

        def stub_kernel(q, k, v, causal=False, scale=None, kpad_bias=None,
                        dropout_p=0.0, dropout_seed=None,
                        block_q=512, block_k=512, interpret=False):
            kernel_calls.append({'block_q': block_q, 'block_k': block_k})
            s = jnp.einsum('bhqd,bhkd->bhqk', q, k) / np.sqrt(q.shape[-1])
            return jnp.einsum('bhqk,bhkd->bhqd',
                              jnp.asarray(np.ones(1, 'float32')) * 0 +                               jnp.exp(s - s.max(-1, keepdims=True)) /
                              jnp.exp(s - s.max(-1, keepdims=True))
                              .sum(-1, keepdims=True), v)

        monkeypatch.setattr(fa, 'flash_attention_bhld', stub_kernel)
        return tr, kernel_calls

    def _q(self):
        import paddle_tpu as paddle
        return paddle.to_tensor(
            np.random.default_rng(0).standard_normal((2, 1024, 2, 8))
            .astype('float32'))

    def test_tuned_xla_disables_flash(self, flashable):
        tr, kernel_calls = flashable
        sig = at.attention_signature(2, 2, 1024, 8, False, False, 0.0,
                                     dtype='float32')
        at._CACHE[sig] = {'mode': 'xla', 'block_q': 0, 'block_k': 0}
        q = self._q()
        tr.scaled_dot_product_attention(q, q, q, training=False)
        assert kernel_calls == []        # flash suppressed by tuned 'xla'

    def test_tuned_flash_blocks_passed_through(self, flashable):
        tr, kernel_calls = flashable
        sig = at.attention_signature(2, 2, 1024, 8, False, False, 0.0,
                                     dtype='float32')
        at._CACHE[sig] = {'mode': 'flash', 'block_q': 256, 'block_k': 128}
        q = self._q()
        tr.scaled_dot_product_attention(q, q, q, training=False)
        assert kernel_calls and kernel_calls[0] == {'block_q': 256,
                                                    'block_k': 128}

    def test_malformed_cache_entry_falls_back(self, flashable):
        tr, kernel_calls = flashable
        sig = at.attention_signature(2, 2, 1024, 8, False, False, 0.0,
                                     dtype='float32')
        at._CACHE[sig] = {'mode': 'flash'}    # missing block fields
        q = self._q()
        out = tr.scaled_dot_product_attention(q, q, q, training=False)
        # treated as untuned: static heuristic (seq 1024 >= 512 -> flash
        # with default blocks), and no crash
        assert tuple(out.shape) == (2, 1024, 2, 8)
        assert kernel_calls and kernel_calls[0] == {'block_q': 512,
                                                    'block_k': 512}


def test_invalid_flash_blocks_treated_untuned():
    sig = at.attention_signature(2, 2, 1024, 8, False, False, 0.0,
                                 dtype='float32')
    for bad in ({'mode': 'flash', 'block_q': 0, 'block_k': 0},
                {'mode': 'flash', 'block_q': 384, 'block_k': 512},
                {'mode': 'flash', 'block_q': 2048, 'block_k': 512}):
        at._CACHE[sig] = bad
        assert at.lookup(2, 2, 1024, 8, False, False, 0.0,
                         dtype='float32') is None
    at._CACHE[sig] = {'mode': 'flash', 'block_q': 256, 'block_k': 512}
    assert at.lookup(2, 2, 1024, 8, False, False, 0.0,
                     dtype='float32') is not None

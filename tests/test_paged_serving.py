"""Paged KV cache serving: token-exactness vs the fixed-slot baseline and
the no-cache oracle, prefix sharing, chunked prefill, speculative decoding
(accept-all / reject-all / k=1 boundaries), page-exhaustion accounting +
doctor, concurrency-at-fixed-memory, and the retrace gate.

Everything runs on CPU in manual-pump mode (deterministic).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.serving import (PageAllocator, PagesExhaustedError,
                                PrefixCache, QueueFullError, ServingEngine,
                                TinyCausalLM, chain_hashes, paged_kv)
from paddle_tpu.serving.scheduler import (AdmissionQueue, Request,
                                          STATUS_DEADLINE, STATUS_ERROR)

pytestmark = pytest.mark.serving


@pytest.fixture(autouse=True)
def _telemetry_off():
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(autouse=True, scope='module')
def _xla_compile_cache(tmp_path_factory):
    """In-session compile dedup: many tests below build engines over the
    SAME seed-0 TinyCausalLM, whose jitted programs embed the weights as
    constants — identical HLO per engine. A session-local compilation
    cache makes every repeat a deserialize instead of a compile, keeping
    this module's wall time inside the tier-1 budget. The dir is a fresh
    tmp path per session, so nothing persists across runs (retrace-gate
    semantics elsewhere stay deterministic)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    d = str(tmp_path_factory.mktemp('xla_cache'))
    jax.config.update('jax_compilation_cache_dir', d)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    # jax decides ONCE whether a process uses its cache, on the first
    # compile: reset, or the directory set here is never looked at
    cc.reset_cache()
    yield
    # hand the worker back WITHOUT a live cache (as test_inference_aot
    # does): once initialized it serves every later test of the process,
    # and an executable it loaded, serialized again by `compilecache`,
    # cannot run ("Function ... not found": test_compilecache,
    # test_tenancy)
    jax.config.update('jax_compilation_cache_dir', None)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 1.0)
    cc.reset_cache()


def _lm(seed=0, **kw):
    kw.setdefault('vocab', 32)
    kw.setdefault('embed', 16)
    kw.setdefault('num_heads', 2)
    kw.setdefault('max_batch', 4)
    kw.setdefault('max_seq', 32)
    kw.setdefault('prompt_buckets', (4, 8))
    return TinyCausalLM.random(seed=seed, **kw)


def _tokens(resp):
    return [int(t) for t in resp.outputs['tokens']]


def _ref(lm, prompt, n):
    return [int(t) for t in lm.reference_decode(prompt, n)]


class _ConstDraft(serving.GenerativeSpec):
    """Draft that always proposes one constant token: with a constant the
    target never emits, every speculation is rejected (the reject-all
    boundary); with one it does emit, acceptance is partial."""

    def __init__(self, token, vocab, max_seq=32, max_batch=4,
                 prompt_buckets=(4, 8)):
        self.token = int(token)
        self.vocab = int(vocab)
        self.max_seq = int(max_seq)
        self.max_batch = int(max_batch)
        self.prompt_buckets = tuple(prompt_buckets)

    def init_paged_cache(self, num_pages, page_size):
        return paged_kv.create_paged_cache(1, num_pages, page_size, 1, 1)

    def _logits(self, prefix):
        return jnp.zeros(prefix + (self.vocab,)).at[..., self.token].set(1.0)

    def prefill_chunk(self, cache, block_row, tokens, start, length):
        return cache, self._logits((tokens.shape[0],))

    def verify_tokens(self, cache, block_tables, tokens, positions):
        return cache, self._logits(tuple(tokens.shape))


# ---------------------------------------------------------------------------
# allocator + prefix-cache bookkeeping
# ---------------------------------------------------------------------------

class TestPageBookkeeping:
    def test_allocator_freelist_refcounts_and_null_page(self):
        a = PageAllocator(5)                 # 4 usable, page 0 reserved
        assert a.usable == 4 and a.free_count() == 4
        pages = [a.alloc() for _ in range(4)]
        assert 0 not in pages                # null page never handed out
        with pytest.raises(PagesExhaustedError, match='grow num_pages'):
            a.alloc()
        a.incref(pages[0])
        a.decref(pages[0])
        assert a.free_count() == 0           # still referenced once
        a.decref(pages[0])
        assert a.free_count() == 1           # now actually freed
        p2 = a.alloc()
        assert p2 == pages[0]                # freelist reuse
        a.decref(pages[1])
        with pytest.raises(ValueError, match='decref of free page'):
            a.decref(pages[1])               # double free must raise

    def test_chain_hash_commits_to_whole_prefix(self):
        ps = 4
        a = chain_hashes(np.arange(8, dtype=np.int32), ps)
        b = chain_hashes(np.arange(8, dtype=np.int32), ps)
        assert a == b and len(a) == 2
        # same second page, different first page: digest MUST differ
        other = np.concatenate([np.array([9, 9, 9, 9], np.int32),
                                np.arange(4, 8, dtype=np.int32)])
        c = chain_hashes(other, ps)
        assert c[1] != a[1]
        # trailing partial page gets no digest (never shared)
        assert len(chain_hashes(np.arange(7, dtype=np.int32), ps)) == 1

    def test_prefix_cache_lru_eviction_spares_referenced_pages(self):
        a = PageAllocator(4)                 # 3 usable
        pc = PrefixCache(a)
        d1, d2 = b'digest-1', b'digest-2'
        p1, p2 = a.alloc(), a.alloc()
        pc.insert(d1, p1)
        pc.insert(d2, p2)
        a.decref(p1)                         # only the cache pins p1 now
        assert pc.lookup(d2) == p2           # p2: cache + caller + owner
        free_before = a.free_count()
        assert pc.evict_one()                # evicts p1 (LRU, unpinned)
        assert a.free_count() == free_before + 1
        assert pc.lookup(d1) is None
        # p2 is still referenced beyond the cache: never evicted
        a.decref(p2)                         # drop the original owner ref
        assert not pc.evict_one()            # caller ref from lookup remains
        a.decref(p2)
        assert pc.evict_one()


# ---------------------------------------------------------------------------
# scheduler: page-gated admission primitives
# ---------------------------------------------------------------------------

class TestPageGatedAdmission:
    def test_pop_ready_while_is_strict_fifo(self):
        q = AdmissionQueue('m', capacity=8)
        reqs = [Request('m', {'i': i}) for i in range(4)]
        for r in reqs:
            q.push(r)
        # predicate declines the SECOND request: nothing behind it pops
        ready, expired = q.pop_ready_while(
            lambda r: r.inputs['i'] != 1, max_n=4)
        assert [r.inputs['i'] for r in ready] == [0]
        assert len(q) == 3 and not expired

    def test_push_front_bypasses_capacity(self):
        q = AdmissionQueue('m', capacity=1)
        q.push(Request('m', {}))
        with pytest.raises(QueueFullError):
            q.push(Request('m', {}))
        q.push_front(Request('m', {'readmitted': True}))   # no shed
        ready, _ = q.pop_ready(1)
        assert ready[0].inputs.get('readmitted')

    def test_queue_full_error_carries_reason(self):
        err = QueueFullError('m', 4, reason='page_exhaustion')
        assert err.reason == 'page_exhaustion'
        assert 'page_exhaustion' in str(err)


# ---------------------------------------------------------------------------
# token-exactness: paged vs the no-cache oracle
# ---------------------------------------------------------------------------

class TestPagedExactness:
    def _serve(self, lm, prompts, lens, **register_kw):
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, **register_kw)
        futs = [ep.submit({'tokens': p}, max_new_tokens=n)
                for p, n in zip(prompts, lens)]
        eng.run_until_idle()
        return eng, [f.result(10) for f in futs]

    def test_paged_matches_reference_interleaved(self):
        lm = _lm(max_batch=2)
        prompts = [np.array([1, 2, 3], np.int32),
                   np.array([5, 6], np.int32),
                   np.array([7, 8, 9, 10, 11], np.int32),
                   np.array([4], np.int32)]
        lens = (6, 3, 4, 8)                 # mixed: forces join/leave churn
        _, paged = self._serve(lm, prompts, lens, page_size=4)
        for p, n, rp in zip(prompts, lens, paged):
            ref = _ref(lm, p, n)
            assert _tokens(rp) == ref, (p, _tokens(rp), ref)
        assert all(r.ok for r in paged)

    def test_page_reuse_after_free_stays_exact(self):
        # pool sized so the second wave MUST reuse the first wave's freed
        # pages; outputs must be untouched by the recycling
        lm = _lm(max_batch=2, max_seq=16)
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=4, num_pages=9,
                          prefix_cache=False)
        waves = []
        for wave in range(3):
            prompts = [np.array([1 + wave, 2, 3], np.int32),
                       np.array([6 + wave, 7], np.int32)]
            futs = [ep.submit({'tokens': p}, max_new_tokens=4)
                    for p in prompts]
            eng.run_until_idle()
            for p, f in zip(prompts, futs):
                assert _tokens(f.result(10)) == _ref(lm, p, 4)
            waves.append(True)
        alloc = eng._models['lm'].target.alloc
        # pages actually cycled: more allocations than the pool holds
        assert alloc.allocated_total > alloc.usable
        assert alloc.freed_total > 0

    def test_chunked_prefill_long_prompt_exact_and_interleaved(self):
        lm = _lm(max_batch=2, max_seq=64, prompt_buckets=(4, 8))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=4)
        long_p = np.arange(1, 25, dtype=np.int32)      # 24 > bucket 8
        short_p = np.array([3, 1], np.int32)
        f_long = ep.submit({'tokens': long_p}, max_new_tokens=4)
        f_short = ep.submit({'tokens': short_p}, max_new_tokens=2)
        eng.pump()                    # long admits chunk 1; short admits too
        runner = eng._models['lm']
        # the short request decodes WHILE the long one is still prefilling:
        # chunked prefill must not stall the decode batch
        assert any(s is not None and not s['ready'] for s in runner.slots)
        eng.run_until_idle()
        assert _tokens(f_long.result(10)) == _ref(lm, long_p, 4)
        assert _tokens(f_short.result(10)) == _ref(lm, short_p, 2)
        journal = list(runner.journal)
        steps = {(ev, rid): step for ev, rid, step in journal}
        # the short request finished before the long one left
        assert steps[('leave', f_short.request_id)] <= \
            steps[('leave', f_long.request_id)]


# ---------------------------------------------------------------------------
# prefix caching
# ---------------------------------------------------------------------------

class TestPrefixSharing:
    def test_prefix_hit_skips_recompute_and_stays_exact(self):
        lm = _lm(max_batch=4, max_seq=64, prompt_buckets=(4, 8, 16))
        sys_prompt = np.arange(1, 17, dtype=np.int32)  # 4 full pages @ ps=4

        def serve(prefix_cache):
            eng = ServingEngine()
            ep = eng.register('lm', generative=lm, page_size=4,
                              prefix_cache=prefix_cache)
            futs = []
            for i in range(6):
                p = np.concatenate([sys_prompt,
                                    np.array([20 + i], np.int32)])
                futs.append(ep.submit({'tokens': p}, max_new_tokens=3))
            eng.run_until_idle()
            outs = [_tokens(f.result(10)) for f in futs]
            return eng, outs

        eng_on, outs_on = serve(True)
        eng_off, outs_off = serve(False)
        assert outs_on == outs_off           # sharing never changes tokens
        st_on = eng_on.stats()['models']['lm']
        st_off = eng_off.stats()['models']['lm']
        # the acceptance criterion: shared-prefix pages are NOT recomputed
        assert st_on['prefill_tokens'] < st_off['prefill_tokens']
        assert st_on['prefix_hit_pages'] >= 4 * 5   # 5 later admits x 4 pages
        info = eng_on._models['lm'].kv_info()
        assert info['prefix_hit_rate'] > 0.5
        # and each hit admit is exact vs the oracle
        p = np.concatenate([sys_prompt, np.array([25], np.int32)])
        assert outs_on[5] == _ref(lm, p, 3)

    def test_cached_prefix_survives_owner_finishing(self):
        lm = _lm(max_batch=2, max_seq=64, prompt_buckets=(4, 8))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=4)
        shared = np.arange(1, 9, dtype=np.int32)       # 2 full pages
        f1 = ep.submit({'tokens': shared}, max_new_tokens=2)
        eng.run_until_idle()                 # owner admitted AND finished
        assert f1.result(10).ok
        before = eng.stats()['models']['lm']['prefill_tokens']
        f2 = ep.submit({'tokens': shared}, max_new_tokens=2)
        eng.run_until_idle()
        assert _tokens(f2.result(10)) == _ref(lm, shared, 2)
        computed = eng.stats()['models']['lm']['prefill_tokens'] - before
        # only the (recompute-last-token) tail was prefilled, not the pages
        assert computed <= 4


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------

class TestSpeculativeDecoding:
    def _exact(self, lm, draft, k, prompts, lens):
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=4, draft=draft,
                          draft_k=k)
        futs = [ep.submit({'tokens': p}, max_new_tokens=n)
                for p, n in zip(prompts, lens)]
        eng.run_until_idle()
        for p, n, f in zip(prompts, lens, futs):
            assert _tokens(f.result(10)) == _ref(lm, p, n), (p, n)
        return eng.stats()['models']['lm']

    def test_accept_all_draft_is_exact_and_fully_accepted(self):
        lm = _lm()
        prompts = [np.array([1, 2, 3], np.int32), np.array([5], np.int32)]
        st = self._exact(lm, lm, 3, prompts, (7, 5))   # draft == target
        assert st['spec_proposed'] > 0
        assert st['draft_acceptance'] == 1.0

    def test_reject_all_draft_is_exact_with_zero_acceptance(self):
        lm = _lm()
        prompt = np.array([1, 2, 3], np.int32)
        ref = _ref(lm, prompt, 8)
        bad = next(t for t in range(lm.vocab) if t not in ref)
        draft = _ConstDraft(bad, lm.vocab, max_seq=lm.max_seq)
        st = self._exact(lm, draft, 3, [prompt], (8,))
        assert st['spec_proposed'] > 0
        assert st['draft_acceptance'] == 0.0
        # reject-all still makes progress: one target token per round
        # (token 1 of 8 comes from prefill, the other 7 from decode)
        assert st['decode_tokens'] == 7

    def test_k1_boundary_exact(self):
        # k=1: one proposed token per round, accept-all regime (the
        # divergent k=1 mix rides the reject-all ConstDraft test's shape)
        lm = _lm()
        prompts = [np.array([1, 2, 3], np.int32), np.array([9], np.int32)]
        st = self._exact(lm, lm, 1, prompts, (6, 4))          # accept-all
        assert st['draft_acceptance'] == 1.0

    def test_divergent_draft_partial_acceptance_exact(self):
        lm = _lm()
        draft = _lm(seed=7)
        prompts = [np.array([1, 2, 3], np.int32),
                   np.array([5, 6], np.int32),
                   np.array([7, 8, 9, 10, 11], np.int32)]
        st = self._exact(lm, draft, 3, prompts, (8, 6, 9))
        assert 0.0 <= st['draft_acceptance'] <= 1.0
        # speculation batches fewer dispatch rounds than tokens emitted
        assert st['batches'] < st['decode_tokens']

    def test_speculation_stays_exact_across_preemption(self):
        # regression: a preempted sequence's generated tokens fold into
        # its re-admitted prompt; the spec path's position invariant must
        # not double-count them (it did: pos jumped by len(done) after
        # every round, skipping K/V positions and truncating output)
        lm = _lm(max_batch=4, prompt_buckets=(4, 8))
        draft = _lm(seed=7)
        eng = ServingEngine(queue_capacity=8)
        ep = eng.register('lm', generative=lm, page_size=4, num_pages=9,
                          max_concurrency=4, prefix_cache=False,
                          draft=draft, draft_k=3)
        prompts = [np.array([1 + i, 2, 3, 4, 5, 6], np.int32)
                   for i in range(4)]
        futs = [ep.submit({'tokens': p}, max_new_tokens=10)
                for p in prompts]
        eng.run_until_idle()
        st = eng.stats()['models']['lm']
        assert st['preemptions'] + st['decode_stalls'] > 0  # pressure real
        for p, f in zip(prompts, futs):
            r = f.result(10)
            assert r.ok
            assert _tokens(r) == _ref(lm, p, 10)
            assert len(r.outputs['tokens']) == 10

    def test_speculation_composes_with_prefix_and_chunking(self):
        lm = _lm(max_seq=64, prompt_buckets=(4, 8))
        draft = _lm(seed=3, max_seq=64, prompt_buckets=(4, 8))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=4, draft=draft,
                          draft_k=2)
        long_p = np.arange(1, 21, dtype=np.int32)       # chunked (20 > 8)
        f1 = ep.submit({'tokens': long_p}, max_new_tokens=5)
        eng.run_until_idle()
        assert _tokens(f1.result(10)) == _ref(lm, long_p, 5)
        f2 = ep.submit({'tokens': long_p}, max_new_tokens=5)  # prefix hit
        eng.run_until_idle()
        assert _tokens(f2.result(10)) == _ref(lm, long_p, 5)
        assert eng.stats()['models']['lm']['prefix_hit_pages'] > 0


# ---------------------------------------------------------------------------
# concurrency at fixed memory (the >=4x acceptance criterion)
# ---------------------------------------------------------------------------

class TestConcurrencyAtFixedMemory:
    def test_paged_sustains_4x_slot_concurrency(self):
        # slot baseline: max_batch=4 slots x max_seq=32 = 128 cached
        # positions. Paged at the SAME KV memory: 16 usable pages x 8
        # tokens = 128 positions — but 16 block-table rows.
        lm = _lm(max_batch=16, max_seq=32, prompt_buckets=(4, 8))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=8, num_pages=17,
                          max_concurrency=16, prefix_cache=False)
        futs = [ep.submit({'tokens': np.array([1 + i, 2, 3], np.int32)},
                          max_new_tokens=4) for i in range(16)]
        eng.pump()
        runner = eng._models['lm']
        active = sum(1 for s in runner.slots if s is not None)
        slot_baseline = 4                    # what [4, 32] slots could hold
        assert active >= 4 * slot_baseline, (active, slot_baseline)
        eng.run_until_idle()
        for i, f in enumerate(futs):
            p = np.array([1 + i, 2, 3], np.int32)
            assert _tokens(f.result(10)) == _ref(lm, p, 4)


# ---------------------------------------------------------------------------
# page exhaustion: stalls, preemption, shed attribution, doctor
# ---------------------------------------------------------------------------

class TestPageExhaustion:
    def test_pressure_preempts_and_completes_exactly(self):
        lm = _lm(max_batch=4, prompt_buckets=(4, 8))
        eng = ServingEngine(queue_capacity=8)
        ep = eng.register('lm', generative=lm, page_size=4, num_pages=7,
                          max_concurrency=4, prefix_cache=False)
        prompts = [np.array([1 + i, 2, 3, 4, 5], np.int32)
                   for i in range(4)]
        futs = [ep.submit({'tokens': p}, max_new_tokens=8) for p in prompts]
        eng.run_until_idle()
        for p, f in zip(prompts, futs):
            r = f.result(10)
            assert r.ok
            assert _tokens(r) == _ref(lm, p, 8)
        st = eng.stats()['models']['lm']
        # the pool (6 usable pages < 4 seqs x 4 pages) forced real pressure
        assert st['decode_stalls'] + st['preemptions'] > 0

    def test_sequence_that_can_never_fit_fails_not_livelocks(self):
        lm = _lm(max_batch=2, max_seq=32, prompt_buckets=(4, 8))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=4, num_pages=3,
                          prefix_cache=False)   # 2 usable pages = 8 positions
        f = ep.submit({'tokens': np.array([1, 2, 3, 4, 5, 6], np.int32)},
                      max_new_tokens=16)        # needs 22 positions
        eng.run_until_idle(max_steps=200)
        assert f._req.response is not None, "livelocked instead of failing"
        assert f._req.response.status == STATUS_ERROR
        with pytest.raises(RuntimeError, match='more KV pages'):
            f.result(10)

    def test_oversize_prompt_rejected_at_submit(self):
        lm = _lm(max_batch=2)
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=4, num_pages=3)
        with pytest.raises(ValueError, match='grow'):
            ep.submit({'tokens': np.arange(1, 16, dtype=np.int32)})

    def test_shed_attribution_distinguishes_pages_from_traffic(self):
        obs.enable()
        lm = _lm(max_batch=2, prompt_buckets=(4, 8))
        eng = ServingEngine(queue_capacity=2)
        ep = eng.register('lm', generative=lm, page_size=4, num_pages=3,
                          max_concurrency=2, prefix_cache=False)
        # two 8-token prompts: the first consumes both usable pages, the
        # second cannot be admitted -> page starvation backs up the queue
        for i in range(2):
            ep.submit({'tokens': np.array([1 + i, 2, 3, 4, 5, 6, 7, 8],
                                          np.int32)}, max_new_tokens=4)
        eng.pump()
        runner = eng._models['lm']
        assert runner.page_starved()
        ep.submit({'tokens': np.array([9, 2, 3], np.int32)})  # fills queue
        with pytest.raises(QueueFullError) as ei:
            ep.submit({'tokens': np.array([9, 2, 3], np.int32)})
        assert ei.value.reason == 'page_exhaustion'
        stats = eng.stats()
        assert stats['shed_page_exhaustion'] == 1
        assert stats['shed_queue_full'] == 0
        snap = obs.snapshot()
        assert snap['counters']['serving.shed.page_exhaustion'] == 1
        # a queue-full shed on a NON-starved model keeps the other label
        ep2 = eng.register('fast', generative=_lm(seed=2), page_size=4,
                           queue_capacity=1)
        ep2.submit({'tokens': np.array([1], np.int32)})
        with pytest.raises(QueueFullError) as ei2:
            ep2.submit({'tokens': np.array([2], np.int32)})
        assert ei2.value.reason == 'queue_full'
        assert eng.stats()['shed_queue_full'] == 1

    def test_doctor_names_page_exhaustion_not_overload(self):
        obs.enable()
        lm = _lm(max_batch=2, prompt_buckets=(4, 8))
        eng = ServingEngine(queue_capacity=2)
        ep = eng.register('lm', generative=lm, page_size=4, num_pages=3,
                          max_concurrency=2, prefix_cache=False)
        for i in range(2):
            ep.submit({'tokens': np.array([1 + i, 2, 3, 4, 5, 6, 7, 8],
                                          np.int32)}, max_new_tokens=4)
        eng.pump()
        for _ in range(3):                  # page-starved sheds
            try:
                ep.submit({'tokens': np.array([9], np.int32)})
            except QueueFullError:
                pass
        eng.run_until_idle()
        diagnoses = obs.diagnose(events=obs.event_log(),
                                 snapshot=obs.snapshot())
        causes = {d['cause'] for d in diagnoses}
        assert 'kv_page_exhaustion' in causes
        # overload counts ONLY non-page sheds: none here
        assert 'serving_overload' not in causes
        d = next(d for d in diagnoses if d['cause'] == 'kv_page_exhaustion')
        assert 'num_pages' in d['fix']
        assert 'replicas' in d['fix']       # ...will NOT help

    def test_doctor_cli_surfaces_kv_page_exhaustion(self, tmp_path):
        obs.enable()
        obs.event('serving.shed', model='lm', request=1,
                  reason='page_exhaustion')
        obs.event('serving.page_exhausted', model='lm', where='decode',
                  pages_free=0)
        obs.event('serving.preempt', model='lm', request=2, tokens_so_far=3)
        log = tmp_path / 'events.jsonl'
        obs.dump_jsonl(str(log))
        import subprocess
        import sys
        out = subprocess.run(
            [sys.executable, 'tools/doctor.py', str(log)],
            capture_output=True, text=True)
        assert 'kv_page_exhaustion' in out.stdout


# ---------------------------------------------------------------------------
# retrace gate: the whole paged feature set compiles NOTHING after warmup
# ---------------------------------------------------------------------------

class TestPagedRetraceGate:
    def test_zero_compiles_across_paged_chunked_and_speculative(self):
        obs.enable()
        obs.install_jax_hooks()
        lm = _lm(max_batch=4, max_seq=64, prompt_buckets=(4, 8))
        draft = _lm(seed=5, max_seq=64, prompt_buckets=(4, 8))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=4, draft=draft,
                          draft_k=3, max_concurrency=4)
        eng.warmup()
        before = obs.snapshot()['counters'].get('jax.compiles', 0)
        rng = np.random.RandomState(1)
        futs = []
        for _ in range(24):
            n = int(rng.randint(1, 24))    # includes chunked (> bucket 8)
            futs.append(ep.submit(
                {'tokens': rng.randint(1, 30, size=n).astype(np.int32)},
                max_new_tokens=int(rng.randint(1, 6))))
        eng.run_until_idle()
        assert all(f.result(10).ok for f in futs)
        after = obs.snapshot()['counters'].get('jax.compiles', 0)
        # paged decode + chunked prefill + speculative verify: 0 new
        # compiles across varied prompts, lengths, joins and leaves
        assert after == before

    def test_warmup_compiles_the_whole_closed_set(self):
        obs.enable()
        obs.install_jax_hooks()
        lm = _lm()
        eng = ServingEngine()
        eng.register('lm', generative=lm, page_size=4, draft=_lm(seed=4),
                      draft_k=2)
        programs = eng.warmup()['lm']
        # per-bucket prefills x2 sides + decode + draft decode + propose
        # + verify
        assert programs == 2 * len(lm.prompt_buckets) + 4


# ---------------------------------------------------------------------------
# lifecycle: eviction with pages, stats/telemetry surface
# ---------------------------------------------------------------------------

class TestPagedLifecycle:
    def test_stop_evicts_resident_and_preempted_with_partials(self):
        lm = _lm(max_batch=2, prompt_buckets=(4,))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=4)
        f = ep.submit({'tokens': np.array([1, 2], np.int32)},
                      max_new_tokens=64)
        eng.pump()                          # prefill done: slot-resident
        eng.stop()
        with pytest.raises(RuntimeError, match='mid-decode'):
            f.result(1)
        resp = f._req.response
        assert resp.status == STATUS_ERROR
        assert len(resp.outputs['tokens']) >= 1
        alloc = eng._models['lm'].target.alloc
        assert alloc.used_count() == 0       # pages all returned

    def test_deadline_mid_decode_returns_partial_tokens(self):
        lm = _lm(max_batch=2, prompt_buckets=(4,))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=4)
        f = ep.submit({'tokens': np.array([1, 2], np.int32)},
                      max_new_tokens=64, deadline_ms=1)
        eng.pump()
        import time
        time.sleep(0.01)
        eng.run_until_idle()
        r = f.result(10)
        assert r.status == STATUS_DEADLINE
        assert r.outputs is not None and len(r.outputs['tokens']) >= 1

    def test_model_error_containment_matches_slot_runner(self):
        lm = _lm(max_batch=2, prompt_buckets=(4,))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=4)
        runner = eng._models['lm']
        orig_prefill, orig_decode = runner._prefill, runner._decode

        def boom(*a, **kw):
            raise RuntimeError('kaboom')

        runner._prefill = boom
        f = ep.submit({'tokens': np.array([1, 2], np.int32)})
        eng.pump()
        with pytest.raises(RuntimeError, match='kaboom'):
            f.result(5)
        assert runner.slots == [None] * 2
        assert runner.target.alloc.used_count() == 0

        runner._prefill = orig_prefill
        f2 = ep.submit({'tokens': np.array([1, 2], np.int32)},
                       max_new_tokens=8)
        eng.pump()
        runner._decode = boom
        eng.pump()
        with pytest.raises(RuntimeError, match='kaboom'):
            f2.result(5)
        assert runner.slots == [None] * 2
        assert runner.target.alloc.used_count() == 0

        runner._decode = orig_decode
        f3 = ep.submit({'tokens': np.array([1, 2], np.int32)},
                       max_new_tokens=2)
        eng.run_until_idle()
        assert f3.result(10).ok

    def test_register_validates_paged_knobs(self):
        eng = ServingEngine()
        lm = _lm()
        with pytest.raises(ValueError, match='only to'):
            eng.register('c', predict_fn=lambda f: f['x'],
                         example={'x': np.zeros((4,), np.float32)},
                         num_pages=8)
        with pytest.raises(ValueError, match='draft max_seq'):
            eng.register('d', generative=lm,
                         draft=_lm(max_seq=lm.max_seq // 2))
        with pytest.raises(ValueError, match='draft_k'):
            eng.register('e', generative=lm, draft=_lm(), draft_k=0)

    @pytest.mark.parametrize('kind', ['slot', 'paged'])
    def test_register_rejects_kv_cache(self, kind):
        # one cache, so no option names it: Python's own TypeError
        eng = ServingEngine()
        with pytest.raises(TypeError, match='kv_cache'):
            eng.register('lm', generative=_lm(), kv_cache=kind)
        assert 'lm' not in eng._models

    def test_kv_telemetry_and_dump_columns(self, tmp_path):
        obs.enable()
        lm = _lm(max_seq=64, prompt_buckets=(4, 8))
        draft = _lm(seed=5, max_seq=64, prompt_buckets=(4, 8))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm, page_size=4, draft=draft,
                          draft_k=2)
        shared = np.arange(1, 9, dtype=np.int32)
        for i in range(4):
            ep.submit({'tokens': np.concatenate(
                [shared, np.array([20 + i], np.int32)])}, max_new_tokens=3)
        eng.run_until_idle()
        snap = obs.snapshot()
        assert 'serving.kv.page_utilization' in snap['gauges']
        assert 'serving.kv.prefix_hit_rate' in snap['gauges']
        assert snap['counters'].get('serving.spec.proposed', 0) > 0
        log = tmp_path / 'events.jsonl'
        obs.dump_jsonl(str(log))
        import sys
        sys.path.insert(0, 'tools')
        try:
            import telemetry_dump
        finally:
            sys.path.pop(0)
        summary = telemetry_dump.serving_summary(
            telemetry_dump.load_events(str(log))[0])
        assert summary['page_utilization'] is not None
        assert summary['prefix_hit_rate'] is not None
        assert summary['draft_acceptance'] is not None
        rendered = telemetry_dump.render_serving(summary)
        assert 'paged kv' in rendered
        assert 'draft acceptance' in rendered

"""Serving runtime: bucket selection, continuous batching join/leave,
deadlines + load shedding, KV-cache correctness, retrace flatness.

Everything runs on CPU with the engine in manual-pump mode (deterministic)
except the threaded-mode smoke which exercises the worker thread + bounded
client waits.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.resilience import faultinject as fi
from paddle_tpu.resilience.watchdog import WatchdogTimeout
from paddle_tpu.serving import (BucketSpec, QueueFullError, ServingEngine,
                                TinyCausalLM, pad_to_bucket, select_bucket,
                                stack_examples)
from paddle_tpu.serving.scheduler import (STATUS_DEADLINE, STATUS_ERROR,
                                          STATUS_OK)

pytestmark = pytest.mark.serving


def _mlp_fn(w):
    def predict(feeds):
        return feeds['x'] @ w
    return predict


def _example(n=8):
    return {'x': np.zeros((n,), np.float32)}


@pytest.fixture(autouse=True)
def _telemetry_off():
    yield
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------------------
# bucket-shape selection
# ---------------------------------------------------------------------------

class TestBucketing:
    def test_select_bucket_picks_smallest_fit(self):
        assert select_bucket(1, (1, 2, 4)) == 1
        assert select_bucket(3, (1, 2, 4)) == 4
        assert select_bucket(4, (1, 2, 4)) == 4

    def test_select_bucket_rejects_oversize_and_nonpositive(self):
        with pytest.raises(ValueError, match='exceeds the largest bucket'):
            select_bucket(5, (1, 2, 4))
        with pytest.raises(ValueError):
            select_bucket(0, (1, 2, 4))

    def test_pad_to_bucket_pads_and_never_truncates(self):
        a = np.arange(3)
        out = pad_to_bucket(a, 8)
        assert out.shape == (8,) and list(out[:3]) == [0, 1, 2]
        assert not out[3:].any()
        assert pad_to_bucket(a, 3) is a            # already at bucket
        with pytest.raises(ValueError, match='exceeds bucket'):
            pad_to_bucket(np.arange(9), 8)

    def test_stack_examples_shape_mismatch_rejected(self):
        good = [np.zeros((4,), np.float32)] * 2
        assert stack_examples(good, 4).shape == (4, 4)
        with pytest.raises(ValueError, match='registered example spec'):
            stack_examples([np.zeros((4,), np.float32),
                            np.zeros((5,), np.float32)], 4)

    def test_bucket_spec_sorted_and_validated(self):
        spec = BucketSpec((8, 1, 4, 4))
        assert spec.batch_buckets == (1, 4, 8)
        assert spec.max_batch == 8
        with pytest.raises(ValueError):
            BucketSpec(())
        with pytest.raises(ValueError):
            BucketSpec((0, 2))


# ---------------------------------------------------------------------------
# one-shot dynamic batching
# ---------------------------------------------------------------------------

class TestBatchServing:
    def _engine(self, buckets=(1, 2, 4), capacity=32):
        w = np.eye(8, dtype=np.float32) * 2.0
        eng = ServingEngine(queue_capacity=capacity)
        ep = eng.register('m', predict_fn=_mlp_fn(w), example=_example(),
                          bucket_spec=BucketSpec(buckets))
        return eng, ep

    def test_batched_results_match_per_request_inputs(self):
        eng, ep = self._engine()
        futs = [ep.submit({'x': np.full((8,), i, np.float32)})
                for i in range(5)]
        eng.run_until_idle()
        for i, f in enumerate(futs):
            r = f.result(timeout=10)
            assert r.ok
            assert np.allclose(r.outputs, 2.0 * i)

    def test_requests_pack_into_buckets(self):
        eng, ep = self._engine(buckets=(1, 2, 4))
        for _ in range(5):
            ep.submit(_example())
        eng.run_until_idle()
        stats = eng.stats()['models']['m']
        # 5 queued requests: one bucket-4 batch + one bucket-1 batch
        assert stats['batches'] == 2
        assert stats['completed'] == 5

    def test_input_validation_rejects_wrong_shape_at_submit(self):
        eng, ep = self._engine()
        with pytest.raises(ValueError, match='closed'):
            ep.submit({'x': np.zeros((9,), np.float32)})
        with pytest.raises(ValueError, match='missing inputs'):
            ep.submit({'y': np.zeros((8,), np.float32)})

    def test_model_exception_fails_batch_not_engine(self):
        eng = ServingEngine()

        def boom(feeds):
            raise RuntimeError('kernel panic')
        ep = eng.register('b', predict_fn=boom, example=_example(),
                          jit_compile=False)
        f = ep.submit(_example())
        eng.run_until_idle()
        with pytest.raises(RuntimeError, match='kernel panic'):
            f.result(timeout=10)
        # engine still serves other models afterwards
        ep2 = eng.register('ok', predict_fn=_mlp_fn(
            np.eye(8, dtype=np.float32)), example=_example())
        f2 = ep2.submit(_example())
        eng.run_until_idle()
        assert f2.result(timeout=10).ok

    def test_multi_tenant_round_robin_serves_both(self):
        w = np.eye(8, dtype=np.float32)
        eng = ServingEngine()
        ep_a = eng.register('a', predict_fn=_mlp_fn(w), example=_example())
        ep_b = eng.register('b', predict_fn=_mlp_fn(3 * w),
                            example=_example())
        fa = [ep_a.submit({'x': np.ones((8,), np.float32)})
              for _ in range(3)]
        fb = [ep_b.submit({'x': np.ones((8,), np.float32)})
              for _ in range(3)]
        eng.run_until_idle()
        assert all(np.allclose(f.result(10).outputs, 1.0) for f in fa)
        assert all(np.allclose(f.result(10).outputs, 3.0) for f in fb)

    def test_threaded_mode_and_engine_stop(self):
        eng, ep = self._engine()
        eng.warmup()
        eng.start()
        try:
            r = ep.predict({'x': np.ones((8,), np.float32)}, timeout=30)
            assert r.ok and np.allclose(r.outputs, 2.0)
        finally:
            eng.stop()
        assert not eng.alive()
        # a stopped engine strands no client: result() raises promptly
        f = ep.submit(_example())
        with pytest.raises(WatchdogTimeout):
            f.result(timeout=0.5)


# ---------------------------------------------------------------------------
# deadline expiry + load shedding under an injected slow model
# ---------------------------------------------------------------------------

class TestDeadlinesAndShedding:
    def test_queue_full_sheds_429_style(self):
        eng = ServingEngine(queue_capacity=2)
        ep = eng.register('s', predict_fn=_mlp_fn(
            np.eye(8, dtype=np.float32)), example=_example(),
            bucket_spec=BucketSpec((1,)))
        ep.submit(_example())
        ep.submit(_example())
        with pytest.raises(QueueFullError, match='shed'):
            ep.submit(_example())
        assert eng.stats()['shed'] == 1
        eng.run_until_idle()

    def test_expired_request_never_runs_under_slow_model(self):
        # slow_rank-style delay on the serving path: the jitted fn is
        # wrapped host-side so every batch stalls, and queued requests
        # blow their deadline before a slot frees up
        slow = fi.slow_model(jax.jit(_mlp_fn(np.eye(8, dtype=np.float32))),
                             delay_s=0.08)
        eng = ServingEngine(queue_capacity=8)
        ep = eng.register('slow', predict_fn=slow, example=_example(),
                          bucket_spec=BucketSpec((1,)), jit_compile=False)
        f_live = ep.submit(_example())                     # no deadline
        f_dead = ep.submit(_example(), deadline_ms=20)     # dies in queue
        eng.pump()              # runs f_live (80ms); f_dead expires queued
        eng.run_until_idle()
        assert f_live.result(10).ok
        r = f_dead.result(10)
        assert r.status == STATUS_DEADLINE and r.outputs is None
        stats = eng.stats()['models']['slow']
        assert stats['expired'] == 1
        # the expired request consumed NO batch: only f_live ran
        assert stats['batches'] == 1

    def test_deadline_with_load_shed_combined(self):
        slow = fi.slow_model(jax.jit(_mlp_fn(np.eye(8, dtype=np.float32))),
                             delay_s=0.05)
        eng = ServingEngine(queue_capacity=2)
        ep = eng.register('slow', predict_fn=slow, example=_example(),
                          bucket_spec=BucketSpec((1,)), jit_compile=False)
        futs = [ep.submit(_example(), deadline_ms=15) for _ in range(2)]
        shed = 0
        try:
            ep.submit(_example(), deadline_ms=15)
        except QueueFullError:
            shed = 1
        time.sleep(0.03)        # both queued requests expire
        eng.run_until_idle()
        statuses = {f.result(10).status for f in futs}
        assert statuses == {STATUS_DEADLINE}
        assert shed == 1 and eng.stats()['shed'] == 1


# ---------------------------------------------------------------------------
# continuous batching: join/leave ordering + KV-cache correctness
# ---------------------------------------------------------------------------

class TestContinuousBatching:
    def _lm(self, **kw):
        kw.setdefault('max_batch', 2)
        kw.setdefault('max_seq', 32)
        kw.setdefault('prompt_buckets', (4, 8))
        return TinyCausalLM.random(vocab=32, embed=16, num_heads=2, **kw)

    def test_join_leave_ordering_iteration_level(self):
        lm = self._lm()
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm)
        f1 = ep.submit({'tokens': np.array([1, 2, 3], np.int32)},
                       max_new_tokens=6)
        f2 = ep.submit({'tokens': np.array([5, 6], np.int32)},
                       max_new_tokens=2)
        f3 = ep.submit({'tokens': np.array([7], np.int32)},
                       max_new_tokens=2)
        eng.run_until_idle()
        for f in (f1, f2, f3):
            assert f.result(10).ok
        journal = list(eng._models['lm'].journal)
        r1, r2, r3 = f1.request_id, f2.request_id, f3.request_id
        steps = {(ev, rid): step for ev, rid, step in journal}
        # r1+r2 joined the first iteration; r3 had to wait (2 slots)
        assert steps[('join', r1)] == steps[('join', r2)]
        # short r2 left mid-flight, freeing the slot r3 then joined —
        # while r1 was STILL decoding (left strictly later): that is
        # iteration-level continuous batching, not batch-at-a-time
        assert steps[('leave', r2)] < steps[('join', r3)]
        assert steps[('leave', r1)] > steps[('join', r3)]

    def test_kv_cache_decode_matches_uncached_reference(self):
        lm = self._lm()
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm)
        prompts = [np.array([1, 2, 3], np.int32),
                   np.array([5, 6], np.int32),
                   np.array([7, 8, 9, 10, 11], np.int32)]
        lens = (6, 3, 4)
        futs = [ep.submit({'tokens': p}, max_new_tokens=n)
                for p, n in zip(prompts, lens)]
        eng.run_until_idle()
        for p, n, f in zip(prompts, lens, futs):
            got = list(f.result(10).outputs['tokens'])
            ref = list(lm.reference_decode(p, n))
            # token-exact even though requests shared slots/cache and
            # joined/left at different iterations
            assert got == ref, (p, got, ref)

    def test_spec_with_only_the_paged_contract_serves(self):
        lm = self._lm()

        class PagedOnly(serving.GenerativeSpec):
            """The four methods of the contract and nothing else."""
            max_batch, max_seq = lm.max_batch, lm.max_seq
            prompt_buckets = lm.prompt_buckets

            init_paged_cache = staticmethod(lm.init_paged_cache)
            prefill_chunk = staticmethod(lm.prefill_chunk)
            decode_paged = staticmethod(lm.decode_paged)
            verify_tokens = staticmethod(lm.verify_tokens)

        for gone in ('init_cache', 'prefill', 'decode'):
            assert not hasattr(serving.GenerativeSpec, gone), gone
        eng = ServingEngine()
        ep = eng.register('lm', generative=PagedOnly())
        prompts = [np.array([1, 2, 3], np.int32), np.array([5, 6], np.int32)]
        lens = (6, 3)                 # the short one leaves mid-flight
        futs = [ep.submit({'tokens': p}, max_new_tokens=n)
                for p, n in zip(prompts, lens)]
        eng.run_until_idle()
        for p, n, f in zip(prompts, lens, futs):
            assert list(f.result(10).outputs['tokens']) == \
                list(lm.reference_decode(p, n))

    def test_eos_stops_decode_early(self):
        lm = self._lm()
        prompt = np.array([1, 2, 3], np.int32)
        ref = lm.reference_decode(prompt, 8)
        eos = int(ref[1])             # a token the model will emit
        lm.eos_id = eos
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm)
        f = ep.submit({'tokens': prompt}, max_new_tokens=8)
        eng.run_until_idle()
        out = list(f.result(10).outputs['tokens'])
        # stopped AT the first eos occurrence (greedy models may emit the
        # same token at step 0 and 1 — cut at whichever comes first)
        assert out == ref[:ref.index(eos) + 1]

    def test_generative_deadline_returns_partial_tokens(self):
        lm = self._lm()
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm)
        f = ep.submit({'tokens': np.array([1, 2], np.int32)},
                      max_new_tokens=64, deadline_ms=1)
        eng.pump()                    # prefill happens, then deadline hits
        time.sleep(0.01)
        eng.run_until_idle()
        r = f.result(10)
        assert r.status == STATUS_DEADLINE
        assert r.outputs is not None and len(r.outputs['tokens']) >= 1

    def test_prompt_validation(self):
        eng = ServingEngine()
        ep = eng.register('lm', generative=self._lm())
        with pytest.raises(ValueError, match='non-empty'):
            ep.submit({'tokens': np.array([], np.int32)})
        # chunked prefill lifts the per-bucket cap: 9 > largest bucket (8)
        # is admissible now; the sequence BUDGET (max_seq) still binds
        f = ep.submit({'tokens': np.arange(1, 10, dtype=np.int32)},
                      max_new_tokens=2)
        eng.run_until_idle()
        assert f.result(10).ok
        with pytest.raises(ValueError, match='max_seq'):
            ep.submit({'tokens': np.arange(32, dtype=np.int32)})


# ---------------------------------------------------------------------------
# retrace flatness: steady-state traffic compiles NOTHING
# ---------------------------------------------------------------------------

class TestRetraceFlatness:
    def _compiles(self):
        return obs.snapshot()['counters'].get('jax.compiles', 0)

    def test_steady_state_zero_new_compiles_one_shot(self):
        obs.enable()
        obs.install_jax_hooks()
        w = np.eye(8, dtype=np.float32)
        eng = ServingEngine(queue_capacity=512)
        ep = eng.register('m', predict_fn=_mlp_fn(w), example=_example(),
                          bucket_spec=BucketSpec((1, 2, 4)))
        eng.warmup()
        before = self._compiles()
        rng = np.random.RandomState(0)
        futs = []
        for i in range(200):
            futs.append(ep.submit({'x': rng.randn(8).astype(np.float32)}))
            if i % 3 == 0:        # interleave pumping: varied batch sizes
                eng.pump()
        eng.run_until_idle()
        assert all(f.result(10).ok for f in futs)
        assert eng.stats()['models']['m']['completed'] == 200
        # the whole point of bucketing: warmup compiled everything,
        # 200 requests of steady-state traffic compiled NOTHING
        assert self._compiles() == before

    def test_steady_state_zero_new_compiles_generative(self):
        obs.enable()
        obs.install_jax_hooks()
        lm = TinyCausalLM.random(vocab=32, embed=16, num_heads=2,
                                 max_batch=2, max_seq=32,
                                 prompt_buckets=(4, 8))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm)
        eng.warmup()
        before = self._compiles()
        rng = np.random.RandomState(1)
        futs = [ep.submit(
            {'tokens': rng.randint(1, 30, size=rng.randint(1, 8)
                                   ).astype(np.int32)},
            max_new_tokens=int(rng.randint(1, 5))) for _ in range(12)]
        eng.run_until_idle()
        assert all(f.result(10).ok for f in futs)
        assert self._compiles() == before

    def test_program_cache_hits_counted_for_program_models(self):
        obs.enable()
        import paddle_tpu.static as static
        paddle.enable_static()
        try:
            main = static.Program()
            startup = static.Program()
            with static.program_guard(main, startup):
                x = static.data('x', shape=[-1, 4], dtype='float32')
                y = paddle.matmul(x, paddle.to_tensor(
                    np.eye(4, dtype=np.float32)))
            exe = static.Executor()
            eng = ServingEngine()
            ep = eng.register('prog', program=(main, ['x'], [y]),
                              executor=exe,
                              example={'x': np.zeros((4,), np.float32)},
                              bucket_spec=BucketSpec((1, 2)))
            eng.warmup()
            h0 = obs.snapshot()['counters'].get(
                'executor.program_cache.hits', 0)
            m0 = obs.snapshot()['counters'].get(
                'executor.program_cache.misses', 0)
            futs = [ep.submit({'x': np.ones((4,), np.float32)})
                    for _ in range(6)]
            eng.run_until_idle()
            assert all(f.result(10).ok for f in futs)
            hits = obs.snapshot()['counters'].get(
                'executor.program_cache.hits', 0) - h0
            misses = obs.snapshot()['counters'].get(
                'executor.program_cache.misses', 0) - m0
            # every steady-state batch hit the warm program cache
            assert hits >= 1
            assert misses == 0
        finally:
            paddle.disable_static()


# ---------------------------------------------------------------------------
# engine lifecycle + registration validation
# ---------------------------------------------------------------------------

class TestEngineLifecycle:
    def test_stop_completes_in_flight_generative_with_partial_tokens(self):
        lm = TinyCausalLM.random(vocab=32, embed=16, num_heads=2,
                                 max_batch=2, max_seq=32,
                                 prompt_buckets=(4,))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm)
        f = ep.submit({'tokens': np.array([1, 2], np.int32)},
                      max_new_tokens=64)
        eng.pump()                     # prefill: request now slot-resident
        eng.stop()                     # must evict, not strand, the client
        with pytest.raises(RuntimeError, match='mid-decode'):
            f.result(1)
        resp = f._req.response
        assert resp.status == STATUS_ERROR
        assert len(resp.outputs['tokens']) >= 1    # partial output kept
        journal = list(eng._models['lm'].journal)
        assert ('leave', f.request_id, journal[-1][2]) == journal[-1]

    def test_batchless_output_fails_batch_not_engine(self):
        # a predict_fn returning an output with NO leading batch axis is a
        # model bug: the batch must complete as errors, the worker survives
        eng = ServingEngine()
        ep = eng.register('sum', predict_fn=lambda f: f['x'].sum(),
                          example=_example(), bucket_spec=BucketSpec((1,)))
        eng.warmup()                   # never slices, so warmup passes
        f = ep.submit(_example())
        eng.run_until_idle()           # must not raise out of pump()
        with pytest.raises(Exception):
            f.result(5)
        assert f._req.response.status == STATUS_ERROR
        f2 = ep.submit(_example())     # engine still serves afterwards
        eng.run_until_idle()
        with pytest.raises(Exception):
            f2.result(5)
        assert eng.stats()['models']['sum']['errors'] == 2

    def test_generative_model_error_fails_requests_not_engine(self):
        lm = TinyCausalLM.random(vocab=32, embed=16, num_heads=2,
                                 max_batch=2, max_seq=32,
                                 prompt_buckets=(4,))
        eng = ServingEngine()
        ep = eng.register('lm', generative=lm)
        runner = eng._models['lm']
        orig_prefill, orig_decode = runner._prefill, runner._decode

        def boom(*a, **kw):
            raise RuntimeError('kaboom')

        # prefill bug: the request errors, the slot stays free
        runner._prefill = boom
        f = ep.submit({'tokens': np.array([1, 2], np.int32)})
        eng.pump()
        with pytest.raises(RuntimeError, match='kaboom'):
            f.result(5)
        assert runner.slots == [None] * 2

        # decode bug: every co-batched request errors, slots are vacated
        runner._prefill = orig_prefill
        f2 = ep.submit({'tokens': np.array([1, 2], np.int32)},
                       max_new_tokens=8)
        eng.pump()                     # prefill ok, slot resident
        runner._decode = boom
        eng.pump()
        with pytest.raises(RuntimeError, match='kaboom'):
            f2.result(5)
        assert runner.slots == [None] * 2

        # the engine survived both: a healthy request still completes
        runner._decode = orig_decode
        f3 = ep.submit({'tokens': np.array([1, 2], np.int32)},
                       max_new_tokens=2)
        eng.run_until_idle()
        assert f3.result(10).ok

    def test_register_rejects_kwargs_foreign_to_the_model_kind(self):
        eng = ServingEngine()
        lm = TinyCausalLM.random(vocab=32, embed=16, num_heads=2,
                                 max_batch=2, max_seq=16,
                                 prompt_buckets=(4,))
        with pytest.raises(ValueError, match='do not apply to'):
            eng.register('lm', generative=lm, example=_example())
        with pytest.raises(ValueError, match='quantize= applies only'):
            eng.register('m',
                         predict_fn=_mlp_fn(np.eye(8, dtype=np.float32)),
                         example=_example(), quantize='int8')

    def test_multi_input_layer_binds_feeds_by_parameter_name(self):
        class TwoIn(paddle.nn.Layer):
            def forward(self, x, y):
                return x + 2.0 * y

        eng = ServingEngine()
        # feed names match forward's params: binds by name, not key order
        ep = eng.register('two', layer=TwoIn(),
                          example={'x': np.zeros((4,), np.float32),
                                   'y': np.zeros((4,), np.float32)})
        a = np.arange(4, dtype=np.float32)
        b = np.full((4,), 10.0, np.float32)
        f = ep.submit({'x': a, 'y': b})
        eng.run_until_idle()
        np.testing.assert_allclose(np.asarray(f.result(10).outputs),
                                   a + 2.0 * b)

        # feeds that skip a middle parameter still bind by NAME: a
        # positional call would hand `mask` to `kind` (BertModel's
        # input_ids + attention_mask, found bringing serving up on the chip)
        class Skips(paddle.nn.Layer):
            def forward(self, x, kind=None, mask=None):
                assert kind is None
                return x * mask

        ep = eng.register('skips', layer=Skips(),
                          example={'x': np.zeros((4,), np.float32),
                                   'mask': np.zeros((4,), np.float32)})
        f = ep.submit({'x': a, 'mask': b})
        eng.run_until_idle()
        np.testing.assert_allclose(np.asarray(f.result(10).outputs), a * b)
        # names that DON'T match the signature cannot bind unambiguously
        with pytest.raises(ValueError, match='bind unambiguously'):
            eng.register('bad', layer=TwoIn(),
                         example={'p': np.zeros((4,), np.float32),
                                  'q': np.zeros((4,), np.float32)})


# ---------------------------------------------------------------------------
# telemetry surface
# ---------------------------------------------------------------------------

class TestServingTelemetry:
    def test_counters_histograms_and_events_emitted(self, tmp_path):
        obs.enable()
        w = np.eye(8, dtype=np.float32)
        eng = ServingEngine()
        ep = eng.register('m', predict_fn=_mlp_fn(w), example=_example())
        futs = [ep.submit(_example()) for _ in range(3)]
        eng.run_until_idle()
        assert all(f.result(10).ok for f in futs)
        snap = obs.snapshot()
        assert snap['counters']['serving.requests'] >= 3
        assert snap['counters']['serving.completed'] >= 3
        assert snap['counters']['serving.status.ok'] >= 3
        assert snap['histograms']['serving.latency_ms']['count'] >= 3
        assert snap['histograms']['serving.batch_occupancy']['count'] >= 1
        evs = [e for e in obs.event_log() if e['ev'] == 'serving.request']
        assert len(evs) >= 3 and evs[0]['model'] == 'm'
        # telemetry_dump --serving summarizes the request events
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
        assert summary['requests'] >= 3
        assert summary['by_status'].get('ok', 0) >= 3
        assert 'p50_latency_ms' in summary

    def test_expired_requests_report_queue_wait(self):
        eng = ServingEngine()
        ep = eng.register('m', predict_fn=_mlp_fn(
            np.eye(8, dtype=np.float32)), example=_example())
        f = ep.submit(_example(), deadline_ms=1)
        time.sleep(0.01)
        eng.run_until_idle()
        r = f.result(10)
        assert r.status == STATUS_DEADLINE
        # expired requests spent their whole life queued: queue_ms must
        # reflect that, not default to 0
        assert r.queue_ms > 0

    def test_stats_surface_always_on_without_telemetry(self):
        # engine stats work with telemetry disabled (plain tallies)
        assert not obs.enabled()
        eng = ServingEngine()
        ep = eng.register('m', predict_fn=_mlp_fn(
            np.eye(8, dtype=np.float32)), example=_example())
        f = ep.submit(_example())
        eng.run_until_idle()
        assert f.result(10).ok
        s = eng.stats()
        assert s['submitted'] == 1
        assert s['models']['m']['completed'] == 1


class TestShedCounterRace:
    """Regression for the GC001 finding on ServingEngine's shed tallies:
    submit() runs on arbitrary client threads while stats()/health probes
    read the counters, so the += sites must sit under engine._lock. The
    schedule is forced with faultinject.hold_lock — no sleep-and-hope."""

    def test_shed_accounting_serialized_under_engine_lock(self):
        eng = ServingEngine(queue_capacity=1)
        ep = eng.register('s', predict_fn=_mlp_fn(
            np.eye(8, dtype=np.float32)), example=_example(),
            bucket_spec=BucketSpec((1,)))
        ep.submit(_example())   # fill the admission queue
        with fi.hold_lock(eng._lock):
            # the racing submit sheds immediately (queue full) and must
            # park at the counter critical section while we own the guard
            racer = fi.RacingCall(ep.submit, _example())
            assert racer.blocked(), \
                "shed accounting ran outside engine._lock"
        with pytest.raises(QueueFullError):
            racer.join()
        s = eng.stats()
        assert s['shed'] == 1
        assert s['shed_queue_full'] == 1
        assert s['shed_page_exhaustion'] == 0
        eng.run_until_idle()

    def test_submitted_counter_serialized_under_engine_lock(self):
        eng = ServingEngine(queue_capacity=4)
        ep = eng.register('s', predict_fn=_mlp_fn(
            np.eye(8, dtype=np.float32)), example=_example(),
            bucket_spec=BucketSpec((1,)))
        with fi.hold_lock(eng._lock):
            # _cond wraps _lock, so the post-admission bookkeeping parks
            racer = fi.RacingCall(ep.submit, _example())
            assert racer.blocked(), \
                "submitted bookkeeping ran outside engine._cond"
        racer.join()
        assert eng.stats()['submitted'] == 1
        eng.run_until_idle()

"""Cost explorer + flight recorder acceptance tests (ISSUE 13, marker
``obs``).

Covers: the cost ledger populated from all three compile paths (Executor
program cache, ``engine.build_train_step``, serving runner warmup) with
nonzero ``cost_analysis``/``memory_analysis`` numbers that stay stable
across cache hits (``jax.compiles`` flat — no recompiles added), the
roofline estimate, the ``/costs`` endpoint slice and ``telemetry_dump
--costs`` table; one serving request rendering as a connected async flow
in the merged Chrome trace; the SLO tracker + ``slo_burn`` and
``memory_pressure`` doctor detectors (and their ``--fail-on`` CI gates);
and the flight recorder — always-on bounded ring, atomic dumps that never
parse partially, dump-on-NaN-abort / SIGTERM / worker-exception /
watchdog-timeout, ``--merge`` carrying per-rank dumps, and
``tools/postmortem.py`` rendering + diagnosing a dump.
"""
import json
import os
import signal
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.observability import costs, flight, slo

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.close_sink()
    obs.reset()


def _compiles():
    return obs.snapshot()['counters'].get('jax.compiles', 0)


def _lm(seed=0, **kw):
    kw.setdefault('vocab', 32)
    kw.setdefault('embed', 16)
    kw.setdefault('num_heads', 2)
    kw.setdefault('max_batch', 2)
    kw.setdefault('max_seq', 32)
    kw.setdefault('prompt_buckets', (4, 8))
    return serving.TinyCausalLM.random(seed=seed, **kw)


# ---------------------------------------------------------------------------
# cost ledger: the three compile paths
# ---------------------------------------------------------------------------

class TestCostLedger:
    def test_executor_capture_nonzero_and_stable_across_cache_hits(self):
        obs.enable()
        obs.install_jax_hooks()
        import paddle_tpu.static as static
        paddle.enable_static()
        try:
            main, startup = static.Program(), static.Program()
            with static.program_guard(main, startup):
                x = static.data('x', shape=[-1, 8], dtype='float32')
                y = paddle.matmul(x, paddle.to_tensor(
                    np.ones((8, 4), np.float32)))
            exe = static.Executor()
            feed = {'x': np.ones((2, 8), np.float32)}
            exe.run(main, feed=feed, fetch_list=[y])
            entries = [e for e in costs.ledger()
                       if e['kind'] == 'executor.infer']
            assert len(entries) == 1
            e = entries[0]
            # cost_analysis + memory_analysis both nonzero on CPU
            assert e['flops'] > 0 and e['bytes_accessed'] > 0
            assert e['argument_bytes'] > 0 and e['output_bytes'] > 0
            assert e['peak_bytes'] >= e['argument_bytes'] + e['output_bytes']
            # flops/bytes are ledgered on any device; a roofline only
            # where the device's peaks are published (not the CPU)
            assert e['roofline'] is None
            # cache hit: SAME numbers, a hit tick, and NO new compile
            warm = _compiles()
            exe.run(main, feed=feed, fetch_list=[y])
            assert _compiles() == warm, \
                "cost capture added a recompile on a program-cache hit"
            e2 = costs.entry(e['program'])
            assert e2['flops'] == e['flops']
            assert e2['peak_bytes'] == e['peak_bytes']
            assert e2['hits'] == 1
        finally:
            paddle.disable_static()

    def test_engine_train_step_capture_and_flat_compiles(self):
        obs.enable()
        obs.install_jax_hooks()
        from paddle_tpu.engine import build_train_step
        opt = paddle.optimizer.SGD(learning_rate=0.1)

        def loss_fn(params, buffers, batch, key):
            x, t = batch
            pred = x @ params['w']
            return jnp.mean((pred - t) ** 2), (pred,), buffers

        step = build_train_step(loss_fn=loss_fn, optimizer=opt)
        state = step.init_state({'w': jnp.ones((4, 2))})
        batch = (jnp.ones((3, 4)), jnp.zeros((3, 2)))
        state, _ = step(state, batch)
        ent = costs.entry(step.cost_label)
        assert ent is not None and ent['kind'] == 'train_step'
        assert ent['flops'] > 0 and ent['bytes_accessed'] > 0
        assert ent['peak_bytes'] > 0
        warm = _compiles()
        for _ in range(3):
            state, _ = step(state, batch)
        assert _compiles() == warm, \
            "train-step cost capture must not recompile after warmup"
        assert costs.entry(step.cost_label)['flops'] == ent['flops']

    def test_serving_warmup_populates_ledger_for_runner_programs(self):
        obs.enable()
        eng = serving.ServingEngine()
        eng.register('lm', generative=_lm(), page_size=4)
        eng.register('clf', example={'x': np.zeros((4,), np.float32)},
                     predict_fn=lambda feeds: feeds['x'] * 2.0,
                     bucket_spec=serving.BucketSpec((1, 2)))
        eng.warmup()
        programs = {e['program']: e for e in costs.ledger()}
        assert 'serving.lm.prefill4' in programs
        assert 'serving.lm.prefill8' in programs
        assert 'serving.lm.decode' in programs
        assert 'serving.clf.b1' in programs and 'serving.clf.b2' in programs
        assert all(e['flops'] > 0 for e in programs.values())

    def test_roofline_against_published_peaks_and_summary(self):
        obs.enable()
        v5e = 'TPU v5 lite'               # 197 TFLOP/s, 819 GB/s: ridge 240
        r = costs.roofline(197e12 * 2, 819e9, v5e)   # AI=481 >= ridge
        assert r['bound'] == 'compute' and r['est_ms'] == 2000.0
        r2 = costs.roofline(1e9, 819e9, v5e)         # AI~0.001 < ridge
        assert r2['bound'] == 'memory' and r2['est_ms'] == 1000.0
        costs.record_costs('p1', 100.0, 50.0,
                           {'argument_bytes': 10, 'output_bytes': 5})
        s = costs.summary()
        assert s['programs'] == 1 and s['total_flops'] == 100.0
        assert s['max_peak_program'] == 'p1' and s['max_peak_bytes'] == 15

    def test_capture_off_when_telemetry_disabled(self):
        f = jax.jit(lambda x: x + 1)
        assert costs.capture('off.prog', f, jnp.ones(3)) is None
        assert costs.ledger() == []

    def test_costs_endpoint_slice(self):
        obs.enable()
        costs.record_costs('ep.prog', 42.0, 21.0,
                           {'argument_bytes': 8, 'output_bytes': 8})
        srv = obs.MetricsServer(host='127.0.0.1', port=0).start()
        try:
            from urllib.request import urlopen
            body = json.load(urlopen(f"{srv.url}/costs", timeout=10))
            assert body['summary']['programs'] == 1
            assert body['programs'][0]['program'] == 'ep.prog'
            # the route is advertised on 404s
            import urllib.error
            try:
                urlopen(f"{srv.url}/nope", timeout=10)
            except urllib.error.HTTPError as e:
                assert '/costs' in e.read().decode()
        finally:
            srv.stop()

    def test_telemetry_dump_costs_table(self, tmp_path):
        obs.enable()
        costs.record_costs('tbl.prog', 1e6, 5e5,
                           {'argument_bytes': 100, 'output_bytes': 50})
        log = tmp_path / 'events.jsonl'
        obs.dump_jsonl(str(log))
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, 'tools/telemetry_dump.py'),
             str(log), '--costs'], capture_output=True, text=True)
        assert out.returncode == 0
        assert 'tbl.prog' in out.stdout and 'MFLOP' in out.stdout


# ---------------------------------------------------------------------------
# per-request serving traces + SLO
# ---------------------------------------------------------------------------

class TestRequestTraces:
    def test_request_renders_as_connected_flow_in_merged_trace(self,
                                                               tmp_path):
        obs.enable()
        eng = serving.ServingEngine()
        ep = eng.register('lm', generative=_lm(), page_size=4)
        eng.warmup()
        f = ep.submit({'tokens': np.array([1, 2, 3], np.int32)},
                      max_new_tokens=4)
        eng.run_until_idle()
        r = f.result(10)
        assert r.ok
        # breakdown attributed per phase, mirrored onto the request event
        assert r.breakdown.get('prefill', 0) > 0
        assert r.breakdown.get('decode', 0) > 0
        ev = [e for e in obs.event_log() if e.get('ev') == 'serving.request']
        assert ev and 'prefill_ms' in ev[-1] and 'decode_ms' in ev[-1]
        # flush this rank's trace and merge it the mission-control way
        run_dir = tmp_path / 'run'
        from paddle_tpu.observability.flush import RankFlusher
        RankFlusher(str(run_dir), rank=0).flush_now()
        from paddle_tpu.observability import aggregate
        paths = aggregate.write_merged(str(run_dir))
        with open(paths['trace']) as fh:
            trace = json.load(fh)
        lane = [e for e in trace
                if e.get('cat') == 'serving.request'
                and e.get('id') == str(r.request_id)]
        phases = [e['ph'] for e in lane]
        assert phases[0] == 'b' and phases[-1] == 'e', phases
        assert phases.count('n') >= 2, phases   # prefill + decode milestones
        names = {e['name'] for e in lane}
        assert 'prefill_chunk' in names and 'decode' in names
        # one lane: every edge shares the (cat, id) pair Perfetto groups by
        assert {e['pid'] for e in lane} == {0}

    def test_slo_tracker_and_burn_detector(self):
        obs.enable()
        eng = serving.ServingEngine()
        # objective nothing can meet: every request violates
        ep = eng.register('lm', generative=_lm(), page_size=4,
                          slo_ms=0.0001)
        eng.warmup()
        futs = [ep.submit({'tokens': np.array([1, 2], np.int32)},
                          max_new_tokens=2) for _ in range(4)]
        eng.run_until_idle()
        assert all(f.result(10).ok for f in futs)
        burns = slo.burn_rates()
        assert burns['lm'] > 1.0
        snap = obs.snapshot()
        assert snap['counters'].get('slo.violations_total') == 4
        diags = obs.diagnose(events=obs.event_log(), snapshot=snap)
        burn = [d for d in diags if d['cause'] == 'slo_burn']
        assert burn and burn[0]['evidence']['model'] == 'lm'
        assert burn[0]['severity'] == 'critical'    # 100x burn

    def test_slo_objective_validation_and_ok_path(self):
        with pytest.raises(ValueError):
            slo.set_objective('m', 0)
        with pytest.raises(ValueError):
            slo.set_objective('m', 10, objective=1.5)
        slo.set_objective('m', 1e9, objective=0.5)
        assert slo.record('m', 'ok', 5.0) == 0.0
        assert slo.record('unregistered', 'ok', 5.0) is None

    def test_doctor_cli_fail_on_causes(self, tmp_path, monkeypatch):
        obs.enable()
        slo.set_objective('m', 0.001)
        for _ in range(3):
            slo.record('m', 'ok', 100.0)
        costs.record_costs('big.prog', 10.0, 5.0,
                           {'argument_bytes': 900, 'output_bytes': 200})
        log = tmp_path / 'events.jsonl'
        obs.dump_jsonl(str(log))
        env = dict(os.environ, PADDLE_TPU_HBM_BUDGET='1000')
        doctor_py = os.path.join(REPO, 'tools/doctor.py')
        out = subprocess.run(
            [sys.executable, doctor_py, str(log),
             '--fail-on', 'memory_pressure,slo_burn'],
            capture_output=True, text=True, env=env)
        assert out.returncode == 1, out.stdout + out.stderr
        assert 'slo_burn' in out.stdout and 'memory_pressure' in out.stdout
        # severity spelling still works, unknown causes are an error
        ok = subprocess.run(
            [sys.executable, doctor_py, str(log), '--fail-on', 'critical'],
            capture_output=True, text=True, env=env)
        assert ok.returncode == 1
        bad = subprocess.run(
            [sys.executable, doctor_py, str(log), '--fail-on', 'nonsense'],
            capture_output=True, text=True, env=env)
        assert bad.returncode == 2

    def test_memory_pressure_detector_thresholds(self):
        obs.enable()
        costs.record_costs('fits', 1.0, 1.0,
                           {'argument_bytes': 100, 'output_bytes': 0})
        from paddle_tpu.observability import doctor
        snap = obs.snapshot()
        # 10% of budget: silent
        assert list(doctor.detect_memory_pressure(
            snapshot=snap, hbm_budget=1000)) == []
        # 83%: warning
        warn = list(doctor.detect_memory_pressure(
            snapshot=snap, hbm_budget=120))
        assert warn and warn[0]['severity'] == 'warning'
        # over budget: critical
        crit = list(doctor.detect_memory_pressure(
            snapshot=snap, hbm_budget=80))
        assert crit and crit[0]['severity'] == 'critical'
        assert 'microbatch' in crit[0]['fix']
        # no budget -> no finding (CPU reports no bytes_limit)
        assert list(doctor.detect_memory_pressure(snapshot=snap)) == []


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_always_on_and_bounded(self, tmp_path):
        assert not obs.enabled()            # telemetry OFF
        for i in range(flight.MAX_RECORDS * 3):
            flight.record('tick', i=i)
        recs = flight.records()
        assert len(recs) == flight.MAX_RECORDS     # bounded memory
        assert recs[-1]['i'] == flight.MAX_RECORDS * 3 - 1
        path = flight.dump('test', run_dir=str(tmp_path))
        doc = flight.load_dump(path)
        assert doc['reason'] == 'test'
        assert doc['telemetry_enabled'] is False
        assert len(doc['records']) == flight.MAX_RECORDS

    def test_events_mirror_into_ring_while_enabled(self):
        obs.enable()
        obs.event('step', step=7)
        assert any(r.get('ev') == 'step' and r.get('step') == 7
                   for r in flight.records())

    def test_dump_atomic_partial_write_never_parses(self, tmp_path,
                                                    monkeypatch):
        flight.record('x', a=1)
        target = flight.dump_path(run_dir=str(tmp_path))
        # a failed commit leaves NO target file (staged tmp, os.replace)
        real_replace = os.replace

        def boom(src, dst):
            raise OSError('injected')
        monkeypatch.setattr(os, 'replace', boom)
        assert flight.dump('crash', run_dir=str(tmp_path)) is None
        assert not os.path.exists(target)
        monkeypatch.setattr(os, 'replace', real_replace)
        # a torn file (simulated truncation) never parses as a dump
        path = flight.dump('crash', run_dir=str(tmp_path))
        with open(path) as f:
            whole = f.read()
        with open(path, 'w') as f:
            f.write(whole[:len(whole) // 2])
        assert flight.load_dump(path) is None
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, 'tools/postmortem.py'),
             path], capture_output=True, text=True)
        assert out.returncode == 2
        assert 'does not parse' in out.stderr

    def test_nan_abort_dumps_and_postmortem_diagnoses(self, tmp_path,
                                                      monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_FLIGHT_DIR', str(tmp_path))
        obs.enable()
        from paddle_tpu.resilience import NanGuard, NanStepError, faultinject
        guard = NanGuard(max_consecutive_skips=2, verbose=False)

        def loss_fn():
            return 1.0
        poisoned = faultinject.poison_loss(loss_fn, at_steps=(0, 1, 2))
        with pytest.raises(NanStepError):
            for _ in range(3):
                guard.check(poisoned())
        path = flight.dump_path(run_dir=str(tmp_path))
        doc = flight.load_dump(path)
        assert doc['reason'] == 'nan_abort'
        assert doc['exception']['type'] == 'NanStepError'
        assert doc['extra']['consecutive'] == 2
        # the ring carries the skip events leading up to the abort
        assert any(r.get('ev') == 'nan_guard.skip' for r in doc['records'])
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, 'tools/postmortem.py'),
             path, '--tail', '5'], capture_output=True, text=True)
        assert out.returncode == 0
        assert "reason='nan_abort'" in out.stdout
        assert 'NanStepError' in out.stdout
        as_json = subprocess.run(
            [sys.executable, os.path.join(REPO, 'tools/postmortem.py'),
             path, '--json'], capture_output=True, text=True)
        parsed = json.loads(as_json.stdout)
        assert parsed['dump']['reason'] == 'nan_abort'

    def test_engine_in_graph_nan_abort_dumps(self, tmp_path, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_FLIGHT_DIR', str(tmp_path))
        obs.enable()
        from paddle_tpu.engine import build_train_step
        from paddle_tpu.resilience import NanGuard, NanStepError
        opt = paddle.optimizer.SGD(learning_rate=0.1)

        def loss_fn(params, buffers, batch, key):
            return jnp.float32(np.nan), (), buffers

        step = build_train_step(loss_fn=loss_fn, optimizer=opt,
                                nan_guard=True)
        guard = NanGuard(max_consecutive_skips=2, verbose=False)
        state = step.init_state({'w': jnp.ones((2,))}, nan_guard=guard)
        with pytest.raises(NanStepError):
            for _ in range(3):
                state, _ = step(state, jnp.ones((1, 2)))
                step.sync(state, nan_guard=guard)
        doc = flight.load_dump(flight.dump_path(run_dir=str(tmp_path)))
        assert doc['reason'] == 'nan_abort'

    def test_sigterm_dump(self, tmp_path):
        code = (
            "import os, signal, sys\n"
            "sys.path.insert(0, %r)\n"
            "from paddle_tpu.observability import flight\n"
            "flight.record('about_to_die', step=3)\n"
            "assert flight.install_crash_hooks()\n"
            "os.kill(os.getpid(), signal.SIGTERM)\n"
            "print('UNREACHABLE')\n" % REPO)
        env = dict(os.environ, PADDLE_TPU_FLIGHT_DIR=str(tmp_path),
                   JAX_PLATFORMS='cpu')
        out = subprocess.run([sys.executable, '-c', code],
                             capture_output=True, text=True, env=env,
                             timeout=60)
        # the handler dumps, then re-delivers SIGTERM: default death
        assert out.returncode != 0 and 'UNREACHABLE' not in out.stdout
        dumps = [n for n in os.listdir(tmp_path)
                 if n.startswith('flight_rank')]
        assert dumps, 'SIGTERM left no flight dump'
        doc = flight.load_dump(os.path.join(tmp_path, dumps[0]))
        assert doc['reason'] == 'sigterm'
        assert any(r.get('ev') == 'about_to_die' for r in doc['records'])

    def test_worker_exception_dump(self, tmp_path, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_FLIGHT_DIR', str(tmp_path))
        # silence the chained default printer for the intentional crash
        monkeypatch.setattr(threading, 'excepthook', lambda args: None)
        flight.install_crash_hooks()
        try:
            t = threading.Thread(
                target=lambda: (_ for _ in ()).throw(
                    RuntimeError('worker boom')),
                name='doomed')
            t.start()
            t.join(10)
            doc = flight.load_dump(flight.dump_path(run_dir=str(tmp_path)))
            assert doc['reason'] == 'worker_exception'
            assert doc['exception']['message'] == 'worker boom'
            assert doc['extra']['thread'] == 'doomed'
        finally:
            flight.uninstall_crash_hooks()

    def test_watchdog_timeout_dumps_rate_limited_side_file(self, tmp_path,
                                                           monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_FLIGHT_DIR', str(tmp_path))
        from paddle_tpu.resilience import watchdog
        monkeypatch.setattr(watchdog, '_last_flight_dump', [0.0])
        watchdog.WatchdogTimeout('late', what='test wait', waited=1.5)
        # the dump goes to a watchdog-specific SIDE file: a caught client
        # timeout must never clobber the primary black box
        side = os.path.join(str(tmp_path),
                            f'flight_rank{flight.rank_id()}_watchdog.json')
        assert not os.path.exists(flight.dump_path(run_dir=str(tmp_path)))
        doc = flight.load_dump(side)
        assert doc['reason'] == 'watchdog_timeout'
        assert doc['extra'] == {'what': 'test wait', 'waited': 1.5}
        # rate limit: an immediate second construction records into the
        # ring but does not rewrite the file
        before = os.path.getmtime(side)
        watchdog.WatchdogTimeout('late again', what='poll', waited=0.1)
        assert os.path.getmtime(side) == before
        assert any(r.get('ev') == 'watchdog_timeout' and
                   r.get('what') == 'poll' for r in flight.records())

    def test_slo_burn_snapshot_gauge_wins_over_stale_events(self):
        from paddle_tpu.observability import doctor
        # an old violation event says burn 10x, but the live gauge — which
        # every later good request updates — says 0.1x: no finding
        events = [{'ev': 'slo.violation', 'model': 'm', 'burn_rate': 10.0}]
        snap = {'gauges': {'slo.burn_rate{model=m}': 0.1},
                'counters': {'slo.violations{model=m}': 1}}
        assert list(doctor.detect_slo_burn(events=events,
                                           snapshot=snap)) == []
        # events alone (a bare log / flight dump) still fire, last wins,
        # and counts are not double-counted against the counter
        hot = list(doctor.detect_slo_burn(events=events * 3, snapshot=None))
        assert hot and hot[0]['evidence']['violations'] == 3

    def test_labeled_parse_survives_commas_in_program_labels(self):
        from paddle_tpu.observability import doctor
        snap = {'gauges': {
            'cost.peak_bytes{program=executor.p1[4x8,16x2]}': 900.0,
            'cost.peak_bytes{program=executor.p1[4x8,32x2]}': 100.0,
        }}
        got = doctor._labeled(snap['gauges'], 'cost.peak_bytes',
                              key='program')
        assert got == {'executor.p1[4x8,16x2]': 900.0,
                       'executor.p1[4x8,32x2]': 100.0}
        crit = list(doctor.detect_memory_pressure(snapshot=snap,
                                                  hbm_budget=500))
        assert crit and crit[0]['evidence']['program'] == \
            'executor.p1[4x8,16x2]'

    def test_merge_carries_flight_dumps_into_snapshot(self, tmp_path):
        obs.enable()
        run_dir = tmp_path / 'run'
        from paddle_tpu.observability.flush import RankFlusher
        RankFlusher(str(run_dir), rank=0).flush_now()
        flight.record('last_words')
        flight.dump('rank_failed', exc=RuntimeError('chip fell over'),
                    run_dir=str(run_dir))
        from paddle_tpu.observability import aggregate
        snap = aggregate.cluster_snapshot(str(run_dir))
        assert snap['flight_dumps'][0]['reason'] == 'rank_failed'
        assert snap['flight_dumps'][0]['exception']['type'] == \
            'RuntimeError'
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, 'tools/telemetry_dump.py'),
             str(run_dir), '--merge'], capture_output=True, text=True)
        assert out.returncode == 0
        assert 'rank_failed' in out.stdout and 'chip fell over' in out.stdout
        # postmortem over the whole run dir finds the per-rank dump
        pm = subprocess.run(
            [sys.executable, os.path.join(REPO, 'tools/postmortem.py'),
             str(run_dir)], capture_output=True, text=True)
        assert pm.returncode == 0 and "rank_failed" in pm.stdout

    def test_flight_disabled_via_env(self, tmp_path):
        # the kill switch is read at import: simulate via a subprocess
        code = (
            "import sys\n"
            "sys.path.insert(0, %r)\n"
            "from paddle_tpu.observability import flight\n"
            "assert not flight.enabled()\n"
            "assert flight.record('x') is None\n"
            "assert flight.dump('r') is None\n"
            "assert not flight.install_crash_hooks()\n"
            "print('DISABLED_OK')\n" % REPO)
        env = dict(os.environ, PADDLE_TPU_FLIGHT='0',
                   PADDLE_TPU_FLIGHT_DIR=str(tmp_path),
                   JAX_PLATFORMS='cpu')
        out = subprocess.run([sys.executable, '-c', code],
                             capture_output=True, text=True, env=env,
                             timeout=60)
        assert 'DISABLED_OK' in out.stdout, out.stderr
        assert not os.listdir(tmp_path)

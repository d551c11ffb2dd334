"""Telemetry spine acceptance tests (marker ``obs``, tier-1).

Covers: registry semantics (counters/gauges/histograms, reset isolation),
Chrome-trace JSON schema round-trip, the sampled block_until_ready
discipline, TelemetryCallback on a real 2-step ``Model.fit``, interposed
retrace/compile and host-transfer counters, instrumentation of the
Executor / optimizer / resilience / collective narrow waists, the
``utils.profiler`` double-start/fallback regression, the
``tools/telemetry_dump.py`` CLI, and the telemetry-on-vs-off overhead
smoke test (acceptance: within 5% on the CPU tier-1 run).
"""
import ast
import importlib.util
import json
import os
import re
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu import observability as obs

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    """Every test starts disabled with empty buffers and leaves no state."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.close_sink()
    obs.reset()


def _enable(tmp_path=None, **kw):
    obs.enable(log_dir=str(tmp_path) if tmp_path is not None else None, **kw)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_semantics():
    _enable()
    c = obs.counter('t.c')
    assert c.inc() == 1 and c.inc(4) == 5 and c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = obs.gauge('t.g')
    g.set(7)
    g.inc(2)
    g.dec()
    assert g.value == 8
    h = obs.histogram('t.h')
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    st = h.stats()
    assert st['count'] == 3 and st['sum'] == 6.0
    assert st['min'] == 1.0 and st['max'] == 3.0 and st['mean'] == 2.0


def test_histogram_reservoir_is_bounded_but_stats_exact():
    h = obs.histogram('t.res', reservoir_size=64)
    for v in range(10000):
        h.observe(v)
    assert len(h._reservoir) == 64
    assert h.count == 10000 and h.min == 0.0 and h.max == 9999.0
    # the reservoir is a uniform sample: p50 lands in the middle half
    assert 2000 < h.percentile(50) < 8000


def test_registry_kind_conflict_and_reset():
    obs.counter('t.name').inc()
    with pytest.raises(TypeError):
        obs.gauge('t.name')
    obs.reset()
    assert obs.counter('t.name').value == 0   # fresh instrument after reset


def test_counter_thread_safety():
    c = obs.counter('t.mt')

    def work():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000


def test_prometheus_exposition_and_snapshot():
    obs.counter('exec.cache.hits').inc(3)
    obs.gauge('queue.depth').set(2)
    obs.histogram('lat_ms').observe(5.0)
    text = obs.to_prometheus()
    assert '# TYPE paddle_tpu_exec_cache_hits counter' in text
    assert 'paddle_tpu_exec_cache_hits 3' in text
    assert '# TYPE paddle_tpu_queue_depth gauge' in text
    assert 'paddle_tpu_lat_ms_count 1' in text
    assert 'quantile="0.99"' in text
    snap = obs.snapshot()
    assert snap['counters']['exec.cache.hits'] == 3
    assert snap['gauges']['queue.depth'] == 2
    assert snap['histograms']['lat_ms']['count'] == 1


# ---------------------------------------------------------------------------
# spans / Chrome trace
# ---------------------------------------------------------------------------

def test_span_chrome_trace_schema_roundtrip(tmp_path):
    _enable()
    with obs.span('outer', phase='demo'):
        with obs.span('inner'):
            pass
    path = tmp_path / 'trace.json'
    n = obs.dump_chrome_trace(str(path))
    assert n == 2
    evs = json.loads(path.read_text())
    assert isinstance(evs, list) and len(evs) == 2
    for e in evs:
        assert e['ph'] == 'X'
        assert isinstance(e['ts'], float) and isinstance(e['dur'], float)
        assert e['name'] in ('outer', 'inner')
        assert 'pid' in e and 'tid' in e
    by = {e['name']: e for e in evs}
    # inner nests inside outer on the timeline
    assert by['outer']['ts'] <= by['inner']['ts']
    assert by['inner']['ts'] + by['inner']['dur'] <= \
        by['outer']['ts'] + by['outer']['dur'] + 1e-3
    assert by['outer']['args'] == {'phase': 'demo'}


def test_span_disabled_records_nothing():
    with obs.span('ghost'):
        pass
    assert obs.trace_events() == []


def test_sampled_sync_discipline():
    import jax.numpy as jnp
    _enable(sync_every=2)
    x = jnp.ones((4,))
    for _ in range(4):
        with obs.span('work', sync=x):
            pass
    synced = [bool(e.get('args', {}).get('synced'))
              for e in obs.trace_events() if e['name'] == 'work']
    # 1st and every 2nd occurrence blocked; the others never host-synced
    assert synced == [True, False, True, False]


def test_sampled_sync_zero_never_syncs():
    import jax.numpy as jnp
    _enable(sync_every=0)
    for _ in range(3):
        with obs.span('w2', sync=jnp.ones(())):
            pass
    assert all('synced' not in e.get('args', {})
               for e in obs.trace_events())


# ---------------------------------------------------------------------------
# step-event log
# ---------------------------------------------------------------------------

def test_event_log_jsonl_roundtrip(tmp_path):
    _enable()
    obs.event('alpha', a=1)
    obs.event('beta', b='x')
    path = tmp_path / 'events.jsonl'
    assert obs.dump_jsonl(str(path)) == 2
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r['ev'] for r in recs] == ['alpha', 'beta']
    assert recs[0]['a'] == 1 and recs[1]['b'] == 'x'
    assert all(isinstance(r['ts'], float) for r in recs)


def test_event_emit_disabled_is_noop():
    obs.event('ghost')
    assert obs.event_log() == []


def test_live_sink_streams_events(tmp_path):
    _enable()
    path = tmp_path / 'live.jsonl'
    obs.set_sink(str(path))
    obs.event('one', n=1)
    obs.event('two', n=2)
    obs.close_sink()
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r['ev'] for r in recs] == ['one', 'two']


# ---------------------------------------------------------------------------
# interposed counters: retraces/compiles + host transfers
# ---------------------------------------------------------------------------

def test_retrace_and_compile_counters_fire():
    import jax
    _enable()
    f = jax.jit(lambda x: x * 3 + 1)
    f(np.float32(1.0))
    f(np.ones((3,), np.float32))   # new shape -> retrace + recompile
    snap = obs.snapshot()['counters']
    assert snap.get('jax.traces', 0) >= 2
    assert snap.get('jax.compiles', 0) >= 2
    assert snap.get('jax.compile_ms', 0) > 0
    s = obs.counters_summary()
    assert s['jax_traces'] >= 2 and s['jax_compiles'] >= 2


def test_host_transfer_counter_on_tensor_numpy():
    _enable()
    t = paddle.to_tensor(np.ones((8, 8), np.float32))
    before = obs.snapshot()['counters'].get('host_transfer.bytes', 0)
    t.numpy()
    snap = obs.snapshot()['counters']
    assert snap['host_transfer.bytes'] - before >= 8 * 8 * 4
    assert snap['host_transfer.calls'] >= 1
    assert snap['host_transfer.tensor.numpy.bytes'] >= 8 * 8 * 4


def _tiny_static_program():
    import paddle_tpu.static as static
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = static.data('x', shape=[-1, 3], dtype='float32')
        y = x * 2.0 + 1.0
    return main, startup, y


def test_executor_cache_counters_and_fetch_bytes():
    import paddle_tpu.static as static
    paddle.enable_static()
    try:
        main, startup, y = _tiny_static_program()
        exe = static.Executor()
        exe.run(startup)
        _enable()
        feed = {'x': np.ones((2, 3), np.float32)}
        out1 = exe.run(main, feed=feed, fetch_list=[y])
        out2 = exe.run(main, feed=feed, fetch_list=[y])
        np.testing.assert_allclose(out1[0], out2[0])
        snap = obs.snapshot()['counters']
        assert snap['executor.program_cache.misses'] == 1
        assert snap['executor.program_cache.hits'] == 1
        assert snap['executor.run.calls'] == 2
        assert snap['host_transfer.executor.fetch.bytes'] >= 2 * 2 * 3 * 4
    finally:
        paddle.disable_static()


# ---------------------------------------------------------------------------
# narrow-waist instrumentation: optimizer / resilience / collectives
# ---------------------------------------------------------------------------

def test_optimizer_step_metrics():
    _enable()
    lin = nn.Linear(3, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=lin.parameters())
    loss = lin(paddle.to_tensor(np.ones((4, 3), np.float32))).sum()
    loss.backward()
    opt.step()
    snap = obs.snapshot()
    assert snap['counters']['optimizer.step.calls'] == 1
    assert snap['histograms']['optimizer.step_ms']['count'] == 1


def test_nan_guard_skip_event():
    from paddle_tpu.resilience import NanGuard
    _enable()
    g = NanGuard(verbose=False)
    assert g.check(np.float32('nan')) is True
    assert obs.snapshot()['counters']['nan_guard.skips'] == 1
    evs = [e for e in obs.event_log() if e['ev'] == 'nan_guard.skip']
    assert len(evs) == 1 and evs[0]['consecutive'] == 1


def test_retry_attempt_event(monkeypatch):
    import sys
    from paddle_tpu.resilience import retry as retry_fn
    retry_mod = sys.modules['paddle_tpu.resilience.retry']
    monkeypatch.setattr(retry_mod, '_sleep', lambda s: None)
    _enable()
    calls = [0]

    @retry_fn(max_attempts=3, backoff=0.001, jitter=0)
    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise OSError('transient')
        return 'ok'

    assert flaky() == 'ok'
    assert obs.snapshot()['counters']['retry.attempts'] == 2
    evs = [e for e in obs.event_log() if e['ev'] == 'retry.attempt']
    assert [e['attempt'] for e in evs] == [1, 2]
    assert all(e['fn'] == 'flaky' for e in evs)


def test_checkpoint_save_restore_events(tmp_path):
    from paddle_tpu.resilience import CheckpointManager
    _enable()
    mgr = CheckpointManager(str(tmp_path / 'ckpt'), max_keep=2)
    step = mgr.save({'w': np.arange(8.0)}, meta={'epoch': 1})
    state, meta = mgr.load()
    np.testing.assert_allclose(state['w'], np.arange(8.0))
    snap = obs.snapshot()
    assert snap['counters']['checkpoint.saves'] == 1
    assert snap['counters']['checkpoint.restores'] == 1
    assert snap['histograms']['checkpoint.save_ms']['count'] == 1
    assert snap['histograms']['checkpoint.restore_ms']['count'] == 1
    kinds = [e['ev'] for e in obs.event_log()]
    assert 'checkpoint.save' in kinds and 'checkpoint.restore' in kinds
    save_ev = next(e for e in obs.event_log()
                   if e['ev'] == 'checkpoint.save')
    assert save_ev['step'] == step and save_ev['bytes'] > 0
    assert save_ev['duration_ms'] >= 0


def test_collective_counters():
    import paddle_tpu.distributed as dist
    _enable()
    t = paddle.to_tensor(np.ones((4, 4), np.float32))
    dist.all_reduce(t)
    snap = obs.snapshot()['counters']
    assert snap['collective.all_reduce.calls'] == 1
    assert snap['collective.all_reduce.bytes'] == 4 * 4 * 4


# ---------------------------------------------------------------------------
# TelemetryCallback on a real 2-step Model.fit
# ---------------------------------------------------------------------------

def _fit_tiny(tmp_path, steps=2, jit=False):
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 1))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=0.01,
                                parameters=net.parameters())
    model.prepare(optimizer=opt, loss=nn.MSELoss(), jit=jit)
    x = np.random.rand(steps * 4, 4).astype('float32')
    y = np.random.rand(steps * 4, 1).astype('float32')
    model.fit(list(zip(x, y)), batch_size=4, epochs=1, verbose=0)
    return model


def test_telemetry_callback_two_step_fit(tmp_path):
    """Acceptance: with telemetry enabled a tiny fit emits a JSONL step-
    event log and a valid Chrome trace (list of ph/ts/dur events)."""
    _enable(tmp_path)
    _fit_tiny(tmp_path, steps=2)

    # fit auto-attached the callback; counters reflect the 2 steps
    snap = obs.snapshot()
    assert snap['counters']['hapi.steps'] == 2
    assert snap['histograms']['hapi.step_ms']['count'] == 2
    assert snap['counters']['optimizer.step.calls'] == 2
    assert snap['gauges'].get('hapi.steps_per_sec', 0) > 0

    # JSONL step-event log on disk
    ev_path = tmp_path / 'events.jsonl'
    assert ev_path.exists()
    recs = [json.loads(l) for l in ev_path.read_text().splitlines()]
    kinds = [r['ev'] for r in recs]
    assert kinds[0] == 'train_begin' and kinds[-1] == 'train_end'
    steps = [r for r in recs if r['ev'] == 'step']
    assert len(steps) == 2
    for s in steps:
        assert 'loss' in s and s['step_ms'] > 0 and s['epoch'] == 0
    # the train_end summary carries the interposed counters
    end = recs[-1]
    assert end['counters']['jax_traces'] >= 0
    assert 'host_transfer_bytes' in end['counters']

    # Chrome trace on disk: a JSON list of ph/ts/dur events incl. the steps
    trace = json.loads((tmp_path / 'trace.json').read_text())
    assert isinstance(trace, list) and trace
    assert all(e['ph'] == 'X' and 'ts' in e and 'dur' in e for e in trace)
    assert sum(1 for e in trace if e['name'] == 'hapi.step') == 2
    assert any(e['name'] == 'hapi.epoch' for e in trace)


def test_telemetry_callback_jit_fit_records_cache_size(tmp_path):
    _enable(tmp_path)
    _fit_tiny(tmp_path, steps=2, jit=True)
    snap = obs.snapshot()
    assert snap['counters']['hapi.steps'] == 2
    assert snap['gauges'].get('hapi.jit_cache_size', 0) >= 1
    # the jitted path really traced/compiled something this process
    assert obs.counters_summary()['jax_traces'] > 0


def test_fit_without_telemetry_writes_nothing(tmp_path):
    _fit_tiny(tmp_path, steps=2)
    assert not (tmp_path / 'events.jsonl').exists()
    assert obs.snapshot()['counters'] == {}


def test_dataloader_wait_metrics():
    from paddle_tpu.io import DataLoader
    _enable()
    data = [(np.ones((3,), np.float32), np.float32(1.0)) for _ in range(8)]
    loader = DataLoader(data, batch_size=2, shuffle=False)
    assert len(list(loader)) == 4
    snap = obs.snapshot()
    assert snap['counters']['dataloader.batches'] == 4
    assert snap['histograms']['dataloader.next_wait_ms']['count'] == 4


def test_reader_buffered_metrics():
    from paddle_tpu.reader import buffered
    _enable()
    out = list(buffered(lambda: iter(range(10)), 4)())
    assert out == list(range(10))
    snap = obs.snapshot()
    assert snap['histograms']['reader.buffered.wait_ms']['count'] >= 10


# ---------------------------------------------------------------------------
# utils.profiler: double-start / fallback regression (previously untested)
# ---------------------------------------------------------------------------

def test_profiler_start_trace_failure_falls_back_to_cprofile(monkeypatch):
    import jax
    from paddle_tpu.utils import profiler as prof

    def boom(log_dir):
        raise RuntimeError('trace backend unavailable')

    monkeypatch.setattr(jax.profiler, 'start_trace', boom)
    prof.start_profiler()
    assert prof._active['dir'] is None
    assert prof._active['py'] is not None   # cProfile fallback engaged
    prof.stop_profiler(None)
    assert prof._active == {'dir': None, 'py': None}


def test_profiler_double_start_leak_is_cleared(monkeypatch, capsys):
    """A start while a trace is active raises inside jax -> the fallback
    cProfile ends up enabled ALONGSIDE the active trace. stop_profiler must
    clear both states (the double-start leak path)."""
    import jax
    from paddle_tpu.utils import profiler as prof

    started, stopped = [], []

    def fake_start(log_dir):
        if started:
            raise RuntimeError('already tracing')
        started.append(log_dir)

    monkeypatch.setattr(jax.profiler, 'start_trace', fake_start)
    monkeypatch.setattr(jax.profiler, 'stop_trace',
                        lambda: stopped.append(True))
    prof.start_profiler(log_dir='/tmp/obs_prof_test')
    assert prof._active['dir'] == '/tmp/obs_prof_test'
    prof.start_profiler(log_dir='/tmp/obs_prof_test')   # double start
    assert prof._active['py'] is not None               # leaked fallback
    prof.stop_profiler(None)
    capsys.readouterr()
    assert stopped == [True]
    assert prof._active == {'dir': None, 'py': None}    # BOTH cleared


def test_annotate_bridges_to_telemetry_span():
    import jax
    from paddle_tpu.utils import profiler as prof
    _enable()
    ann = prof.annotate('region')
    assert isinstance(ann, obs.Span)
    with ann:
        pass
    assert any(e['name'] == 'region' for e in obs.trace_events())
    obs.disable()
    # telemetry off + no device trace: the raw TraceAnnotation contract
    assert isinstance(prof.annotate('region'),
                      jax.profiler.TraceAnnotation)


# ---------------------------------------------------------------------------
# tools/telemetry_dump.py
# ---------------------------------------------------------------------------

def _load_dump_tool():
    path = os.path.join(REPO, 'tools', 'telemetry_dump.py')
    spec = importlib.util.spec_from_file_location('telemetry_dump', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_telemetry_dump_table_and_chrome(tmp_path, capsys):
    _enable()
    obs.event('step', step=0, loss=1.0, step_ms=2.5)
    obs.event('checkpoint.save', step=1, bytes=10, duration_ms=4.0)
    obs.event('nan_guard.skip', step=2)
    log = tmp_path / 'events.jsonl'
    obs.dump_jsonl(str(log))

    tool = _load_dump_tool()
    assert tool.main([str(log)]) == 0
    out = capsys.readouterr().out
    assert 'step' in out and 'nan_guard.skip' in out and '3 event(s)' in out

    chrome = tmp_path / 'trace.json'
    assert tool.main([str(log), '--chrome', str(chrome)]) == 0
    evs = json.loads(chrome.read_text())
    assert isinstance(evs, list) and len(evs) == 3
    durs = [e for e in evs if e['ph'] == 'X']
    insts = [e for e in evs if e['ph'] == 'i']
    assert len(durs) == 2 and len(insts) == 1   # *_ms events become slices
    assert all('ts' in e for e in evs)
    assert tool.main([str(log), '--ev', 'step']) == 0
    assert '1 event(s)' in capsys.readouterr().out


def test_telemetry_dump_missing_file(tmp_path, capsys):
    tool = _load_dump_tool()
    assert tool.main([str(tmp_path / 'nope.jsonl')]) == 2


# ---------------------------------------------------------------------------
# the environment surface: documented == read
# ---------------------------------------------------------------------------

def _option_names_read(path):
    """The whole-string `PADDLE_TPU_*` constants of a file that are not (in)
    a docstring: the names the code hands to `os.environ`."""
    tree = ast.parse(open(path).read())
    docstrings = {
        id(node.body[0].value) for node in ast.walk(tree)
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef))
        and node.body and isinstance(node.body[0], ast.Expr)
        and isinstance(node.body[0].value, ast.Constant)}
    return {node.value for node in ast.walk(tree)
            if isinstance(node, ast.Constant) and isinstance(node.value, str)
            and id(node) not in docstrings
            and re.fullmatch(r'PADDLE_TPU_[A-Z0-9_]+', node.value)}


def test_documented_options_are_the_ones_read():
    """`observability/state.py`'s docstring is the one file that documents
    the package's environment surface ("catalogued here so one file
    documents the env surface"): every option a module of the package reads
    has a row there, and every row names an option something under
    `paddle_tpu/` still reads. A deleted or added option cannot leave its
    row behind, nor go without one."""
    package = os.path.dirname(obs.__file__)
    doc = ast.get_docstring(ast.parse(
        open(os.path.join(package, 'state.py')).read()))
    assert 'one file documents the env surface' in ' '.join(doc.split())
    rows = {name for line in doc.splitlines() if line.startswith('- ``')
            for name in re.findall(r'PADDLE_TPU_[A-Z0-9_]+', line)}
    read_here = set().union(*(
        _option_names_read(os.path.join(package, f))
        for f in os.listdir(package) if f.endswith('.py')))
    assert read_here - rows == set(), 'read in observability/, no row'
    read_anywhere = set().union(*(
        _option_names_read(os.path.join(d, f))
        for d, _, files in os.walk(os.path.dirname(package))
        for f in files if f.endswith('.py')))
    assert rows - read_anywhere == set(), 'a row, and nothing reads it'
    assert 'PADDLE_TPU_TELEMETRY' in rows & read_here      # the walk works


# ---------------------------------------------------------------------------
# overhead smoke: telemetry on vs off (acceptance: within 5%)
# ---------------------------------------------------------------------------

def test_overhead_smoke_executor_loop():
    """Telemetry-on steady-state Executor.run step time stays within 5% of
    telemetry-off (plus a small absolute guard against scheduler noise).
    Interleaved min-of-trials keeps the comparison robust on shared CI."""
    import paddle_tpu.static as static
    paddle.enable_static()
    try:
        main, startup, y = _tiny_static_program()
        exe = static.Executor()
        exe.run(startup)
        feed = {'x': np.ones((2, 3), np.float32)}

        def run_steps(n=60):
            sw = obs.Stopwatch()
            for _ in range(n):
                exe.run(main, feed=feed, fetch_list=[y])
            return sw.elapsed()

        # warm both paths (compile + span-name sync counters)
        run_steps(5)
        _enable()
        run_steps(5)
        obs.disable()

        t_off, t_on = [], []
        for _ in range(5):
            obs.disable()
            t_off.append(run_steps())
            _enable()
            t_on.append(run_steps())
        obs.disable()
        best_off, best_on = min(t_off), min(t_on)
        assert best_on <= best_off * 1.05 + 0.010, \
            f"telemetry overhead too high: on={best_on:.4f}s " \
            f"off={best_off:.4f}s ({best_on / best_off:.3f}x)"
    finally:
        paddle.disable_static()

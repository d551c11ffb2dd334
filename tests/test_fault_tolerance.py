"""Distributed & input-pipeline fault tolerance: the chaos matrix.

Acceptance anchors (ISSUE 5):
(a) a killed DataLoader worker no longer hangs the consumer — the epoch
    completes (respawn) or raises within the watchdog budget, with the
    quarantine/restart count reported;
(b) barrier() with an expired deadline raises DistributedTimeoutError
    naming the op within 2x the configured timeout;
(c) a SIGKILLed rank under launch()/spawn() terminates all sibling ranks
    with a RankFailedError identifying the rank;
with telemetry counters for restarts/quarantines/timeouts asserted under
PADDLE_TPU_TELEMETRY=1.

Everything is CPU-only, deterministic (resilience.faultinject), and
tier-1-safe (no sleeps beyond ~2s in any surviving code path).
"""
import os
import queue
import signal
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import observability as obs
from paddle_tpu.io import DataLoader, DataLoaderWorkerError
from paddle_tpu.io.dataset import Dataset
from paddle_tpu.resilience import faultinject as fi
from paddle_tpu.resilience import watchdog

pytestmark = pytest.mark.fault


class Toy(Dataset):
    def __init__(self, n=16):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.full((3,), i, np.float32)


@pytest.fixture
def telemetry(monkeypatch):
    """PADDLE_TPU_TELEMETRY=1 for this test, counters zeroed."""
    monkeypatch.setenv('PADDLE_TPU_TELEMETRY', '1')
    obs.enable()
    obs.reset()
    yield obs
    obs.disable()
    obs.reset()


def _loader(ds, **kw):
    kw.setdefault('batch_size', 2)
    kw.setdefault('num_workers', 2)
    kw.setdefault('use_buffer_reader', False)
    return DataLoader(ds, **kw)


def _nbatch_samples(batches):
    return sum(np.asarray(b).shape[0] for b in batches)


# ---------------------------------------------------------------------------
# watchdog primitives
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_bounded_get_dead_producer_raises_fast(self):
        q = queue.Queue()
        t = threading.Thread(target=lambda: None)   # dies without posting
        t.start()
        t.join(1)
        start = time.monotonic()
        with pytest.raises(watchdog.WatchdogTimeout, match='died'):
            watchdog.bounded_get(q, timeout=30.0, alive=t.is_alive,
                                 what='sentinel')
        assert time.monotonic() - start < 2.0   # liveness, not the deadline

    def test_bounded_get_timeout_when_alive_but_stuck(self):
        q = queue.Queue()
        with pytest.raises(watchdog.WatchdogTimeout, match='within'):
            watchdog.bounded_get(q, timeout=0.3, alive=lambda: True)

    def test_bounded_get_drains_after_producer_death(self):
        q = queue.Queue()
        q.put('last-words')
        assert watchdog.bounded_get(q, alive=lambda: False) == 'last-words'

    def test_heartbeat_file_and_age(self, tmp_path):
        hb_path = tmp_path / 'hb_0'
        hb = watchdog.Heartbeat(hb_path, interval=0.05).start()
        try:
            time.sleep(0.2)
            age = watchdog.heartbeat_age(hb_path)
            assert age is not None and age < 1.0
        finally:
            hb.stop()
        assert watchdog.heartbeat_age(tmp_path / 'missing') is None


# ---------------------------------------------------------------------------
# self-healing DataLoader: threaded path
# ---------------------------------------------------------------------------

class TestThreadedLoader:
    def test_worker_exception_propagates_not_hangs(self):
        """The silent-hang fix: a raising dataset[i] reaches the consumer
        as DataLoaderWorkerError instead of killing the thread silently."""
        dl = _loader(fi.poison_sample(Toy(), [3]), use_shared_memory=False)
        start = time.monotonic()
        with pytest.raises(DataLoaderWorkerError) as ei:
            list(dl)
        assert time.monotonic() - start < 5.0
        assert 'dataset[3]' in str(ei.value)
        assert 'PoisonedSampleError' in str(ei.value)

    def test_quarantine_within_budget(self, telemetry):
        dl = _loader(fi.poison_sample(Toy(), [3, 7]),
                     use_shared_memory=False, skip_bad_samples=2)
        batches = list(dl)
        assert _nbatch_samples(batches) == 14   # 16 - 2 quarantined
        report = dl.quarantine_report()
        assert sorted(i for i, _ in report) == [3, 7]
        assert all('PoisonedSampleError' in err for _, err in report)
        snap = obs.snapshot()['counters']
        assert snap['dataloader.quarantined'] == 2
        assert obs.counters_summary()['quarantined_samples'] == 2

    def test_quarantine_budget_exhausted_raises(self):
        dl = _loader(fi.poison_sample(Toy(), [1, 3, 5]),
                     use_shared_memory=False, skip_bad_samples=1)
        with pytest.raises(DataLoaderWorkerError) as ei:
            list(dl)
        assert 'exhausted' in str(ei.value)
        assert len(dl.quarantine_report()) == 1   # budget, not overrun

    def test_whole_batch_quarantined_keeps_order(self):
        dl = _loader(fi.poison_sample(Toy(8), [2, 3]),
                     use_shared_memory=False, skip_bad_samples=2)
        vals = [v for b in list(dl) for v in np.asarray(b)[:, 0].tolist()]
        assert vals == [0.0, 1.0, 4.0, 5.0, 6.0, 7.0]   # in order, no hole

    def test_sync_path_quarantine(self):
        """skip_bad_samples applies on the num_workers=0 path too."""
        dl = DataLoader(fi.poison_sample(Toy(), [3, 7]), batch_size=2,
                        num_workers=0, use_buffer_reader=False,
                        skip_bad_samples=2)
        batches = list(dl)
        assert _nbatch_samples(batches) == 14
        assert sorted(i for i, _ in dl.quarantine_report()) == [3, 7]

    def test_sync_path_default_budget_fails_loudly(self):
        dl = DataLoader(fi.poison_sample(Toy(), [3]), batch_size=2,
                        num_workers=0, use_buffer_reader=False)
        with pytest.raises(DataLoaderWorkerError, match='exhausted'):
            list(dl)

    def test_hung_worker_trips_watchdog(self, telemetry):
        """A worker wedged mid-sample fails the epoch within the watchdog
        budget instead of hanging the consumer forever."""
        dl = _loader(fi.hang_worker(Toy(8), 2, hang_s=30.0),
                     use_shared_memory=False, timeout=1.0)
        start = time.monotonic()
        with pytest.raises(DataLoaderWorkerError, match='wedged'):
            list(dl)
        assert time.monotonic() - start < 4.0   # ~1s budget + poll slack
        assert obs.snapshot()['counters']['dataloader.watchdog_timeouts'] \
            == 1

    def test_collate_error_propagates(self):
        def bad_collate(samples):
            raise TypeError('collate boom')
        dl = _loader(Toy(8), use_shared_memory=False,
                     collate_fn=bad_collate)
        with pytest.raises(DataLoaderWorkerError, match='collate'):
            list(dl)

    def test_timeout_zero_env_disables_watchdog(self, monkeypatch):
        """PADDLE_TPU_DATA_TIMEOUT=0 (or timeout<0) disables the deadline
        instead of turning it into an instant trip; timeout=0 still means
        'unspecified' (default budget)."""
        monkeypatch.setenv('PADDLE_TPU_DATA_TIMEOUT', '0')
        dl = _loader(Toy(8), use_shared_memory=False)
        assert dl.timeout == 0.0
        assert _nbatch_samples(list(dl)) == 8   # liveness still bounds it
        monkeypatch.delenv('PADDLE_TPU_DATA_TIMEOUT')
        assert _loader(Toy(8), timeout=-1).timeout == 0.0
        assert _loader(Toy(8)).timeout > 0

    def test_skip_budget_env_default(self, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_DATA_SKIP_BUDGET', '2')
        dl = _loader(fi.poison_sample(Toy(), [0, 15]),
                     use_shared_memory=False)
        assert dl.skip_bad_samples == 2
        assert _nbatch_samples(list(dl)) == 14


# ---------------------------------------------------------------------------
# self-healing DataLoader: fork()ed process workers + shm ring
# ---------------------------------------------------------------------------

def _native_pool_available():
    try:
        import multiprocessing as mp
        from paddle_tpu._native.prefetch import native_available
        return native_available() and 'fork' in mp.get_all_start_methods()
    except Exception:
        return False


needs_pool = pytest.mark.skipif(not _native_pool_available(),
                                reason='native ring / fork unavailable')


@needs_pool
class TestProcessPoolLoader:
    def test_killed_worker_respawns_and_epoch_completes(self, telemetry,
                                                        tmp_path):
        """Acceptance (a): SIGKILLed process worker mid-epoch -> respawn +
        parent-side rebuild of the orphaned batch; every sample arrives."""
        once = tmp_path / 'kill-fired'
        dl = _loader(fi.kill_worker(Toy(), 5, once), timeout=20.0,
                     worker_max_restarts=2)
        batches = list(dl)
        assert _nbatch_samples(batches) == 16       # nothing lost
        assert once.exists()                        # the kill really fired
        snap = obs.snapshot()['counters']
        assert snap['dataloader.worker_restarts'] >= 1
        assert obs.counters_summary()['worker_restarts'] >= 1

    def test_killed_worker_without_restart_budget_raises(self, tmp_path):
        once = tmp_path / 'kill-fired'
        dl = _loader(fi.kill_worker(Toy(), 5, once), timeout=10.0,
                     worker_max_restarts=0)
        start = time.monotonic()
        with pytest.raises(RuntimeError, match='died without a traceback'):
            list(dl)
        assert time.monotonic() - start < 8.0       # bounded, not a hang

    def test_process_poison_quarantine_within_budget(self, telemetry):
        dl = _loader(fi.poison_sample(Toy(), [3, 7]), timeout=10.0,
                     skip_bad_samples=4)
        batches = list(dl)
        assert _nbatch_samples(batches) == 14
        assert sorted(i for i, _ in dl.quarantine_report()) == [3, 7]
        assert obs.snapshot()['counters']['dataloader.quarantined'] == 2


# ---------------------------------------------------------------------------
# reader decorators: no unbounded waits
# ---------------------------------------------------------------------------

class TestReaderLiveness:
    def test_multiprocess_reader_killed_worker_raises(self):
        """A reader worker SIGKILLed mid-stream can never post its done
        sentinel; the liveness-bounded get raises instead of hanging."""
        import multiprocessing as mp
        if 'fork' not in mp.get_all_start_methods():
            pytest.skip('fork unavailable')
        from paddle_tpu.reader import multiprocess_reader

        def suicidal():
            yield np.float32(1.0)
            os.kill(os.getpid(), signal.SIGKILL)

        reader = multiprocess_reader([lambda: suicidal()], queue_size=4)
        start = time.monotonic()
        with pytest.raises(RuntimeError):
            list(reader())
        assert time.monotonic() - start < 10.0

    def test_buffered_reader_error_still_propagates(self):
        from paddle_tpu.reader import buffered

        def boom():
            yield 1
            raise ValueError('reader boom')

        with pytest.raises(ValueError, match='reader boom'):
            list(buffered(lambda: boom(), 4)())


# ---------------------------------------------------------------------------
# collective deadlines
# ---------------------------------------------------------------------------

class TestCollectiveDeadline:
    def test_barrier_deadline_raises_within_2x(self, telemetry):
        """Acceptance (b): expired barrier deadline -> actionable
        DistributedTimeoutError naming the op, within 2x the timeout."""
        import paddle_tpu.distributed as dist
        prev = dist.set_timeout(0.5)
        try:
            start = time.monotonic()
            with fi.slow_collective(30.0, ops=['barrier']):
                with pytest.raises(dist.DistributedTimeoutError) as ei:
                    dist.barrier()
            elapsed = time.monotonic() - start
            assert elapsed < 2 * 0.5, elapsed
            assert ei.value.op == 'barrier'
            assert ei.value.timeout == 0.5
            assert 'barrier' in str(ei.value)
            snap = obs.snapshot()['counters']
            assert snap['distributed.timeouts'] == 1
            assert obs.counters_summary()['dist_timeouts'] == 1
        finally:
            dist.set_timeout(prev)

    def test_eager_all_reduce_deadline(self):
        import paddle_tpu.distributed as dist
        prev = dist.set_timeout(0.4)
        try:
            t = paddle_tpu.to_tensor(np.ones(4, np.float32))
            with fi.slow_collective(30.0, ops=['all_reduce']):
                with pytest.raises(dist.DistributedTimeoutError,
                                   match='all_reduce'):
                    dist.all_reduce(t)
        finally:
            dist.set_timeout(prev)

    def test_collectives_complete_under_deadline(self):
        import paddle_tpu.distributed as dist
        prev = dist.set_timeout(30.0)
        try:
            dist.barrier()
            t = paddle_tpu.to_tensor(np.ones(4, np.float32))
            out = dist.all_reduce(t)
            assert out is not None
        finally:
            dist.set_timeout(prev)

    def test_set_timeout_policy(self, monkeypatch):
        from paddle_tpu.distributed import deadline
        prev = deadline.set_timeout(None)
        try:
            assert deadline.get_timeout() is None
            deadline.set_timeout(7.5)
            assert deadline.get_timeout() == 7.5
            deadline.set_timeout(0)       # 0 disables
            assert deadline.get_timeout() is None
        finally:
            deadline.set_timeout(prev)
        # env seeding
        monkeypatch.setenv('PADDLE_TPU_DIST_TIMEOUT', '12.5')
        assert deadline._env_timeout() == 12.5
        monkeypatch.setenv('PADDLE_TPU_DIST_TIMEOUT', 'nonsense')
        assert deadline._env_timeout() is None


# ---------------------------------------------------------------------------
# supervised launch
# ---------------------------------------------------------------------------

def _sigkill_rank1():
    rank = int(os.environ.get('PADDLE_TRAINER_ID', '0'))
    if rank == 1:
        os.kill(os.getpid(), signal.SIGKILL)
    for _ in range(20):        # ~2s ceiling; the supervisor kills us first
        time.sleep(0.1)
    return rank


def _rank_times_ten():
    return int(os.environ.get('PADDLE_TRAINER_ID', '0')) * 10


@pytest.mark.skipif(sys.platform == 'win32', reason='posix only')
class TestSupervisedSpawn:
    def test_sigkilled_rank_fails_fast_with_diagnostics(self, telemetry):
        """Acceptance (c): SIGKILL on rank 1 -> RankFailedError naming the
        rank + signal, siblings terminated, telemetry counter bumped."""
        import paddle_tpu.distributed as dist
        ctx = dist.spawn(fi.slow_rank(_sigkill_rank1, rank=0, delay_s=0.0),
                         nprocs=2, backend='cpu', join=False)
        with pytest.raises(dist.RankFailedError) as ei:
            ctx.join()
        e = ei.value
        assert e.rank == 1
        assert e.signal_name == 'SIGKILL'
        assert 'rank 1' in str(e) and 'SIGKILL' in str(e)
        assert not any(p.is_alive() for p in ctx.processes)   # kill-tree
        assert obs.snapshot()['counters']['distributed.rank_failures'] == 1

    def test_boot_failure_restarted_within_budget(self, telemetry):
        import paddle_tpu.distributed as dist
        with fi.boot_fail(rank=1, times=1):
            res = dist.spawn(_rank_times_ten, nprocs=2, backend='cpu',
                             max_restarts=1).join()
        assert res == [0, 10]
        snap = obs.snapshot()['counters']
        assert snap['distributed.rank_restarts'] == 1
        assert obs.counters_summary()['rank_restarts'] == 1

    def test_boot_failure_without_budget_raises(self):
        import paddle_tpu.distributed as dist
        with fi.boot_fail(rank=1, times=1):
            with pytest.raises(dist.RankFailedError) as ei:
                dist.spawn(_rank_times_ten, nprocs=2, backend='cpu')
        assert ei.value.rank == 1
        assert ei.value.exitcode == 43

    def test_join_timeout_terminates_stragglers(self):
        import paddle_tpu.distributed as dist
        ctx = dist.spawn(fi.slow_rank(_rank_times_ten, rank=1, delay_s=60),
                         nprocs=2, backend='cpu', join=False)
        with pytest.raises(RuntimeError) as ei:
            ctx.join(timeout=4.0)
        assert 'still running' in str(ei.value)
        assert 'exit codes' in str(ei.value)
        assert not any(p.is_alive() for p in ctx.processes)


@pytest.mark.skipif(sys.platform == 'win32', reason='posix only')
class TestSupervisedLaunchCLI:
    def test_first_nonzero_exit_kills_siblings(self, tmp_path):
        """launch() fail-fast: rank 1 exits 3 -> rank 0 is terminated and
        the launcher reports which rank failed."""
        script = tmp_path / 'failing_rank.py'
        script.write_text(
            "import os, sys, time\n"
            "rank = int(os.environ['PADDLE_TRAINER_ID'])\n"
            "if rank == 1:\n"
            "    print('rank 1 bailing', file=sys.stderr)\n"
            "    sys.exit(3)\n"
            "for _ in range(600):\n"     # rank 0: 60s unless terminated
            "    time.sleep(0.1)\n")
        import subprocess as sp
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS='cpu',
                   PYTHONPATH=os.pathsep.join(
                       [repo] + ([os.environ['PYTHONPATH']]
                                 if os.environ.get('PYTHONPATH') else [])))
        start = time.monotonic()
        out = sp.run([sys.executable, '-m', 'paddle_tpu.distributed.launch',
                      '--nproc_per_node', '2', '--log_dir', str(tmp_path),
                      str(script)],
                     env=env, capture_output=True, text=True, timeout=300)
        elapsed = time.monotonic() - start
        assert out.returncode != 0
        assert 'rank 1' in out.stderr
        assert 'exit code 3' in out.stderr
        assert 'rank 1 bailing' in out.stderr     # log tail quoted
        assert elapsed < 45, elapsed              # rank 0 did NOT run 60s

    def test_boot_restart_flag(self, tmp_path):
        """--max_restarts heals a transient boot crash (script version:
        crash on first attempt, succeed on retry via a marker file)."""
        script = tmp_path / 'flaky_boot.py'
        script.write_text(
            "import os, pathlib, sys\n"
            "rank = os.environ['PADDLE_TRAINER_ID']\n"
            "marker = pathlib.Path(__file__).parent / ('boot_%s' % rank)\n"
            "if rank == '1' and not marker.exists():\n"
            "    marker.write_text('fired')\n"
            "    os._exit(9)\n"
            "(pathlib.Path(__file__).parent / ('ok_%s' % rank))"
            ".write_text('done')\n")
        import subprocess as sp
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS='cpu',
                   PYTHONPATH=os.pathsep.join(
                       [repo] + ([os.environ['PYTHONPATH']]
                                 if os.environ.get('PYTHONPATH') else [])))
        out = sp.run([sys.executable, '-m', 'paddle_tpu.distributed.launch',
                      '--nproc_per_node', '2', '--max_restarts', '1',
                      '--log_dir', str(tmp_path), str(script)],
                     env=env, capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert (tmp_path / 'ok_0').exists() and (tmp_path / 'ok_1').exists()
        assert (tmp_path / 'boot_1').exists()     # the crash really fired


# ---------------------------------------------------------------------------
# hapi surfacing
# ---------------------------------------------------------------------------

class TestHapiQuarantineSurfacing:
    def test_fit_warns_on_quarantined_samples(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.hapi import Model

        class Pair(Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                return (np.full((3,), i, np.float32),
                        np.zeros((1,), np.int64))

        net = nn.Linear(3, 2)
        model = Model(net)
        model.prepare(
            optimizer=paddle_tpu.optimizer.SGD(
                learning_rate=0.1, parameters=net.parameters()),
            loss=nn.CrossEntropyLoss())
        loader = DataLoader(fi.poison_sample(Pair(), [2]), batch_size=2,
                            num_workers=2, use_shared_memory=False,
                            skip_bad_samples=1)
        with pytest.warns(RuntimeWarning, match='quarantined 1'):
            model.fit(loader, epochs=1, verbose=0)

"""Async resharding checkpoints + elastic world size (ISSUE 14).

Acceptance anchors (docs/RESILIENCE.md, "Elastic training"):

- async saves: ``checkpoint.save_stall_ms`` p50 <= 10% of the synchronous
  baseline under a ``faultinject.slow_fs`` disk; commit still atomic; a
  background failure surfaces on the next save/fence;
- sharded checkpoints: ENOSPC partway through a shard write leaves NO
  visible partial ``ckpt_<step>/`` and the previous checkpoint restorable;
  restore validates the merged CRC manifest before touching state;
- resharding restore matrix (mesh 1<->2<->4, FSDP and FSDP+TP,
  replicated<->sharded both directions): post-restore params/opt-state are
  BITWISE-equal to the saved state, and continued training tracks an
  uninterrupted run (bitwise on the same mesh, allclose across mesh sizes
  whose XLA programs reduce in different orders);
- the preemption fence: an async save in flight when SIGTERM fires is
  finished-or-abandoned BEFORE the preemption checkpoint starts
  (``faultinject.sigterm_at_step`` + ``slow_fs`` regression);
- elastic supervisor: a 4-rank spawn under chaos (rank SIGKILL +
  poisoned/hung DataLoader samples) with ``elastic=True`` completes after
  >= 1 downsize, with the restored boundary state bitwise-equal to the
  uninterrupted reference and the recovery-time histogram populated;
- doctor: ``checkpoint_stall`` (fix-it: async_=True) and
  ``elastic_downsize`` (names the dead rank) detectors, surfaced by
  ``tools/doctor.py --fail-on``; ``tools/ckpt.py`` inspects/verifies and
  dry-runs ``--compat`` resharding.
"""
import json
import os
import subprocess
import sys
import time
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import engine, nn
from paddle_tpu import observability as obs
from paddle_tpu.resilience import CheckpointManager
from paddle_tpu.resilience import async_checkpoint as ac
from paddle_tpu.resilience import faultinject as fi
from paddle_tpu.distributed.strategy import ShardingConfig

pytestmark = pytest.mark.fault

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def telemetry():
    os.environ['PADDLE_TPU_TELEMETRY'] = '1'
    obs.enable()
    obs.reset()
    yield obs
    obs.disable()
    obs.reset()
    os.environ.pop('PADDLE_TPU_TELEMETRY', None)


def _data(n=6, seed=0):
    rs = np.random.RandomState(seed)
    return [(rs.rand(8, 32).astype('f4'), rs.rand(8, 4).astype('f4'))
            for _ in range(n)]


def _net_opt(seed=7):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(32, 64), nn.Tanh(), nn.Linear(64, 4))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    return net, opt


def _state(nleaves=3, size=4096, seed=0):
    rs = np.random.RandomState(seed)
    return {'params': {('w%d' % i): rs.rand(size // 16, 16).astype('f4')
                       for i in range(nleaves)},
            'buffers': {}, 'opt': {}}


def _host_params(state):
    return {k: np.asarray(v) for k, v in state['params'].items()}


def _mesh_cfg(k, model=1, rules=None):
    if k is None:
        return None
    devs = np.asarray(jax.devices()[:k * model])
    if model > 1:
        mesh = Mesh(devs.reshape(k, model), ('data', 'model'))
    else:
        mesh = Mesh(devs, ('data',))
    return ShardingConfig(mesh=mesh, fsdp=True, min_size=64,
                          param_rules=rules,
                          tensor_parallel_degree=model)


# ---------------------------------------------------------------------------
# async saves
# ---------------------------------------------------------------------------

class TestAsyncSave:
    def test_async_stall_le_10pct_of_sync(self, tmp_path, telemetry):
        """The acceptance ratio: under a slow disk, the async save's
        training-thread stall is <= 10% of the synchronous save's."""
        state = _state(nleaves=4)
        mgr = CheckpointManager(tmp_path / 'sync', max_keep=2)

        def stalls(mgr, async_, compute_s=0.0):
            out = []
            with fi.FaultInjector().slow_fs(0.01, match='ckpt_'):
                for i in range(3):
                    t0 = time.perf_counter()
                    mgr.save(state, step=i, world=1, async_=async_)
                    out.append((time.perf_counter() - t0) * 1000.0)
                    if compute_s:
                        time.sleep(compute_s)
                mgr.fence()
            return sorted(out)[len(out) // 2]

        sync_p50 = stalls(mgr, async_=False)
        amgr = CheckpointManager(tmp_path / 'async', max_keep=2)
        async_p50 = stalls(amgr, async_=True,
                           compute_s=max(0.1, 1.5 * sync_p50 / 1000.0))
        assert async_p50 <= 0.10 * sync_p50, (async_p50, sync_p50)
        # both paths feed the stall histogram; commits recorded either way
        snap = obs.snapshot()['histograms']
        assert snap['checkpoint.save_stall_ms']['count'] == 6
        assert snap['checkpoint.commit_ms']['count'] == 6

    def test_async_commit_is_loadable_and_ordered(self, tmp_path):
        mgr = CheckpointManager(tmp_path, max_keep=10)
        for i in range(3):
            st = _state(seed=i)
            mgr.save(st, step=i, world=1, async_=True)
        mgr.fence()
        assert mgr.steps() == [0, 1, 2]
        got, _ = mgr.load(step=2)
        np.testing.assert_array_equal(got['params']['w0'],
                                      _state(seed=2)['params']['w0'])

    def test_default_step_numbers_see_inflight_commit(self, tmp_path):
        """Regression: save(step=None) must fence BEFORE reading
        latest_step(), or back-to-back async saves on a slow disk both
        pick the same number and silently overwrite each other."""
        mgr = CheckpointManager(tmp_path, max_keep=10)
        with fi.FaultInjector().slow_fs(0.01, match='ckpt_'):
            for i in range(3):
                mgr.save(_state(seed=i), world=1, async_=True)
            mgr.fence()
        assert mgr.steps() == [0, 1, 2]

    def test_background_failure_surfaces_on_fence(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        with fi.FaultInjector().disk_full(after_bytes=64,
                                          match='shard_rank'):
            mgr.save(_state(), step=5, world=1, async_=True)
            with pytest.raises(Exception) as ei:
                mgr.fence()
        assert 'atomic write' in str(ei.value) or 'space' in str(ei.value)
        assert 5 not in mgr.steps()

    def test_donation_secure_copies_jax_leaves(self, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_DONATE', '1')
        arr = jnp.arange(8.0)
        secured = ac.secure_for_async({'params': {'w': arr}})
        assert secured['params']['w'] is not arr
        np.testing.assert_array_equal(np.asarray(secured['params']['w']),
                                      np.asarray(arr))
        monkeypatch.setenv('PADDLE_TPU_DONATE', '0')
        same = ac.secure_for_async({'params': {'w': arr}})
        assert same['params']['w'] is arr


# ---------------------------------------------------------------------------
# sharded checkpoints: atomicity + validation
# ---------------------------------------------------------------------------

class TestShardedCheckpoint:
    def test_enospc_mid_shard_keeps_previous_restorable(self, tmp_path):
        """Satellite: disk_full partway through a shard write leaves no
        partial ckpt_<step> visible; the previous checkpoint restores."""
        mgr = CheckpointManager(tmp_path)
        first = _state(seed=1)
        mgr.save(first, step=0, world=2)
        with fi.FaultInjector().disk_full(after_bytes=128,
                                          match='shard_rank'):
            with pytest.raises(Exception):
                mgr.save(_state(seed=2), step=1, world=2)
        assert mgr.steps() == [0]
        assert not os.path.exists(tmp_path / 'ckpt_00000001')
        got, _ = mgr.load()
        np.testing.assert_array_equal(got['params']['w0'],
                                      first['params']['w0'])

    def test_corrupt_shard_falls_back_with_warning(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save(_state(seed=1), step=0, world=2)
        mgr.save(_state(seed=2), step=1, world=2)
        fi.corrupt_file(tmp_path / 'ckpt_00000001' / 'shard_rank1.npz',
                        offset=-20, nbytes=4)
        with pytest.warns(UserWarning, match='CRC32 mismatch'):
            got, _meta = mgr.load()
        np.testing.assert_array_equal(got['params']['w0'],
                                      _state(seed=1)['params']['w0'])

    def test_truncated_manifest_is_invisible(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save(_state(), step=0, world=1)
        man = tmp_path / 'ckpt_00000000' / 'manifest.json'
        fi.truncate_file(man, keep_bytes=20)
        with pytest.warns(UserWarning, match='unreadable manifest'):
            assert mgr.load() is None

    def test_per_rank_writes_and_rank0_barrier_commit(self, tmp_path):
        """Multi-process protocol in one process: ranks 1..3 write their
        shards + markers first; rank 0's save waits for the markers, CRCs
        every shard, and commits the merged manifest."""
        state = _state(nleaves=2, size=4096)
        mgr = CheckpointManager(tmp_path)
        for r in (1, 2, 3):
            assert mgr.save(state, step=7, world=4, rank=r) == 7
        assert mgr.steps() == []          # no manifest yet: invisible
        mgr.save(state, step=7, world=4, rank=0)
        assert mgr.steps() == [7]
        man = mgr.load_manifest(7)
        assert man['world'] == 4 and len(man['shards']) == 4
        # every rank's file really carries pieces (leaves split 4 ways)
        sharded = [leaf for leaf in man['leaves']
                   if len(leaf['pieces']) == 4]
        assert sharded, man['leaves']
        got, _ = mgr.load(step=7)
        for k in state['params']:
            np.testing.assert_array_equal(got['params'][k],
                                          state['params'][k])

    def test_rank0_barrier_times_out_loudly(self, tmp_path):
        from paddle_tpu.resilience.watchdog import WatchdogTimeout
        with pytest.raises(WatchdogTimeout, match='never committed'):
            ac.save_sharded(tmp_path, _state(), step=0, world=3, rank=0,
                            barrier_timeout=0.3)
        # no manifest: the step never became visible
        assert not os.path.exists(
            os.path.join(ac.step_dir(tmp_path, 0), 'manifest.json'))

    def test_rotation_removes_sharded_dirs(self, tmp_path):
        mgr = CheckpointManager(tmp_path, max_keep=2)
        for i in range(4):
            mgr.save(_state(seed=i), step=i, world=1)
        assert mgr.steps() == [2, 3]
        assert not os.path.exists(tmp_path / 'ckpt_00000000')


# ---------------------------------------------------------------------------
# resharding restore matrix
# ---------------------------------------------------------------------------

_TP_RULES = {'2.weight': P(None, 'model')}

# (save config spec, restore config spec): (data_degree|None, model_degree)
_MATRIX = [
    ((1, 1), (2, 1)),          # grow 1 -> 2
    ((2, 1), (4, 1)),          # grow 2 -> 4
    ((4, 1), (2, 1)),          # the elastic downsize: k -> k/2
    ((4, 1), (None, 1)),       # sharded -> replicated
    ((None, 1), (4, 1)),       # replicated -> sharded
    ((4, 1), (4, 1)),          # same mesh (control: bitwise throughout)
    ((2, 2), (1, 2)),          # FSDP+TP: data 2 -> 1, model axis kept
]


class TestReshardingMatrix:
    _cache = {}

    def _run(self, spec, epochs, ckpt_dir=None, resume_from=None, seed=7):
        """``epochs`` epochs over the same 6 batches under the config
        spec; returns (report, params, opt) with host copies.
        Uninterrupted runs are cached per (spec, epochs)."""
        key = (spec, epochs)
        cacheable = resume_from is None and ckpt_dir is None and seed == 7
        if cacheable and key in self._cache:
            return self._cache[key]
        k, model = spec
        cfg = _mesh_cfg(k, model, rules=_TP_RULES if model > 1 else None)
        net, opt = _net_opt(seed=seed)
        report = engine.fit(net, nn.MSELoss(), opt, _data(6),
                            epochs=epochs, prefetch=0, sharding=cfg,
                            checkpoint=ckpt_dir, checkpoint_every=0,
                            async_save=False, resume_from=resume_from,
                            preempt_save=False)
        out = (report, _host_params(report['state']),
               jax.tree_util.tree_map(np.asarray, report['state']['opt']))
        if cacheable:
            self._cache[key] = out
        return out

    @pytest.mark.parametrize('save_spec,restore_spec', _MATRIX,
                             ids=lambda s: 'x'.join(str(x) for x in s))
    def test_post_restore_bitwise_and_continued_loss(self, tmp_path,
                                                     save_spec,
                                                     restore_spec):
        # phase A: train 1 epoch (6 dispatches) under the SAVE config,
        # checkpointing at the epoch boundary
        _repA, paramsA, optA = self._run(save_spec, 1,
                                         ckpt_dir=str(tmp_path))
        mgr = CheckpointManager(str(tmp_path))
        k, model = restore_spec
        cfgB = _mesh_cfg(k, model, rules=_TP_RULES if model > 1 else None)

        # post-restore params/opt-state BITWISE vs the saved state
        got = mgr.restore(sharding=cfgB)
        assert got is not None
        stB, _meta = got
        for name in paramsA:
            np.testing.assert_array_equal(
                paramsA[name], np.asarray(stB['params'][name]),
                err_msg=f'param {name} not bitwise across '
                        f'{save_spec}->{restore_spec}')
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
            optA, stB['opt'])

        # continue for a second epoch under the RESTORE config, vs an
        # uninterrupted 2-epoch run: bitwise when the save config is the
        # same program; allclose across program boundaries (different
        # mesh sizes reduce grads in different orders, and Adam's lr-sized
        # steps amplify the ulps — the LOSS trajectory is what must track)
        _repU, paramsU, _optU = self._run(restore_spec, 2)
        repC, paramsC, _optC = self._run(restore_spec, 2,
                                         resume_from=str(tmp_path),
                                         seed=31)  # restore overwrites init
        assert repC['resumed_from'] == 6
        same_program = save_spec == restore_spec
        if same_program:
            for name in paramsU:
                np.testing.assert_array_equal(paramsU[name], paramsC[name],
                                              err_msg=name)
        else:
            lossU = self._run(restore_spec, 2)[0]['loss']
            lossC = repC['loss']
            # log points differ in count (the resumed run logs fewer
            # dispatches); compare the final logged losses
            np.testing.assert_allclose(lossU[-1], lossC[-1], rtol=5e-3)
            for name in paramsU:
                np.testing.assert_allclose(paramsU[name], paramsC[name],
                                           rtol=0.2, atol=5e-3,
                                           err_msg=name)
        assert all(np.isfinite(l) for l in repC['loss'])

    def test_tp_layout_survives_restore(self, tmp_path):
        """FSDP+TP: the rule-matched param comes back ON the model axis
        after a resharding restore (the layout IS the parallelism)."""
        self._run((2, 2), 6, ckpt_dir=str(tmp_path))
        cfgB = _mesh_cfg(1, 2, rules=_TP_RULES)
        stB, _ = CheckpointManager(str(tmp_path)).restore(sharding=cfgB)
        sh = stB['params']['2.weight'].sharding
        assert 'model' in (ax for part in sh.spec if part
                           for ax in (part if isinstance(part, tuple)
                                      else (part,)))


# ---------------------------------------------------------------------------
# the preemption fence (bugfix regression)
# ---------------------------------------------------------------------------

class TestPreemptionFence:
    def test_sigterm_fences_inflight_async_save(self, tmp_path, telemetry):
        """Regression: SIGTERM (sigterm_at_step) lands while an async save
        is still committing (slow_fs). The preemption checkpoint must
        fence it first — afterwards every visible ckpt dir is committed
        and the preemption checkpoint is the newest restorable state."""
        net, opt = _net_opt()
        src = fi.sigterm_at_step(_data(n=16), 6)
        with fi.FaultInjector().slow_fs(0.01, match='ckpt_'):
            report = engine.fit(net, nn.MSELoss(), opt, src, epochs=1,
                                prefetch=0, checkpoint=str(tmp_path),
                                checkpoint_every=2, async_save=True)
        assert report['preempted']
        assert report['dispatches'] < 16
        mgr = CheckpointManager(str(tmp_path))
        st, meta = mgr.restore()
        assert meta['dispatches'] == report['dispatches']
        # no partial dirs: everything visible has a committed manifest
        for name in os.listdir(tmp_path):
            if name.startswith('ckpt_'):
                assert os.path.exists(
                    os.path.join(tmp_path, name, 'manifest.json')), name
        # the fence really ran before the preemption save
        fences = [e for e in obs.event_log()
                  if e.get('ev') == 'checkpoint.fence']
        assert fences

    def test_hapi_checkpoint_saver_async_preempt(self, tmp_path):
        """CheckpointSaver(async_save=True): epoch saves ride the
        background thread; the SIGTERM save fences + commits sync and
        resume continues bitwise (the PR 1 contract, now async-safe)."""
        from paddle_tpu.hapi import Model
        from paddle_tpu.hapi.callbacks import CheckpointSaver
        from paddle_tpu.io.dataset import Dataset

        class Pair(Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                rs = np.random.RandomState(i)
                return (rs.rand(4).astype('f4'),
                        rs.rand(2).astype('f4'))

        def build():
            paddle.seed(3)
            net = nn.Linear(4, 2)
            m = Model(net)
            m.prepare(optimizer=paddle.optimizer.SGD(
                learning_rate=0.1, parameters=net.parameters()),
                loss=nn.MSELoss())
            return m

        # reference: 3 uninterrupted epochs
        ref = build()
        ref.fit(Pair(), epochs=3, batch_size=4, verbose=0, shuffle=False)
        ref_w = {k: v.numpy().copy()
                 for k, v in ref.network.state_dict().items()}

        saver = CheckpointSaver(str(tmp_path), save_freq=1,
                                async_save=True)
        m = build()
        with fi.FaultInjector().slow_fs(0.005, match=str(tmp_path)):
            m.fit(Pair(), epochs=3, batch_size=4, verbose=0, shuffle=False,
                  callbacks=[saver, fi.PreemptAtStep(3)])
        assert saver.preempted
        m2 = build()
        m2.fit(Pair(), epochs=3, batch_size=4, verbose=0, shuffle=False,
               callbacks=[CheckpointSaver(str(tmp_path), save_freq=1)],
               resume_from=str(tmp_path))
        for k, v in m2.network.state_dict().items():
            np.testing.assert_array_equal(ref_w[k], v.numpy(), err_msg=k)

    def test_sync_save_fences_previous_async(self, tmp_path):
        """Ordering: a sync save issued while an async one is in flight
        waits for it — step N can never land after step N+1."""
        mgr = CheckpointManager(tmp_path, max_keep=10)
        with fi.FaultInjector().slow_fs(0.01, match='ckpt_'):
            mgr.save(_state(seed=0), step=0, world=1, async_=True)
            mgr.save(_state(seed=1), step=1, world=1)   # sync: must fence
        assert mgr.steps() == [0, 1]
        assert not mgr.in_flight()


# ---------------------------------------------------------------------------
# elastic supervisor: chaos soak + rejoin
# ---------------------------------------------------------------------------

def _soak_worker(ckpt_dir, kill_marker):
    """Chaos-soak rank: deterministic training via engine.fit with
    world-sharded async checkpoints, fed through a DataLoader whose
    dataset is poisoned (quarantined) and briefly hung (watchdog-sized);
    rank 1 SIGKILLs itself once at a mid-run step."""
    import numpy as np
    import zlib
    import paddle_tpu as paddle
    from paddle_tpu import engine as eng, nn as pnn
    from paddle_tpu.resilience import faultinject as f

    rank = int(os.environ.get('PADDLE_TRAINER_ID', '0'))
    world = int(os.environ.get('PADDLE_TRAINERS_NUM', '1'))
    gen = int(os.environ.get('PADDLE_TPU_ELASTIC_GENERATION', '0'))
    rs = np.random.RandomState(0)
    batches = [(rs.rand(8, 32).astype('f4'), rs.rand(8, 4).astype('f4'))
               for _ in range(6)]
    maybe_die = f.kill_rank_at_step(9, kill_marker, rank=1)
    seen = [0]

    def die_after_a_commit(step):
        """Rank 1 dies at step 9 once the epoch's checkpoint (step 6) is
        committed: rank 0 commits when every rank's shard has landed, this
        rank's too, and with 50 ms between the save and the kill the async
        writers of four processes on a loaded host did not always get
        there, so that generation 1 had nothing to restore."""
        if step == 9 and rank == 1 and gen == 0:
            deadline = time.monotonic() + 120
            while not CheckpointManager(ckpt_dir).steps() \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
        maybe_die(step)

    class Chaos:
        def __iter__(self):
            for i, b in enumerate(batches):
                die_after_a_commit(seen[0])
                seen[0] += 1
                if i == 2:
                    time.sleep(0.05)        # hung-worker flavor (bounded)
                yield b

    paddle.seed(7)
    net = pnn.Sequential(pnn.Linear(32, 64), pnn.Tanh(),
                         pnn.Linear(64, 4))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    report = eng.fit(net, pnn.MSELoss(), opt, Chaos(), epochs=3,
                     prefetch=0, checkpoint=ckpt_dir, checkpoint_every=0,
                     async_save=True, resume_from=ckpt_dir, world=world,
                     rank=rank, preempt_save=False)
    crc = 0
    for k in sorted(report['state']['params']):
        crc = zlib.crc32(np.ascontiguousarray(
            np.asarray(report['state']['params'][k])).tobytes(), crc)
    return (rank, world, gen, crc & 0xFFFFFFFF,
            report['resumed_from'])


def _idle_worker(seconds):
    rank = int(os.environ.get('PADDLE_TRAINER_ID', '0'))
    gen = int(os.environ.get('PADDLE_TPU_ELASTIC_GENERATION', '0'))
    if rank == 1 and gen == 0:
        os._exit(17)
    for _ in range(int(seconds * 10)):
        time.sleep(0.1)
    return (rank, int(os.environ.get('PADDLE_TRAINERS_NUM', '1')), gen)


@pytest.mark.skipif(sys.platform == 'win32', reason='posix only')
class TestElasticSupervisor:
    def test_chaos_soak_downsizes_and_finishes_bitwise(self, tmp_path,
                                                       telemetry):
        """THE acceptance test: 4 ranks, rank 1 SIGKILLed mid-run, job
        completes on 3 survivors after one downsize; final params bitwise
        == an uninterrupted single-process reference; the state restored
        at the downsize boundary is bitwise-equal to the reference run at
        that step; recovery-time histogram populated."""
        import paddle_tpu.distributed as dist
        ckpt = str(tmp_path / 'ckpts')
        marker = str(tmp_path / 'killed')
        ctx = dist.spawn(_soak_worker, (ckpt, marker), nprocs=4,
                         backend='cpu', join=False, elastic=True,
                         max_restarts=2)
        results = ctx.join(timeout=240)
        sup = ctx._supervisor
        assert os.path.exists(marker)            # the kill really fired
        assert sup.downsizes >= 1
        assert len(results) == 3                 # world shrank 4 -> 3
        assert all(r is not None for r in results)
        crcs = {r[3] for r in results}
        assert len(crcs) == 1                    # survivors agree bitwise

        # uninterrupted reference (single process, no chaos, same math)
        ref_dir = str(tmp_path / 'ref')
        ref = _soak_worker(os.path.join(ref_dir, 'ck'),
                           os.path.join(ref_dir, 'killed'))
        assert ref[3] in crcs                    # bitwise vs uninterrupted

        # the downsize boundary: what generation 1 restored is bitwise
        # identical to the reference run's state at that checkpoint step
        resumed_step = results[0][4]
        assert resumed_step is not None
        restored, _meta = CheckpointManager(ckpt).restore(step=resumed_step)
        ref_ck, _ = CheckpointManager(
            os.path.join(ref_dir, 'ck')).restore(step=resumed_step)
        for k in restored['params']:
            np.testing.assert_array_equal(restored['params'][k],
                                          ref_ck['params'][k], err_msg=k)

        snap = obs.snapshot()
        assert snap['histograms']['elastic.recovery_ms']['count'] >= 1
        assert snap['counters']['distributed.elastic_downsizes'] >= 1
        evs = [e['ev'] for e in obs.event_log()
               if str(e.get('ev', '')).startswith('elastic.')]
        assert 'elastic.rank_death' in evs and 'elastic.downsize' in evs \
            and 'elastic.relaunch' in evs

    def test_rejoin_keeps_world_size(self, tmp_path, telemetry):
        """A rejoin marker inside the grace window re-claims the dead
        slot: the new generation keeps the old world size (no downsize)."""
        import paddle_tpu.distributed as dist
        ctx = dist.spawn(_idle_worker, (0.5,), nprocs=2, backend='cpu',
                         join=False, elastic=True, max_restarts=1,
                         rejoin_grace_s=15.0)
        run_dir = ctx._result_dir
        # pre-arm the replacement offer: _wait_rejoin consumes it the
        # moment the death opens the grace window
        with open(os.path.join(run_dir, 'rejoin_any'), 'w'):
            pass
        results = ctx.join(timeout=120)
        sup = ctx._supervisor
        assert len(results) == 2                 # world size kept
        assert sup.downsizes == 0
        assert sup.generation == 1
        assert [r[2] for r in results] == [1, 1]
        evs = [e['ev'] for e in obs.event_log()]
        assert 'elastic.rejoin' in evs

    def test_budget_exhausted_fails_fast(self, tmp_path):
        """elastic with max_restarts=0... the budget still bounds it: the
        supervisor falls back to the fail-fast RankFailedError."""
        import paddle_tpu.distributed as dist

        ctx = dist.spawn(_always_dying_worker, (), nprocs=2, backend='cpu',
                         join=False, elastic=True, max_restarts=1)
        with pytest.raises(dist.RankFailedError):
            ctx.join(timeout=120)


def _always_dying_worker():
    # rank 0 dies in EVERY generation (it exists at every world size), so
    # the restart budget must eventually exhaust into a fail-fast
    rank = int(os.environ.get('PADDLE_TRAINER_ID', '0'))
    if rank == 0:
        os._exit(23)
    time.sleep(2.0)
    return rank


# ---------------------------------------------------------------------------
# doctor + CLIs
# ---------------------------------------------------------------------------

class TestDoctorDetectors:
    def test_checkpoint_stall_fires_and_names_async_fix(self):
        snapshot = {'histograms': {
            'checkpoint.save_stall_ms': {'count': 4, 'mean': 50.0,
                                         'sum': 200.0, 'p50': 50.0},
            'hapi.step_ms': {'count': 100, 'mean': 100.0, 'sum': 1e4,
                             'p50': 100.0}}, 'counters': {}, 'gauges': {}}
        found = [d for d in obs.diagnose(snapshot=snapshot)
                 if d['cause'] == 'checkpoint_stall']
        assert found and 'async_=True' in found[0]['fix']
        assert found[0]['severity'] == 'warning'

    def test_checkpoint_stall_quiet_when_async(self):
        snapshot = {'histograms': {
            'checkpoint.save_stall_ms': {'count': 4, 'mean': 0.5,
                                         'sum': 2.0, 'p50': 0.5},
            'hapi.step_ms': {'count': 100, 'mean': 100.0, 'sum': 1e4,
                             'p50': 100.0}}, 'counters': {}, 'gauges': {}}
        assert not [d for d in obs.diagnose(snapshot=snapshot)
                    if d['cause'] == 'checkpoint_stall']

    def test_elastic_downsize_info_names_dead_rank(self):
        events = [{'ev': 'elastic.downsize', 'dead_rank': 2,
                   'old_world': 4, 'new_world': 3, 'signal': 'SIGKILL'}]
        found = [d for d in obs.diagnose(events=events)
                 if d['cause'] == 'elastic_downsize']
        assert found and found[0]['severity'] == 'info'
        assert 'rank 2' in found[0]['detail']
        assert found[0]['evidence']['dead_rank'] == 2

    def test_doctor_cli_fail_on_elastic_downsize(self, tmp_path):
        log = tmp_path / 'events.jsonl'
        log.write_text(json.dumps(
            {'ev': 'elastic.downsize', 'dead_rank': 1, 'old_world': 4,
             'new_world': 3}) + '\n')
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, 'tools', 'doctor.py'),
             str(log), '--fail-on', 'elastic_downsize'],
            capture_output=True, text=True)
        assert out.returncode == 1, out.stdout + out.stderr
        assert 'elastic_downsize' in out.stdout


class TestCkptCLI:
    def _cli(self, *args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, 'tools', 'ckpt.py')]
            + [str(a) for a in args], capture_output=True, text=True)

    def test_inspect_verify_and_compat(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save(_state(nleaves=2), step=3, world=4,
                 meta={'epoch': 2})
        out = self._cli(tmp_path, '--verify', '--compat', '2')
        assert out.returncode == 0, out.stderr
        assert 'format 2' in out.stdout and 'shards 4' in out.stdout
        assert 'OK ' in out.stdout and 'feasible' in out.stdout
        assert "'epoch': 2" in out.stdout
        j = self._cli(tmp_path, '--json', '--compat', 'data=2')
        data = json.loads(j.stdout)
        assert data[0]['compat']['degree'] == 2

    def test_corrupt_shard_exits_nonzero(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save(_state(), step=0, world=2)
        fi.corrupt_file(tmp_path / 'ckpt_00000000' / 'shard_rank0.npz',
                        offset=-10, nbytes=2)
        out = self._cli(tmp_path, '--verify')
        assert out.returncode == 1
        assert 'BAD' in out.stdout


# ---------------------------------------------------------------------------
# frontends
# ---------------------------------------------------------------------------

class TestFrontendWiring:
    def test_train_step_restore_state_across_meshes(self, tmp_path):
        """build_train_step + restore_state: the step compiles against the
        restored structure and places it per ITS config."""
        from paddle_tpu.nn.layer_base import buffer_values, param_values
        from paddle_tpu.core import rng as prng
        cfgA = _mesh_cfg(4, 1)
        net, opt = _net_opt()
        stepA = engine.build_train_step(net=net, loss=nn.MSELoss(),
                                        optimizer=opt, sharding=cfgA)
        state = stepA.init_state(param_values(net), buffer_values(net))
        for x, y in _data(3):
            state, out = stepA(state, ((x,), (y,)), prng.next_key())
        float(out.loss)
        mgr = CheckpointManager(tmp_path)
        mgr.save(state, step=0, sharding=cfgA)

        cfgB = _mesh_cfg(2, 1)
        netB, optB = _net_opt(seed=11)
        stepB = engine.build_train_step(net=netB, loss=nn.MSELoss(),
                                        optimizer=optB, sharding=cfgB)
        restored, meta = stepB.restore_state(mgr)
        for k, v in state['params'].items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(restored['params'][k]))
        # and it dispatches: the sharded program was compiled by adoption
        restored, out = stepB(restored, ((_data(1)[0][0],),
                                         (_data(1)[0][1],)),
                              prng.next_key())
        assert np.isfinite(float(out.loss))

    def test_rng_exact_resume_with_dropout(self, tmp_path):
        """Regression: a checkpoint carrying ``extra`` (RNG streams) is
        promoted to the manifest format even unsharded — a dropout net's
        resumed run must draw the SAME keys as the uninterrupted one."""
        def build(seed=7):
            paddle.seed(seed)
            net = nn.Sequential(nn.Linear(32, 64), nn.Dropout(0.3),
                                nn.Linear(64, 4))
            opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=net.parameters())
            return net, opt

        net, opt = build()
        full = engine.fit(net, nn.MSELoss(), opt, _data(6), epochs=2,
                          prefetch=0)
        net, opt = build()
        engine.fit(net, nn.MSELoss(), opt, _data(6), epochs=1, prefetch=0,
                   checkpoint=str(tmp_path))
        net2, opt2 = build(seed=99)
        resumed = engine.fit(net2, nn.MSELoss(), opt2, _data(6), epochs=2,
                             prefetch=0, resume_from=str(tmp_path))
        for k, v in full['state']['params'].items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(resumed['state']['params'][k]),
                err_msg=k)

    def test_model_fit_resumes_from_engine_checkpoint(self, tmp_path):
        """Model.fit(resume_from=) adopts an engine-layout sharded
        checkpoint (params + functional opt slots) saved on another
        mesh."""
        from paddle_tpu.hapi import Model
        net, opt = _net_opt()
        report = engine.fit(net, nn.MSELoss(), opt, _data(4), epochs=1,
                            prefetch=0, sharding=_mesh_cfg(4, 1),
                            checkpoint=str(tmp_path), checkpoint_every=0,
                            preempt_save=False)
        trained = _host_params(report['state'])

        paddle.seed(123)
        net2 = nn.Sequential(nn.Linear(32, 64), nn.Tanh(),
                             nn.Linear(64, 4))
        m = Model(net2)
        m.prepare(optimizer=paddle.optimizer.Adam(
            learning_rate=1e-2, parameters=net2.parameters()),
            loss=nn.MSELoss())
        m.fit(None, epochs=0, verbose=0, resume_from=str(tmp_path))
        for k, v in net2.state_dict().items():
            if k in trained:
                np.testing.assert_array_equal(trained[k], v.numpy(),
                                              err_msg=k)

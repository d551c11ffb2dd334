"""Tenancy + elasticity (docs/SERVING.md, "Tenancy + autoscaling").

The two halves of ROADMAP item 2's robustness story, tested end to end:

- **Admission isolation**: a ``faultinject.tenant_storm`` flooding one
  tenant of a shared engine sheds as ``'quota'`` at the front door when
  per-tenant ``TenantPolicy`` quotas are on, and every victim request is
  served within a pump of its no-storm solo wait — while quotas OFF the
  same storm queues the victim behind the backlog. DRR pop order under
  ``pump()`` is exactly deterministic, weights honored across pops.
- **Elastic replica count**: the ``FleetAutoscaler`` grows on sustained
  SLO burn (``faultinject.burn_ramp`` through the real signal path),
  boots the new replica warm from the compile-cache artifact tier
  (cache hits == program count, zero fresh compiles), shrinks through
  ``router.drain()`` with zero aborted in-flight requests, and its
  cooldown + hysteresis + sustain window provably cannot flap under an
  oscillating signal.
- **Doctor coverage**: ``noisy_neighbor`` and ``autoscale_flap`` fire
  on injector-driven runs and stay quiet on healthy ones.

Everything is manual-drive (``pump()``) on a virtual arbiter clock —
queue interleavings are pinned by the pump cadence, not wall-clock.
"""
import numpy as np
import pytest

from paddle_tpu import compilecache as cc
from paddle_tpu import observability as obs
from paddle_tpu.observability import doctor as doc
from paddle_tpu.observability import slo
from paddle_tpu.observability.timing import Stopwatch
from paddle_tpu.resilience import faultinject as fi
from paddle_tpu.serving import (BucketSpec, FleetAutoscaler, FleetRouter,
                                QueueFullError, QuotaExceededError,
                                ServingEngine, TenantArbiter, TenantPolicy,
                                WeightedFairQueue)
from paddle_tpu.serving import admission

pytestmark = pytest.mark.serving


def _example():
    return {'x': np.zeros((8,), np.float32)}


def _one():
    return {'x': np.ones((8,), np.float32)}


def _engine(tenants=None, buckets=(1, 2, 4), jit=False, capacity=64):
    eng = ServingEngine(queue_capacity=capacity, tenants=tenants)
    w = np.eye(8, dtype=np.float32)
    eng.register('m', predict_fn=lambda feeds: feeds['x'] @ w,
                 example=_example(), bucket_spec=BucketSpec(buckets),
                 jit_compile=jit)
    return eng   # manual drive: pump cadence IS the clock


def _compiles():
    return obs.snapshot()['counters'].get('jax.compiles', 0)


@pytest.fixture(autouse=True)
def _clean_slate():
    admission.reset_tenant_stats()
    slo.reset()
    cc.reset_stats()
    yield
    obs.disable()
    obs.reset()
    admission.reset_tenant_stats()
    slo.reset()
    cc.reset_stats()


# ---------------------------------------------------------------------------
# weighted-fair admission: DRR pop order
# ---------------------------------------------------------------------------

class _Req:
    """Bare queue citizen: tenant + liveness, nothing else."""

    def __init__(self, tenant):
        self.tenant = tenant
        self.sw = Stopwatch()
        self.queue_ms = 0.0

    def expired(self):
        return False


class TestWeightedFairQueue:
    def test_drr_pop_order_weights_held_across_pops(self):
        arb = TenantArbiter()
        arb.set_policy(TenantPolicy('A', weight=2.0))
        arb.set_policy(TenantPolicy('B', weight=1.0))
        q = WeightedFairQueue('m', capacity=16, arbiter=arb)
        for _ in range(4):
            q.push(_Req('A'))
        for _ in range(2):
            q.push(_Req('B'))
        assert q.tenants_queued() == {'A': 4, 'B': 2}
        # the DRR cursor and deficits persist ACROSS pops: weight 2:1
        # means every 3-slot window is A,A,B — not just the first
        first, _ = q.pop_ready_while(None, 3)
        second, _ = q.pop_ready_while(None, 3)
        assert [r.tenant for r in first] == ['A', 'A', 'B']
        assert [r.tenant for r in second] == ['A', 'A', 'B']
        assert len(q) == 0

    def test_drr_pop_order_deterministic_via_pump(self):
        def run():
            obs.reset()
            obs.enable()
            arb = TenantArbiter()
            arb.set_policy(TenantPolicy('A', weight=2.0))
            arb.set_policy(TenantPolicy('B', weight=1.0))
            eng = _engine(tenants=arb, buckets=(3,))
            pend = [eng.submit('m', _one(), tenant='A') for _ in range(6)]
            pend += [eng.submit('m', _one(), tenant='B') for _ in range(3)]
            while eng.pump():
                pass
            assert all(p.result(timeout=10).ok for p in pend)
            order = [e['tenant'] for e in obs.event_log()
                     if e.get('ev') == 'serving.request']
            eng.stop()
            obs.disable()
            obs.reset()
            return order
        # batch capacity 3, weights 2:1 -> every pump drains A,A,B; the
        # completion order is a pure function of the submit order
        assert run() == ['A', 'A', 'B'] * 3
        assert run() == ['A', 'A', 'B'] * 3   # and it is reproducible


# ---------------------------------------------------------------------------
# tenant storm: quota isolation
# ---------------------------------------------------------------------------

def _storm_round(quotas, storm=True, ticks=10, qps=6.0, seed=0):
    """One manual-drive round: per tick one virtual-clock storm burst +
    one victim request + one pump. Returns how many pumps each victim
    request waited from its submit to its completion (what the virtual
    clock and the queue decide: no wall time), per-reason storm sheds (as
    seen by the injector) and the admission ledger."""
    admission.reset_tenant_stats()
    clock = [0.0]
    arb = None
    if quotas:
        arb = TenantArbiter(clock=lambda: clock[0])
        arb.set_policy(TenantPolicy('storm', weight=1.0, rate=0.5,
                                    burst=1))
        arb.set_policy(TenantPolicy('victim', weight=4.0, rate=1000.0))
    eng = _engine(tenants=arb)
    pend, shed = [], {}
    pumps, submitted_at, waits = [0], {}, []

    def pump():
        worked = eng.pump()
        pumps[0] += 1
        for p in [p for p in submitted_at if p.done()]:
            waits.append(pumps[0] - submitted_at.pop(p))
        return worked

    for t in range(ticks):
        clock[0] = float(t)
        if storm:
            burst = fi.tenant_storm(eng, 'm', _one(), tenant='storm',
                                    qps=qps, duration_ticks=1,
                                    seed=seed + t)
            for r, n in burst['shed'].items():
                shed[r] = shed.get(r, 0) + n
        try:
            pend.append(eng.submit('m', _one(), tenant='victim'))
            submitted_at[pend[-1]] = pumps[0]
        except QueueFullError:
            pass
        pump()
    while pump():
        pass
    completed = sum(p.result(timeout=10).ok for p in pend)
    ledger = admission.tenant_stats()
    eng.stop()
    return {'waits': waits, 'completed': completed, 'offered': ticks,
            'shed': shed, 'ledger': ledger}


@pytest.mark.fault
class TestTenantIsolation:
    def test_quota_overflow_is_shaped(self):
        clock = [0.0]
        arb = TenantArbiter(clock=lambda: clock[0])
        arb.set_policy(TenantPolicy('t', rate=1.0, burst=1))
        eng = _engine(tenants=arb)
        eng.submit('m', _one(), tenant='t')          # spends the bucket
        with pytest.raises(QuotaExceededError) as ei:
            eng.submit('m', _one(), tenant='t')
        assert isinstance(ei.value, QueueFullError)  # shed, not a crash
        assert ei.value.reason == 'quota'
        assert ei.value.tenant == 't'
        while eng.pump():
            pass
        eng.stop()

    def test_victim_p99_isolated_with_quotas_on(self):
        solo = _storm_round(quotas=False, storm=False)
        off = _storm_round(quotas=False)
        obs.enable()
        on = _storm_round(quotas=True)
        snap = obs.snapshot()
        # alone, a victim request is served by the pump after its submit
        base = max(solo['waits'])
        assert base == 1 and len(solo['waits']) == solo['offered']
        # quotas ON: every victim request completes, none more than one
        # pump (the slack) later than it would alone
        assert on['completed'] == on['offered'] == len(on['waits'])
        assert max(on['waits']) <= base + 1, (on['waits'], solo['waits'])
        # quotas OFF: the same storm queues the victim behind the whole
        # backlog — degradation, not isolation
        assert max(off['waits']) >= 2 * base, (off['waits'], solo['waits'])
        # the storm was shed at the front door as 'quota', nothing else
        assert set(on['shed']) == {'quota'} and sum(on['shed'].values()) > 0
        assert 'quota' not in off['shed']
        # attribution: the always-on ledger and the labeled telemetry
        # counters both pin the sheds on the storm tenant
        n_quota = sum(on['shed'].values())
        assert on['ledger']['storm']['shed'] == {'quota': n_quota}
        ctr = snap['counters']
        assert ctr.get('serving.shed.quota', 0) == n_quota
        assert ctr.get('serving.tenant.shed{tenant=storm}', 0) == n_quota
        assert on['ledger']['victim']['requests'] == on['offered']


# ---------------------------------------------------------------------------
# autoscaler: grow / shrink / cooldown / flap-proofing
# ---------------------------------------------------------------------------

def _fleet(n=1, factory=None):
    factory = factory or (lambda name: _engine())
    router = FleetRouter()
    for i in range(n):
        router.add_replica(f'r{i}', factory(f'r{i}'))
    return router


class TestAutoscaler:
    def test_degenerate_band_and_envelope_are_rejected(self):
        router = _fleet()
        with pytest.raises(ValueError):
            FleetAutoscaler(router, replica_factory=_engine,
                            burn_low=1.0, burn_high=1.0)
        with pytest.raises(ValueError):
            FleetAutoscaler(router, replica_factory=_engine,
                            min_replicas=0)
        with pytest.raises(ValueError):
            FleetAutoscaler(router, replica_factory=_engine,
                            min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError):
            FleetAutoscaler(router)      # no factory, no supervisor

    def test_grow_shrink_cooldown_sequence(self):
        router = _fleet(1)
        sig = {'v': 5.0}
        auto = FleetAutoscaler(router,
                               replica_factory=lambda name: _engine(),
                               min_replicas=1, max_replicas=3,
                               burn_high=1.0, burn_low=0.25,
                               sustain_ticks=2, cooldown_ticks=2,
                               warmup=False, signal=lambda: sig['v'])
        # sustained pressure: grow only after sustain_ticks consecutive
        # observations, then a full cooldown before the next action —
        # observations taken DURING cooldown count toward the next
        # window, so the second grow lands on the first live tick
        assert [auto.tick() for _ in range(8)] == \
            [None, 'grow', 'cooldown', 'cooldown', 'grow',
             'cooldown', 'cooldown', None]      # None: at max_replicas
        assert len(router.replicas()) == 3
        sig['v'] = 0.0
        # calm: same shape downwards, floored at min_replicas
        assert [auto.tick() for _ in range(8)] == \
            [None, 'shrink', 'cooldown', 'cooldown', 'shrink',
             'cooldown', 'cooldown', None]      # None: at min_replicas
        assert len(router.replicas()) == 1
        grows = [d for d in auto.decisions() if d['action'] == 'grow']
        shrinks = [d for d in auto.decisions() if d['action'] == 'shrink']
        assert len(grows) == 2 and len(shrinks) == 2
        assert all('replica' in d for d in grows + shrinks)
        assert all(d['aborted'] == 0 for d in shrinks)
        for h in router.replicas():
            h.engine.stop()

    def test_oscillating_signal_cannot_flap(self):
        obs.enable()
        router = _fleet(2)
        flip = {'n': 0}

        def sig():
            flip['n'] += 1
            return 5.0 if flip['n'] % 2 else 0.0
        auto = FleetAutoscaler(router,
                               replica_factory=lambda name: _engine(),
                               min_replicas=1, max_replicas=4,
                               burn_high=1.0, burn_low=0.25,
                               sustain_ticks=2, cooldown_ticks=1,
                               warmup=False, signal=sig)
        # a signal whipsawing across both thresholds every tick can never
        # sustain either condition: the fleet does not move at all
        assert all(auto.tick() is None for _ in range(12))
        assert len(router.replicas()) == 2
        assert all(d['action'] == 'steady' for d in auto.decisions())
        # ... and the flap doctor agrees there is nothing to report
        assert not list(doc.detect_autoscale_flap(
            events=obs.event_log(), snapshot=obs.snapshot()))
        for h in router.replicas():
            h.engine.stop()

    def test_grows_on_sustained_slo_burn(self):
        # the REAL signal path: faultinject.burn_ramp drives the peak
        # per-model slo burn over the high-water mark
        router = _fleet(1)
        auto = FleetAutoscaler(router,
                               replica_factory=lambda name: _engine(),
                               min_replicas=1, max_replicas=2,
                               burn_high=1.0, burn_low=0.25,
                               sustain_ticks=2, cooldown_ticks=0,
                               warmup=False)
        slo.set_objective('m', 50.0, 0.9)
        achieved = fi.burn_ramp('m', burn=3.0, requests=20)
        assert achieved >= 1.0
        actions = [auto.tick() for _ in range(3)]
        assert actions[0] is None and 'grow' in actions
        assert len(router.replicas()) == 2
        slo.clear_objective('m')
        for h in router.replicas():
            h.engine.stop()

    def test_shrink_drains_in_flight_zero_aborted(self):
        router = _fleet(2)
        pend = [router.submit('m', _one(), deadline_ms=20000)
                for _ in range(6)]
        auto = FleetAutoscaler(router,
                               replica_factory=lambda name: _engine(),
                               min_replicas=1, max_replicas=2,
                               burn_high=1.0, burn_low=0.25,
                               sustain_ticks=1, cooldown_ticks=0,
                               warmup=False, signal=lambda: 0.0)
        assert auto.tick() == 'shrink'
        assert len(router.replicas()) == 1
        shrink = [d for d in auto.decisions()
                  if d['action'] == 'shrink'][0]
        assert shrink['aborted'] == 0    # the drain contract
        for h in router.replicas():
            while h.engine.pump():
                pass
        # every request submitted BEFORE the shrink completes: the
        # victim's share finished inside drain(), the survivor's here
        assert sum(1 for p in pend if p.result(timeout=10).ok) == 6
        for h in router.replicas():
            h.engine.stop()


# ---------------------------------------------------------------------------
# elasticity x compile cache: warm scale-up, compile-flat chaos
# ---------------------------------------------------------------------------

@pytest.mark.fault
class TestWarmElasticity:
    def test_scale_up_boots_warm_from_artifact_tier(self, tmp_path):
        obs.enable()
        with cc.use(str(tmp_path)):      # first boot populates the tier
            e0 = _engine(jit=True)
            e0.warmup()
        assert cc.stats()['stores'] == 3          # one per bucket
        router = FleetRouter()
        router.add_replica('r0', e0)
        auto = FleetAutoscaler(
            router, replica_factory=lambda name: _engine(jit=True),
            min_replicas=1, max_replicas=2, burn_high=1.0, burn_low=0.25,
            sustain_ticks=1, cooldown_ticks=0, warmup=True,
            artifact_dir=str(tmp_path), signal=lambda: 5.0)
        cc.reset_stats()
        before = _compiles()
        assert auto.tick() == 'grow'
        st = cc.stats()
        # zero-compile elasticity: the new replica's whole program set
        # deserializes — hits == programs, not one fresh compile
        assert st['hits'] == 3 and st['misses'] == 0, st
        assert _compiles() == before
        # and the warm replica actually serves
        pend = [router.submit('m', _one(), deadline_ms=20000)
                for _ in range(4)]
        for h in router.replicas():
            while h.engine.pump():
                pass
        assert all(p.result(timeout=10).ok for p in pend)
        for h in router.replicas():
            h.engine.stop()

    def test_chaos_cycle_stays_compile_flat(self, tmp_path):
        obs.enable()
        with cc.use(str(tmp_path)):
            e0 = _engine(jit=True, capacity=256)
            e0.warmup()
        router = FleetRouter()
        router.add_replica('r0', e0)
        sig = {'v': 0.0}
        auto = FleetAutoscaler(
            router,
            replica_factory=lambda name: _engine(jit=True, capacity=256),
            min_replicas=1, max_replicas=2, burn_high=1.0, burn_low=0.25,
            sustain_ticks=1, cooldown_ticks=0, warmup=True,
            artifact_dir=str(tmp_path), signal=lambda: sig['v'])
        base = _compiles()
        # storm -> grow -> traffic on both replicas -> calm -> shrink:
        # the whole elastic cycle compiles NOTHING after warmup
        for t in range(4):
            fi.tenant_storm(e0, 'm', _one(), tenant='storm', qps=5.0,
                            duration_ticks=1, seed=t)
            e0.pump()
        sig['v'] = 5.0
        assert auto.tick() == 'grow'
        pend = [router.submit('m', _one(), deadline_ms=20000)
                for _ in range(8)]
        for h in router.replicas():
            while h.engine.pump():
                pass
        sig['v'] = 0.0
        assert auto.tick() == 'shrink'
        for h in router.replicas():
            while h.engine.pump():
                pass
        assert sum(1 for p in pend if p.result(timeout=10).ok) == 8
        assert _compiles() == base
        shrink = [d for d in auto.decisions()
                  if d['action'] == 'shrink'][0]
        assert shrink['aborted'] == 0
        for h in router.replicas():
            h.engine.stop()


# ---------------------------------------------------------------------------
# doctor: noisy_neighbor + autoscale_flap
# ---------------------------------------------------------------------------

@pytest.mark.fault
class TestDoctor:
    def test_registered(self):
        assert doc.DETECTORS['noisy_neighbor'] is doc.detect_noisy_neighbor
        assert doc.DETECTORS['autoscale_flap'] is doc.detect_autoscale_flap

    def test_noisy_neighbor_fires_on_storm_quiet_on_balanced(self):
        obs.enable()
        _storm_round(quotas=True)
        hits = list(doc.detect_noisy_neighbor(events=obs.event_log(),
                                              snapshot=obs.snapshot()))
        assert len(hits) == 1
        ev = hits[0]['evidence']
        assert hits[0]['cause'] == 'noisy_neighbor'
        assert ev['tenant'] == 'storm' and ev['share'] >= 0.6
        assert ev.get('victim') == 'victim'
        assert 'TenantPolicy' in hits[0]['fix']
        obs.reset()
        admission.reset_tenant_stats()
        # balanced multi-tenant traffic with no sheds: quiet
        eng = _engine()
        pend = [eng.submit('m', _one(), tenant=t)
                for t in ('A', 'B') for _ in range(3)]
        while eng.pump():
            pass
        assert all(p.result(timeout=10).ok for p in pend)
        assert not list(doc.detect_noisy_neighbor(
            events=obs.event_log(), snapshot=obs.snapshot()))
        eng.stop()

    def test_autoscale_flap_fires_on_tight_reversals(self):
        evs = [{'ev': 'fleet.autoscale', 'action': a, 'tick': t,
                'cooldown_ticks': 1}
               for a, t in (('grow', 1), ('shrink', 3), ('grow', 5),
                            ('shrink', 7))]
        hits = list(doc.detect_autoscale_flap(events=evs))
        assert len(hits) == 1 and hits[0]['cause'] == 'autoscale_flap'
        assert hits[0]['evidence']['reversals'] == 3
        # same actions, spaced far beyond the cooldown window: quiet
        spaced = [dict(e, tick=e['tick'] * 100) for e in evs]
        assert not list(doc.detect_autoscale_flap(events=spaced))

    def test_autoscale_flap_counter_fallback(self):
        snap = {'counters': {'fleet.autoscale.grows': 2,
                             'fleet.autoscale.shrinks': 2}}
        hits = list(doc.detect_autoscale_flap(events=[], snapshot=snap))
        assert len(hits) == 1 and hits[0]['severity'] == 'warning'
        assert not list(doc.detect_autoscale_flap(
            events=[], snapshot={'counters':
                                 {'fleet.autoscale.grows': 2}}))

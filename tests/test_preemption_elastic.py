"""Checkpoint-aware preemption, elastic/spot pools, and the
reservation-lifecycle invariants they flush out: exactly-once
release/settle under kill-vs-LAUNCHING races, epoch-guarded stale
terminal events, checkpoint-bounded lost work, resize drains, and the
provisioning controller."""
import threading
import time

import pytest

from repro.core.engine.cluster import Cluster
from repro.core.engine.dashboard import scheduler_page
from repro.core.engine.events import EventBus, TOPIC_CONTAINER_STATUS
from repro.core.engine.launcher import ThreadPoolRunner, VirtualRunner
from repro.core.engine.lifecycle import (IllegalTransition, JobPreempted,
                                         JobState, check_transition)
from repro.core.engine.placement import Placement
from repro.core.engine.registry import JobRegistry, JobSpec
from repro.core.engine.scheduler import Scheduler
from repro.core.provision.elastic import ElasticController, PoolPolicy
from repro.core.provision.pricing import CPU_PRICING, spot_pricing
from repro.train.fault import preemption_hook


def _spec(name, duration=1.0, resources=None, user="u", priority=0,
          args=None):
    return JobSpec(name=name, project="p", user=user, duration=duration,
                   priority=priority, resources=resources or {},
                   args=args or {})


def _engine(capacity, *, quota_k=100, preemption=True,
            starvation_threshold=0.0, checkpoint_interval=None, **kw):
    registry = JobRegistry()
    bus = EventBus()
    runner = VirtualRunner(registry, bus,
                           checkpoint_interval=checkpoint_interval)
    cl = Cluster(capacity, {k: 0.0 for k in capacity})
    sched = Scheduler(registry, runner, bus, quota_k=quota_k, cluster=cl,
                      preemption=preemption,
                      starvation_threshold=starvation_threshold, **kw)
    return registry, bus, runner, sched, cl


# -- lifecycle ------------------------------------------------------------
def test_preempted_state_transitions():
    check_transition(JobState.RUNNING, JobState.PREEMPTED)
    check_transition(JobState.PREEMPTED, JobState.QUEUED)
    check_transition(JobState.PREEMPTED, JobState.KILLED)
    for bad in [(JobState.PREEMPTED, JobState.RUNNING),
                (JobState.QUEUED, JobState.PREEMPTED),
                (JobState.LAUNCHING, JobState.PREEMPTED),
                (JobState.FINISHED, JobState.PREEMPTED)]:
        with pytest.raises(IllegalTransition):
            check_transition(*bad)


# -- starvation-triggered preemption -------------------------------------
def test_starved_high_priority_preempts_lowest_priority():
    registry, bus, runner, sched, cl = _engine(
        {"vcpu": 4.0}, starvation_threshold=30.0, checkpoint_interval=10.0)
    hog = registry.submit(_spec("hog", duration=1000.0,
                                resources={"vcpu": 4}))
    sched.submit(hog)
    assert registry.get(hog.job_id).state == JobState.RUNNING
    hi = registry.submit(_spec("hi", duration=50.0, resources={"vcpu": 4},
                               user="vip", priority=10))
    sched.submit(hi)
    # not yet starved: waited 0 < threshold
    assert registry.get(hi.job_id).state == JobState.QUEUED
    assert registry.get(hog.job_id).state == JobState.RUNNING
    runner.advance_to(40.0)
    sched._maybe_launch()           # poke dispatch past the threshold
    assert registry.get(hi.job_id).state == JobState.RUNNING
    hog_job = registry.get(hog.job_id)
    assert hog_job.state == JobState.QUEUED     # preempted -> requeued
    assert hog_job.preemptions == 1
    assert hog_job.epoch == 1
    assert sched.stats["preempted"] == 1
    # fair-share settled the actual partial runtime of the hog's segment
    assert sched._usage[("p", "u")] == pytest.approx(40.0)
    sched.run_to_completion()
    assert registry.get(hi.job_id).state == JobState.FINISHED
    assert registry.get(hog.job_id).state == JobState.FINISHED
    # resumed from the 40s checkpoint: 50 (hi) + 960 remaining, not 1000
    assert runner.now == pytest.approx(40.0 + 50.0 + 960.0)
    assert runner.preempt_stats["max_lost_s"] <= 10.0 + 1e-9


def test_starved_policy_head_found_behind_low_priority_same_queue():
    """A starved high-priority job parked *behind* an older low-priority
    job in the same queue is that queue's policy head — the starvation
    scan must find it in candidate sort order, not arrival order."""
    registry, bus, runner, sched, cl = _engine(
        {"vcpu": 4.0}, starvation_threshold=30.0, checkpoint_interval=10.0)
    mid = registry.submit(_spec("mid", duration=1000.0,
                                resources={"vcpu": 4}, user="other",
                                priority=5))
    sched.submit(mid)               # runs, holds the whole pool
    a = registry.submit(_spec("a", duration=1000.0, resources={"vcpu": 4}))
    sched.submit(a)                 # priority 0, arrives first
    b = registry.submit(_spec("b", duration=50.0, resources={"vcpu": 4},
                              priority=10))
    sched.submit(b)                 # policy head despite arriving second
    runner.advance_to(40.0)
    sched._maybe_launch()
    # b's priority 10 justifies preempting the priority-5 runner; a's
    # priority 0 would not — scanning arrival order would find a, bail
    assert registry.get(b.job_id).state == JobState.RUNNING
    assert registry.get(mid.job_id).preemptions == 1
    sched.run_to_completion()
    for j in (mid, a, b):
        assert registry.get(j.job_id).state == JobState.FINISHED


def test_killed_while_preempted_queued_frees_runner_state():
    """A job killed after a preemption (while re-queued, with no live
    run in the virtual runner) must not leak its checkpoint progress or
    duration draws for the life of the engine."""
    registry, bus, runner, sched, cl = _engine(
        {"vcpu": 4.0}, starvation_threshold=30.0, checkpoint_interval=10.0)
    hog = registry.submit(_spec("hog", duration=1000.0,
                                resources={"vcpu": 4}))
    sched.submit(hog)
    hi = registry.submit(_spec("hi", duration=500.0, resources={"vcpu": 4},
                               user="vip", priority=10))
    sched.submit(hi)
    runner.advance_to(40.0)
    sched._maybe_launch()           # hog preempted; hi occupies the pool
    assert registry.get(hog.job_id).state == JobState.QUEUED
    assert hog.job_id in runner._done_frac
    sched.kill(hog.job_id)
    assert hog.job_id not in runner._done_frac
    assert hog.job_id not in runner._dur_cache
    sched.run_to_completion()
    assert registry.get(hi.job_id).state == JobState.FINISHED


def test_equal_priority_never_preempted():
    registry, bus, runner, sched, cl = _engine(
        {"vcpu": 4.0}, starvation_threshold=0.0)
    a = registry.submit(_spec("a", duration=100.0, resources={"vcpu": 4}))
    sched.submit(a)
    b = registry.submit(_spec("b", duration=10.0, resources={"vcpu": 4},
                              user="other"))
    sched.submit(b)
    runner.advance_to(50.0)
    sched._maybe_launch()
    # same effective priority: b waits for a to finish, no preemption
    assert registry.get(a.job_id).state == JobState.RUNNING
    assert sched.stats["preempted"] == 0
    sched.run_to_completion()
    assert registry.get(b.job_id).state == JobState.FINISHED


def test_checkpoint_interval_bounds_lost_work():
    registry, bus, runner, sched, cl = _engine(
        {"vcpu": 1.0}, checkpoint_interval=10.0)
    j = registry.submit(_spec("train", duration=100.0,
                              resources={"vcpu": 1}))
    sched.submit(j)
    runner.advance_to(37.0)
    assert sched.preempt(j.job_id)
    assert runner.preempt_stats["lost_work_s"] == pytest.approx(7.0)
    # requeued and (capacity being free) immediately relaunched with only
    # the un-checkpointed remainder left
    job = registry.get(j.job_id)
    assert job.state == JobState.RUNNING
    assert runner.expected_duration(job) == pytest.approx(70.0)
    sched.run_to_completion()
    assert runner.now == pytest.approx(37.0 + 70.0)
    assert job.state == JobState.FINISHED


def test_no_checkpoint_interval_restarts_from_zero():
    registry, bus, runner, sched, cl = _engine({"vcpu": 1.0})
    j = registry.submit(_spec("nockpt", duration=100.0,
                              resources={"vcpu": 1}))
    sched.submit(j)
    runner.advance_to(37.0)
    assert sched.preempt(j.job_id)
    assert runner.preempt_stats["lost_work_s"] == pytest.approx(37.0)
    sched.run_to_completion()
    assert runner.now == pytest.approx(37.0 + 100.0)


def test_per_job_checkpoint_interval_override():
    registry, bus, runner, sched, cl = _engine(
        {"vcpu": 1.0}, checkpoint_interval=50.0)
    j = registry.submit(_spec("fine", duration=100.0,
                              resources={"vcpu": 1},
                              args={"checkpoint_interval": 5.0}))
    sched.submit(j)
    runner.advance_to(23.0)
    assert sched.preempt(j.job_id)
    assert runner.preempt_stats["lost_work_s"] == pytest.approx(3.0)


def test_preempt_refuses_non_running_and_kill_wins():
    registry, bus, runner, sched, cl = _engine({"vcpu": 1.0})
    a = registry.submit(_spec("a", duration=10.0, resources={"vcpu": 1}))
    sched.submit(a)
    b = registry.submit(_spec("b", duration=10.0, resources={"vcpu": 1}))
    sched.submit(b)                 # queued behind a
    assert not sched.preempt(b.job_id)      # QUEUED: nothing to preempt
    sched.kill(a.job_id)
    assert not sched.preempt(a.job_id)      # KILLED: terminal wins
    sched.run_to_completion()
    assert registry.get(b.job_id).state == JobState.FINISHED


def test_fair_share_charges_every_segment():
    """A job preempted twice charges usage for all three partial
    segments — the sum of actual runtimes, not the declared duration."""
    registry, bus, runner, sched, cl = _engine(
        {"vcpu": 1.0}, checkpoint_interval=10.0)
    j = registry.submit(_spec("seg", duration=100.0, resources={"vcpu": 1}))
    sched.submit(j)
    runner.advance_to(20.0)
    sched.preempt(j.job_id)         # segment 1: 20s, checkpointed 20
    runner.advance_to(50.0)
    sched.preempt(j.job_id)         # segment 2: 30s, progress 50
    sched.run_to_completion()       # segment 3: the remaining 50
    assert registry.get(j.job_id).state == JobState.FINISHED
    assert registry.get(j.job_id).preemptions == 2
    assert sched._usage[("p", "u")] == pytest.approx(20.0 + 30.0 + 50.0)


# -- epoch guard: stale terminal events ----------------------------------
def test_stale_terminal_event_cannot_settle_new_incarnation():
    registry, bus, runner, sched, cl = _engine(
        {"vcpu": 1.0}, checkpoint_interval=10.0)
    j = registry.submit(_spec("j", duration=100.0, resources={"vcpu": 1}))
    sched.submit(j)
    runner.advance_to(30.0)
    sched.preempt(j.job_id)         # epoch 0 -> 1; relaunches immediately
    job = registry.get(j.job_id)
    assert job.state == JobState.RUNNING and job.epoch == 1
    assert cl.used["vcpu"] == 1.0
    # a worker from the superseded incarnation reports FINISHED late
    bus.publish(TOPIC_CONTAINER_STATUS,
                {"job_id": j.job_id, "status": "FINISHED", "epoch": 0})
    assert job.state == JobState.RUNNING        # ignored
    assert cl.used["vcpu"] == 1.0               # reservation intact
    assert cl.stats["release_underflow"] == 0
    sched.run_to_completion()
    assert job.state == JobState.FINISHED
    assert cl.used["vcpu"] == 0.0


# -- satellite: kill racing LAUNCHING — exactly-once release + settle -----
class CountingCluster(Cluster):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.effective_releases = 0

    def release(self, job_id):
        req = super().release(job_id)
        if req is not None:
            self.effective_releases += 1
        return req


class GatedThreadRunner(ThreadPoolRunner):
    """launch() parks the job instead of handing it to a worker, so a
    test can interleave a kill while the job is still LAUNCHING — the
    exact race the scheduler's settle path must survive."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.parked = []

    def launch(self, job):
        job.preempt_flag = threading.Event()
        with self._cv:
            self._inflight[job.job_id] = \
                self._inflight.get(job.job_id, 0) + 1
        self.parked.append(job)

    def run_parked(self):
        for job in self.parked:
            self._run(job)
        del self.parked[:]


def test_kill_racing_launching_settles_exactly_once():
    registry = JobRegistry()
    bus = EventBus()
    runner = GatedThreadRunner(registry, bus, max_workers=1)
    cl = CountingCluster({"vcpu": 1.0}, {"vcpu": 0.0})
    sched = Scheduler(registry, runner, bus, quota_k=10, cluster=cl)
    usage_calls = []
    orig_charge = sched._charge_usage
    sched._charge_usage = lambda key, amt: (usage_calls.append(amt),
                                            orig_charge(key, amt))[1]
    j = registry.submit(_spec("victim", duration=None,
                              resources={"vcpu": 1}))
    j.spec.fn = lambda wd, job: {"ran": True}
    sched.submit(j)
    assert registry.get(j.job_id).state == JobState.LAUNCHING
    assert cl.used["vcpu"] == 1.0
    killed_events = []
    bus.subscribe(TOPIC_CONTAINER_STATUS,
                  lambda m: killed_events.append(m)
                  if m.get("status") == "KILLED" else None)
    sched.kill(j.job_id)            # races the worker pickup
    assert cl.used["vcpu"] == 0.0   # slot freed immediately
    runner.run_parked()             # worker finally picks the job up
    runner.shutdown()
    assert registry.get(j.job_id).state == JobState.KILLED
    # the invariants the audit pins: one effective release, one
    # fair-share settle, one terminal event, zero accounting drift
    assert cl.effective_releases == 1
    assert len(usage_calls) == 1
    assert len(killed_events) == 1
    assert cl.used["vcpu"] == 0.0
    assert cl.stats["release_underflow"] == 0


def test_threadpool_cooperative_preempt_resumes():
    registry = JobRegistry()
    bus = EventBus()
    runner = ThreadPoolRunner(registry, bus, max_workers=2)
    cl = Cluster({"vcpu": 1.0}, {"vcpu": 0.0})
    sched = Scheduler(registry, runner, bus, quota_k=10, cluster=cl,
                      preemption=True, starvation_threshold=1e9)
    calls = []
    waiting = threading.Event()

    def fn(workdir, job):
        calls.append(job.epoch)
        if len(calls) == 1:
            hook = preemption_hook(job)
            flag = job.preempt_flag     # this incarnation's signal
            waiting.set()
            assert flag.wait(10.0), "preempt signal never came"
            hook(step=7)            # raises the external JobPreempted
            raise AssertionError("hook should have raised")
        return {"resumed": True}

    j = registry.submit(JobSpec(name="coop", project="p", user="u", fn=fn,
                                resources={"vcpu": 1}))
    sched.submit(j)
    deadline = time.monotonic() + 10.0
    while registry.get(j.job_id).state != JobState.RUNNING:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    # preempt the fn while it waits: a preemption before the worker
    # thread reaches the fn relaunches the job, and the two incarnations
    # then race for the first call
    assert waiting.wait(10.0)
    assert sched.preempt(j.job_id)
    deadline = time.monotonic() + 10.0
    while registry.get(j.job_id).state not in (JobState.FINISHED,
                                               JobState.FAILED):
        assert time.monotonic() < deadline, registry.get(j.job_id).state
        time.sleep(0.005)
    runner.shutdown()
    job = registry.get(j.job_id)
    assert job.state == JobState.FINISHED, job.error
    assert job.preemptions == 1 and job.epoch == 1
    # two incarnations ran; the second saw the bumped epoch (the first
    # may observe either 0 or 1 depending on when the signal lands)
    assert len(calls) == 2 and calls[-1] == 1
    assert job.outputs.get("resumed") is True
    assert cl.used["vcpu"] == 0.0
    assert cl.stats["release_underflow"] == 0


def test_spurious_jobpreempted_fails_the_job():
    registry = JobRegistry()
    bus = EventBus()
    runner = ThreadPoolRunner(registry, bus, max_workers=1)
    sched = Scheduler(registry, runner, bus, quota_k=10,
                      cluster=Cluster({"vcpu": 1.0}, {"vcpu": 0.0}))

    def fn(workdir, job):
        raise JobPreempted("nobody asked")

    j = registry.submit(JobSpec(name="spurious", project="p", user="u",
                                fn=fn, resources={"vcpu": 1}))
    sched.submit(j)
    sched.run_to_completion()
    runner.shutdown()
    assert registry.get(j.job_id).state == JobState.FAILED


# -- satellite: release-underflow drift counter ---------------------------
def test_release_underflow_is_counted_not_masked():
    cl = Cluster({"vcpu": 4.0}, {"vcpu": 0.0})
    cl.reserve("a", {"vcpu": 2.0})
    # simulate drifted books: a second holder appears without a reserve
    cl._held["ghost"] = {"vcpu": 3.0}
    cl.release("a")
    assert cl.stats["release_underflow"] == 0
    cl.release("ghost")             # would drive used to -3
    assert cl.used["vcpu"] == 0.0   # still clamped (pool stays usable)
    assert cl.stats["release_underflow"] == 1
    assert cl.stats["release_underflow_amount"] == pytest.approx(3.0)
    # idempotent double release of a normal job does NOT count as drift
    cl.reserve("b", {"vcpu": 1.0})
    cl.release("b")
    cl.release("b")
    assert cl.stats["release_underflow"] == 1


# -- satellite: zero-capacity utilization + dashboard ---------------------
def test_zero_capacity_dimension_reports_inf_not_zero():
    cl = Cluster({"vcpu": 2.0}, {"vcpu": 0.0})
    cl.reserve("a", {"vcpu": 2.0})
    cl.resize({"vcpu": 0.0})        # shrink below the live reservation
    util = cl.utilization()
    assert util["vcpu"] == float("inf")     # flagged, not 0%
    cl.release("a")
    assert cl.utilization()["vcpu"] == 0.0  # empty zero-cap dim is 0


def test_dashboard_renders_overcommit_without_zerodivision():
    registry, bus, runner, sched, cl = _engine({"vcpu": 2.0},
                                               preemption=False)
    j = registry.submit(_spec("j", duration=100.0, resources={"vcpu": 2}))
    sched.submit(j)
    cl.resize({"vcpu": 0.0})
    page = scheduler_page(sched)    # must not raise ZeroDivisionError
    assert "OVERCOMMIT" in page
    sched.run_to_completion()
    assert "OVERCOMMIT" not in scheduler_page(sched)


# -- elasticity: resize + drain ------------------------------------------
def test_resize_grow_admits_waiting_job():
    registry, bus, runner, sched, cl = _engine({"vcpu": 1.0},
                                               preemption=False)
    a = registry.submit(_spec("a", duration=100.0, resources={"vcpu": 1}))
    sched.submit(a)
    b = registry.submit(_spec("b", duration=10.0, resources={"vcpu": 1},
                              user="other"))
    sched.submit(b)
    assert registry.get(b.job_id).state == JobState.QUEUED
    sched.resize_pool(cl.name or "default", {"vcpu": 2.0})
    assert registry.get(b.job_id).state == JobState.RUNNING
    sched.run_to_completion()


def test_resize_shrink_drains_via_preemption():
    registry, bus, runner, sched, cl = _engine(
        {"vcpu": 2.0}, checkpoint_interval=5.0)
    a = registry.submit(_spec("a", duration=100.0, resources={"vcpu": 1}))
    sched.submit(a)
    runner.advance_to(1.0)
    b = registry.submit(_spec("b", duration=100.0, resources={"vcpu": 1},
                              user="other"))
    sched.submit(b)                 # b launched later than a
    overage = sched.resize_pool(cl.name or "default", {"vcpu": 1.0})
    assert overage == {"vcpu": pytest.approx(1.0)}
    # the latest-started reservation drained through the preemption path
    assert registry.get(b.job_id).preemptions == 1
    assert registry.get(a.job_id).state == JobState.RUNNING
    assert cl.used["vcpu"] <= 1.0 + 1e-9
    assert sched.stats["drained"] == 1
    sched.run_to_completion()
    assert registry.get(a.job_id).state == JobState.FINISHED
    assert registry.get(b.job_id).state == JobState.FINISHED


def test_spot_reclaim_preempts_and_requeues():
    registry = JobRegistry()
    bus = EventBus()
    runner = VirtualRunner(registry, bus, checkpoint_interval=5.0)
    spot = Cluster({"vcpu": 2.0}, {"vcpu": 0.0}, name="spot", spot=True,
                   reclaim_rate=1e-4)
    sched = Scheduler(registry, runner, bus, quota_k=10,
                      placement=Placement({"spot": spot}), preemption=True,
                      starvation_threshold=1e9)
    jobs = [registry.submit(_spec(f"s{i}", duration=50.0,
                                  resources={"vcpu": 1})) for i in range(2)]
    for j in jobs:
        sched.submit(j)
    runner.advance_to(12.0)
    victims = sched.reclaim("spot")
    assert len(victims) == 2
    assert sched.stats["reclaimed"] == 2
    # capacity untouched (a transient reclaim): both relaunch and resume
    sched.run_to_completion()
    for j in jobs:
        job = registry.get(j.job_id)
        assert job.state == JobState.FINISHED
        assert job.preemptions == 1
    assert runner.preempt_stats["max_lost_s"] <= 5.0 + 1e-9


# -- elastic controller ---------------------------------------------------
def test_controller_grows_under_pressure_and_shrinks_idle():
    registry, bus, runner, sched, cl = _engine({"vcpu": 8.0},
                                               preemption=False)
    pool = cl.name or "default"
    ctl = ElasticController(sched, {pool: PoolPolicy(
        node_shape={"vcpu": 8.0}, min_nodes=1, max_nodes=3,
        grow_at=0.9, shrink_at=0.3, cooldown_s=10.0)})
    assert ctl.nodes(pool) == 1
    jobs = [registry.submit(_spec(f"j{i}", duration=100.0,
                                  resources={"vcpu": 8})) for i in range(2)]
    for j in jobs:
        sched.submit(j)             # one runs (util 1.0), one queues
    decs = ctl.step(now=0.0)
    assert [d.action for d in decs] == ["grow"]
    assert ctl.nodes(pool) == 2
    assert registry.get(jobs[1].job_id).state == JobState.RUNNING
    # cooldown: an immediate second round does nothing
    assert ctl.step(now=1.0) == []
    sched.run_to_completion()
    # idle now: shrink back down to min_nodes, then hold
    assert [d.action for d in ctl.step(now=200.0)] == ["shrink"]
    assert ctl.step(now=300.0) == []        # at min_nodes
    assert ctl.nodes(pool) == 1
    # node-hours integral: 2 nodes for [0, 200), 1 node for [200, 3600)
    hours = ctl.node_hours(until=3600.0)
    assert hours[pool] == pytest.approx(
        (2 * 200.0 + 1 * 3400.0) / 3600.0, rel=1e-6)


def test_controller_node_hours_integral():
    registry, bus, runner, sched, cl = _engine({"vcpu": 8.0},
                                               preemption=False)
    pool = cl.name or "default"
    ctl = ElasticController(sched, {pool: PoolPolicy(
        node_shape={"vcpu": 8.0}, min_nodes=1, max_nodes=4)})
    # no decisions: flat 1 node for an hour
    assert ctl.node_hours(until=3600.0)[pool] == pytest.approx(1.0)
    assert ctl.provisioned_cost(3600.0, {pool: 2.5}) == pytest.approx(2.5)


# -- spot-aware placement -------------------------------------------------
def test_placement_prices_spot_risk_by_runtime():
    ondemand = Cluster({"vcpu": 8.0}, name="ondemand")
    spot = Cluster({"vcpu": 8.0}, name="spot", spot=True,
                   reclaim_rate=1.0 / 1800.0)     # ~1 reclaim / 30 min
    catalog = {"ondemand": CPU_PRICING,
               "spot": spot_pricing(CPU_PRICING, discount=0.6)}
    pl = Placement({"ondemand": ondemand, "spot": spot}, pricing=catalog,
                   objective="cost", spot_risk_weight=1.0)
    short = _spec("short", duration=60.0, resources={"vcpu": 1})
    long = _spec("long", duration=6 * 3600.0, resources={"vcpu": 1})
    # short job: 60s of risk costs ~3% — the 60% discount wins easily
    assert pl.rank(short, pl.eligible(short))[0] == "spot"
    # long job: 12 expected reclamations inflate spot 13x — on-demand wins
    assert pl.rank(long, pl.eligible(long))[0] == "ondemand"


def test_spot_pricing_preserves_subclass_and_discount():
    from repro.core.provision.pricing import (ChipScaledPricing,
                                              TPU_PRICING)
    sp = spot_pricing(TPU_PRICING, discount=0.5)
    assert isinstance(sp, ChipScaledPricing)
    assert sp.family == "tpu-spot"
    res = {"chips": 8, "hbm_gb": 2}
    assert sp.job_cost(res, 3600.0) == \
        pytest.approx(0.5 * TPU_PRICING.job_cost(res, 3600.0))
    with pytest.raises(ValueError):
        spot_pricing(TPU_PRICING, discount=1.5)


# -- zombie incarnations: stale workers must not touch the live job ------
def test_stale_epoch_finalize_cannot_terminalize_live_incarnation():
    """A worker from a superseded incarnation that completes late must
    not write the registry, bill, or publish a terminal event — the
    relaunched incarnation owns the job now."""
    registry = JobRegistry()
    bus = EventBus()
    runner = ThreadPoolRunner(registry, bus, max_workers=1)
    j = registry.submit(_spec("zombie", duration=None,
                              resources={"vcpu": 1}))
    for s in (JobState.QUEUED, JobState.LAUNCHING, JobState.RUNNING):
        registry.set_state(j.job_id, s)
    j.epoch = 1                     # the job was preempted + relaunched
    terminal = []
    bus.subscribe(TOPIC_CONTAINER_STATUS, terminal.append)
    runner._finalize(j, "old log", JobState.FINISHED, epoch=0)
    runner.shutdown()
    assert registry.get(j.job_id).state == JobState.RUNNING
    assert terminal == []
    assert j.cost is None           # stale segment not billed
    # the live incarnation's own finalize still works
    runner2 = ThreadPoolRunner(registry, bus, max_workers=1)
    j.runtime = 1.0
    runner2._finalize(j, "new log", JobState.FINISHED, epoch=1)
    runner2.shutdown()
    assert registry.get(j.job_id).state == JobState.FINISHED
    assert [m["status"] for m in terminal] == ["FINISHED"]


# -- train/fault tie-in ---------------------------------------------------
def test_preemption_hook_is_silent_until_signalled():
    class FakeJob:
        job_id = "job-x"
        epoch = 0
        preempt_flag = threading.Event()
    hook = preemption_hook(FakeJob)
    hook(3)                         # no signal: no raise
    FakeJob.preempt_flag.set()
    with pytest.raises(JobPreempted) as ei:
        hook(4)
    assert getattr(ei.value, "external", False) is True


def test_preemption_hook_survives_flag_replacement():
    """The relaunch installs a fresh (unset) preempt_flag on the shared
    Job; a superseded worker's hook must still observe its preemption
    via the epoch it captured at creation — polling the live flag alone
    would lose the signal."""
    class FakeJob:
        job_id = "job-y"
        epoch = 0
        preempt_flag = threading.Event()
    hook = preemption_hook(FakeJob)
    hook(1)
    # scheduler preempts (epoch bump) and the relaunch replaces the flag
    # before this worker's next poll
    FakeJob.epoch = 1
    FakeJob.preempt_flag = threading.Event()    # fresh, unset
    with pytest.raises(JobPreempted):
        hook(2)

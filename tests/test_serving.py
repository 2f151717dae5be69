"""Multi-tenant serving engine + DYVERSE integration."""
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import Quota, TenantSpec
from repro.serving import EngineConfig, MultiTenantEngine
from repro.serving.request import Phase, Request
from repro.serving.scheduler import QuotaScheduler


def mk_req(rid, tenant="t", prompt_len=8, max_new=4, t0=0.0):
    return Request(rid=rid, tenant=tenant, prompt=list(range(1, prompt_len + 1)),
                   max_new_tokens=max_new, arrival_t=t0)


# ---------------------------------------------------------------- scheduler
def test_scheduler_respects_slot_quota():
    s = QuotaScheduler(page_size=16)
    s.add_tenant("t", Quota(slots=2, pages=100))
    for i in range(5):
        s.submit(mk_req(i, t0=i))
    admitted = s.admit_waiting("t")
    assert len(admitted) == 2
    assert s.depth("t") == 3


def test_scheduler_respects_page_quota():
    s = QuotaScheduler(page_size=16)
    s.add_tenant("t", Quota(slots=10, pages=2))   # 2 pages = 32 tokens
    s.submit(mk_req(1, prompt_len=20, max_new=4))  # needs 2 pages
    s.submit(mk_req(2, prompt_len=20, max_new=4))
    admitted = s.admit_waiting("t")
    assert len(admitted) == 1                      # second doesn't fit


def test_quota_shrink_preempts_youngest():
    s = QuotaScheduler(page_size=16)
    s.add_tenant("t", Quota(slots=3, pages=100))
    rs = [s.submit(mk_req(i, t0=float(i))) for i in range(3)]
    s.admit_waiting("t")
    pre = s.set_quota("t", Quota(slots=1, pages=100))
    assert len(pre) == 2
    assert pre[0].req.arrival_t >= pre[1].req.arrival_t   # youngest first
    assert len(s.active("t")) == 1
    assert s.active("t")[0] is rs[0]                      # oldest survives


def test_remove_tenant_evicts_all():
    s = QuotaScheduler()
    s.add_tenant("t", Quota(slots=2, pages=100))
    for i in range(4):
        s.submit(mk_req(i))
    s.admit_waiting("t")
    out = s.remove_tenant("t")
    assert len(out) == 4
    assert all(r.phase == Phase.EVICTED for r in out)
    assert "t" not in s.tenants


# ---------------------------------------------------------------- engine
@pytest.fixture(scope="module")
def engine():
    eng = MultiTenantEngine(EngineConfig(policy="none", slot_cap=4,
                                         capacity_slots=8,
                                         capacity_pages=128,
                                         max_seq_len=64))
    assert eng.add_tenant(TenantSpec(name="chat", slo_latency=60.0),
                          get_reduced("tinyllama-1.1b"))
    assert eng.add_tenant(TenantSpec(name="ssm", slo_latency=60.0),
                          get_reduced("rwkv6-3b"))
    return eng


def test_engine_completes_mixed_tenants(engine):
    rng = np.random.default_rng(0)
    rs = []
    for i in range(6):
        t = "chat" if i % 2 else "ssm"
        rs.append(engine.submit(t, list(rng.integers(1, 200, 8)),
                                max_new_tokens=4))
    engine.drain(max_steps=100)
    assert all(r.phase == Phase.DONE for r in rs)
    assert all(len(r.generated) == 4 for r in rs)
    assert all(r.latency() is not None and r.latency() > 0 for r in rs)


def test_engine_greedy_decode_deterministic(engine):
    out = []
    for _ in range(2):
        r = engine.submit("chat", [5, 6, 7, 8, 9, 10, 11, 12], max_new_tokens=5)
        engine.drain(max_steps=60)
        out.append(tuple(r.generated))
    assert out[0] == out[1]


def test_submit_to_unknown_tenant_goes_to_cloud(engine):
    before = len(engine.cloud_serviced)
    r = engine.submit("nope", [1, 2, 3])
    assert r.phase == Phase.EVICTED
    assert len(engine.cloud_serviced) == before + 1


def test_dyverse_round_scales_up_violating_tenant():
    eng = MultiTenantEngine(EngineConfig(policy="sps", slot_cap=4,
                                         capacity_slots=8, capacity_pages=128,
                                         max_seq_len=64,
                                         round_interval_steps=10**9))
    # SLO impossible on CPU → every request violates → scale-up on round
    assert eng.add_tenant(TenantSpec(name="hot", slo_latency=1e-4),
                          get_reduced("tinyllama-1.1b"))
    for i in range(4):
        eng.submit("hot", [1, 2, 3, 4], max_new_tokens=2)
    eng.drain(max_steps=60)
    before = eng.ctrl.pool.units("hot")
    eng.ctrl.run_round()
    after = eng.ctrl.pool.units("hot")
    assert after > before
    assert eng.ctrl.registry["hot"].scale_count == 1


def test_engine_termination_redirects_to_cloud():
    # slot_cap=4 so vip's scale-up target is actually enforceable — the
    # controller no longer evicts siblings to fund slots past the
    # scheduler's clamp (the quota-divergence fix)
    eng = MultiTenantEngine(EngineConfig(policy="sps", slot_cap=4,
                                         capacity_slots=4, capacity_pages=64,
                                         max_seq_len=64,
                                         round_interval_steps=10**9))
    # two tenants; "vip" violates hard and needs more than free → evict "low"
    assert eng.add_tenant(TenantSpec(name="vip", slo_latency=1e-4, premium=5.0),
                          get_reduced("tinyllama-1.1b"))
    assert eng.add_tenant(TenantSpec(name="low", slo_latency=60.0),
                          get_reduced("tinyllama-1.1b"))
    for i in range(3):
        eng.submit("vip", [1, 2, 3], max_new_tokens=2)
        eng.submit("low", [4, 5, 6], max_new_tokens=2)
    eng.drain(max_steps=80)
    eng.submit("low", [7, 8], max_new_tokens=2)   # in-flight during eviction
    eng.ctrl.run_round()
    assert "low" not in eng.ctrl.registry
    assert "low" not in eng.tenants
    assert any(r.req.tenant == "low" for r in eng.cloud_serviced)
    # vip keeps running after the round
    r = eng.submit("vip", [9, 10, 11], max_new_tokens=2)
    eng.drain(max_steps=40)
    assert r.phase == Phase.DONE

# ----------------------------------------------------- preemption regression
def _tiny_cfg(**kw):
    base = dict(policy="none", slot_cap=2, capacity_slots=4,
                capacity_pages=64, max_seq_len=64,
                round_interval_steps=10**9)
    base.update(kw)
    return EngineConfig(**base)


def test_preemption_resume_bitwise_identical():
    """A preempted-then-resumed request must produce EXACTLY the token
    stream of an unpreempted run, keep its TTFT, and never double-append
    (the resume path re-prefills prompt + generated[:-1] and feeds the
    last generated token back at the restored KV position)."""
    from repro.serving.spec import VirtualClock

    def fresh():
        clock = VirtualClock(0.25)
        eng = MultiTenantEngine(_tiny_cfg(), seed=3, clock=clock)
        assert eng.add_tenant(TenantSpec(name="t", slo_latency=60.0),
                              get_reduced("tinyllama-1.1b"))
        return eng, clock

    # reference: run to completion without interference
    ref, clock = fresh()
    r0 = ref.submit("t", [5, 7, 9, 11], max_new_tokens=8)
    while r0.phase != Phase.DONE:
        clock.tick()
        ref.step()
    want = list(r0.generated)
    assert len(want) == 8

    # victim: preempt mid-decode, idle a while, restore, finish
    eng, clock = fresh()
    r1 = eng.submit("t", [5, 7, 9, 11], max_new_tokens=8)
    for _ in range(4):                      # prefill + a few decode steps
        clock.tick()
        eng.step()
    assert r1.phase == Phase.DECODE and 1 < len(r1.generated) < 8
    ttft = r1.first_token_t
    mid = list(r1.generated)
    eng.ctrl.actuator.apply_quota("t", Quota(slots=0, pages=64))
    assert r1.phase == Phase.QUEUED and r1.batch_slot == -1
    rt = eng.tenants["t"]
    assert all(rs is not r1 for rs in rt.slot_req)   # slot really freed
    for _ in range(3):                      # starved: no progress, no decode
        clock.tick()
        eng.step()
    assert r1.generated == mid              # nothing generated while queued
    eng.ctrl.actuator.apply_quota("t", Quota(slots=2, pages=64))
    while r1.phase != Phase.DONE:
        clock.tick()
        eng.step()
    assert r1.generated == want             # bitwise-identical continuation
    assert r1.first_token_t == ttft         # TTFT survives preemption


def test_pages_never_exceed_quota_during_shrink():
    """Worst-case page reservation at admission makes pages_used ≤
    quota.pages a STEP-TIME invariant, including across mid-run quota
    shrinks (no decode-growth overcommit between scaling rounds)."""
    from repro.serving.spec import VirtualClock
    clock = VirtualClock(0.25)
    eng = MultiTenantEngine(_tiny_cfg(slot_cap=4, page_size=4),
                            seed=0, clock=clock)
    assert eng.add_tenant(TenantSpec(name="t", slo_latency=60.0),
                          get_reduced("tinyllama-1.1b"))
    eng.ctrl.actuator.apply_quota("t", Quota(slots=4, pages=12))
    rng = np.random.default_rng(0)
    shrink_at = {6: Quota(slots=4, pages=8), 12: Quota(slots=4, pages=5)}
    for step in range(20):
        if step % 2 == 0:
            eng.submit("t", [int(x) for x in rng.integers(1, 200, 6)],
                       max_new_tokens=6)        # worst case 12 tokens → 3 pages
        if step in shrink_at:
            eng.ctrl.actuator.apply_quota("t", shrink_at[step])
        clock.tick()
        eng.step()
        tq = eng.sched.tenants["t"]
        used = tq.pages_used(eng.cfg.page_size)
        assert used <= tq.quota.pages, (step, used, tq.quota.pages)
        # and the worst-case reservation really covers the live contexts
        for rs in tq.active:
            assert rs.context_len <= len(rs.req.prompt) + rs.req.max_new_tokens


def test_actuator_controller_quota_agreement():
    """The quota the controller bills (pool) and the quota the scheduler
    enforces must be the same object: spec.max_units caps units at
    admission to the compiled decode-batch limit, so no round can grant
    slots the actuator would clamp away."""
    eng = MultiTenantEngine(_tiny_cfg(policy="sdps", slot_cap=2,
                                      capacity_slots=16, capacity_pages=64,
                                      default_units=8))
    assert eng.add_tenant(TenantSpec(name="t", slo_latency=1e-4),
                          get_reduced("tinyllama-1.1b"))
    # default 8 units was capped to slot_cap=2 at admission
    assert eng.ctrl.pool.units("t") == 2
    assert eng.ctrl.registry["t"].spec.max_units == 2
    assert eng.sched.tenants["t"].quota.slots == 2
    # drive violating traffic through several rounds: billed == enforced
    for r in range(3):
        for _ in range(6):
            eng.submit("t", [1, 2, 3], max_new_tokens=2)
        eng.drain(max_steps=60)
        eng.ctrl.run_round()
        billed = eng.ctrl.registry["t"].quota.slots
        enforced = eng.sched.tenants["t"].quota.slots
        assert billed == enforced <= eng.cfg.slot_cap


# ----------------------------------------------------- eviction accounting
def test_eviction_cloud_latency_accounting():
    """Procedure-3 eviction redirects the live queue to the Cloud with
    finish_t = now + CLOUD_LATENCY_S exactly (virtual clock), and the
    evicted requests never appear in `completed` — including requests
    still sitting in `waiting`."""
    from repro.serving.engine import CLOUD_LATENCY_S
    from repro.serving.spec import VirtualClock
    clock = VirtualClock(0.25)
    eng = MultiTenantEngine(_tiny_cfg(slot_cap=1), seed=0, clock=clock)
    assert eng.add_tenant(TenantSpec(name="t", slo_latency=60.0),
                          get_reduced("tinyllama-1.1b"))
    rs = [eng.submit("t", [1 + i, 2, 3], max_new_tokens=8) for i in range(3)]
    for _ in range(2):                      # 1 active mid-decode, 2 waiting
        clock.tick()
        eng.step()
    assert rs[0].phase == Phase.DECODE
    assert [r.phase for r in rs[1:]] == [Phase.QUEUED, Phase.QUEUED]
    now = clock()
    eng._evict_tenant("t")
    assert all(r.phase == Phase.EVICTED for r in rs)
    assert all(r.finish_t == now + CLOUD_LATENCY_S for r in rs)
    assert all(r in eng.cloud_serviced for r in rs)
    assert eng.completed == []
    assert "t" not in eng.tenants and "t" not in eng.sched.tenants
    # stepping on is harmless and never resurrects evicted requests
    clock.tick()
    eng.step()
    assert eng.completed == []


def test_eviction_while_all_requests_waiting():
    from repro.serving.engine import CLOUD_LATENCY_S
    from repro.serving.spec import VirtualClock
    clock = VirtualClock(0.5)
    eng = MultiTenantEngine(_tiny_cfg(), seed=0, clock=clock)
    assert eng.add_tenant(TenantSpec(name="t", slo_latency=60.0),
                          get_reduced("tinyllama-1.1b"))
    clock.tick()
    rs = [eng.submit("t", [4, 5, 6], max_new_tokens=4) for _ in range(2)]
    eng._evict_tenant("t")                   # nothing ever prefilled
    assert all(r.phase == Phase.EVICTED for r in rs)
    assert all(r.finish_t == clock() + CLOUD_LATENCY_S for r in rs)
    assert all(r.latency() == CLOUD_LATENCY_S for r in rs)
    assert eng.completed == []


# ------------------------------------------------ admission stamp and spans
def test_admit_stamp_set_once_through_preemption_and_requeue():
    """``admit_t`` is the first move from the queue to the active set:
    arrival ≤ admit ≤ first token, and neither a preemption nor a
    requeue (the federation's timeout / migration path) restamps it."""
    from repro.serving.spec import VirtualClock

    clock = VirtualClock(0.25)
    eng = MultiTenantEngine(_tiny_cfg(), seed=3, clock=clock)
    assert eng.add_tenant(TenantSpec(name="t", slo_latency=60.0),
                          get_reduced("tinyllama-1.1b"))
    r = eng.submit("t", [5, 7, 9, 11], max_new_tokens=12)
    assert r.admit_t is None
    clock.tick()
    eng.step()
    admitted = r.admit_t
    assert admitted == 0.25
    assert r.req.arrival_t <= admitted <= r.first_token_t
    for _ in range(2):
        clock.tick()
        eng.step()
    # preempted and re-admitted
    eng.ctrl.actuator.apply_quota("t", Quota(slots=0, pages=64))
    assert r.phase == Phase.QUEUED
    clock.tick()
    eng.step()
    eng.ctrl.actuator.apply_quota("t", Quota(slots=2, pages=64))
    clock.tick()
    eng.step()
    assert r.phase == Phase.DECODE and r.admit_t == admitted
    # pulled out mid-decode and requeued, as the federation does
    tq, rt = eng.sched.tenants["t"], eng.tenants["t"]
    tq.active.remove(r)
    rt.slot_req[r.batch_slot] = None
    r.generated.clear()
    eng.sched.requeue(r)
    while r.phase != Phase.DONE:
        clock.tick()
        eng.step()
    assert r.admit_t == admitted
    assert r.req.arrival_t <= r.admit_t <= r.first_token_t


def test_span_names_pinned_by_a_recorded_trace(tmp_path):
    """Every host span a traced engine writes is in ``SPAN_NAMES`` and
    every name there is written: one tenant, two requests, a DYVERSE
    round every two steps."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.obs import SPAN_NAMES

    eng = MultiTenantEngine(_tiny_cfg(policy="sdps", round_interval_steps=2))
    assert eng.add_tenant(TenantSpec(name="t", slo_latency=60.0),
                          get_reduced("tinyllama-1.1b"))
    eng.submit("t", [1, 2, 3, 4], max_new_tokens=3)
    eng.drain(max_steps=20)                 # compile outside the trace
    rs = [eng.submit("t", [5, 6, 7, 8], max_new_tokens=3) for _ in range(2)]
    jax.profiler.start_trace(str(tmp_path))
    eng.drain(max_steps=20)
    jax.profiler.stop_trace()
    assert all(r.phase == Phase.DONE for r in rs)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = {ev.name for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith("serve.")}
    assert seen == SPAN_NAMES

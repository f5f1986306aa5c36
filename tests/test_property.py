"""Property-based tests (hypothesis) on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.power_manager import PowerManager
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.serving.ring import KVRing

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402


# ---------------------------------------------------------------------------
# PowerManager: node budget is NEVER exceeded under arbitrary command traces
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 7),
                          st.floats(350, 800),
                          st.floats(0.0, 2.0)), min_size=1, max_size=40))
def test_power_budget_invariant(commands):
    pm = PowerManager(8, 4800.0, initial_caps=[600.0] * 8)
    t = 0.0
    for gpu, watts, dt in commands:
        t += dt
        pm.tick(t)
        pm.set_cap(t, gpu, watts)
        # worst-case draw never exceeds the budget
        assert pm._worst_case() <= 4800.0 + 1e-6
        assert all(400.0 - 1e-9 <= c <= 750.0 + 1e-9 for c in pm.commanded)
    pm.tick(t + 10.0)
    assert sum(pm.effective) <= 4800.0 + 1e-6


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 7), st.floats(10, 300))
def test_power_shift_conserves_budget(n_src, watts):
    pm = PowerManager(8, 4800.0, initial_caps=[600.0] * 8)
    src = list(range(n_src))
    dst = list(range(n_src, 8))
    t_ready, freed = pm.shift(0.0, src, dst, watts)
    assert pm._worst_case() <= 4800.0 + 1e-6
    pm.tick(t_ready)
    pm.apply_raise(t_ready, dst, freed)
    assert pm._worst_case() <= 4800.0 + 1e-6
    assert sum(pm.commanded) <= 4800.0 + 1e-6


# ---------------------------------------------------------------------------
# KV ring buffer: conservation + FIFO of ready slots
# ---------------------------------------------------------------------------

@settings(max_examples=50, deadline=None)
@given(st.lists(st.booleans(), min_size=1, max_size=200),
       st.integers(1, 8))
def test_ring_conservation(ops, n_slots):
    ring = KVRing(n_slots)
    put_seq = 0
    pulled = []
    for is_put in ops:
        if is_put:
            idx = ring.try_put(put_seq)
            if idx is not None:
                put_seq += 1
        else:
            out = ring.try_pull()
            if out is not None:
                pulled.append(out)
        assert ring.n_free + ring.n_ready <= n_slots
    assert pulled == sorted(pulled)          # FIFO
    assert len(pulled) + ring.n_ready == put_seq


# ---------------------------------------------------------------------------
# RG-LRU scan: kernel == sequential reference on random shapes
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3),
       st.sampled_from([64, 128, 256]),
       st.sampled_from([128, 256]),
       st.integers(0, 1000))
def test_rglru_random(B, S, W, seed):
    key = jax.random.key(seed)
    ks = jax.random.split(key, 3)
    la = -jnp.abs(jax.random.normal(ks[0], (B, S, W))) * 0.3
    x = jax.random.normal(ks[1], (B, S, W))
    h0 = jax.random.normal(ks[2], (B, W))
    out = rglru_scan(la, x, h0, chunk=64, bw=128, interpret=True)
    ref = rglru_scan_ref(la, x, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# goodput metric sanity
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.floats(0, 10), st.floats(0.01, 3.0),
                          st.floats(0.001, 0.2), st.integers(2, 300)),
                min_size=1, max_size=50))
def test_goodput_bounds(reqs):
    from repro.core.goodput import RequestRecord, summarize
    records = []
    for i, (arr, ttft_off, tpot, out) in enumerate(reqs):
        r = RequestRecord(i, arr, 100, out)
        r.prefill_done = arr + ttft_off
        r.finish = r.prefill_done + tpot * (out - 1)
        records.append(r)
    s = summarize(records, duration_s=20.0, avg_provisioned_w=4800.0)
    assert 0.0 <= s.slo_attainment <= 1.0
    assert s.n_good <= s.n_finished == len(records)
    # manual check
    manual = sum(1 for r in records
                 if r.ttft <= 1.0 + 1e-9 and r.tpot <= 0.040 + 1e-9)
    assert s.n_good == manual


# ---------------------------------------------------------------------------
# cost model: monotone in power, KV transfer in TPOT accounting
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.floats(400, 740), st.floats(5, 300))
def test_costmodel_monotone_in_power(cap, extra):
    from repro.configs import get_config
    from repro.core.costmodel import MI300X, CostModel
    from repro.core.power_model import mi300x
    cm = CostModel(get_config("llama31_8b"), MI300X, mi300x())
    hi = min(cap + extra, 750.0)
    assert cm.prefill_time(4096, cap) >= cm.prefill_time(4096, hi) - 1e-12
    assert cm.decode_step_time(32, 4096, cap) >= \
        cm.decode_step_time(32, 4096, hi) - 1e-12


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 64), st.integers(128, 16384))
def test_decode_time_monotone_in_batch_and_ctx(batch, ctx):
    from repro.configs import get_config
    from repro.core.costmodel import MI300X, CostModel
    from repro.core.power_model import mi300x
    cm = CostModel(get_config("llama31_8b"), MI300X, mi300x())
    t = cm.decode_step_time(batch, ctx, 600)
    assert cm.decode_step_time(batch + 1, ctx, 600) >= t - 1e-12
    assert cm.decode_step_time(batch, ctx + 512, 600) >= t - 1e-12
    # throughput (tokens/s) must not decrease with batch
    assert (batch + 1) / cm.decode_step_time(batch + 1, ctx, 600) >= \
        batch / t - 1e-9


# ---------------------------------------------------------------------------
# sanitizer: hierarchical power conservation holds under random node churn
# and controller role flips (the runtime half of simcheck — the
# InvariantSanitizer validates every dispatch and raises on violation)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["fail", "leave", "join"]),
                          st.floats(0.5, 25.0)),
                min_size=1, max_size=3),
       st.integers(0, 999))
def test_churn_roleflip_power_conservation(events, seed):
    import dataclasses

    from repro.configs import get_config
    from repro.core.cluster import ClusterConfig, ClusterSimulator
    from repro.core.controller import ControllerConfig, policy_4p4d
    from repro.core.fleet import FleetConfig, FleetManager
    from repro.core.simulator import Workload

    ctrl = dataclasses.replace(ControllerConfig(), allow_power=True,
                               allow_gpu=True, ttft_slo=2.0)
    cs = ClusterSimulator(get_config("llama31_8b"), policy_4p4d(500), 3,
                          node_budget_w=4000.0, ctrl_cfg=ctrl,
                          cluster_cfg=ClusterConfig(allow_shift=True),
                          sanitize=True)
    fm = FleetManager(cs, FleetConfig(elastic=True))
    gone = set()
    for i, (kind, t) in enumerate(sorted(events, key=lambda e: e[1])):
        nid = i % 3
        if kind == "join":
            if nid in gone:                 # rejoin a departed node
                fm.schedule_join(t, nid)
                gone.discard(nid)
        elif nid not in gone and len(gone) < 2:   # keep >= 1 node alive
            (fm.schedule_fail if kind == "fail" else fm.schedule_leave)(t, nid)
            gone.add(nid)
    wl = Workload.uniform(30, qps=4.0, in_tokens=2048, out_tokens=64,
                          seed=seed)
    # every dispatch is validated: a conservation / causality / residency /
    # energy break anywhere in the churn+role-flip machinery raises here
    cs.run(wl)
    assert cs.loop.sanitizer.checks > 0
    cs.assert_facility_invariant()


# ---------------------------------------------------------------------------
# autoscaler: the decision loop never violates facility power conservation,
# whatever workload shape / tariff / config it is handed — every membership
# op it issues goes through the same source-before-sink machinery, and the
# sanitizer validates every dispatch along the way
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.sampled_from(["predictive", "reactive"]),
       st.floats(2.0, 10.0),      # trough arrival rate
       st.floats(12.0, 24.0),     # peak arrival rate
       st.floats(0.05, 0.60),     # off-peak electricity price
       st.integers(0, 999))
def test_autoscaler_power_conservation(mode, trough, peak, price, seed):
    import dataclasses

    from repro.configs import get_config
    from repro.core.autoscale import (AutoscaleConfig, PredictiveAutoscaler,
                                      SignalTrace)
    from repro.core.cluster import ClusterConfig, ClusterSimulator
    from repro.core.controller import ControllerConfig, policy_4p4d
    from repro.core.fleet import FleetConfig, FleetManager
    from repro.core.simulator import Workload

    ctrl = dataclasses.replace(ControllerConfig(), allow_power=True,
                               ttft_slo=2.0)
    cs = ClusterSimulator(get_config("llama31_8b"), policy_4p4d(500), 3,
                          node_budget_w=4000.0, ctrl_cfg=ctrl,
                          cluster_cfg=ClusterConfig(allow_shift=True),
                          seed=seed, router_policy="cost", sanitize=True)
    fm = FleetManager(cs, FleetConfig(elastic=True), standby=(2,))
    asc = PredictiveAutoscaler(
        fm, AutoscaleConfig(mode=mode, period_s=2.0, window_s=12.0,
                            holdoff_s=4.0, season_s=20.0),
        price_trace=SignalTrace([0.0, 8.0, 20.0],
                                [price, 3.0 * price, price]),
        carbon_trace=SignalTrace([0.0], [400.0]))
    asc.start()
    wl = Workload.phased_mix([
        Workload.uniform(20, qps=trough, in_tokens=2048, out_tokens=64,
                         seed=seed, ttft_slo=2.0),
        Workload.uniform(60, qps=peak, in_tokens=2048, out_tokens=64,
                         seed=seed + 1, ttft_slo=2.0)])
    # every dispatch is validated; any budget over-commit the decision
    # loop could provoke (join during drain, leave of the power sink, ...)
    # raises inside the run
    cs.run(wl)
    assert cs.loop.sanitizer.checks > 0
    cs.assert_facility_invariant()
    for t, budgets, total in cs.budget_trace:
        assert total <= cs.facility_budget_w + 1e-6, (t, budgets)


# ---------------------------------------------------------------------------
# Chaos schedules: power conservation + KV single-residency survive
# randomized emergencies x correlated failures x lossy migrations
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 999),           # chaos layout seed
       st.floats(0.45, 0.9),          # emergency depth (frac of nameplate)
       st.integers(1, 2),             # correlated rack size
       st.integers(0, 3),             # link faults
       st.booleans())                 # retries on (degraded) vs off (naive)
def test_chaos_schedule_invariants(seed, frac, rack, n_links, retries):
    import dataclasses

    from repro.configs import get_config
    from repro.core.chaos import ChaosConfig, ChaosEngine
    from repro.core.cluster import (AdmissionConfig, ClusterConfig,
                                    ClusterSimulator)
    from repro.core.controller import ControllerConfig, policy_4p4d
    from repro.core.fleet import FleetConfig, FleetManager
    from repro.core.simulator import Workload

    ctrl = dataclasses.replace(ControllerConfig(), allow_power=True,
                               ttft_slo=2.0)
    cs = ClusterSimulator(get_config("llama31_8b"), policy_4p4d(500), 3,
                          node_budget_w=4000.0, ctrl_cfg=ctrl,
                          cluster_cfg=ClusterConfig(allow_shift=True),
                          seed=seed, sanitize=True,
                          admission=AdmissionConfig(slo_aware=True))
    fm = FleetManager(cs, FleetConfig(
        migrate_max_retries=4 if retries else 0))
    ch = ChaosEngine(fm, ChaosConfig(seed=seed))
    ch.inject(horizon_s=8.0, emergency_frac=(frac, frac),
              rack_size=rack, rejoin_after_s=2.5,
              n_link_faults=n_links, link_fault_s=0.4)
    # the sanitizer validates hierarchical power conservation AND KV
    # single-residency at EVERY dispatch; a violation raises mid-run
    cs.run(Workload.uniform(30, qps=5.0, in_tokens=2048, out_tokens=64,
                            seed=seed, ttft_slo=2.0))
    assert cs.loop.sanitizer.checks > 0
    cs.assert_facility_invariant()
    for t, budgets, total in cs.budget_trace:
        assert total <= cs.facility_budget_w + 1e-6, (t, budgets)
    # the ledger terminally resolves: finished or shed, nothing stranded
    assert cs.n_unfinished() == 0
    for r in cs.records:
        assert (r.finish is not None) or (r.shed_t is not None)


# ---------------------------------------------------------------------------
# Multi-tenancy: random tenant mixes under priority preemption + affinity
# routing + prefix caching keep power conservation, prefix-block
# single-residency, and the no-silent-drop guarantee (sanitizer validates
# every dispatch), and per-tenant attribution never loses a record
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(0, 999),            # workload seed
       st.integers(0, 3),              # high-priority tenant's priority edge
       st.integers(2, 6),              # decode slots per GPU (saturation)
       st.booleans())                  # preemption on vs off
def test_tenant_mix_preemption_affinity_invariants(seed, pri, slots, preempt):
    import dataclasses

    from repro.configs import get_config
    from repro.core.cluster import ClusterSimulator
    from repro.core.controller import policy_4p4d
    from repro.core.costmodel import MI300X
    from repro.core.prefixcache import PrefixCacheConfig
    from repro.core.simulator import Workload
    from repro.core.tenancy import TenantRegistry, TenantSpec

    reg = TenantRegistry([TenantSpec("vip", priority=pri, weight=2.0),
                          TenantSpec("batch", priority=0, weight=0.5)],
                         preempt=preempt)
    cs = ClusterSimulator(get_config("llama31_8b"), policy_4p4d(500), 2,
                          node_budget_w=4000.0, seed=seed, sanitize=True,
                          gpu=dataclasses.replace(MI300X,
                                                  max_active_decode=slots),
                          router_policy="affinity", tenancy=reg,
                          cache_cfg=PrefixCacheConfig())
    wl = Workload(
        Workload.sessions(6, turns=3, qps=2.0, tenant="vip",
                          seed=seed).entries
        + Workload.uniform(18, qps=8.0, in_tokens=1536, out_tokens=256,
                           seed=seed + 1, tenant="batch").entries)
    # every dispatch validated: conservation, prefix-block residency,
    # preempt no-silent-drop — a break anywhere raises inside the run
    cs.run(wl)
    assert cs.loop.sanitizer.checks > 0
    cs.assert_facility_invariant()
    assert cs.n_unfinished() == 0
    # per-tenant attribution is a partition of the ledger
    s = cs.summary()
    by_tenant = {"vip": 0, "batch": 0}
    for r in cs.records:
        by_tenant[r.tenant] += 1
    assert by_tenant["vip"] == s.per_tenant["vip"]["n_total"] == 18
    assert by_tenant["batch"] == s.per_tenant["batch"]["n_total"] == 18
    if not preempt:
        assert all(not nd.preempt_trace for nd in cs.nodes)

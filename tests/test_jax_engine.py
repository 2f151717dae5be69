"""engine="jax": registry contract, tolerance equivalence vs batched,
and counter-based RNG invariances (repeats, rng_workers, device count).

The jax engine is NOT bitwise-pinned to the numpy trio (different
random bits, float32 math, different reduction order — see
repro/sim/engines/jax_backend.py). Its contract is statistical: same
arrival/jitter distributions, so violation rates and latency summaries
agree within the tolerances pinned here, and the discrete control-plane
outcomes (re-placements, Cloud fallbacks, failed nodes) — which are
robust to sub-percent VR noise at these scales — agree exactly.
"""
import dataclasses
import hashlib
import os
import subprocess
import sys
import warnings
from pathlib import Path

import numpy as np
import pytest

from repro.sim import (ENGINE_BACKENDS, ENGINES, SCENARIOS, EdgeNodeSim,
                       Scenario, FleetSpec, SimConfig, TenantClassSpec,
                       TopologySpec, engine_matrix, resolve_engine,
                       run_scenario)

# quick-scale statistical tolerance on Eq.-1 violation rates: measured
# |ΔVR| across the registry scenarios is ≤ 0.002 at quick scale; 0.02
# leaves an order of magnitude of headroom without masking regressions
VR_TOL = 0.02


def _quick(name, engine):
    sc = SCENARIOS[name]
    if sc.engine != engine:
        sc = dataclasses.replace(sc, engine=engine)
    return run_scenario(sc, quick=True)


# ------------------------------------------------------------- registry
def test_registry_contracts():
    assert set(ENGINES) == {"scalar", "vectorized", "batched", "jax"}
    for name in ("scalar", "vectorized", "batched"):
        b = resolve_engine(name)
        assert b.contract == "bitwise"
        assert b.rng_scheme == "numpy-substream"
    b = resolve_engine("jax")
    assert (b.contract, b.rng_scheme) == ("tolerance", "counter-jax")
    s = ENGINE_BACKENDS["serving"]
    assert (s.contract, s.rng_scheme) == ("token-level", "engine-owned")
    assert not s.node_capable


def test_unknown_engine_rejected_by_registry():
    with pytest.raises(ValueError, match="turbo"):
        resolve_engine("turbo")


def test_serving_engine_not_node_capable():
    with pytest.raises(ValueError, match="node-capable"):
        EdgeNodeSim([], SimConfig(engine="serving"))


def test_engine_matrix_reflects_registry():
    m = engine_matrix()
    for name, b in ENGINE_BACKENDS.items():
        assert name in m
        assert b.contract in m
        assert b.rng_scheme in m
    # the matrix rendered into the repro.sim docstring can't drift
    import repro.sim as sim

    for name in ENGINE_BACKENDS:
        assert name in sim.__doc__


# ------------------------------------------- tolerance vs batched engine
@pytest.mark.parametrize("scenario", ["mixed_fleet", "paper_game_32"])
def test_jax_matches_batched_within_tolerance(scenario):
    rb = _quick(scenario, "batched")
    rj = _quick(scenario, "jax")
    assert rb.outcomes.keys() == rj.outcomes.keys()
    for k in rb.outcomes:
        ob, oj = rb.outcomes[k], rj.outcomes[k]
        assert abs(ob.violation_rate - oj.violation_rate) < VR_TOL, k
        # the discrete control-plane outcomes are identical at this scale
        assert ob.replaced == oj.replaced, k
        assert ob.cloud == oj.cloud, k
        lb, lj = rb.results[k], rj.results[k]
        assert lb.total_requests > 0 and lj.total_requests > 0
        # mean user-visible latency: same lognormal model, same scales
        mb = np.mean(np.concatenate(
            [r.latencies for r in lb.node_results.values()]))
        mj = np.mean(np.concatenate(
            [r.latencies for r in lj.node_results.values()]))
        assert abs(mb - mj) / mb < 0.05, k


def test_jax_matches_batched_through_node_failure():
    """Mid-run node failure + refugee re-placement: the jax stepper's
    caches must follow the fleet epochs exactly like batched."""
    rb = _quick("node_failure_midrun", "batched")
    rj = _quick("node_failure_midrun", "jax")
    for k in rb.outcomes:
        ob, oj = rb.outcomes[k], rj.outcomes[k]
        assert abs(ob.violation_rate - oj.violation_rate) < VR_TOL, k
        assert rb.results[k].failed_nodes == rj.results[k].failed_nodes
        assert ob.replaced == oj.replaced, k
        assert ob.cloud == oj.cloud, k


# --------------------------------------------------------- determinism
def _lat_digest(res):
    h = hashlib.sha256()
    for key in sorted(res.results):
        for name in sorted(res.results[key].node_results):
            h.update(res.results[key].node_results[name]
                     .latencies.tobytes())
    return h.hexdigest()


def test_jax_repeated_runs_bitwise_identical():
    a = _quick("mixed_fleet", "jax")
    b = _quick("mixed_fleet", "jax")
    for k in a.outcomes:
        assert a.outcomes[k].violation_rate == b.outcomes[k].violation_rate
    assert _lat_digest(a) == _lat_digest(b)


def test_jax_invariant_to_rng_workers():
    """rng_workers sizes the numpy engines' jitter thread pool; the
    counter-based streams must not even see it."""
    sc = SCENARIOS["mixed_fleet"]
    a = run_scenario(dataclasses.replace(sc, engine="jax", rng_workers=1),
                     quick=True)
    b = run_scenario(dataclasses.replace(sc, engine="jax", rng_workers=4),
                     quick=True)
    assert _lat_digest(a) == _lat_digest(b)


_DEVICE_PROBE = """
import dataclasses, hashlib, numpy as np
from repro.sim import SCENARIOS, run_scenario
import jax
res = run_scenario(dataclasses.replace(
    SCENARIOS["mixed_fleet"], engine="jax", policies=("sdps",)), quick=True)
h = hashlib.sha256()
for name in sorted(res.results["sdps"].node_results):
    h.update(res.results["sdps"].node_results[name].latencies.tobytes())
print(len(jax.devices()), res.outcomes["sdps"].violation_rate, h.hexdigest())
"""


@pytest.mark.slow
def test_jax_invariant_to_device_count():
    """Sharding the row axis over more devices must not change a single
    bit: every row's draws come from its own (seed, tenant, chunk) key,
    wherever it is computed."""
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(root / "src"))
    outs = []
    for ndev in (1, 2):
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={ndev}")
        r = subprocess.run([sys.executable, "-c", _DEVICE_PROBE], env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr
        ndev_seen, vr, digest = r.stdout.split()
        assert int(ndev_seen) == ndev
        outs.append((vr, digest))
    assert outs[0] == outs[1]


# ------------------------------------------------------- option plumbing
def test_jit_scale_deprecation_shim():
    import repro.sim.edgesim as es

    es._JIT_SCALE_WARNED.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg = SimConfig(engine="batched", jit_scale=True)
    assert cfg.backend_options == {"jit_scale": True}
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    # warns once per process, maps every time
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg2 = SimConfig(engine="batched", jit_scale=True)
    assert cfg2.backend_options == {"jit_scale": True}
    assert not w
    # an explicit backend_options entry wins over the legacy flag
    cfg3 = SimConfig(jit_scale=True,
                     backend_options={"jit_scale": False})
    assert cfg3.backend_options == {"jit_scale": False}
    assert SimConfig().backend_options == {}


def test_pallas_scale_matches_numpy():
    from repro.sim.engines.jax_backend import _pallas_latency_scale
    from repro.sim.workload import FleetBatch, make_game_fleet

    fleet = make_game_fleet(12, np.random.default_rng(3))
    fb = FleetBatch(fleet)
    units = np.arange(1, 13, dtype=np.int64)
    ref = fb.latency_scale(units, 0, 120)
    demand = fb.demand_rates(0, 120)
    capacity = np.maximum(units, 1) * fb.unit_rate
    got = _pallas_latency_scale(
        fb.base_pf.astype(np.float32), fb.alpha.astype(np.float32),
        demand.astype(np.float32), capacity.astype(np.float32),
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5)


def test_jax_rejects_opaque_custom_workload():
    from repro.sim.workload import GameWorkload

    @dataclasses.dataclass
    class Mystery(GameWorkload):
        # inherits Poisson arrivals but hides the rate declaration
        batch_arrival_lam = None
        arrival_rng_free = False

    wl = Mystery(name="m0", base_latency=0.1, work_per_request=1.0,
                 unit_rate=2.0)
    sc = Scenario(name="mystery", fleet=FleetSpec(workloads=(wl,)),
                  topology=TopologySpec(n_nodes=1), engine="jax",
                  policies=("none",), duration_s=60, round_interval=60)
    with pytest.raises(ValueError, match="batched"):
        run_scenario(sc)

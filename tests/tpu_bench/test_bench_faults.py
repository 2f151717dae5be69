"""A run with its timed path broken underneath must come out not
correct: once for each fault a cell can have. The look for a chip is
skipped and the rest of the run is driven at a tiny size on the CPU.

Serving cells can return their state unchanged (the decode step leaves
the KV cache as it was), leave half of the batch out (half the decode
slots computed from nothing) and alter a token where it is produced.
Fleet cells can return their state unchanged (a chunk step that does
nothing, or a DYVERSE round that closes the round's metrics and scales
nothing), leave half of the batch out (half the tenants' rows) and alter
an answer where it is produced: in the kernels their traffic runs (the
dense kernel for streams, the Poisson and jitter kernels for games) and
in the controller (priorities of the wrong sign, so the round visits its
tenants in reverse). No cell spans chips, so none can leave out an
exchange between them."""
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from conftest import TINY_BENCH
from tpu_bench.common import BENCH_DIR, load_json
from tpu_bench.run import measure

ARGS = dict(seed=2**34 + 5, seconds=1.5, trace=0)


def _serving_faults(mp, fault):
    import repro.models.model as model_mod
    import repro.models.transformer as tfm

    step = tfm.decode_step_stack
    if fault == "state unchanged":
        def broken(params, x, cfg, cache, pos):
            x, _ = step(params, x, cfg, cache, pos)
            return x, cache
        mp.setattr(tfm, "decode_step_stack", broken)
    elif fault == "half the batch":
        def broken(params, x, cfg, cache, pos):
            x, cache = step(params, x, cfg, cache, pos)
            return x.at[x.shape[0] // 2:].set(0), cache
        mp.setattr(tfm, "decode_step_stack", broken)
    elif fault == "token altered":
        last = model_mod._last_logits
        mp.setattr(model_mod, "_last_logits",
                   lambda p, x, cfg: jnp.roll(last(p, x, cfg), 1, axis=-1))


@pytest.mark.parametrize("fault", [None, "state unchanged", "half the batch",
                                   "token altered"])
def test_serving_run_catches_each_fault(fault, tiny_serving, monkeypatch):
    if fault:
        _serving_faults(monkeypatch, fault)
    result, checks = measure(SimpleNamespace(workload="serve-chat", **ARGS),
                             TINY_BENCH, require_chip=False,
                             files=tiny_serving)
    assert result["correct"] is (fault is None), checks
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}


def _fleet_faults(mp, fault):
    from repro.sim.engines import jax_backend as jb

    from repro.core import DyverseController, RoundReport

    mp.setattr(jb, "_KERNEL_CACHE", {})
    dense = jb._dense_impl
    priorities = DyverseController.update_priorities
    if fault == "state unchanged":
        mp.setattr(jb.JaxFleetStepper, "step", lambda self, t0, t1: None)
    elif fault == "round unchanged":
        def broken(self):
            self.monitor.roll_round()
            return RoundReport(policy=self.policy)
        mp.setattr(DyverseController, "run_round", broken)
    elif fault == "priorities altered":
        def broken(self):
            out = priorities(self)
            self._cols.priority[:] = -self._cols.priority
            return out
        mp.setattr(DyverseController, "update_priorities", broken)
    elif fault == "half the batch":
        def broken(S, keys, active, scale, sigma, slo):
            lat, viol, lsum, vt = dense(S, keys, active, scale, sigma, slo)
            half = lat.shape[0] // 2
            return (lat, viol, lsum.at[half:].set(0), vt.at[half:].set(0))
        mp.setattr(jb, "_dense_impl", broken)
    elif fault == "answer altered":
        def broken(S, keys, active, scale, sigma, slo):
            lat, viol, lsum, vt = dense(S, keys, active, scale, sigma, slo)
            return lat, viol, lsum * 1.001, vt
        mp.setattr(jb, "_dense_impl", broken)


@pytest.mark.parametrize("fault", [None, "state unchanged", "half the batch",
                                   "answer altered", "round unchanged",
                                   "priorities altered"])
def test_fleet_run_catches_each_fault(fault, tiny_fleet, monkeypatch):
    _fleet_faults(monkeypatch, fault)
    traffic = load_json(BENCH_DIR / "traffic" / "stream.json")
    result, checks = measure(SimpleNamespace(workload="fleet-stream", **ARGS),
                             TINY_BENCH, require_chip=False,
                             files=(tiny_fleet, traffic))
    assert result["correct"] is (fault is None), checks
    assert set(result["metrics"]) == {"tenant_s_per_s", "setup_s"}


def _game_faults(mp, fault):
    from repro.sim.engines import jax_backend as jb

    mp.setattr(jb, "_KERNEL_CACHE", {})
    jitter, poisson = jb._jitter_impl, jb._poisson_impl
    if fault == "state unchanged":
        mp.setattr(jb.JaxFleetStepper, "step", lambda self, t0, t1: None)
    elif fault == "half the batch":
        def broken(L, keys, sigma):
            out = jitter(L, keys, sigma)
            return out.at[out.shape[0] // 2:].set(0)
        mp.setattr(jb, "_jitter_impl", broken)
    elif fault == "answer altered":
        mp.setattr(jb, "_poisson_impl",
                   lambda keys, lam: poisson(keys, lam).at[0, 0].add(1))


@pytest.mark.parametrize("fault", [None, "state unchanged", "half the batch",
                                   "answer altered"])
def test_game_fleet_run_catches_each_fault(fault, tiny_fleet, monkeypatch):
    _game_faults(monkeypatch, fault)
    traffic = load_json(BENCH_DIR / "traffic" / "game.json")
    result, checks = measure(SimpleNamespace(workload="fleet-game", **ARGS),
                             TINY_BENCH, require_chip=False,
                             files=(tiny_fleet, traffic))
    assert result["correct"] is (fault is None), checks
    assert set(result["metrics"]) == {"tenant_s_per_s", "setup_s"}

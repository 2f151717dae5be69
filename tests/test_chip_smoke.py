"""CPU rehearsal of ``chip_smoke.py``: its phases at reduced sizes, and
its refusal to run without a TPU."""
import functools
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_reduced

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **extra)
    return env


@pytest.fixture(scope="module")
def served(smoke):
    return smoke.serving_phase(get_reduced("tinyllama-1.1b"))


def test_serving_phase_and_cache_check(smoke, served):
    assert len(served.completed) + len(served.cloud_serviced) == 32
    assert smoke.cache_check(served) <= smoke.logits_tol(2)


def _decode_at_next_pos(rt):
    decode = rt._decode
    return lambda params, cache, token, pos: decode(params, cache, token,
                                                    pos + 1)


def _decode_drops_writes(rt):
    decode = rt._decode
    return lambda params, cache, token, pos: (
        decode(params, cache, token, pos)[0], cache)


@pytest.mark.parametrize("fault", ["decode_at_next_pos", "decode_drops_writes",
                                   "prefill_into_other_slot"])
def test_cache_check_catches_planted_fault(smoke, served, monkeypatch, fault):
    from repro.serving import engine

    rt = next(iter(served.tenants.values()))
    if fault == "decode_at_next_pos":
        monkeypatch.setattr(rt, "_decode", _decode_at_next_pos(rt))
    elif fault == "decode_drops_writes":
        monkeypatch.setattr(rt, "_decode", _decode_drops_writes(rt))
    else:                       # slot 0 decodes against an empty cache
        insert = engine._insert_cache
        monkeypatch.setattr(engine, "_insert_cache",
                            lambda cache, one, slot, *a: insert(
                                cache, one, slot + 1, *a))
    with pytest.raises(smoke.SmokeFailure, match="disagrees with prefill"):
        smoke.cache_check(served)


def test_serving_phase_fails_on_lost_request(smoke, monkeypatch):
    from repro.serving import MultiTenantEngine

    submit = MultiTenantEngine.submit

    def lossy(self, tenant, prompt, **kw):
        rs = submit(self, tenant, prompt, **kw)
        if self._rid == 3:          # drop one request from the books
            self.sched.tenants[tenant].waiting.remove(rs)
        return rs

    monkeypatch.setattr(MultiTenantEngine, "submit", lossy)
    with pytest.raises(smoke.SmokeFailure, match="request accounting"):
        smoke.serving_phase(get_reduced("tinyllama-1.1b"), requests=4,
                            max_new_tokens=4, round_steps=2)


def test_fleet_phase(smoke, monkeypatch):
    from repro.sim.engines import jax_backend

    # the Pallas kernel compiles for the TPU only; run it interpreted here
    monkeypatch.setattr(jax_backend, "_pallas_latency_scale",
                        functools.partial(jax_backend._pallas_latency_scale,
                                          interpret=True))
    rows = smoke.fleet_phase(dict(smoke.STREAM_FLEET, per_node=100),
                             dict(smoke.GAME_FLEET, per_node=10))
    assert [(r["fleet"], r["options"]) for r in rows] == [
        ("stream", {}), ("game", {}), ("game", {"pallas": True})]
    assert all(abs(r["dvr"]) <= smoke.VR_TOL for r in rows)


def test_sharded_phase_on_four_virtual_devices():
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('s', {str(SCRIPT)!r})\n"
        "s = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(s)\n"
        "s.sharded_phase((dict(s.STREAM_FLEET, per_node=100),\n"
        "                 dict(s.GAME_FLEET, per_node=5)))\n")
    env = _cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "outputs each on 4 devices" in r.stdout
    assert r.stdout.count("devices=4") == 2


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_main_refuses_cpu(args):
    r = subprocess.run([sys.executable, str(SCRIPT), *args], env=_cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert "platform=cpu" in r.stdout
    for line in r.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)

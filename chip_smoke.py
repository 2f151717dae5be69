"""Smoke run of the system's two device paths on a TPU.

    python chip_smoke.py              # one chip: serving + fleet phases
    python chip_smoke.py --chips 4    # the fleet engine's row sharding
                                      # over 4 chips vs the unsharded run

Phases (each importable, so tests can rehearse them at reduced sizes):

* ``serving_phase`` — two tinyllama-1.1b tenants at published widths
  (random weights from a seed) on one ``MultiTenantEngine`` under its
  ``DyverseController`` (policy ``sdps``): requests accounted for,
  tokens generated, finite logits, quotas moved by DYVERSE rounds.
* ``cache_check`` — prefill followed by decoding through the KV cache
  gives the same last-position logits as ``prefill_fn`` on the whole
  sequence, within :func:`logits_tol`, while the cache of a different
  prefix misses it by more than that.
* ``fleet_phase`` — the ``jax`` fleet engine through ``run_scenario``:
  a 4 × 25,000-tenant stream fleet, a game fleet (time-varying latency
  scale), and the game fleet with the Pallas latency-scale kernel, each
  within :data:`VR_TOL` of the ``batched`` numpy engine on the same fleet.
* ``sharded_phase`` (``--chips 4`` only) — the same fleets with rows
  sharded over every device, bitwise equal to ``shard=False``.

The script refuses to run anywhere but on a TPU: it exits non-zero and
prints no result. Its last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: |ΔVR| allowed between the jax engine and the batched numpy engine on
#: the same fleet (federation_bench.jax_scale_sweep's tolerance): the two
#: draw the same distributions from different random streams, in f32 vs f64
VR_TOL = 0.02


def logits_tol(num_layers: int) -> float:
    """Max |Δlogit| allowed between prefill+decode and a whole-sequence
    prefill, as a fraction of the reference's largest |logit|.

    Both paths compute in bfloat16, whose relative step is 2^-8. They
    run different matmul shapes (one token against a cache, a whole
    sequence at once), so each of a layer's two residual branches
    (attention, MLP) can round differently: one bf16 step of either sign
    per branch, adding up as a random walk over the 2·num_layers
    branches. For tinyllama's 22 layers that is 2.6%."""
    return 2.0 ** -8 * math.sqrt(2 * num_layers)


ARCH = "tinyllama-1.1b"
#: the serving engine: TENANTS tenants of SLOT_CAP decode slots each
TENANTS, SLOT_CAP, MAX_SEQ_LEN = 2, 8, 1024
#: one prompt length, because prefill compiles once per distinct length
PROMPT_LEN = 128
SLO_S = 12.0
SERVE_SEED = 0
#: the cache check prefills CHECK_PREFIX tokens and decodes CHECK_DECODE
CHECK_PREFIX, CHECK_DECODE, CHECK_SEED = 120, 8, 1
FLEET_SEED = 7
STREAM_FLEET = dict(kind="stream", n_nodes=4, per_node=25_000,
                    duration=240, round_interval=120)
GAME_FLEET = dict(kind="game", n_nodes=4, per_node=1000, duration=240,
                  round_interval=120)


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ serving
def _watch_logits(fn, flags: list):
    """Wrap a jitted prefill/decode so every logits array it returns
    adds a device-side all-finite flag to ``flags``."""
    import jax.numpy as jnp

    def wrapped(*args):
        logits, cache = fn(*args)
        flags.append(jnp.isfinite(logits).all())
        return logits, cache
    return wrapped


def serving_phase(cfg=None, *, requests: int = 16, max_new_tokens: int = 32,
                  round_steps: int = 16):
    """Serve ``requests`` per tenant through ``MultiTenantEngine`` and
    check the result. Returns the engine (its tenants stay loaded)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import PricingModel, TenantSpec
    from repro.serving import EngineConfig, MultiTenantEngine
    from repro.serving.spec import VirtualClock

    cfg = cfg or get_config(ARCH)
    # a virtual clock (one step = 0.25 s, as in the serving federation)
    # makes every DYVERSE decision independent of the hardware's speed
    clock = VirtualClock(0.25)
    eng = MultiTenantEngine(EngineConfig(
        slot_cap=SLOT_CAP, max_seq_len=MAX_SEQ_LEN,
        round_interval_steps=round_steps, policy="sdps",
        capacity_slots=TENANTS * SLOT_CAP,
        capacity_pages=TENANTS * SLOT_CAP * 16), seed=SERVE_SEED,
        clock=clock)
    t0 = time.perf_counter()
    names = [f"t{i}" for i in range(TENANTS)]
    for name in names:
        check(eng.add_tenant(TenantSpec(name=name, slo_latency=SLO_S,
                                        pricing=PricingModel.HYBRID,
                                        arch=cfg.name), cfg),
              f"tenant {name} was not admitted")
    flags: list = []
    for rt in eng.tenants.values():
        rt._prefill = _watch_logits(rt._prefill, flags)
        rt._decode = _watch_logits(rt._decode, flags)
    print(f"serving: {TENANTS} x {cfg.name} (layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} param_dtype="
          f"{cfg.param_dtype} dtype={cfg.dtype}) loaded in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    rng = np.random.default_rng(SERVE_SEED)
    for _ in range(requests):
        for name in names:
            eng.submit(name, rng.integers(1, cfg.vocab_size,
                                          PROMPT_LEN).tolist(),
                       max_new_tokens=max_new_tokens)
    submitted = requests * TENANTS

    quota_moves = rounds = 0
    t0 = time.perf_counter()
    max_steps = 4 * requests * max_new_tokens
    while eng.steps < max_steps and any(
            tq.active or tq.waiting for tq in eng.sched.tenants.values()):
        before = {n: tq.quota for n, tq in eng.sched.tenants.items()}
        eng.step()
        clock.tick()
        if eng.steps % round_steps == 0:
            rounds += 1
            after = {n: tq.quota for n, tq in eng.sched.tenants.items()}
            quota_moves += after != before
    all_finite = bool(jnp.stack(flags).all()) if flags else False
    wall = time.perf_counter() - t0

    done, cloud = len(eng.completed), len(eng.cloud_serviced)
    tokens = sum(len(rs.generated) for rs in eng.completed)
    print(f"serving: steps={eng.steps} rounds={rounds} "
          f"rounds_moving_quotas={quota_moves} submitted={submitted} "
          f"completed={done} cloud={cloud} tokens={tokens} "
          f"logit_arrays={len(flags)} all_finite={all_finite} "
          f"host_wall_s={wall:.3f} tokens_per_host_s={tokens / wall:.1f} "
          f"quotas={ {n: (q.slots, q.pages) for n, q in before.items()} }",
          flush=True)
    check(submitted == done + cloud,
          f"request accounting: {submitted} != {done} + {cloud}")
    check(tokens > 0, "no tokens generated")
    check(all(len(rs.generated) == max_new_tokens for rs in eng.completed),
          "a completed request has the wrong number of tokens")
    check(all_finite, "non-finite logits")
    check(quota_moves >= 2, f"only {quota_moves} rounds moved quotas")
    return eng


def _cached_logits(eng, rt, prefix, tail):
    """Last logits of prefilling ``prefix`` into slot 0 of the tenant's
    cache and then decoding ``tail`` through it, one token a step."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.engine import _insert_cache

    _, one = rt._prefill(rt.params, {"tokens": jnp.asarray(prefix[None],
                                                           jnp.int32)})
    cache = _insert_cache(rt.cache, one, 0, rt.cfg, eng.cfg.max_seq_len)
    B = len(rt.slot_req)
    for j, t in enumerate(tail):
        token = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        token[0], pos[0] = t, len(prefix) + j
        logits, cache = rt._decode(rt.params, cache, jnp.asarray(token),
                                   jnp.asarray(pos))
    return np.asarray(logits[0], np.float32)


def cache_check(eng) -> float:
    """Prefill CHECK_PREFIX tokens, decode CHECK_DECODE more through the
    cache, and compare the last logits with ``prefill_fn`` on the whole
    sequence. Returns max |Δ| / max |reference|.

    As a control, the same tokens are decoded through the cache of a
    different prefix: that must miss the reference by more than the
    tolerance, or the check could not tell a wrong cache from the right
    one."""
    import jax.numpy as jnp
    import numpy as np

    rt = next(iter(eng.tenants.values()))
    rng = np.random.default_rng(CHECK_SEED)
    seq = rng.integers(1, rt.cfg.vocab_size, CHECK_PREFIX + CHECK_DECODE)
    other = rng.integers(1, rt.cfg.vocab_size, CHECK_PREFIX)
    prefix, tail = seq[:CHECK_PREFIX], seq[CHECK_PREFIX:]
    ref, _ = rt._prefill(rt.params, {"tokens": jnp.asarray(seq[None],
                                                           jnp.int32)})
    ref = np.asarray(ref[0], np.float32)
    got = _cached_logits(eng, rt, prefix, tail)
    wrong = _cached_logits(eng, rt, other, tail)
    diff = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    rel = diff / scale
    rel_wrong = float(np.abs(wrong - ref).max()) / scale
    tol = logits_tol(rt.cfg.num_layers)
    print(f"cache_check: prefill {CHECK_PREFIX} + decode {CHECK_DECODE} vs "
          f"prefill {CHECK_PREFIX + CHECK_DECODE}: max_abs_diff={diff!r} "
          f"max_abs_ref={scale!r} rel={rel!r} tol={tol!r} "
          f"argmax_equal={int(got.argmax()) == int(ref.argmax())} "
          f"control_other_prefix_rel={rel_wrong!r}", flush=True)
    check(math.isfinite(rel) and rel <= tol,
          f"cache path disagrees with prefill: rel {rel} > {tol}")
    check(rel_wrong > tol, f"the cache of another prefix also agrees with "
                           f"prefill: rel {rel_wrong} <= {tol}")
    return rel


# -------------------------------------------------------------------- fleet
def fleet_scenario(kind: str, n_nodes: int, per_node: int, duration: int,
                   round_interval: int, engine: str = "jax",
                   backend_options: dict | None = None):
    """The federation_bench fleets as a Scenario (sdps, reactive)."""
    from repro.sim.scenario import (FleetSpec, Scenario, TenantClassSpec,
                                    TopologySpec)

    return Scenario(
        name=f"smoke_{kind}",
        fleet=FleetSpec(classes=(TenantClassSpec(kind,
                                                 n_nodes * per_node),)),
        topology=TopologySpec(n_nodes=n_nodes, headroom=16),
        duration_s=duration, round_interval=round_interval, seed=FLEET_SEED,
        policies=("sdps",), engine=engine,
        backend_options=dict(backend_options or {}))


def run_fleet(sc):
    """(violation rate, FederationResult, host wall seconds)."""
    from repro.sim.scenario import run_scenario

    t0 = time.perf_counter()
    res = run_scenario(sc)
    wall = time.perf_counter() - t0
    oc = res.outcomes["sdps"]
    vrs = [oc.violation_rate, *oc.per_node_vr.values()]
    check(all(math.isfinite(v) for v in vrs), f"{sc.name}: non-finite VR")
    return oc.violation_rate, res.results["sdps"], wall


def fleet_phase(stream: dict = STREAM_FLEET,
                game: dict = GAME_FLEET) -> list[dict]:
    """The jax engine vs the batched engine on a stream and a game fleet,
    plus the game fleet through the Pallas latency-scale kernel."""
    runs = [(stream, {}), (game, {}), (game, {"pallas": True})]
    ref_vr: dict = {}
    rows = []
    for shape, opts in runs:
        key = shape["kind"]
        if key not in ref_vr:
            vr, _, wall = run_fleet(fleet_scenario(**shape,
                                                   engine="batched"))
            ref_vr[key] = vr
            print(f"fleet: {key} {shape['n_nodes']}x{shape['per_node']} "
                  f"x{shape['duration']}s batched VR={vr:.6f} "
                  f"host_wall_s={wall:.3f}", flush=True)
        vr, res, wall = run_fleet(fleet_scenario(**shape,
                                                 backend_options=opts))
        dvr = vr - ref_vr[key]
        reqs = int(res.total_requests)
        print(f"fleet: {key} jax{opts or ''} VR={vr:.6f} "
              f"dVR_pp={dvr * 100:+.4f} requests={reqs} "
              f"host_wall_s={wall:.3f}", flush=True)
        check(abs(dvr) <= VR_TOL,
              f"{key} {opts}: |dVR| {abs(dvr)} > {VR_TOL}")
        rows.append({"fleet": key, "options": opts, "vr": vr, "dvr": dvr})
    return rows


def _digest(res) -> str:
    h = hashlib.sha256()
    for name in sorted(res.node_results):
        h.update(res.node_results[name].latencies.tobytes())
    return h.hexdigest()


def sharded_phase(fleets=(STREAM_FLEET, GAME_FLEET)) -> None:
    """Rows sharded over every device must be bitwise equal to the
    unsharded run, and the kernels' outputs must be spread over every
    device."""
    import jax

    from repro.sim.engines import jax_backend

    ndev = len(jax.devices())
    check(ndev > 1, f"sharding needs several devices, found {ndev}")
    for shape in fleets:
        jax_backend._KERNEL_CACHE.clear()
        vr_s, res_s, wall_s = run_fleet(fleet_scenario(**shape))
        sharded = [k for k in jax_backend._KERNEL_CACHE if k[1] == ndev]
        vr_u, res_u, wall_u = run_fleet(fleet_scenario(
            **shape, backend_options={"shard": False}))
        d_s, d_u = _digest(res_s), _digest(res_u)
        print(f"sharded: {shape['kind']} {shape['n_nodes']}x"
              f"{shape['per_node']}x{shape['duration']}s devices={ndev} "
              f"VR sharded={vr_s!r} unsharded={vr_u!r} "
              f"digest sharded={d_s[:16]} unsharded={d_u[:16]} "
              f"kernels_sharded={sorted(str(k[0]) for k in sharded)} "
              f"host_wall_s sharded={wall_s:.3f} unsharded={wall_u:.3f}",
              flush=True)
        check(sharded, f"{shape['kind']}: no kernel ran sharded")
        check(vr_s == vr_u and d_s == d_u,
              f"{shape['kind']}: sharded run differs from unsharded")
        check(res_s.per_node_vr == res_u.per_node_vr,
              f"{shape['kind']}: per-node VR differs")
        if shape["kind"] == "stream":
            _check_spread(ndev)


def _check_spread(ndev: int) -> None:
    """Call the sharded dense kernel the stream run compiled and check
    that its outputs are spread over every device."""
    import jax
    import numpy as np

    from repro.sim.engines import jax_backend

    (_, S), f = next((k[0], f) for k, f in jax_backend._KERNEL_CACHE.items()
                     if k[1] == ndev and k[0][0] == "dense")
    rows = 2 * ndev
    outs = f(np.zeros((rows, 2), np.uint32), np.ones((rows, S), bool),
             np.ones(rows, np.float32), np.ones(rows, np.float32),
             np.ones(rows, np.float32))
    for out in outs:
        devs = {s.device for s in out.addressable_shards}
        check(len(devs) == ndev,
              f"dense kernel output on {len(devs)} of {ndev} devices")
    print(f"sharded: dense kernel outputs each on {ndev} devices, "
          f"{rows // ndev} of {rows} rows per device", flush=True)


# --------------------------------------------------------------------- main
def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the fleet engine's row sharding over "
                         "4 chips against the unsharded run")
    args = ap.parse_args(argv)

    dev = device_info()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{dev['platform']!r}); this smoke runs only on a TPU",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {dev['count']}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        phases = [("sharded", sharded_phase)]
    else:
        def serving():
            eng = serving_phase()
            print(f"serving: peak_bytes_in_use={peak_bytes()}", flush=True)
            cache_check(eng)
        phases = [("serving", serving), ("fleet", fleet_phase)]
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:                # noqa: BLE001 — report every phase
            traceback.print_exc()
            failed.append(name)
        print(f"phase {name}: {'FAILED' if name in failed else 'ok'} in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
    if failed:
        print(f"chip_smoke: phases failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

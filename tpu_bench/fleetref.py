"""Plain reference of one fleet chunk: per tenant, the requests, the SLO
violations and the latency sum that the chunk should produce.

It follows the simulator's documented model (``repro.sim.workload``,
``repro.sim.engines.jax_backend``), written out again here without
importing the program:

* arrivals per second: a stream tenant's frames ``⌊fps·(t+1)⌋ −
  ⌊fps·t⌋``; a game tenant's ``Poisson(λ_t)`` with ``λ_t = users · rate ·
  max(1 + amp·sin(2πt/period + users), 0.05)``, drawn from the tenant's
  counter key (kind 0);
* latency of each request: ``base · pf · max(1, ρ)^α · exp(σ z)`` with
  ``ρ = demand / (max(units, 1) · unit_rate)``, ``z`` standard normal
  from the tenant's counter key (kind 1); a violation when it exceeds
  the SLO (``base``). Where a latency lies within ``band`` (relative) of
  the SLO, rounding decides it: the reference counts such requests
  apart, and a tenant's violations are right when they lie between the
  reference's sure count and that count plus the undecided ones. On the dense layout (≤ 1 request a second) the
  request of second ``s`` takes ``z[s]`` of a draw of ``S`` values, else
  the j-th request of the chunk takes ``z[j]`` of a draw of ``L`` values,
  ``L`` the chunk's most requests of any tenant rounded up to 64;
* counter keys ``(k0, k1 ⊕ mix(2·t0 + kind))`` with ``k0 = mix(crc ⊕
  mix(seed))``, ``k1 = mix(crc · 0x9E3779B9 + seed)``, ``crc`` the CRC-32
  of the tenant's name and ``mix`` the splitmix32 finaliser.

The arithmetic is float64 from the float32 normals; the control
(``dtype="bfloat16"``) computes the latencies in bfloat16 instead.
"""
from __future__ import annotations

import zlib

import numpy as np

LANE = 64


def mix32(x):
    x = np.asarray(x, np.uint32).copy()
    with np.errstate(over="ignore"):
        x ^= x >> np.uint32(16)
        x *= np.uint32(0x7FEB352D)
        x ^= x >> np.uint32(15)
        x *= np.uint32(0x846CA68B)
        x ^= x >> np.uint32(16)
    return x


def keys(names: list[str], seed: int, t0: int, kind: int) -> np.ndarray:
    s = np.uint32(seed & 0xFFFFFFFF)
    crc = np.array([zlib.crc32(n.encode()) for n in names], np.uint32)
    with np.errstate(over="ignore"):
        k0 = mix32(crc ^ mix32(s))
        k1 = mix32(crc * np.uint32(0x9E3779B9) + s)
    cw = mix32(np.uint32((2 * t0 + kind) & 0xFFFFFFFF))
    return np.stack([k0, k1 ^ cw], axis=1)


def _draw(fn, key_data, *args, backend: str | None = "cpu"):
    """``fn(key, *args)`` for each row's counter key, on ``backend``
    (None: JAX's default device, the chip the program draws on)."""
    import jax

    dev = jax.devices(backend)[0]
    with jax.default_device(dev):
        return np.asarray(jax.jit(jax.vmap(
            lambda k, *a: fn(jax.random.wrap_key_data(k), *a)))(
                key_data, *args))


def normals(key_data: np.ndarray, n: int) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    return _draw(lambda k: jax.random.normal(k, (n,), jnp.float32), key_data)


def poissons(key_data: np.ndarray, lam: np.ndarray) -> np.ndarray:
    """Poisson counts of each row's key at float32 rates, drawn on the
    default device, as the program draws them: the rejection sampler's
    float32 transcendentals differ in their last bits between backends,
    which flips about one count in 3,000 on the chip against the CPU, and
    a count, unlike a latency, has no tolerance."""
    import jax
    import jax.numpy as jnp

    return _draw(lambda k, l: jax.random.poisson(k, l, dtype=jnp.int32),
                 key_data, lam.astype(np.float32), backend=None)


def params(chunk: dict, table: dict) -> dict:
    """The parameter table's entries for the chunk's tenants, in its
    row order."""
    idx = np.array([table["index"][n] for n in chunk["names"]])
    return {k: (np.asarray(v)[idx] if isinstance(v, np.ndarray) else v)
            for k, v in table.items() if k != "index"}


def rates(p: dict, traffic: dict, t0: int, t1: int) -> tuple:
    """(arrival rate or None, demand) per (tenant, second)."""
    t = np.arange(t0, t1, dtype=np.float64)
    if traffic["workload"] == "stream":
        return None, (p["fps"] * p["work_per_request"])[:, None]
    users = p["n_users"][:, None]
    phase = np.maximum(1.0 + p["burst_amp"] * np.sin(
        2 * np.pi * t[None] / p["burst_period"] + users), 0.05)
    lam = users * p["rate_per_user"] * phase
    return lam, lam * p["work_per_request"]


def reference(chunk: dict, table: dict, traffic: dict, seed: int,
              dtype: str = "float64", band: float = 0.0) -> dict:
    """Requests, violations and latency sums of every tenant of a chunk;
    ``viol_near`` counts the requests whose latency lies within ``band``
    of the SLO, which ``viol`` leaves out."""
    t0, t1 = chunk["t0"], chunk["t1"]
    S = t1 - t0
    names = chunk["names"]
    p = params(chunk, table)
    lam, demand = rates(p, traffic, t0, t1)
    if lam is None:
        t = np.arange(t0, t1 + 1, dtype=np.float64)
        counts = np.diff(np.floor(p["fps"][:, None] * t[None]), axis=1)
        counts = counts.astype(np.int64)
    else:
        counts = poissons(keys(names, seed, t0, 0), lam).astype(np.int64)
    cap = np.maximum(chunk["units"], 1) * p["unit_rate"]
    rho = demand / cap[:, None]
    scale = (p["base_latency"] * p["provisioned_factor"]
             * np.where(rho > 1.0, np.maximum(rho, 1.0) ** p["alpha"], 1.0))
    scale = np.broadcast_to(scale, counts.shape)
    totals = counts.sum(1)
    dense = lam is None and counts.max(initial=0) <= 1
    n = S if dense else -(-int(totals.max(initial=0)) // LANE) * LANE
    z = normals(keys(names, seed, t0, 1), max(n, 1))[:, :n]
    if dense:
        take, zr = counts > 0, z
    else:
        # request j of a tenant falls in the second its arrivals reach
        sec = np.zeros((len(names), n), np.int64)
        ends = np.cumsum(counts, axis=1)
        for i in range(len(names)):
            sec[i] = np.minimum(np.searchsorted(ends[i], np.arange(n),
                                                side="right"), S - 1)
        take = np.arange(n)[None] < totals[:, None]
        zr, scale = z, np.take_along_axis(scale, sec, axis=1)
    sigma = np.asarray(p["jitter_sigma"], np.float64)
    if dtype == "bfloat16":
        import jax.numpy as jnp

        bf = jnp.bfloat16
        lat = np.asarray((jnp.asarray(scale, bf) * jnp.exp(
            jnp.asarray(zr, bf) * jnp.asarray(sigma, bf))), np.float64)
        lat = np.where(take, lat, 0.0)
        lat_sums = np.where(take, lat, 0.0).astype(np.float32).sum(
            1, dtype=np.float32).astype(np.float64)
    else:
        lat = np.where(take, scale * np.exp(zr.astype(np.float64) * sigma),
                       0.0)
        lat_sums = lat.sum(1)
    slo = np.asarray(p["base_latency"], np.float64)
    near = take & (np.abs(lat - slo) <= band * slo)
    viol = ((lat > slo) & take & ~near).sum(1)
    return {"totals": totals, "viol": viol, "viol_near": near.sum(1),
            "lat_sums": lat_sums}


def compare(got: dict, ref: dict) -> dict:
    """The three numbers the check holds against its limits: requests
    that differ, violations outside what the reference allows (its sure
    count, up to its undecided ones), and the widest relative gap of a
    latency sum."""
    req = int(np.abs(np.asarray(got["totals"]) - ref["totals"]).sum())
    v = np.asarray(got["viol"], np.int64)
    lo, hi = ref["viol"], ref["viol"] + ref["viol_near"]
    viol = int((np.maximum(lo - v, 0) + np.maximum(v - hi, 0)).sum())
    has = ref["lat_sums"] > 0
    g = np.asarray(got["lat_sums"], np.float64)
    lat = float((np.abs(g[has] - ref["lat_sums"][has])
                 / ref["lat_sums"][has]).max(initial=0.0))
    return {"req_abs_diff": req, "viol_outside_band": viol,
            "latsum_max_rel_diff": lat}


def rows_of(chunk: dict, names) -> np.ndarray:
    """The chunk's row of each named tenant."""
    at = {n: i for i, n in enumerate(chunk["names"])}
    return np.array([at[n] for n in names], np.int64)


def select(ref: dict, rows) -> dict:
    return {k: np.asarray(v)[rows] for k, v in ref.items()}


def kernel_calls(chunk: dict, traffic: dict) -> list[dict]:
    """The shapes of the fleet kernels a chunk ran (one row tile)."""
    rows, S = len(chunk["names"]), chunk["t1"] - chunk["t0"]
    if traffic["workload"] == "stream" and chunk["max_count"] <= 1:
        return [{"kind": "dense", "rows": rows, "cols": S}]
    L = -(-int(chunk["totals"].max(initial=0)) // LANE) * LANE
    calls = [{"kind": "jitter", "rows": rows, "cols": L}]
    if traffic["workload"] == "game":
        calls.insert(0, {"kind": "poisson", "rows": rows, "cols": S})
    return calls

"""``engine="jax"``: the accelerator-native mega-scale fleet engine.

Ports the :class:`~repro.sim.edgesim.FleetStepper` chunk math to
``jax.jit`` + ``vmap``: the whole fleet's jitter draw and latency
evaluation run as one fused (rows × max-requests) float32 kernel, with
only the ragged per-request bookkeeping (flat extraction, per-second
violation attribution, Monitor feeds) left to numpy. On a multi-device
runtime the row axis is sharded across devices with ``jax.shard_map``.

RNG scheme (``counter-jax``): every draw comes from a counter-based
threefry stream whose 64-bit key_data is a vectorized splitmix32 mix::

    k0 = mix32(crc32(tenant) ^ mix32(seed))
    k1 = mix32(crc32(tenant)·φ32 + seed) ^ mix32(2·chunk_t0 + kind)

with ``kind`` 0 for arrival counts and 1 for jitter (both key words
depend on the tenant, so a full key collision needs a 64-bit
coincidence). A tenant's draws therefore depend only on (seed, tenant
name, chunk start, draw kind) — NOT on which node hosts it, how rows
are ordered, how many RNG worker threads exist, or how many devices
the matrix is sharded over. Repeated runs are bitwise identical to
each other; placement changes, node failures, ``rng_workers`` and
device counts can never perturb the trace.

Equivalence contract (``tolerance``) — exactly where and why bitwise
equality with the scalar/vectorized/batched trio breaks:

1. **Different random streams.** The trio draws from per-tenant numpy
   PCG64 substreams; this engine draws the same *distributions*
   (Poisson(λ) arrivals, lognormal(0, σ) jitter) from threefry counter
   streams. Identical λ/σ, different bits — so per-request latencies,
   and every quantity downstream of them, are statistically equivalent
   rather than equal.
2. **float32 arithmetic.** Jitter and latency math run in f32 (the
   accelerator-native dtype); SLO comparisons near the threshold can
   resolve differently than the trio's f64 path even for equal inputs.
3. **Reduction order.** Per-tenant latency sums come from dense row
   reductions / an f64 cumulative-sum difference, not numpy's pairwise
   ``.sum()`` per tenant.

The deterministic *rate* math (arrival λ, demand, the latency-scale
factor) is still evaluated by the shared float64
:class:`~repro.sim.workload.FleetBatch` path, so controller inputs
differ only through the sampled noise. Tolerances are pinned by
tests/test_jax_engine.py: violation rates and latency summaries match
the batched engine within a few percentage points at smoke scale, and
tighter as fleets grow.

Workload support: a class must either declare its arrival counts
RNG-free (``arrival_rng_free = True``, e.g. StreamWorkload's closed
form) or expose its Poisson rate matrix (``batch_arrival_lam``, e.g.
GameWorkload); anything else raises with a pointer at
``engine="batched"``.

``SimConfig.backend_options`` knobs: ``shard`` (bool, default True —
shard rows over devices when more than one is visible) and ``pallas``
(bool, default False — route the latency-scale factor through the fused
Pallas kernel, which compiles for the TPU only).
"""
from __future__ import annotations

import functools
import inspect
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.sim.edgesim import FleetStepper
from repro.sim.engines.base import EngineBackend

_F32 = jnp.float32
_KIND_ARRIVAL = np.uint32(0)
_KIND_JITTER = np.uint32(1)
# dense (rows × L) request matrices are padded to a multiple of this so
# chunk-to-chunk arrival noise doesn't force a recompile per chunk
_LANE = 64
# row-tile cap: ceiling on the dense matrix a single kernel call may
# materialise (cells), so huge-L fleets page through row tiles instead
# of allocating tens of GB
_MAX_CELLS = 1 << 27


def _pad_len(n: int) -> int:
    return -(-n // _LANE) * _LANE if n else 0


# ----------------------------------------------------- key derivation
def _mix32(x: np.ndarray) -> np.ndarray:
    """splitmix32 finalizer, vectorized over uint32 — the host-side key
    mixer. Deriving the 64-bit threefry key_data with numpy instead of
    vmapped ``fold_in`` chains is ~50× cheaper per chunk (vmapped
    scalar fold_in doesn't batch well on CPU) while keeping the same
    counter-RNG properties: the key is a pure function of
    (seed, tenant, chunk, kind), so draws stay placement-, worker- and
    device-count-invariant."""
    x = np.uint32(x) if np.isscalar(x) else x.astype(np.uint32, copy=True)
    with np.errstate(over="ignore"):
        x ^= x >> np.uint32(16)
        x *= np.uint32(0x7FEB352D)
        x ^= x >> np.uint32(15)
        x *= np.uint32(0x846CA68B)
        x ^= x >> np.uint32(16)
    return x


def _fused_impl(L, keys, totals, scale, sigma, slo):
    """One row per (node, tenant): draw L jitter values from the row's
    counter key, evaluate latency = scale·exp(σz), compare to the SLO,
    and reduce — all fused in one jit. Rows are independent, so the
    function is shard_map-safe over the leading axis."""
    ar = jnp.arange(L, dtype=jnp.int32)
    valid = ar[None, :] < totals[:, None]
    z = jax.vmap(lambda k: jax.random.normal(
        jax.random.wrap_key_data(k), (L,), dtype=_F32))(keys)
    lat = scale[:, None] * jnp.exp(z * sigma[:, None])
    viol = valid & (lat > slo[:, None])
    lat_sum = jnp.where(valid, lat, jnp.zeros((), _F32)).sum(axis=1)
    return lat, viol, lat_sum, viol.sum(axis=1, dtype=jnp.int32)


def _dense_impl(S, keys, active, scale, sigma, slo):
    """Sparse-arrival fast path (≤1 request per tenant-second, e.g.
    stream fleets): the (rows × seconds) grid IS the request layout, so
    per-second violation flags and row reductions all come out of the
    kernel and the ragged searchsorted/bincount attribution vanishes."""
    z = jax.vmap(lambda k: jax.random.normal(
        jax.random.wrap_key_data(k), (S,), dtype=_F32))(keys)
    lat = scale[:, None] * jnp.exp(z * sigma[:, None])
    viol = active & (lat > slo[:, None])
    lat_sum = jnp.where(active, lat, jnp.zeros((), _F32)).sum(axis=1)
    return lat, viol, lat_sum, viol.sum(axis=1, dtype=jnp.int32)


def _jitter_impl(L, keys, sigma):
    """Jitter-only variant for time-varying latency scales (the
    per-request scale product happens numpy-side there)."""
    z = jax.vmap(lambda k: jax.random.normal(
        jax.random.wrap_key_data(k), (L,), dtype=_F32))(keys)
    return jnp.exp(z * sigma[:, None])


def _poisson_impl(keys, lam):
    return jax.vmap(lambda k, l: jax.random.poisson(
        jax.random.wrap_key_data(k), l, dtype=jnp.int32))(keys, lam)


# --------------------------------------------------- pallas scale kernel
def _scale_kernel(base_ref, alpha_ref, demand_ref, cap_ref, o_ref):
    # fused demand → ρ → max(1, ρ)^α → scale chain, one pass per block
    rho = demand_ref[...] / cap_ref[...]
    o_ref[...] = base_ref[...] * jnp.maximum(1.0, rho) ** alpha_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_latency_scale(base_pf, alpha, demand, capacity,
                          interpret=False):
    """base·pf·max(1, demand/capacity)^α as a Pallas kernel over row
    blocks (``backend_options={"pallas": True}``). It compiles for the
    TPU; off the TPU only ``interpret=True`` runs it."""
    from jax.experimental import pallas as pl

    T, W = demand.shape
    bT = min(T, 256)
    grid = (-(-T // bT),)
    col = lambda i: (i, 0)  # noqa: E731
    return pl.pallas_call(
        _scale_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bT, 1), col), pl.BlockSpec((bT, 1), col),
                  pl.BlockSpec((bT, W), col), pl.BlockSpec((bT, 1), col)],
        out_specs=pl.BlockSpec((bT, W), col),
        out_shape=jax.ShapeDtypeStruct((T, W), demand.dtype),
        interpret=interpret,
    )(base_pf[:, None], alpha[:, None], demand, capacity[:, None])


# ------------------------------------------------------------- stepper
_KERNEL_CACHE: dict = {}


class JaxFleetStepper(FleetStepper):
    """Fleet stepper for ``engine="jax"`` (see module docstring for the
    RNG scheme and tolerance contract). Reuses the batched stepper's
    epoch-cached fleet stacking, eviction masks, units gathering and
    node/Monitor accounting; replaces the draw + latency math with the
    fused jit kernels, and per-tenant python loops with dense
    reductions. ``users()`` is sampled once per fleet epoch rather than
    per chunk (the built-in workloads report constant users)."""

    def __init__(self, nodes: list):
        super().__init__(nodes)
        opts = nodes[0].cfg.backend_options if nodes else {}
        self._use_pallas = bool(opts.get("pallas", False))
        self._mesh = None
        self._ndev = 1
        if opts.get("shard", True):
            devs = jax.devices()
            if len(devs) > 1:
                self._mesh = Mesh(np.array(devs), ("data",))
                self._ndev = len(devs)

    # -------------------------------------------------------- caches
    def _gather_rngs(self, entries: list) -> None:
        # counter-RNG engine: draws are keyed by (seed, tenant, chunk,
        # kind) at call time — skip the per-tenant Generator gather
        self._arr_rngs = self._jit_rngs = None

    def _rebuild(self) -> None:
        super()._rebuild()
        entries = self._entries
        T = len(entries)
        self._act_p = None
        # row padding keeps every kernel's leading axis divisible by the
        # device count; padded rows carry totals=0 and are sliced away
        self._Tp = -(-T // self._ndev) * self._ndev if T else 0
        pad = self._Tp - T
        seeds = np.empty(T, np.uint32)
        for node, sl in zip(self.nodes, self._node_slices):
            seeds[sl] = node.cfg.seed & 0xFFFFFFFF
        crcs = np.array([zlib.crc32(name.encode())
                         for _, name, _ in entries], np.uint32)
        # two independent per-row key words; the chunk/kind word is
        # XORed in per chunk (see _row_keys). Both words depend on the
        # tenant, so a full key collision needs a 64-bit coincidence.
        with np.errstate(over="ignore"):
            self._k0 = np.pad(_mix32(crcs ^ _mix32(seeds)), (0, pad))
            self._k1 = np.pad(
                _mix32(crcs * np.uint32(0x9E3779B9) + seeds), (0, pad))
        self._key_buf = np.empty((self._Tp, 2), np.uint32)
        self._key_buf[:, 0] = self._k0
        self._scale_units: np.ndarray | None = None
        self._scale_cache: np.ndarray | None = None
        self._sigma32 = jnp.asarray(np.pad(np.array(
            [wl.jitter_sigma for _, _, wl in entries], np.float32),
            (0, pad)))
        self._slo32_np = self._slos.astype(np.float32)
        self._slo32 = jnp.asarray(np.pad(self._slo32_np, (0, pad),
                                         constant_values=np.inf))
        self._users_arr = np.array([wl.users() for _, _, wl in entries],
                                   np.int64)
        self._wan_np = np.asarray(self._wan, np.float64)
        # single-class fleets in row order skip the group scatter copy
        groups = self._batch.groups
        self._single_group_ordered = (
            len(groups) == 1
            and np.array_equal(groups[0][1], np.arange(T)))
        self._counts_buf = None
        self._counts_out_ok = bool(
            self._single_group_ordered
            and "out" in inspect.signature(
                groups[0][0].batch_arrival_counts).parameters)
        self._modes = []
        for cls, idx, sub in self._batch.groups:
            if getattr(cls, "arrival_rng_free", False):
                self._modes.append("free")
            elif callable(getattr(cls, "batch_arrival_lam", None)):
                self._modes.append("poisson")
            else:
                raise ValueError(
                    f"engine='jax' cannot batch arrivals for workload "
                    f"class {cls.__name__}: it neither declares "
                    f"arrival_rng_free nor implements batch_arrival_lam; "
                    f"use engine='batched' for custom workload classes")

    def _row_keys(self, t0: int, kind: np.uint32) -> np.ndarray:
        """(Tp, 2) uint32 threefry key_data for this (chunk, kind):
        per-row words from the rebuild-time mixes, chunk word XORed in.
        Reuses one buffer — callers copy on device upload."""
        ch = _mix32(np.uint32((2 * t0 + int(kind)) & 0xFFFFFFFF))
        np.bitwise_xor(self._k1, ch, out=self._key_buf[:, 1])
        return self._key_buf

    # -------------------------------------------------------- kernels
    def _call(self, name, impl, n_args, n_out):
        """jit-compile ``impl`` (shard_map'd over the row axis when a
        multi-device mesh is up), memoised process-wide."""
        key = (name, self._ndev)
        f = _KERNEL_CACHE.get(key)
        if f is None:
            f = impl
            if self._mesh is not None:
                spec = P("data")
                # check_vma=False: the poisson sampler's internal while
                # loop has no replication rule, and every kernel here is
                # row-local anyway
                f = jax.shard_map(f, mesh=self._mesh,
                                  in_specs=(spec,) * n_args,
                                  out_specs=(spec,) * n_out if n_out > 1
                                  else spec,
                                  check_vma=False)
            f = jax.jit(f)
            _KERNEL_CACHE[key] = f
        return f

    def _arrival_counts(self, t0: int, t1: int) -> np.ndarray:
        T, S = len(self._entries), t1 - t0
        groups = self._batch.groups
        if len(groups) == 1 and self._modes[0] == "free" \
                and self._single_group_ordered:
            cls, _, sub = groups[0]
            if self._counts_out_ok:
                buf = self._counts_buf
                if buf is None or buf.shape != (T, S):
                    buf = self._counts_buf = np.empty((T, S), np.int64)
                return cls.batch_arrival_counts(sub, [None] * len(sub),
                                                t0, t1, out=buf)
            return cls.batch_arrival_counts(sub, [None] * len(sub), t0, t1)
        out = np.empty((T, S), np.int64)
        akeys = None
        for (cls, idx, sub), mode in zip(groups, self._modes):
            if mode == "free":
                out[idx] = cls.batch_arrival_counts(
                    sub, [None] * len(sub), t0, t1)
                continue
            lam = cls.batch_arrival_lam(sub, t0, t1)
            if akeys is None:
                akeys = self._row_keys(t0, _KIND_ARRIVAL).copy()
            gk = akeys[:T][idx]
            G = len(idx)
            gp = -(-G // self._ndev) * self._ndev
            lam32 = np.zeros((gp, S), np.float32)
            lam32[:G] = lam
            keys_p = np.zeros((gp,) + gk.shape[1:], gk.dtype)
            keys_p[:G] = gk
            f = self._call("poisson", _poisson_impl, 2, 1)
            drawn = np.asarray(f(jnp.asarray(keys_p), jnp.asarray(lam32)))
            out[idx] = drawn[:G]
        return out

    def _latency_scale(self, units: np.ndarray, t0: int,
                       t1: int) -> np.ndarray:
        if not self._use_pallas:
            # a (T, 1) column means every class reported time-invariant
            # demand, so the factor depends on the units vector alone —
            # reuse it while allocations are unchanged
            cached = self._scale_cache
            if cached is not None and cached.shape[1] == 1 \
                    and np.array_equal(units, self._scale_units):
                return cached
            scale = self._batch.latency_scale(units, t0, t1)
            if scale.shape[1] == 1:
                self._scale_units = units.copy()
                self._scale_cache = scale
            return scale
        fb = self._batch
        demand = fb.demand_rates(t0, t1)
        capacity = np.maximum(units, 1) * fb.unit_rate
        return np.asarray(_pallas_latency_scale(
            jnp.asarray(fb.base_pf, _F32), jnp.asarray(fb.alpha, _F32),
            jnp.asarray(demand, _F32), jnp.asarray(capacity, _F32)))

    # ---------------------------------------------------------- step
    # (the public step() lives on FleetStepper: it wraps this body with
    # the optional flight-recorder chunk span and clock-cursor update)
    def _step(self, t0: int, t1: int) -> None:
        epochs = tuple(n._fleet_epoch for n in self.nodes)
        if epochs != self._epochs:
            self._rebuild()
            self._epochs = epochs
        T, S = len(self._entries), t1 - t0
        if T == 0:
            return
        counts = self._arrival_counts(t0, t1)
        totals = counts.sum(axis=1)
        evicted = self._evicted_mask()
        units = self._units_vector(evicted)
        scale = self._latency_scale(units, t0, t1)
        starts = np.zeros(T + 1, np.int64)
        np.cumsum(totals, out=starts[1:])
        L = _pad_len(int(totals.max()))
        slo_rep = np.repeat(self._slo32_np, totals)
        if L == 0:
            flat_lat = np.empty(0, np.float32)
            viol_ts = np.zeros((T, S), np.int64)
            viol_t = np.zeros(T, np.int64)
            lat_sums = np.zeros(T, np.float64)
        else:
            jkeys = jnp.asarray(self._row_keys(t0, _KIND_JITTER))
            if scale.shape[1] == 1 and counts.max() <= 1:
                flat_lat, viol_ts, viol_t, lat_sums = self._step_dense(
                    jkeys, counts, scale, S, T)
            else:
                totals_p = np.zeros(self._Tp, np.int32)
                totals_p[:T] = totals
                if scale.shape[1] == 1:
                    flat_lat, vflat, lat_sums, viol_t = self._step_const(
                        jkeys, totals_p, totals, scale, L, T)
                else:
                    flat_lat, vflat, lat_sums, viol_t = self._step_varying(
                        jkeys, totals_p, totals, starts, scale, counts,
                        slo_rep, L, T)
                vpos = np.flatnonzero(vflat)
                if vpos.size:
                    ends = np.cumsum(counts.ravel())
                    viol_ts = np.bincount(
                        np.searchsorted(ends, vpos, side="right"),
                        minlength=ends.size).reshape(T, S)
                else:
                    viol_ts = np.zeros((T, S), np.int64)
        # Cloud-serviced rows: WAN penalty on the user-visible latencies
        # (after violation counting — evicted rows never enter Eq. 1)
        if flat_lat.size and evicted.any():
            wan_add = np.where(evicted, self._wan_np, 0.0)
            flat_lat = flat_lat + np.repeat(wan_add.astype(np.float32),
                                            totals)
        self._feed_nodes(t0, t1, counts, totals, starts, flat_lat,
                         slo_rep, viol_ts, viol_t, lat_sums, evicted,
                         users_arr=self._users_arr)

    def _row_tiles(self, L: int):
        """Row-tile extents keeping each dense (rows × L) call under
        the cell budget (and divisible by the device count)."""
        rows = max(self._ndev,
                   (_MAX_CELLS // max(L, 1)) // self._ndev * self._ndev)
        return [(lo, min(lo + rows, self._Tp))
                for lo in range(0, self._Tp, rows)]

    def _step_dense(self, jkeys, counts, scale, S, T):
        """≤1 request per tenant-second and a time-invariant scale
        column (stream fleets): the (rows × seconds) grid is the request
        layout, so per-second violation attribution falls straight out
        of the kernel and the ragged cumsum/searchsorted tail is
        skipped. This is the mega-scale hot path: on CPU the device
        buffers alias host memory, so everything but the final ragged
        gather is zero-copy."""
        if getattr(self, "_act_p", None) is None \
                or self._act_p.shape[1] != S:
            # reused across chunks: padding rows stay zero forever, so
            # per-chunk work is one [:T] assignment, no fresh 12 MB page
            # faults
            self._act_p = np.zeros((self._Tp, S), bool)
            self._scale_p = np.zeros(self._Tp, np.float32)
        act_p, scale_p = self._act_p, self._scale_p
        np.greater(counts, 0, out=act_p[:T])
        active = act_p[:T]
        scale_p[:T] = scale[:, 0]
        f = self._call(("dense", S), functools.partial(_dense_impl, S),
                       5, 4)
        tiles = self._row_tiles(S)
        if len(tiles) == 1:
            lat_d, viol_d, lsum_d, vt_d = f(
                jkeys, jnp.asarray(act_p), jnp.asarray(scale_p),
                self._sigma32, self._slo32)
            flat_lat = np.asarray(lat_d)[:T][active]
            return (flat_lat, np.asarray(viol_d)[:T],
                    np.asarray(vt_d)[:T].astype(np.int64),
                    np.asarray(lsum_d)[:T].astype(np.float64))
        flat_parts = []
        viol_ts = np.empty((T, S), np.int32)
        lat_sums = np.empty(T, np.float64)
        viol_t = np.empty(T, np.int64)
        for lo, hi in tiles:
            lat_d, viol_d, lsum_d, vt_d = f(
                jkeys[lo:hi], jnp.asarray(act_p[lo:hi]),
                jnp.asarray(scale_p[lo:hi]), self._sigma32[lo:hi],
                self._slo32[lo:hi])
            tl = min(hi, T)
            if tl <= lo:
                break
            flat_parts.append(np.asarray(lat_d)[:tl - lo][active[lo:tl]])
            viol_ts[lo:tl] = np.asarray(viol_d)[:tl - lo]
            lat_sums[lo:tl] = np.asarray(lsum_d)[:tl - lo]
            viol_t[lo:tl] = np.asarray(vt_d)[:tl - lo]
        flat_lat = (np.concatenate(flat_parts) if flat_parts
                    else np.empty(0, np.float32))
        return flat_lat, viol_ts, viol_t, lat_sums

    def _step_const(self, jkeys, totals_p, totals, scale, L, T):
        """Time-invariant scale column: latency, violations and row sums
        all come out of the fused kernel; numpy only extracts the ragged
        request axis."""
        scale_p = np.zeros(self._Tp, np.float32)
        scale_p[:T] = scale[:, 0]
        scale_p = jnp.asarray(scale_p)
        f = self._call(("fused", L), functools.partial(_fused_impl, L),
                       5, 4)
        ar = np.arange(L)
        flat_parts, vflat_parts = [], []
        lat_sums = np.empty(T, np.float64)
        viol_t = np.empty(T, np.int64)
        for lo, hi in self._row_tiles(L):
            lat_d, viol_d, lsum_d, vt_d = f(
                jkeys[lo:hi], jnp.asarray(totals_p[lo:hi]),
                scale_p[lo:hi], self._sigma32[lo:hi], self._slo32[lo:hi])
            tl = min(hi, T)
            if tl <= lo:
                break
            valid = ar[None, :] < totals[lo:tl, None]
            flat_parts.append(np.asarray(lat_d)[:tl - lo][valid])
            vflat_parts.append(np.asarray(viol_d)[:tl - lo][valid])
            lat_sums[lo:tl] = np.asarray(lsum_d)[:tl - lo]
            viol_t[lo:tl] = np.asarray(vt_d)[:tl - lo]
        flat_lat = (np.concatenate(flat_parts) if flat_parts
                    else np.empty(0, np.float32))
        vflat = (np.concatenate(vflat_parts) if vflat_parts
                 else np.empty(0, bool))
        return flat_lat, vflat, lat_sums, viol_t

    def _step_varying(self, jkeys, totals_p, totals, starts, scale,
                      counts, slo_rep, L, T):
        """Time-varying scale matrix (bursty game fleets): the kernel
        draws dense jitter; the per-request scale product and reductions
        run numpy-side on the flat request axis."""
        f = self._call(("jitter", L), functools.partial(_jitter_impl, L),
                       2, 1)
        ar = np.arange(L)
        parts = []
        for lo, hi in self._row_tiles(L):
            jit_d = f(jkeys[lo:hi], self._sigma32[lo:hi])
            tl = min(hi, T)
            if tl <= lo:
                break
            valid = ar[None, :] < totals[lo:tl, None]
            parts.append(np.asarray(jit_d)[:tl - lo][valid])
        flat_jit = (np.concatenate(parts) if parts
                    else np.empty(0, np.float32))
        per_req = np.repeat(scale.ravel().astype(np.float32),
                            counts.ravel())
        flat_lat = per_req * flat_jit
        vflat = flat_lat > slo_rep
        csum = np.zeros(flat_lat.size + 1, np.float64)
        np.cumsum(flat_lat, dtype=np.float64, out=csum[1:])
        lat_sums = csum[starts[1:]] - csum[starts[:-1]]
        viol_t = np.zeros(T, np.int64)
        if vflat.any():
            np.add.reduceat(vflat.astype(np.int64), starts[:-1],
                            out=viol_t)
            viol_t[totals == 0] = 0
        return flat_lat, vflat, lat_sums, viol_t


class JaxBackend(EngineBackend):
    name = "jax"
    contract = "tolerance"
    rng_scheme = "counter-jax"
    when_to_use = "mega-scale fleets (10^5+); jit+vmap, device sharding"

    def tenant_rng(self, seed: int, name: str) -> tuple:
        # streams are derived from (seed, crc32(name), chunk, kind) at
        # draw time — there is no stateful generator to carry around
        return (None, None)

    def make_stepper(self, nodes: list):
        return JaxFleetStepper(nodes)


JAX_BACKEND = JaxBackend()

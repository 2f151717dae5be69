"""Fleet cells: the DYVERSE federation simulator's ``jax`` engine over a
fleet of tenants, chunk by chunk, with a controller round on every node
at each chunk boundary.

Set-up builds the fleet from the traffic file and the seed, places it on
the nodes and runs warm-up chunks, so that the window compiles nothing.
The window then runs whole chunks (``JaxFleetStepper.step`` and the
rounds) until ``--seconds`` have passed; ``tenant_s_per_s`` is the
simulated tenant-seconds of those chunks over the time they took.

Chunks drawn from the seed (about one in ``check.sample_every``, and the
window's first) are checked once the window has closed, each in three
parts: the stepper's per-tenant outputs (requests, violations, latency
sums) against the plain reference in ``tpu_bench.fleetref``; the closed
round's metrics each node's controller then held, against the same
reference; and the DYVERSE round that followed, run again by the plain
reference in ``tpu_bench.roundref`` from the state the controller held
before it, against the units, counters and terminations the program
left, and against the units the next chunk ran with.

The loop is a copy of ``EdgeFederation.run``'s, since the federation has
no public chunk step: it calls ``_replace_terminated`` and
``_apply_faults`` as ``run`` does. The per-tenant outputs are taken
where the stepper hands them to the nodes (``_feed_nodes``), with the
units vector the chunk used (``_units_vector``).
"""
from __future__ import annotations

import time

import numpy as np

from tpu_bench import fleetref, roundref
from tpu_bench.common import check, span
from tpu_bench.traffic import fleet_tenants


def build_fleet(conf: dict, traffic: dict, seed: int):
    """(workloads, per-tenant parameter table) of the cell's fleet."""
    from repro.sim.workload import GameWorkload, StreamWorkload

    n = conf["nodes"] * conf["tenants_per_node"]
    spread = fleet_tenants(traffic, n, seed)
    cls = {"stream": StreamWorkload, "game": GameWorkload}[traffic["workload"]]
    param = traffic["spread"]["param"]
    conv = int if traffic["spread"]["dist"] == "integers" else float
    wls = [cls(name=f"{traffic['prefix']}-{i}", **traffic["params"],
               **{param: conv(spread[param][i])}) for i in range(n)]
    table = {"index": {w.name: i for i, w in enumerate(wls)},
             param: spread[param], **traffic["params"]}
    return wls, table


class FleetCell:
    def __init__(self, conf: dict, traffic: dict, seed: int):
        from repro.sim.engines.base import resolve_engine
        from repro.sim.federation import EdgeFederation, FederationConfig

        self.conf, self.traffic, self.seed = conf, traffic, seed
        wls, self.table = build_fleet(conf, traffic, seed)
        fc = conf["federation"]
        self.ri = fc["round_interval"]
        self.fed = EdgeFederation(wls, FederationConfig(
            n_nodes=conf["nodes"], capacity_units=conf["capacity_units"],
            seed=seed, **fc))
        self.stepper = resolve_engine(fc["engine"]).make_stepper(
            self.fed.nodes)
        self.t = 0
        self.capture: list[dict] | None = None
        #: indices of the chunks whose rounds are captured, and those
        #: rounds' inputs and outcomes by chunk index
        self.sampled: set = set()
        self.rounds: dict[int, list] = {}
        self.n_chunks = 0
        self._units = None
        st = self.stepper
        units_vector, feed = st._units_vector, st._feed_nodes

        def units_capture(evicted):
            self._units = units_vector(evicted)
            return self._units

        def feed_capture(t0, t1, counts, totals, starts, lat, slo_rep,
                         viol_ts, viol_t, lat_sums, evicted, **kw):
            if self.capture is not None:
                self.capture.append({
                    "i": self.n_chunks, "t0": t0, "t1": t1,
                    "names": [name for _, name, _ in st._entries],
                    "totals": np.asarray(totals).copy(),
                    "viol": np.asarray(viol_t).copy(),
                    "lat_sums": np.asarray(lat_sums, np.float64).copy(),
                    "units": np.asarray(self._units).copy(),
                    "max_count": int(counts.max()) if counts.size else 0})
            return feed(t0, t1, counts, totals, starts, lat, slo_rep,
                        viol_ts, viol_t, lat_sums, evicted, **kw)
        st._units_vector, st._feed_nodes = units_capture, feed_capture

    def warm_lengths(self, lengths) -> None:
        """Compile the jitter kernel for every padded request count in
        ``lengths``, as ``JaxFleetStepper._step_varying`` would call it:
        on fleets whose count moves from chunk to chunk, a count first
        met inside the window would compile there."""
        import functools

        from repro.sim.engines import jax_backend as jb

        st = self.stepper
        keys = np.zeros((st._Tp, 2), np.uint32)
        for L in lengths:
            f = st._call(("jitter", L), functools.partial(jb._jitter_impl, L),
                         2, 1)
            f(keys, st._sigma32).block_until_ready()

    def tenants(self) -> int:
        return sum(len(n.workloads) for n in self.fed.nodes)

    @staticmethod
    def round_inputs(node) -> list[tuple]:
        """What the node's controller holds before its round, one row of
        ``roundref.FIELDS`` per tenant, in admission order."""
        ctrl = node.ctrl
        rows = []
        for name, st in ctrl.registry.items():
            sp, m = st.spec, ctrl.monitor.current(name)
            rows.append((name, sp.premium, st.ordinal, st.age, st.loyalty,
                         st.reward_count, st.scale_count,
                         sp.pricing.name == "PFP", st.active, sp.slo_latency,
                         sp.down_threshold, sp.donation, sp.min_units,
                         sp.max_units, ctrl.pool.units(name), m.requests,
                         m.users, m.data_mb, m.lat_sum, m.violations))
        return rows

    @staticmethod
    def round_outcome(node, report) -> dict:
        ctrl = node.ctrl
        return {"after": {name: (ctrl.pool.units(name), st.scale_count,
                                 st.reward_count, st.priority)
                          for name, st in ctrl.registry.items()},
                "terminated": list(report.terminated)}

    def chunk(self, walls: dict | None = None) -> None:
        """One chunk of ``round_interval`` simulated seconds, as
        ``EdgeFederation.run`` steps it."""
        fed, t = self.fed, self.t
        t1 = t + self.ri
        w0 = time.perf_counter()
        with span("fleet.step"):
            self.stepper.step(t, t1)
        w1 = time.perf_counter()
        rounds = []
        i = self.n_chunks
        sampled = i in self.sampled
        with span("fleet.round"):
            reports = []
            for node in fed.nodes:
                if node.name in fed.failed:
                    continue
                rows = self.round_inputs(node) if sampled else None
                r0 = time.perf_counter()
                with span("ctrl.round"):
                    report = node.run_controller_round(t1)
                rounds.append((time.perf_counter() - r0)
                              / max(len(node.workloads), 1))
                reports.append((node, report))
                if sampled:
                    self.rounds.setdefault(i, []).append(
                        (rows, self.round_outcome(node, report)))
            for node, report in reports:
                fed._replace_terminated(node, report.terminated, t1)
        fed._apply_faults(t1)
        self.t = t1
        self.n_chunks += 1
        if walls is not None:
            walls["step"].append(w1 - w0)
            walls["round"].extend(rounds)


#: the numbers ``correct`` compares, each against the traffic file's limit
NUMBERS = ("req_abs_diff", "viol_outside_band", "latsum_max_rel_diff",
           "round_mismatch")


def sample_chunks(seed: int, every: int, n: int = 4096) -> set:
    """The window's first chunk and about one in ``every`` after it,
    drawn from the seed: the chunks whose rounds are captured."""
    gaps = np.random.default_rng(seed ^ 0x5A3C).geometric(1.0 / every, n)
    return {0, *np.cumsum(gaps).tolist()}


def check_chunk(cell, chunk: dict, after: dict | None, rounds: list,
                conf: dict, traffic: dict, seed: int) -> dict:
    """The compared numbers of one chunk: its outputs, the metrics each
    node's controller held at the round, and the round itself, each
    against the plain references. ``after`` is the next chunk, whose
    units show what the round applied."""
    chk = traffic["check"]
    ref = fleetref.reference(chunk, cell.table, traffic, seed,
                             band=chk["viol_band_rel"])
    d = dict(fleetref.compare(chunk, ref), round_mismatch=0)
    p = fleetref.params(chunk, cell.table)
    for rows, outcome in rounds:
        names = [r[0] for r in rows]
        at = fleetref.rows_of(chunk, names)
        want = fleetref.select(ref, at)
        col = {f: np.array([r[i] for r in rows])
               for i, f in enumerate(roundref.FIELDS) if f != "name"}
        held = fleetref.compare({"totals": col["requests"],
                                 "viol": col["violations"],
                                 "lat_sums": col["lat_sum"]}, want)
        # a stream tenant has one user; data is per request
        users = p["n_users"][at] if "n_users" in p else np.ones(len(at))
        data = want["totals"] * np.float64(p["data_per_request_mb"])
        d["req_abs_diff"] += held["req_abs_diff"] + int(
            (col["users"] != users).sum() + (col["data_mb"] != data).sum())
        d["viol_outside_band"] += held["viol_outside_band"]
        d["latsum_max_rel_diff"] = max(d["latsum_max_rel_diff"],
                                       held["latsum_max_rel_diff"])
        rr = roundref.scaling_round(rows, conf["capacity_units"])
        d["round_mismatch"] += roundref.mismatch(outcome, rr)
        if after is not None:
            kept = list(rr["after"])
            got = np.asarray(after["units"])[fleetref.rows_of(after, kept)]
            d["round_mismatch"] += int(sum(
                int(g) != rr["after"][n][0] for g, n in zip(got, kept)))
    return d


def check_chunks(cell, chunks: list, conf: dict, traffic: dict,
                 seed: int) -> tuple[list, list]:
    """Up to ``check.chunks`` of the window's sampled chunks, drawn from
    the seed, each checked by ``check_chunk``."""
    by_i = {c["i"]: c for c in chunks}
    cands = sorted(i for i in cell.rounds if i in by_i)
    rng = np.random.default_rng(seed ^ 0xF1EE7)
    pick = sorted(int(i) for i in rng.permutation(cands)[
        :traffic["check"]["chunks"]])
    return [check_chunk(cell, by_i[i], by_i.get(i + 1), cell.rounds[i],
                        conf, traffic, seed) for i in pick], pick


def run(conf: dict, traffic: dict, seed: int, seconds: float,
        tracer=None) -> dict:
    """One run of a fleet cell; see ``tpu_bench.run`` for the result."""
    from tpu_bench.common import CompileCounter, memory_peak_bytes

    compiles = CompileCounter()
    t0 = time.perf_counter()
    cell = FleetCell(conf, traffic, seed)
    cell.capture = []
    for _ in range(traffic["warm_chunks"]):
        cell.chunk()
    margin = traffic.get("warm_length_margin")
    # the padded counts the warm chunks met, widened by the margin
    seen = [c["cols"] for ch in cell.capture
            for c in fleetref.kernel_calls(ch, traffic)
            if c["kind"] == "jitter"]
    if margin is not None and seen:
        lane = fleetref.LANE
        cell.warm_lengths(range(max(lane, min(seen) - margin),
                                max(seen) + margin + 1, lane))
    setup_s = time.perf_counter() - t0
    c0 = compiles.count
    chk = traffic["check"]
    cell.capture, cell.n_chunks = [], 0
    cell.sampled = sample_chunks(seed, chk["sample_every"])
    walls = {"step": [], "round": []}
    tenant_s = 0.0
    if tracer is not None:
        tracer.start()
    clock = time.perf_counter
    with span("window"):
        w0 = clock()
        while clock() - w0 < seconds:
            tenant_s += cell.tenants() * cell.ri
            cell.chunk(walls)
        window_s = clock() - w0
    if tracer is not None:
        tracer.stop()
    in_window_compiles = compiles.count - c0
    mem = memory_peak_bytes(1)
    chunks = cell.capture
    cell.capture = None
    n_chunks = len(chunks)
    finite = all(np.isfinite(c["lat_sums"]).all() for c in chunks)
    t_ref = time.perf_counter()
    diffs, pick = check_chunks(cell, chunks, conf, traffic, seed)
    ref_s = time.perf_counter() - t_ref
    worst = {k: max((d[k] for d in diffs), default=float("inf"))
             for k in NUMBERS}
    checks = {k: check(worst[k], chk[k]) for k in worst}
    checks["no_chunk_checked"] = check(0 if diffs else 1, 0)
    checks["non_finite_chunks"] = check(0 if finite else 1, 0)
    calls = [call for c in chunks
             for call in fleetref.kernel_calls(c, traffic)]
    info = {"chunks": n_chunks, "tenants": cell.tenants(),
            "simulated_s": cell.t, "compiles_in_window": in_window_compiles,
            "chunks_checked": pick,
            "rounds_checked": sum(len(cell.rounds[i]) for i in pick),
            "reference_s": ref_s, **worst}
    return {"setup_s": setup_s,
            "e2e": {"tenant_s_per_s": tenant_s / window_s},
            "walls": walls, "kernel_calls": calls, "window_s": window_s,
            "checks": checks, "info": info, "memory_peak_bytes": mem,
            "attempted": int(sum(c["totals"].sum() for c in chunks)),
            "failed": 0 if finite else n_chunks}

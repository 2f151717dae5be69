"""Serving cells: tenants of one model on ``MultiTenantEngine``, under
DYVERSE, driven by an open loop of requests on the host clock.

Set-up builds the engine, gives each tenant the benchmark's weights and
warms every shape the traffic uses. The window then sends each request
when it is due, calls ``MultiTenantEngine.step`` until ``--seconds`` have
passed, and stamps every output token when the step that produced it
returns. Once the window has closed, a sample of the finished requests
is checked against the plain float32 reference of the model module that
the configuration names under ``module`` (``common.model_module``),
which also makes the tenants' weights.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from tpu_bench.common import check, model_module, percentile, span
from tpu_bench.traffic import llm_requests


def model_config(conf: dict):
    """The program's ``ModelConfig`` for this configuration file."""
    return model_module(conf).program_config(conf)


@dataclasses.dataclass
class Served:
    """One request of the window, with the harness's own stamps."""
    req: object              # traffic.LLMRequest
    in_window: bool = True   # due inside the window, not before it
    rs: object = None        # the engine's RequestState
    submit_t: float = 0.0
    stamps: list = dataclasses.field(default_factory=list)


class ServingCell:
    def __init__(self, conf: dict, traffic: dict, seed: int):
        from repro.core import PricingModel, TenantSpec
        from repro.serving import EngineConfig, MultiTenantEngine

        self.conf, self.traffic, self.seed = conf, traffic, seed
        self.model = conf["model"]
        arch = model_module(conf)
        self.cfg = arch.program_config(conf)
        self.eng = MultiTenantEngine(EngineConfig(**conf["engine"]),
                                     seed=seed & 0x7FFFFFFF)
        self.names = [f"t{i}" for i in range(conf["tenants"])]
        self.calls: list = []       # (kind, tenant, shape) per model call
        self.preempted = 0
        for i, name in enumerate(self.names):
            spec = TenantSpec(name=name, slo_latency=traffic["slo_s"],
                              pricing=PricingModel.HYBRID, arch=self.cfg.name,
                              **conf["tenant_spec"])
            if not self.eng.add_tenant(spec, self.cfg):
                raise RuntimeError(f"tenant {name} was not admitted")
            rt = self.eng.tenants[name]
            rt.params = None
            rt.params = arch.make_weights(self.model, seed, i)
            rt._prefill = self._count_prefill(rt, rt._prefill)
            rt._decode = self._count_decode(rt, rt._decode)
        set_quota = self.eng.sched.set_quota

        def counted_set_quota(name, quota):
            out = set_quota(name, quota)
            self.preempted += len(out)
            return out
        self.eng.sched.set_quota = counted_set_quota
        run_round = self.eng.ctrl.run_round

        def spanned_round():
            with span("ctrl.round"):
                return run_round()
        self.eng.ctrl.run_round = spanned_round

    def _count_prefill(self, rt, fn):
        """Wrap a tenant's jitted prefill to log each call's prompt length."""
        def prefill(params, batch):
            self.calls.append(("prefill", rt.name,
                               int(batch["tokens"].shape[1])))
            return fn(params, batch)
        return prefill

    def _count_decode(self, rt, fn):
        """Wrap a tenant's jitted decode to log each call's live slots by
        their context length (the new token included)."""
        def decode(params, cache, token, pos):
            self.calls.append(("decode", rt.name,
                               [int(rt.pos[i]) + 1
                                for i, r in enumerate(rt.slot_req)
                                if r is not None]))
            return fn(params, cache, token, pos)
        return decode

    def _busy(self) -> bool:
        return any(tq.active or tq.waiting
                   for tq in self.eng.sched.tenants.values())

    def warm(self) -> None:
        """Every prompt bucket into every decode slot of every tenant,
        decoded a step: prefill, cache insertion and decode compile for
        each shape the window will use."""
        rng = np.random.default_rng(0)
        slots = self.conf["engine"]["slot_cap"]
        for length in self.traffic["prompt"]["buckets"]:
            for name in self.names:
                for _ in range(slots):
                    self.eng.submit(name, rng.integers(
                        1, self.model["vocab_size"], length).tolist(),
                        max_new_tokens=2)
            while self._busy():
                self.eng.step()
        self.eng.completed.clear()
        self.calls.clear()

    def window(self, seconds: float, at_window=None) -> dict:
        """The open loop: ``warm_in_s`` seconds of the mix's traffic
        before the window (so that the window opens on a node already
        under the mix's load), then the window of ``seconds``. Times in
        the result are from the window's start; ``at_window`` is called
        just before it (to start a trace)."""
        warm_in = float(self.traffic.get("warm_in_s", 0.0))
        reqs = llm_requests(self.traffic, self.seed, warm_in + seconds,
                            self.model["vocab_size"])
        served = [Served(r, in_window=r.due_s >= warm_in) for r in reqs]
        state = {"live": [], "next": 0}
        clock = time.perf_counter
        self.steps = 0
        t0 = clock()
        if warm_in:
            self._loop(served, state, warm_in, clock, t0)
        self.calls.clear()
        steps0 = self.steps
        if at_window is not None:
            at_window()
        with span("window"):
            w0 = clock()
            t_end = self._loop(served, state, warm_in + seconds, clock, t0)
        shift = w0 - t0
        for s in served:
            s.req.due_s -= shift
            s.submit_t -= shift
            s.stamps = [x - shift for x in s.stamps]
        # the window is whole step calls, and never shorter than asked
        return {"served": served, "steps": self.steps - steps0,
                "window_s": max(t_end - w0, seconds),
                "calls": list(self.calls)}

    def _loop(self, served, state, t_stop, clock, t0) -> float:
        """Send what is due and step the engine until ``t_stop`` seconds
        after ``t0``; every new token is stamped when its step returns."""
        live, nxt = state["live"], state["next"]
        t_end = clock()
        while True:
            now = clock() - t0
            if now >= t_stop:
                break
            while nxt < len(served) and served[nxt].req.due_s <= now:
                s = served[nxt]
                s.submit_t = clock() - t0
                s.rs = self.eng.submit(self.names[s.req.tenant],
                                       s.req.prompt.tolist(),
                                       max_new_tokens=s.req.max_new_tokens)
                live.append(s)
                nxt += 1
            if not self._busy():
                wait = (served[nxt].req.due_s if nxt < len(served)
                        else t_stop) - now
                time.sleep(max(0.0, min(wait, t_stop - now)))
                continue
            with span("engine.step"):
                self.eng.step()
            self.steps += 1
            t_end = clock()
            stamp = t_end - t0
            still = []
            for s in live:
                n = len(s.rs.generated)
                s.stamps.extend([stamp] * (n - len(s.stamps)))
                if s.rs.finish_t is None:
                    still.append(s)
            live[:] = still
        state["next"] = nxt
        return t_end

    def accounting(self, w: dict) -> int:
        """|submitted − (completed + cloud + in flight)| over the run."""
        eng = self.eng
        in_flight = sum(len(tq.active) + len(tq.waiting)
                        for tq in eng.sched.tenants.values())
        sent = sum(s.rs is not None for s in w["served"])
        return abs(sent - (len(eng.completed) + len(eng.cloud_serviced)
                           + in_flight))

    def free(self) -> None:
        """Drop the engine and everything it holds on the device (its
        objects hold cycles, so the collector has to see them again)."""
        self.eng = None
        gc.unfreeze()
        gc.collect()


def e2e_metrics(w: dict) -> dict:
    """tokens_per_s, ttft_p95_ms and itl_p95_ms of one window: the
    tokens stamped inside it, the first tokens of the requests due in it
    (one still unanswered counts at its age when the window closes), and
    every gap between tokens that ends inside it."""
    end = w["window_s"]
    served = w["served"]
    tokens = sum(1 for s in served for x in s.stamps if x >= 0)
    ttft = [((s.stamps[0] if s.stamps else end) - s.req.due_s) * 1e3
            for s in served if s.in_window]
    itl = [(b - a) * 1e3 for s in served
           for a, b in zip(s.stamps, s.stamps[1:]) if b >= 0]
    return {"tokens_per_s": tokens / end,
            "ttft_p95_ms": percentile(ttft, 95),
            "itl_p95_ms": percentile(itl, 95) if itl else float("nan")}


def sample_for_check(w: dict, seed: int, tokens: int,
                     max_requests: int) -> list:
    """Requests finished inside the window, drawn from the seed: the one
    with the most served tokens first, then one from each (tenant, decode
    slot) that served any, then others, until ``tokens`` served tokens
    or ``max_requests`` requests."""
    done = [s for s in w["served"]
            if s.rs is not None and s.rs.finish_t is not None
            and s.stamps and s.stamps[-1] >= 0]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.rs.generated),
                                       len(s.req.prompt)))
    rng = np.random.default_rng(seed ^ 0x5EED)
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    seen, first, later = set(), [], []
    for s in rest:
        key = (s.req.tenant, s.rs.batch_slot)
        (later if key in seen else first).append(s)
        seen.add(key)
    out = [longest]
    for s in first + later:
        if len(out) >= max_requests or (
                len(out) > len(first) and
                sum(len(x.rs.generated) for x in out) >= tokens):
            break
        out.append(s)
    return out


def reference_gaps(conf: dict, traffic: dict, seed: int, sample: list,
                   *, fp8: bool = False) -> list[float]:
    """For each sampled request, run the configuration's reference once
    over its prompt and served tokens, padded to the mix's
    ``reference_len``, and return, per request, the widest gap by which a
    served token's reference logit lies below the reference's best.

    With ``fp8`` the control runs beside the reference, and the gap is
    that of the token the control puts first."""
    import jax.numpy as jnp

    arch, model = model_module(conf), conf["model"]
    by_tenant: dict[int, list] = {}
    for s in sample:
        by_tenant.setdefault(s.req.tenant, []).append(s)
    gaps = []
    for tenant, group in sorted(by_tenant.items()):
        params = arch.make_weights(model, seed, tenant)
        for s in group:
            prompt, gen = list(s.req.prompt), list(s.rs.generated)
            seq = prompt + gen[:-1]
            toks = np.zeros(traffic["reference_len"], np.int32)
            toks[:len(seq)] = seq
            # the logits at position p predict the token at p + 1
            sel = np.zeros(traffic["output"]["max"], np.int32)
            n = len(gen)
            sel[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
            ref = np.asarray(arch.reference_logits(
                model, params, jnp.asarray(toks), jnp.asarray(sel)))[:n]
            best = ref.max(-1)
            if fp8:
                ctl = np.asarray(arch.reference_logits(
                    model, params, jnp.asarray(toks), jnp.asarray(sel),
                    fp8=True))[:n]
                picked = ctl.argmax(-1)
            else:
                picked = np.asarray(gen)
            gaps.append(float((best - ref[np.arange(n), picked]).max()))
        del params
        gc.collect()
    return gaps


def run(conf: dict, traffic: dict, seed: int, seconds: float,
        tracer=None) -> dict:
    """One run of a serving cell; see ``tpu_bench.run`` for the result."""
    from tpu_bench.common import CompileCounter, memory_peak_bytes

    compiles = CompileCounter()
    t0 = time.perf_counter()
    cell = ServingCell(conf, traffic, seed)
    cell.warm()
    # objects made in set-up live for the whole run: keep the collector
    # from walking them again inside the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t0
    marks = {}

    def at_window():
        marks["compiles"] = compiles.count
        if tracer is not None:
            tracer.start()
    w = cell.window(seconds, at_window)
    if tracer is not None:
        tracer.stop()
    in_window_compiles = compiles.count - marks["compiles"]
    mem = memory_peak_bytes(1)
    acct = cell.accounting(w)
    cloud = len(cell.eng.cloud_serviced)
    sent = [s for s in w["served"] if s.rs is not None]
    due = [s for s in w["served"] if s.in_window]
    finished = sum(1 for s in due if s.rs is not None
                   and s.rs.finish_t is not None)
    lags = [s.submit_t - s.req.due_s for s in sent]
    wrong_len = sum(1 for s in sent if s.rs.phase.value == "done"
                    and len(s.rs.generated) != s.req.max_new_tokens)
    cell.free()
    chk = traffic["check"]
    sample = sample_for_check(w, seed, chk["tokens"], chk["max_requests"])
    t_ref = time.perf_counter()
    gaps = reference_gaps(conf, traffic, seed, sample)
    ref_s = time.perf_counter() - t_ref
    checks = {
        "max_logit_gap": check(max(gaps) if gaps else float("inf"),
                               chk["max_logit_gap"]),
        "accounting_gap": check(acct, 0),
        "wrong_length": check(wrong_len, 0),
    }
    info = {"requests_due": len(due), "sent_in_run": len(sent),
            "finished_of_due": finished, "cloud": cloud, "steps": w["steps"],
            "preemptions": cell.preempted,
            "compiles_in_window": in_window_compiles,
            "generator_lag_max_ms": max(lags) * 1e3 if lags else 0.0,
            "generator_lag_p95_ms": percentile(lags, 95) * 1e3 if lags
            else 0.0,
            "checked_requests": len(sample),
            "checked_tokens": sum(len(s.rs.generated) for s in sample),
            "reference_s": ref_s}
    return {"setup_s": setup_s, "window": w, "e2e": e2e_metrics(w),
            "checks": checks, "info": info, "memory_peak_bytes": mem,
            "attempted": len(due), "failed": cloud}

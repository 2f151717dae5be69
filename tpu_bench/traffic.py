"""The one general generator: it turns a traffic file's parameters and a
seed into the inputs of a run.

Every seed gets the same schedule: the same arrival times, sizes and
tenants, drawn once from a fixed stream. The seed draws the prompt
tokens (and the cell's weights), which change what is served but not
how much. The order is fixed too: at some hundred requests a p95 is set
by which prompt of a burst comes first, and reordering sizes among
blocks of four requests moved ``serve-code``'s TTFT p95 by half from
seed to seed while two runs of one seed agreed within a tenth.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

#: the fixed stream the schedules and multisets are drawn from (not the
#: run's seed)
MULTISET_SEED = 0


@dataclass
class LLMRequest:
    due_s: float            # when the open loop sends it, from window start
    tenant: int
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def _fixed():
    return np.random.default_rng(MULTISET_SEED)


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    """n lengths of a length spec: lognormal (median, sigma) or uniform
    (min, max), rounded up to ``buckets`` where given, clipped to
    [min, max] otherwise."""
    if spec["dist"] == "lognormal":
        x = np.ceil(rng.lognormal(np.log(spec["median"]), spec["sigma"], n))
    elif spec["dist"] == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, n).astype(np.float64)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    if "buckets" in spec:
        b = np.asarray(sorted(spec["buckets"]))
        return b[np.minimum(np.searchsorted(b, x), len(b) - 1)]
    return np.clip(x, spec.get("min", 1), spec.get("max", np.inf)).astype(
        np.int64)


def _gaps(spec: dict, rate: float, n: int, rng) -> np.ndarray:
    """n inter-arrival gaps of mean 1/rate: Poisson (exponential gaps) or
    Gamma gaps of the given coefficient of variation."""
    if spec["dist"] == "poisson":
        return rng.exponential(1.0 / rate, n)
    if spec["dist"] == "gamma":
        k = 1.0 / spec["cv"] ** 2
        return rng.gamma(k, 1.0 / (rate * k), n)
    raise ValueError(f"unknown arrival distribution {spec['dist']!r}")


def llm_requests(traffic: dict, seed: int, seconds: float,
                 vocab: int, rate: float | None = None) -> list[LLMRequest]:
    """The open-loop schedule of one serving run: ``rate·seconds``
    requests due in [0, seconds), sorted by due time."""
    rate = traffic["rate_rps"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    fixed = _fixed()
    gaps = _gaps(traffic["arrivals"], rate, n, fixed)
    prompts = _lengths(traffic["prompt"], n, fixed)
    outs = _lengths(traffic["output"], n, fixed)
    split = np.asarray(traffic["tenants"]["split"], np.float64)
    owner = np.repeat(np.arange(len(split)),
                      np.diff(np.round(np.concatenate(
                          [[0], np.cumsum(split)]) / split.sum() * n)
                          .astype(np.int64)))
    owner = fixed.permutation(owner)
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    rng = np.random.default_rng(seed)
    swap = traffic["tenants"].get("swap_every_s")
    if swap:
        # the share of tenant k moves to tenant (k + epoch) mod n every
        # swap_every_s seconds: the hot tenant changes
        owner = (owner + (due // swap).astype(np.int64)) % len(split)
    return [LLMRequest(float(due[i]), int(owner[i]),
                       rng.integers(1, vocab, int(prompts[i]),
                                    dtype=np.int32),
                       int(outs[i])) for i in range(n)]


def fleet_tenants(traffic: dict, n: int, seed: int) -> dict:
    """Per-tenant parameters of a fleet of n tenants: a fixed multiset of
    the class's spread parameter (``fps`` or ``users``), in an order the
    seed chooses. Returns arrays keyed by parameter name."""
    fixed = _fixed()
    spread = traffic["spread"]
    lo, hi = spread["range"]
    if spread["dist"] == "uniform":
        vals = fixed.uniform(lo, hi, n)
    elif spread["dist"] == "integers":
        vals = fixed.integers(lo, hi + 1, n).astype(np.float64)
    else:
        raise ValueError(f"unknown spread {spread['dist']!r}")
    vals = np.random.default_rng(seed).permutation(vals)
    return {spread["param"]: vals}

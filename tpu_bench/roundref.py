"""Plain reference of one DYVERSE scaling round on one Edge node
(arXiv:1810.04608, Procedures 1–3), under the system-aware priorities
(sDPS, Eq. 6) with every weight 1 (§5) and reactive scaling, written out
again here without importing the program.

A round takes, for every tenant the node hosts (in the order it was
admitted): its state (premium, launch ordinal, Age_s, Loyalty_s,
Reward_s, Scale_s, pricing, SLO, down threshold, donation, floor and
ceiling of units, units held) and the closed round's metrics (requests,
users, data, latency sum, violations). Then:

* Procedure 1: each tenant's priority, ``P + 1/ID + Age + Loyalty +
  Request + Users + Data + Reward + 1/max(Scale, 1)`` (pay-for-resource
  and hybrid pricing; pay-for-priority takes ``1/max(x, 1)`` of the three
  workload terms); tenants are visited by descending priority, ties in
  admission order;
* Procedure 2: a tenant whose mean latency exceeds its SLO asks for
  ``max(1, round(units · VR))`` more units (never past its ceiling);
  while the node's free units fall short, the lowest (priority, name)
  other tenant is terminated, but only one of strictly lower priority,
  and the tenant gets what is free up to its ask (``Scale_s`` + 1). A
  tenant whose mean latency lies in ``(dThr · SLO, SLO]`` gives one unit
  if it donates (``Reward_s`` + 1) and holds otherwise; one with a
  lower mean latency, or no requests, gives one unit (``Scale_s`` + 1).
  No tenant gives a unit at its floor;
* Procedure 3: a terminated tenant's units return to the node.
"""
from __future__ import annotations

import numpy as np

#: the fields of one tenant's input row, in order
FIELDS = ("name", "premium", "ordinal", "age", "loyalty", "reward", "scale",
          "pfp", "active", "slo", "down_threshold", "donation", "min_units",
          "max_units", "units", "requests", "users", "data_mb", "lat_sum",
          "violations")


def priorities(rows: list[tuple]) -> np.ndarray:
    """Eq. 6 for every tenant, each term in float64 in the order written."""
    c = {f: [r[i] for r in rows] for i, f in enumerate(FIELDS)}
    f = lambda k: np.asarray(c[k], np.float64)  # noqa: E731
    base = (f("premium") + 1.0 / np.maximum(f("ordinal"), 1.0) + f("age")
            + f("loyalty"))
    add = base + f("requests") + f("users") + f("data_mb")
    rec = (base + 1.0 / np.maximum(f("requests"), 1.0)
           + 1.0 / np.maximum(f("users"), 1.0)
           + 1.0 / np.maximum(f("data_mb"), 1.0))
    score = np.where(np.asarray(c["pfp"], bool), rec, add)
    return score + f("reward") + 1.0 / np.maximum(f("scale"), 1.0)


def scaling_round(rows: list[tuple], capacity_units: int) -> dict:
    """One round on a node of ``capacity_units`` units. Returns, for
    each tenant that stays, ``(units, Scale_s, Reward_s, priority)``
    after the round, and the names terminated, in the order they were."""
    n = len(rows)
    t = [dict(zip(FIELDS, r)) for r in rows]
    pri = priorities(rows).tolist()
    free = capacity_units - sum(x["units"] for x in t)
    alive = [True] * n
    terminated: list[str] = []
    victims = sorted(range(n), key=lambda k: (pri[k], t[k]["name"]))

    def terminate(k):
        nonlocal free
        alive[k] = False
        free += t[k]["units"]
        terminated.append(t[k]["name"])

    for k in sorted(range(n), key=lambda k: -pri[k]):
        x = t[k]
        if not alive[k]:
            continue
        if not x["active"]:
            terminate(k)
            continue
        req = x["requests"]
        mean = x["lat_sum"] / req if req else 0.0
        if req and mean > x["slo"]:
            want = max(1, round(x["units"] * (x["violations"] / req)))
            if x["max_units"] is not None:
                want = min(want, x["max_units"] - x["units"])
            if want <= 0:
                continue
            while free < want:
                j = next((j for j in victims if alive[j] and j != k), None)
                if j is None or pri[j] >= pri[k]:
                    break
                terminate(j)
            grant = min(want, free)
            if grant > 0:
                x["units"] += grant
                free -= grant
                x["scale"] += 1
        elif req and mean > x["down_threshold"] * x["slo"]:
            if x["donation"] and x["units"] > x["min_units"]:
                x["units"] -= 1
                free += 1
                x["reward"] += 1
        elif x["units"] > x["min_units"]:
            x["units"] -= 1
            free += 1
            x["scale"] += 1
    return {"after": {x["name"]: (x["units"], x["scale"], x["reward"],
                                  pri[k])
                      for k, x in enumerate(t) if alive[k]},
            "terminated": terminated}


def mismatch(got: dict, ref: dict) -> int:
    """Tenants on which the program's round and the reference's differ:
    a tenant terminated by one and not the other, or one whose units,
    Scale_s, Reward_s or priority after the round differ. Priorities
    compare exactly: both sides evaluate Eq. 6 term by term in float64."""
    bad = len(set(got["terminated"]) ^ set(ref["terminated"]))
    for name, want in ref["after"].items():
        if name in got["after"] and tuple(got["after"][name]) != want:
            bad += 1
    return bad

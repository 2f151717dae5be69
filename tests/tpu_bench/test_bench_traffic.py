"""The traffic generator: seeded, the same work for every seed, bucketed."""
import numpy as np

from tpu_bench.common import load_json, BENCH_DIR
from tpu_bench.traffic import fleet_tenants, llm_requests


def _chat():
    return load_json(BENCH_DIR / "traffic" / "chat.json")


def test_same_seed_same_requests():
    a = llm_requests(_chat(), 2**33 + 7, 30.0, 32000)
    b = llm_requests(_chat(), 2**33 + 7, 30.0, 32000)
    assert [(r.due_s, r.tenant, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.tenant, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_gets_the_same_schedule():
    """Same arrival times, sizes and tenants for every seed; the seed
    draws only the prompt tokens."""
    a = llm_requests(_chat(), 1, 30.0, 32000)
    b = llm_requests(_chat(), 2**40 + 2, 30.0, 32000)
    assert len(a) == len(b) == round(_chat()["rate_rps"] * 30)
    assert [(r.due_s, r.tenant, len(r.prompt), r.max_new_tokens)
            for r in a] == [(r.due_s, r.tenant, len(r.prompt),
                             r.max_new_tokens) for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_prompts_round_up_to_buckets_and_outputs_clip():
    t = _chat()
    reqs = llm_requests(t, 5, 60.0, 32000)
    assert {len(r.prompt) for r in reqs} <= set(t["prompt"]["buckets"])
    outs = [r.max_new_tokens for r in reqs]
    assert min(outs) >= t["output"]["min"] and max(outs) <= t["output"]["max"]
    assert all(0 <= r.due_s < 60.0 for r in reqs)
    assert all((r.prompt >= 1).all() and (r.prompt < 32000).all()
               for r in reqs)


def test_tenant_split_and_swap():
    t = _chat()
    reqs = llm_requests(t, 9, 40.0, 32000)
    for epoch in range(4):
        part = [r.tenant for r in reqs if epoch * 10 <= r.due_s < epoch * 10 + 10]
        hot = epoch % 2
        assert sum(x == hot for x in part) > sum(x != hot for x in part)
    assert abs(sum(r.tenant == 0 for r in reqs) / len(reqs) - 0.5) < 0.2


def test_fleet_tenants_same_multiset_other_order():
    t = load_json(BENCH_DIR / "traffic" / "stream.json")
    a, b = fleet_tenants(t, 500, 3)["fps"], fleet_tenants(t, 500, 4)["fps"]
    assert np.array_equal(np.sort(a), np.sort(b)) and not np.array_equal(a, b)
    assert a.min() >= 0.1 and a.max() <= 1.0

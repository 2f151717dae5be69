"""Rate and tail arithmetic of the serving window, with a planted stall."""
from types import SimpleNamespace

import numpy as np

from tpu_bench.common import percentile
from tpu_bench.serving import e2e_metrics


def _window(stall_at=None, stall_s=0.0):
    """40 requests due every 0.25 s, each answered 0.1 s after it is due
    and then one token every 0.02 s for 9 more tokens; a stall delays
    every stamp after ``stall_at`` by ``stall_s``."""
    served = []
    for i in range(40):
        due = 0.25 * i
        stamps = [due + 0.1 + 0.02 * k for k in range(10)]
        if stall_at is not None:
            stamps = [s + stall_s if s >= stall_at else s for s in stamps]
        served.append(SimpleNamespace(req=SimpleNamespace(due_s=due),
                                      stamps=stamps, in_window=True))
    return {"served": served, "window_s": 10.0}


def test_rate_and_tails_of_a_steady_window():
    m = e2e_metrics(_window())
    assert m["tokens_per_s"] == 400 / 10.0
    assert np.isclose(m["ttft_p95_ms"], 100.0)
    assert np.isclose(m["itl_p95_ms"], 20.0)


def test_a_stall_moves_the_tails():
    base = e2e_metrics(_window())
    # a 0.6 s stall at t = 5.05 s: every request first answered after it
    # waits 0.6 s longer; one in twenty decoding across it (due 5.0 s,
    # stall at 5.15 s) gets a 0.62 s gap, too few to move the p95 gap
    hit = e2e_metrics(_window(stall_at=5.05, stall_s=0.6))
    assert hit["ttft_p95_ms"] > base["ttft_p95_ms"] + 500
    assert hit["tokens_per_s"] == base["tokens_per_s"]
    gaps = e2e_metrics(_window(stall_at=5.15, stall_s=0.6))
    assert np.isclose(gaps["itl_p95_ms"], base["itl_p95_ms"])


def test_unanswered_requests_count_at_their_age():
    w = _window()
    for s in w["served"][-3:]:          # due 9.25, 9.5 and 9.75 s
        s.stamps = []
    m = e2e_metrics(w)
    assert m["tokens_per_s"] == 370 / 10.0
    # their first tokens count at 750, 500 and 250 ms: above the others'
    assert np.isclose(m["ttft_p95_ms"],
                      np.percentile([100.0] * 37 + [750, 500, 250], 95))


def test_percentile_interpolates_linearly():
    assert percentile(range(101), 95) == 95.0
    assert percentile([0.0, 10.0], 95) == 9.5


def test_requests_due_before_the_window_count_only_their_tokens():
    w = _window()
    early = SimpleNamespace(req=SimpleNamespace(due_s=-1.0),
                            stamps=[-0.9, -0.1, 0.1, 0.3], in_window=False)
    w["served"].append(early)
    m = e2e_metrics(w)
    assert m["tokens_per_s"] == 402 / 10.0
    assert np.isclose(m["ttft_p95_ms"], 100.0)

"""Campaign-harness tests: deterministic expansion, byte-identical
reports, process-level fault isolation (raise / crash / timeout never
abort sibling cells), validity masking, the shared BENCH schema's
tolerant loader, and the regression differ (passes on the repo's real
trajectories, fails on an injected VR regression)."""
import json
import os
import time
from pathlib import Path

import pytest

from repro.campaign import (CampaignSpec, SweepGrid, Tolerances,
                            build_report, diff_report, expand_campaign,
                            expand_grid, get_campaign, load_bench,
                            load_section, run_cells, write_bench)
from repro.campaign.benchio import SCHEMA_VERSION
from repro.campaign.executor import uses_device
from repro.campaign.registry import MAIN_GRID
from repro.campaign.spec import OPTION_ENGINES

ROOT = Path(__file__).resolve().parents[1]

TINY_GRID = SweepGrid(scenarios=("paper_game_32",),
                      engines=("vectorized", "batched"),
                      policies=("sdps",), scaling_policies=("reactive",))


# ------------------------------------------------------------ expansion
def test_expansion_deterministic():
    spec = get_campaign("ci")
    a, masked_a, _ = expand_campaign(spec, verbose=True)
    b, masked_b, _ = expand_campaign(spec, verbose=True)
    assert [c.cell_id for c in a] == [c.cell_id for c in b]
    assert masked_a == masked_b
    assert len({c.key for c in a}) == len(a)        # de-duplicated


def test_masking_never_emits_invalid_cells():
    cells, masked = expand_grid(MAIN_GRID)
    for cell in cells:
        serving_sc = cell.scenario.serving is not None
        assert serving_sc == (cell.engine == "serving"), cell.cell_id
        if cell.engine == "serving":
            assert cell.scaling_policy == "reactive"
            assert cell.control_plane == "array"
    assert masked, "the main grid must mask something"
    emitted = {c.cell_id for c in cells}
    assert emitted.isdisjoint({cid for cid, _ in masked})


def test_masking_engine_options():
    grid = SweepGrid(scenarios=("paper_game_32",),
                     engines=("vectorized", "batched", "jax"),
                     policies=("sdps",), scaling_policies=("reactive",),
                     backend_options=((), (("pallas", True),),
                                      (("jit_scale", 4),)))
    cells, masked = expand_grid(grid)
    for cell in cells:
        for k, _ in cell.options:
            assert cell.engine in OPTION_ENGINES[k], cell.cell_id
    # every (engine, option) combination outside the table was masked
    assert any("pallas" in cid for cid, _ in masked)
    assert any("jit_scale" in cid for cid, _ in masked)


def test_filters_and_zero_cell_error():
    spec = CampaignSpec(name="t", grids=(TINY_GRID,),
                        include=({"engine": "batched"},))
    cells = expand_campaign(spec)
    assert [c.engine for c in cells] == ["batched"]
    with pytest.raises(ValueError, match="zero cells"):
        expand_campaign(CampaignSpec(
            name="t0", grids=(TINY_GRID,),
            exclude=({"scenario": "paper_game_32"},)))


# ---------------------------------------------------------- determinism
def test_byte_identical_report():
    """Same grid + seed ⇒ byte-identical canonical CampaignReport —
    across process fan-out AND inline execution, despite differing
    wall clocks."""
    spec = CampaignSpec(name="tiny", grids=(TINY_GRID,))
    cells = expand_campaign(spec)
    reports = []
    for workers in (2, 0):
        recs = run_cells(cells, quick=True, workers=workers,
                         cell_timeout_s=300.0)
        reports.append(build_report(
            "tiny", recs, quick=True, workers=workers,
            campaign_wall_s=float(workers)))
    assert all(r["status"] == "ok" for rep in reports for r in rep.records)
    assert reports[0].canonical_json() == reports[1].canonical_json()
    # the two bitwise engines agreed, so no consistency violations
    assert reports[0].consistency_violations() == []
    assert reports[0].gate_failures() == []


# ------------------------------------------------------- fault isolation
def _fake_ok(cell, quick):
    rec = cell.record_stub()
    rec.update(status="ok", violation_rate=0.1, duration_s=1.0,
               tenants=1, n_nodes=1, wall_s=0.0, requests_conserved=True)
    return rec


# cell functions are pickled to spawned children, so they live at
# module level
def _raise_on_batched(cell, quick):
    if cell.engine == "batched":
        raise RuntimeError("boom")
    return _fake_ok(cell, quick)


def _crash_on_batched(cell, quick):
    if cell.engine == "batched":
        os._exit(3)                     # simulated hard crash
    return _fake_ok(cell, quick)


def _hang_on_batched(cell, quick):
    if cell.engine == "batched":
        time.sleep(60.0)
    return _fake_ok(cell, quick)


def test_raising_cell_does_not_abort_siblings():
    cells = expand_campaign(CampaignSpec(name="t", grids=(TINY_GRID,)))
    assert len(cells) == 2

    recs = run_cells(cells, quick=True, workers=2, cell_timeout_s=60.0,
                     cell_fn=_raise_on_batched)
    by_engine = {r["engine"]: r for r in recs}
    assert by_engine["vectorized"]["status"] == "ok"
    assert by_engine["batched"]["status"] == "error"
    assert "boom" in by_engine["batched"]["error"]
    # records come back in cell order regardless of finish order
    assert [r["cell"] for r in recs] == [c.cell_id for c in cells]


def test_crashing_cell_recorded_not_fatal():
    cells = expand_campaign(CampaignSpec(name="t", grids=(TINY_GRID,)))
    recs = run_cells(cells, quick=True, workers=2, cell_timeout_s=60.0,
                     cell_fn=_crash_on_batched)
    by_engine = {r["engine"]: r for r in recs}
    assert by_engine["vectorized"]["status"] == "ok"
    assert by_engine["batched"]["status"] == "crash"
    assert by_engine["batched"]["exitcode"] == 3


def test_timeout_cell_recorded_not_fatal():
    cells = expand_campaign(CampaignSpec(name="t", grids=(TINY_GRID,)))
    recs = run_cells(cells, quick=True, workers=2, cell_timeout_s=1.0,
                     cell_fn=_hang_on_batched)
    by_engine = {r["engine"]: r for r in recs}
    assert by_engine["vectorized"]["status"] == "ok"
    assert by_engine["batched"]["status"] == "timeout"
    rep = build_report("t", recs, quick=True)
    assert any("timeout" in f for f in rep.gate_failures())


LANE_GRID = SweepGrid(scenarios=("paper_game_32",),
                      engines=("vectorized", "jax"), seeds=(1, 2, 3),
                      policies=("sdps",), scaling_policies=("reactive",))


def _stamp(cell, quick):
    rec = _fake_ok(cell, quick)
    t0 = time.monotonic()
    time.sleep(0.2)
    rec.update(pid=os.getpid(), t0=t0, t1=time.monotonic())
    return rec


def _crash_on_jax_seed2(cell, quick):
    if cell.engine == "jax" and cell.seed == 2:
        os._exit(5)
    return _stamp(cell, quick)


def test_uses_device():
    cells = expand_campaign(CampaignSpec(name="t", grids=(LANE_GRID,)))
    assert {c.engine for c in cells if uses_device(c)} == {"jax"}
    jit = SweepGrid(scenarios=("paper_game_32",), engines=("batched",),
                    policies=("sdps",), scaling_policies=("reactive",),
                    backend_options=((), (("jit_scale", True),)))
    assert [uses_device(c) for c in expand_grid(jit)[0]] == [False, True]


def test_device_cells_run_one_after_another_in_one_child():
    """Cells that hold the chip never run in two children at once: the
    jax cells share one child and run in cell order without overlap,
    while each host cell gets a child of its own."""
    cells = expand_campaign(CampaignSpec(name="t", grids=(LANE_GRID,)))
    recs = run_cells(cells, quick=True, workers=3, cell_timeout_s=60.0,
                     cell_fn=_stamp)
    assert [r["cell"] for r in recs] == [c.cell_id for c in cells]
    dev = [r for r in recs if r["engine"] == "jax"]
    host = [r for r in recs if r["engine"] == "vectorized"]
    assert len(dev) == len(host) == 3
    assert len({r["pid"] for r in dev}) == 1
    assert all(a["t1"] <= b["t0"] for a, b in zip(dev, dev[1:]))
    assert len({r["pid"] for r in host} | {dev[0]["pid"]}) == 4
    assert os.getpid() not in {r["pid"] for r in recs}


def test_device_lane_continues_after_crash():
    cells = expand_campaign(CampaignSpec(name="t", grids=(LANE_GRID,)))
    recs = run_cells(cells, quick=True, workers=2, cell_timeout_s=60.0,
                     cell_fn=_crash_on_jax_seed2)
    dev = {r["seed"]: r for r in recs if r["engine"] == "jax"}
    assert dev[2]["status"] == "crash" and dev[2]["exitcode"] == 5
    assert dev[1]["status"] == dev[3]["status"] == "ok"
    assert dev[1]["pid"] != dev[3]["pid"]      # the lane went on anew
    assert all(r["status"] == "ok" for r in recs if r["engine"] != "jax")


# ----------------------------------------------------------- bench I/O
def test_benchio_roundtrip(tmp_path):
    path = write_bench("unit", [{"a": 1}], root=str(tmp_path),
                       quiet=True, extra_field="x")
    payload = load_bench(path)
    assert payload["schema_version"] == SCHEMA_VERSION
    assert payload["rows"] == [{"a": 1}]
    assert payload["extra_field"] == "x"
    assert payload["section"] == "unit"
    assert "cpus" in payload["machine"]


def test_benchio_tolerant_loader(tmp_path):
    assert load_section("missing", root=str(tmp_path)) is None
    bad = tmp_path / "BENCH_corrupt.json"
    bad.write_text("{not json")
    assert load_bench(str(bad)) is None
    # a future schema version degrades to "no baseline"
    future = tmp_path / "BENCH_future.json"
    future.write_text(json.dumps(
        {"schema_version": SCHEMA_VERSION + 1, "rows": []}))
    assert load_bench(str(future)) is None
    # pre-schema_version files (implicit version 0) stay loadable
    legacy = tmp_path / "BENCH_legacy.json"
    legacy.write_text(json.dumps({"section": "legacy", "rows": [{}]}))
    assert load_bench(str(legacy))["rows"] == [{}]
    # rows that aren't a list → not a BENCH payload
    shaped = tmp_path / "BENCH_shape.json"
    shaped.write_text(json.dumps({"rows": "nope"}))
    assert load_bench(str(shaped)) is None


def test_real_trajectories_loadable():
    """The committed PR-3..8 trajectories must load through the shared
    schema (implicit version 0)."""
    for section in ("scenarios", "forecast", "resilience", "serving"):
        payload = load_section(section, root=str(ROOT))
        assert payload is not None, section
        assert payload["rows"], section


# ------------------------------------------------------------- differ
def _records_from_scenarios_baseline():
    payload = load_section("scenarios", root=str(ROOT))
    recs = []
    for row in payload["rows"]:
        recs.append({
            "cell": f"{row['scenario']}/baseline",
            "scenario": row["scenario"], "engine": "batched",
            "control_plane": "array", "placement": row["placement"],
            "policy": row["policy"], "scaling_policy": "reactive",
            "forecaster": "ewma", "seed": 7, "options": [],
            "status": "ok", "duration_s": row["duration_s"],
            "tenants": row["tenants"],
            "violation_rate": row["violation_rate"],
            "requests_conserved": True, "wall_s": 0.1,
        })
    return recs


def test_differ_passes_on_real_trajectories():
    recs = _records_from_scenarios_baseline()
    rep = build_report("diff", recs, quick=False)
    diff = diff_report(rep, root=str(ROOT), prev=None)
    assert diff.compared >= len(recs)
    assert diff.ok, diff.render()
    assert not diff.regressions


def test_differ_fails_on_injected_vr_regression():
    recs = _records_from_scenarios_baseline()
    recs[0]["violation_rate"] += 0.05       # +5pp, tolerance is 0.5pp
    rep = build_report("diff", recs, quick=False)
    diff = diff_report(rep, root=str(ROOT), prev=None)
    assert not diff.ok
    assert any(recs[0]["scenario"] in r and "VR" in r
               for r in diff.regressions), diff.render()


def test_differ_improvement_is_not_fatal():
    recs = _records_from_scenarios_baseline()
    recs[0]["violation_rate"] = max(0.0, recs[0]["violation_rate"] - 0.05)
    rep = build_report("diff", recs, quick=False)
    diff = diff_report(rep, root=str(ROOT), prev=None)
    assert diff.ok
    assert diff.improvements


def test_differ_vs_previous_campaign(tmp_path):
    recs = _records_from_scenarios_baseline()
    rep = build_report("prev", recs, quick=False)
    extra = {k: v for k, v in rep.payload().items() if k != "rows"}
    write_bench("campaign", rep.records, root=str(tmp_path), quiet=True,
                **extra)
    prev = load_section("campaign", root=str(tmp_path))
    # identical new run → clean
    diff = diff_report(rep, root=str(tmp_path), prev=prev)
    assert diff.ok and diff.compared >= len(recs)
    # regressed new run → fails against the previous campaign
    bad = json.loads(json.dumps(rep.records))
    bad[0]["violation_rate"] += 0.05
    rep2 = build_report("next", bad, quick=False)
    diff2 = diff_report(rep2, root=str(tmp_path), prev=prev)
    assert not diff2.ok
    assert any("previous campaign" in r for r in diff2.regressions)
    # a quick run never compares VR against a full-mode campaign
    small = json.loads(json.dumps(rep.records))
    for r in small:
        r["duration_s"] = 60
        r["violation_rate"] += 0.2
    rep3 = build_report("quick", small, quick=True)
    diff3 = diff_report(rep3, root=str(tmp_path), prev=prev)
    assert not any("previous campaign" in r for r in diff3.regressions)


def test_tolerances_configurable():
    recs = _records_from_scenarios_baseline()
    recs[0]["violation_rate"] += 0.05
    rep = build_report("diff", recs, quick=False)
    loose = diff_report(rep, root=str(ROOT), prev=None,
                        tol=Tolerances(vr_pp=10.0))
    assert loose.ok

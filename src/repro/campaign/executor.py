"""Process fan-out for campaign cells: timeout + crash isolation.

Children are started with the ``spawn`` method: a fresh interpreter
that inherits no threads and no JAX state from the parent, so it is safe
to fan out from a process that has already run JAX code. Cells (and any
injected ``cell_fn``) are pickled to the child, so ``cell_fn`` must be a
module-level function. Results come back over a ``Pipe``, and a sliding
window of ``workers`` concurrent children is multiplexed with
:func:`multiprocessing.connection.wait`.

A chip belongs to one process at a time, so cells that run JAX
computations (:func:`uses_device`: the ``jax`` and ``serving`` engines,
and the batched engine's ``jit_scale`` option) never run in two
children at once: they form one lane, run one after another in a single
child, which also keeps its compiled programs between cells. Every
other cell gets a child of its own.

Three failure modes are captured as structured records instead of
killing the campaign:

* the cell raises — the child catches ``BaseException`` and reports
  ``status="error"`` with the message and traceback;
* the child dies outright (segfault, ``os._exit``) — the parent sees
  EOF on the pipe and reports ``status="crash"`` with the exit code;
* the cell overruns ``cell_timeout_s`` — the parent terminates the
  child and reports ``status="timeout"``.

A lane's cells after a crash or timeout go on in a new child. A cell's
timeout counts from the moment its child starts it; a child gets
``_STARTUP_TIMEOUT_S`` to import and reach its first cell.

``workers <= 0`` runs every cell inline in the parent (no processes,
no timeout enforcement) — the mode tests use for determinism checks.

Per-cell seeds are deterministic because the seed IS an axis of the
cell: the child runs ``cell.scenario_with_axes()`` (which pins
``scenario.seed`` to the cell's seed) and every engine derives its RNG
streams from that.
"""
from __future__ import annotations

import dataclasses
import functools
import multiprocessing
import os
import time
import traceback

from repro.campaign.spec import RunSpec


def artifact_dir_for(cell_id: str, artifacts_dir: str) -> str:
    """Filesystem-safe per-cell artifact directory (cell ids contain
    ``/`` separators)."""
    return os.path.join(artifacts_dir, cell_id.replace("/", "_"))


def run_cell(cell: RunSpec, quick: bool,
             artifacts_dir: str | None = None) -> dict:
    """Run one cell to a structured record (the default ``cell_fn``).

    With ``artifacts_dir`` the cell runs under a flight recorder
    (``scenario.trace=True`` — observability-only, results unchanged)
    and writes a Chrome-trace ``trace.json`` per cell under
    ``<artifacts_dir>/<sanitized cell id>/``; the record carries its
    path as ``trace_path``."""
    from repro.sim.engines import resolve_engine
    from repro.sim.scenario import run_scenario

    sc = cell.scenario_with_axes()
    if artifacts_dir is not None:
        sc = dataclasses.replace(sc, trace=True)
    t0 = time.perf_counter()
    res = run_scenario(sc, policies=(cell.policy,),
                       scaling_policies=(cell.scaling_policy,),
                       quick=quick)
    wall = time.perf_counter() - t0
    ran = res.scenario
    rec = cell.record_stub()
    rec.update(
        status="ok",
        duration_s=float(resolve_engine(ran.engine).scenario_duration(ran)),
        tenants=ran.fleet.size,
        n_nodes=ran.topology.n_nodes,
        wall_s=wall,
    )
    rec.update(res.outcomes[cell.policy].to_record())
    if artifacts_dir is not None:
        cell_dir = artifact_dir_for(cell.cell_id, artifacts_dir)
        os.makedirs(cell_dir, exist_ok=True)
        trace_path = os.path.join(cell_dir, "trace.json")
        res.write_trace(trace_path)
        rec["trace_path"] = trace_path
    return rec


def _failure_record(cell: RunSpec, status: str, **extra) -> dict:
    rec = cell.record_stub()
    rec.update(status=status, **extra)
    return rec


def _run_guarded(cell_fn, cell: RunSpec, quick: bool) -> dict:
    try:
        return cell_fn(cell, quick)
    except BaseException as e:  # noqa: BLE001 — isolation is the point
        return _failure_record(cell, "error",
                               error=f"{type(e).__name__}: {e}",
                               traceback=traceback.format_exc())


#: engines whose cells run JAX computations (and so hold the chip)
DEVICE_ENGINES = ("jax", "serving")
_STARTUP_TIMEOUT_S = 300.0


def uses_device(cell: RunSpec) -> bool:
    """Whether ``cell`` runs JAX computations."""
    return (cell.engine in DEVICE_ENGINES
            or any(k == "jit_scale" and v for k, v in cell.options))


def _cells_worker(conn, cells: list[RunSpec], quick: bool, cell_fn) -> None:
    """Run ``cells`` in order, sending ``None`` as each one starts and
    its record when it ends."""
    try:
        for cell in cells:
            conn.send(None)
            conn.send(_run_guarded(cell_fn, cell, quick))
    finally:
        conn.close()


def _lanes(cells: list[RunSpec]) -> list[list[int]]:
    """Cell indices grouped into child processes: every device cell in
    one lane (launched first, as it is the longest), every other cell
    alone."""
    device = [i for i, c in enumerate(cells) if uses_device(c)]
    host = [[i] for i, c in enumerate(cells) if not uses_device(c)]
    return ([device] if device else []) + host


def run_cells(cells: list[RunSpec], *, quick: bool = False,
              workers: int = 2, cell_timeout_s: float = 900.0,
              cell_fn=run_cell, progress=None,
              artifacts_dir: str | None = None) -> list[dict]:
    """Run every cell, returning one record per cell IN CELL ORDER no
    matter how the children finish. ``progress`` (optional) is called
    with each finished record. ``artifacts_dir`` makes the default
    ``cell_fn`` trace every cell and drop a per-cell ``trace.json``
    there (ignored for a custom ``cell_fn``)."""
    if artifacts_dir is not None and cell_fn is run_cell:
        cell_fn = functools.partial(run_cell, artifacts_dir=artifacts_dir)
    if workers <= 0:
        out = []
        for cell in cells:
            rec = _run_guarded(cell_fn, cell, quick)
            if progress is not None:
                progress(rec)
            out.append(rec)
        return out

    ctx = multiprocessing.get_context("spawn")
    records: list = [None] * len(cells)
    pending = _lanes(cells)              # lanes not yet launched
    live: dict = {}                      # conn -> [lane, pos, proc, deadline]

    def launch(lane: list[int]) -> None:
        parent, child = ctx.Pipe(duplex=False)
        proc = ctx.Process(target=_cells_worker,
                           args=(child, [cells[i] for i in lane], quick,
                                 cell_fn),
                           name=f"campaign-{cells[lane[0]].cell_id}")
        proc.start()
        child.close()
        live[parent] = [lane, 0, proc, time.monotonic() + _STARTUP_TIMEOUT_S]

    def record(idx: int, rec: dict) -> None:
        records[idx] = rec
        if progress is not None:
            progress(rec)

    def retire(conn, failed: dict | None = None) -> None:
        """Close a child; ``failed`` is the record of the cell it was
        running, whose lane-mates go on in a new child."""
        lane, pos, proc, _ = live.pop(conn)
        conn.close()
        proc.join()
        if failed is not None:
            record(lane[pos], failed)
            if lane[pos + 1:]:
                pending.insert(0, lane[pos + 1:])

    while pending or live:
        while pending and len(live) < workers:
            launch(pending.pop(0))
        now = time.monotonic()
        budget = max(0.05, min(st[3] for st in live.values()) - now)
        ready = multiprocessing.connection.wait(list(live), timeout=budget)
        for conn in ready:
            st = live[conn]
            lane, pos, proc, _ = st
            try:
                msg = conn.recv()
            except EOFError:
                proc.join()
                retire(conn, _failure_record(
                    cells[lane[pos]], "crash",
                    error=f"worker died (exitcode {proc.exitcode})",
                    exitcode=proc.exitcode))
                continue
            if msg is None:              # the child starts cell lane[pos]
                st[3] = time.monotonic() + cell_timeout_s
                continue
            record(lane[pos], msg)
            st[1] = pos = pos + 1
            st[3] = time.monotonic() + cell_timeout_s
            if pos == len(lane):
                retire(conn)
        now = time.monotonic()
        for conn in [c for c, st in live.items() if st[3] <= now]:
            lane, pos, proc, _ = live[conn]
            proc.terminate()
            proc.join(5.0)
            if proc.is_alive():         # pragma: no cover — stuck child
                proc.kill()
                proc.join()
            retire(conn, _failure_record(
                cells[lane[pos]], "timeout",
                error=f"cell exceeded {cell_timeout_s:.0f}s timeout"))
    return records

"""Every cell of BENCHMARK.json builds from its files, and the file
itself keeps to the benchmark's contract."""
import json
import os
import re
import subprocess
import sys

import pytest

from tpu_bench.common import (BENCH_DIR, ROOT, benchmark, cell_metrics,
                              find_cell, metric_reader, model_module, peaks)

BENCH = benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_builds_from_its_files(cell):
    entry, conf, traffic = find_cell(cell, BENCH)
    assert entry["chips"] == 1
    assert (BENCH_DIR / f"{conf['kind']}.py").exists()
    assert "why" in traffic and "check" in traffic
    e2e = [m["name"] for m in cell_metrics(BENCH, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell_metrics(BENCH, cell, "per_layer")
    assert layer and all(callable(metric_reader(m["name"])) for m in layer)
    assert all(m["moves"] in e2e for m in layer)
    if conf["kind"] == "serving":
        from tpu_bench.serving import model_config

        cfg = model_config(conf)
        assert cfg.num_layers == conf["model"]["num_hidden_layers"]
        assert set(conf["reduced"]) <= set(conf["published"])
    elif conf["kind"] == "fleet":
        from tpu_bench.fleet import build_fleet

        small = dict(conf, tenants_per_node=3)
        wls, table = build_fleet(small, traffic, 1)
        assert len(wls) == 3 * conf["nodes"] and len(table["index"]) == len(wls)


SERVING = [w["name"] for w in BENCH["workloads"]
           if find_cell(w["name"], BENCH)[1]["kind"] == "serving"]


@pytest.mark.parametrize("cell", SERVING)
def test_every_serving_model_module_keeps_to_the_contract(cell):
    """The module that a serving configuration names gives, without
    making a weight: the program's parameter tree, leaf for leaf, in
    the served type; reference logits at the checked positions of the
    mix's reference length, with the fp8 control; and operation and
    byte counts that are positive and grow with the work."""
    import jax
    import jax.numpy as jnp
    from repro.models import build_model

    _, conf, traffic = find_cell(cell, BENCH)
    arch, model = model_module(conf), conf["model"]
    cfg = arch.program_config(conf)
    want = jax.eval_shape(build_model(cfg).init_params, jax.random.key(0))
    got = jax.eval_shape(lambda: arch.make_weights(model, 2**33 + 1, 1))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(g.shape == w.shape and g.dtype == jnp.dtype(cfg.param_dtype)
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    toks = jax.ShapeDtypeStruct((traffic["reference_len"],), jnp.int32)
    sel = jax.ShapeDtypeStruct((traffic["output"]["max"],), jnp.int32)
    for fp8 in (False, True):
        out = jax.eval_shape(lambda p, t, s: arch.reference_logits(
            model, p, t, s, fp8=fp8), got, toks, sel)
        assert out.shape == (sel.shape[0], model["vocab_size"])
        assert out.dtype == jnp.float32
    S = traffic["prompt"]["buckets"]
    for count in (arch.prefill_flops, arch.prefill_bytes):
        assert 0 < count(model, S[0]) < count(model, S[-1])
    ctx = [S[-1]] * conf["engine"]["slot_cap"]
    for count in (arch.decode_flops, arch.decode_bytes):
        assert 0 < count(model, ctx[:1]) < count(model, ctx)


def test_names_units_and_bounds_keep_to_the_contract():
    names = [x["name"] for sec in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in BENCH[sec]]
    assert all(NAME.match(n) for n in names)
    assert len({x["name"] for x in BENCH["end_to_end"] + BENCH["per_layer"]}) \
        == len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    assert all(os.path.exists(ROOT / c["file"]) for c in BENCH["configs"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_peaks_table_refuses_an_unknown_device():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


def test_run_refuses_without_a_chip():
    cell = BENCH["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, str(ROOT / "tpu_bench" / "run.py"),
                        "--workload", cell, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=120, cwd=ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract memory/cost/collective analysis.

  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b \
      --shape train_4k --mesh single --out results/dryrun

``main`` sets XLA_FLAGS before jax first initialises its backend (which
locks the device count); 512 placeholder host devices back both the
16×16 single-pod and 2×16×16 multi-pod meshes. Importing this module
changes no device state. Compilation is AOT — no arrays are ever
allocated at these shapes.
"""
import argparse
import json
import os
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, shape_applicable
from repro.configs.base import TrainConfig
from repro.launch.mesh import make_production_mesh, mesh_axis_size
from repro.launch.specs import input_pspecs, state_pspecs
from repro.models import build_model
from repro.parallel.sharding import use_mesh
from repro.training.train_step import init_train_state, make_train_step

# v5e hardware constants (roofline denominators)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# `%name = <result-shapes> <op>(args...)` — op token must directly precede
# its argument list, else fusion consumers referencing %all-reduce.N match
_OP_RE = re.compile(
    r"=\s*(\(?[a-z0-9_]+\[[0-9,]*\][^=()]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPES_RE = re.compile(r"([a-z0-9_]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8}


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-chip payload bytes of every collective in post-SPMD HLO.

    Result shapes in partitioned HLO are per-device. Wire bytes per chip
    use ring formulas: AR 2·S·(k-1)/k; AG/A2A/RS S·(k-1)/k on the payload
    actually moved; CP S. k comes from replica_groups when parseable.
    """
    per_op: dict[str, dict] = {}
    total_wire = 0.0
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        shapes_blob, op = m.groups()
        if f"{op}-done" in line:
            continue  # counted at -start
        payload = 0
        for dtype, dims in _SHAPES_RE.findall(shapes_blob):
            nbytes = _DTYPE_BYTES.get(dtype, 4)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            payload += n * nbytes
        k = _group_size(line)
        frac = (k - 1) / k if k > 1 else 1.0
        if op == "all-reduce":
            wire = 2 * payload * frac
        elif op == "reduce-scatter":
            wire = payload * k * frac  # operand = result × k
        elif op in ("all-gather", "all-to-all"):
            wire = payload * frac
        else:  # collective-permute
            wire = payload
        d = per_op.setdefault(op, {"count": 0, "payload_bytes": 0.0,
                                   "wire_bytes": 0.0})
        d["count"] += 1
        d["payload_bytes"] += payload
        d["wire_bytes"] += wire
        total_wire += wire
    return {"ops": per_op, "wire_bytes_per_chip": total_wire}


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return 2


def build_step(arch: str, shape_name: str, mesh, tc: TrainConfig,
               cfg=None):
    """Returns (fn, example_args, in_shardings) ready to lower."""
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    specs = model.input_specs(shape)
    in_specs = input_pspecs(cfg, specs, mesh)

    if shape.kind == "train":
        params_sds = jax.eval_shape(model.init_params, jax.random.key(0))
        state_sds = jax.eval_shape(
            lambda p: init_train_state_from_params(p), params_sds)
        p_specs, z_specs = state_pspecs(params_sds, None, mesh,
                                        zero1=tc.zero1,
                                        moe_tp=cfg.moe_strategy == "tp")
        from repro.training.train_step import TrainState
        from repro.training.optimizer import OptState
        state_spec = TrainState(
            params=p_specs,
            opt=OptState(step=P(), m=z_specs, v=z_specs))
        step_fn = make_train_step(model, tc)
        args = (state_sds, specs)
        in_shardings = (state_spec, in_specs)
        out_shardings = (state_spec, None)
        return step_fn, args, in_shardings, out_shardings, cfg, model

    params_sds = jax.eval_shape(model.init_params, jax.random.key(0))
    p_specs, _ = state_pspecs(params_sds, None, mesh, zero1=False,
                              moe_tp=cfg.moe_strategy == "tp")
    if shape.kind == "prefill":
        def serve_prefill(params, batch):
            return model.prefill_fn(params, batch)
        args = (params_sds, specs)
        in_shardings = (p_specs, in_specs)
        return serve_prefill, args, in_shardings, None, cfg, model

    # decode
    def serve_step(params, cache, token, pos):
        return model.decode_fn(params, cache, token, pos)
    args = (params_sds, specs["cache"], specs["token"], specs["pos"])
    in_shardings = (p_specs, in_specs["cache"], in_specs["token"],
                    in_specs["pos"])
    return serve_step, args, in_shardings, None, cfg, model


def init_train_state_from_params(params):
    from repro.training.optimizer import OptState
    from repro.training.train_step import TrainState
    zeros = jax.tree.map(jnp.zeros_like, params)
    return TrainState(params=params,
                      opt=OptState(step=jnp.zeros((), jnp.int32), m=zeros,
                                   v=jax.tree.map(jnp.zeros_like, params)))


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N(_active)·tokens."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len / 3.0  # fwd only: 2N·D
        return 2.0 * n * shape.global_batch * shape.seq_len
    else:
        return 2.0 * n * shape.global_batch
    return 6.0 * n * toks


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             tc: TrainConfig | None = None, extra: dict | None = None,
             overrides: dict | None = None) -> dict:
    import dataclasses
    cfg = get_config(arch)
    # unroll layers for the dry-run by default: XLA cost analysis counts a
    # while-loop body ONCE, so scanned stacks under-report FLOPs/bytes/
    # collectives by ~L×. Unrolled HLO gives faithful roofline terms.
    ov = {"scan_layers": False, **(overrides or {})}
    cfg = dataclasses.replace(cfg, **ov)
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped",
                "reason": "full-attention arch; long_500k needs sub-quadratic "
                          "attention (DESIGN.md §Arch-applicability)"}
    tc = tc or TrainConfig()
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with use_mesh(mesh):
        fn, args, in_sh, out_sh, cfg, model = build_step(arch, shape_name,
                                                         mesh, tc, cfg=cfg)
        in_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), in_sh,
            is_leaf=lambda x: isinstance(x, P))
        kw = {}
        if out_sh is not None:  # train: pin state sharding, donate input state
            out_shardings = (jax.tree.map(
                lambda s: NamedSharding(mesh, s), out_sh[0],
                is_leaf=lambda x: isinstance(x, P)), None)
            kw = dict(out_shardings=out_shardings, donate_argnums=(0,))
        jitted = jax.jit(fn, in_shardings=in_shardings, **kw)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    n_chips = mesh.devices.size
    try:
        mem = compiled.memory_analysis()
        mem_stats = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_stats = {"error": str(e)}
    cost = compiled.cost_analysis() or {}
    hlo_flops = float(cost.get("flops", 0.0))
    hlo_bytes = float(cost.get("bytes accessed", 0.0))
    coll = parse_collectives(compiled.as_text())

    mf = model_flops(cfg, shape)
    # cost_analysis() on a partitioned module reports PER-DEVICE numbers
    # (verified against 6·N·D for tinyllama train_4k), so the roofline
    # terms divide by per-chip peaks directly; the formulas in the spec
    # (HLO/(chips·peak)) are equivalent with global HLO = per-device × chips.
    compute_term = hlo_flops / PEAK_FLOPS
    memory_term = hlo_bytes / HBM_BW
    collective_term = coll["wire_bytes_per_chip"] / LINK_BW
    terms = {"compute_s": compute_term, "memory_s": memory_term,
             "collective_s": collective_term}
    dominant = max(terms, key=terms.get)
    out = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_chips": n_chips,
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem_stats,
        "hlo_flops": hlo_flops, "hlo_bytes": hlo_bytes,
        "collectives": coll,
        "model_flops": mf,
        "model_flops_per_chip": mf / n_chips,
        "useful_flops_frac": (mf / n_chips) / hlo_flops if hlo_flops else None,
        **terms,
        "dominant": dominant,
        # step-time lower bound assuming zero overlap between the three
        # engines; roofline_frac = useful-FLOPs time / that bound (an MFU
        # upper bound for this compiled program)
        "step_time_lb_s": max(terms.values()),
        "roofline_frac": ((mf / n_chips / PEAK_FLOPS) / max(terms.values())
                          if max(terms.values()) > 0 else None),
    }
    if extra:
        out.update(extra)
    return out


def main():
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default=None, help="directory for JSON results")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--scan-layers", action="store_true",
                    help="keep lax.scan over layers (smaller HLO, but cost "
                         "analysis undercounts by ~L×)")
    # §Perf hillclimb knobs
    ap.add_argument("--moe-strategy", default=None, choices=["ep", "tp"])
    ap.add_argument("--bf16-reduce", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--decode-partials", action="store_true")
    ap.add_argument("--decode-grouped", action="store_true")
    ap.add_argument("--attn-bf16-probs", action="store_true")
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args()

    tc = TrainConfig(microbatches=args.microbatches,
                     zero1=not args.no_zero1)
    overrides = {}
    if args.scan_layers:
        overrides["scan_layers"] = True
    if args.remat:
        overrides["remat"] = args.remat
    if args.moe_strategy:
        overrides["moe_strategy"] = args.moe_strategy
    if args.bf16_reduce:
        overrides["bf16_reduce"] = True
    if args.seq_parallel:
        overrides["seq_parallel"] = True
    if args.decode_partials:
        overrides["decode_partials"] = True
    if args.decode_grouped:
        overrides["decode_grouped"] = True
    if args.attn_bf16_probs:
        overrides["attn_bf16_probs"] = True
    if args.attn_chunk:
        overrides["attn_chunk"] = args.attn_chunk
    if args.capacity_factor:
        overrides["capacity_factor"] = args.capacity_factor
    try:
        res = run_cell(args.arch, args.shape, args.mesh == "multi", tc,
                       extra={"tag": args.tag}, overrides=overrides)
    except Exception as e:
        res = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:], "tag": args.tag}
    print(json.dumps({k: v for k, v in res.items() if k != "trace"},
                     indent=2, default=str))
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        fname = f"{args.arch}__{args.shape}__{args.mesh}__{args.tag}.json"
        with open(os.path.join(args.out, fname), "w") as f:
            json.dump(res, f, indent=2, default=str)
    sys.exit(0 if res["status"] in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()

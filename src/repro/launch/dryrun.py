import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_EXTRA_XLA", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape x mesh) combination:
  lower the step with ShapeDtypeStruct stand-ins, compile, and record
  memory_analysis / cost_analysis / per-collective byte counts parsed from
  the post-SPMD HLO. Results are cached as JSON under experiments/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch gemma3-27b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--agg obcsaa]
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, TrainConfig, get_config
from repro.dist.sharding import best_spec
from repro.launch.mesh import (PRODUCTION_DEVICE_KIND, make_production_mesh,
                               num_workers)
from repro.launch import steps as steps_lib
from repro.models.registry import build_model

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
                "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]\d*[a-z0-9]*)\[([0-9,]*)\]")


def _type_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .+ \{")
_WHILE_RE = re.compile(r"while\(.*?\)?, condition=%?([\w.\-]+), "
                       r"body=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _split_computations(hlo_text: str):
    """name -> list of body lines (top-level computations in HLO text)."""
    comps = {}
    cur = None
    entry = None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m:
            cur = m.group(1)
            comps[cur] = []
            if line.startswith("ENTRY"):
                entry = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line.strip())
    return comps, entry


def _computation_multipliers(comps, entry):
    """Execution count of each computation: while bodies run trip_count
    times per parent invocation (nested whiles multiply)."""
    mult = {name: 0 for name in comps}
    if entry is not None:
        mult[entry] = 1
    # edges: parent -> (child, n) for body/condition of each while op
    edges = []
    for parent, lines in comps.items():
        for ls in lines:
            w = _WHILE_RE.search(ls)
            if not w:
                continue
            t = _TRIP_RE.search(ls)
            n = int(t.group(1)) if t else 1
            cond, body = w.group(1), w.group(2)
            edges.append((parent, body, n))
            edges.append((parent, cond, n + 1))
    for _ in range(len(comps)):   # fixpoint over nesting depth
        changed = False
        for parent, child, n in edges:
            v = mult.get(parent, 0) * n
            if child in mult and v > mult[child]:
                mult[child] = v
                changed = True
        if not changed:
            break
    return mult


def parse_collective_bytes(hlo_text: str) -> dict:
    """Per-collective byte accounting from post-SPMD HLO, scaled by while-
    loop trip counts (XLA's aggregate cost_analysis counts loop bodies once;
    scanned layer stacks would otherwise be undercounted ~num_layers x).

    Bytes per op: operand bytes when printed, else result bytes.
    ``wire_bytes`` approximates bytes crossing ICI per device: 2x for
    all-reduce (reduce+broadcast ring), 1x for the others."""
    comps, entry = _split_computations(hlo_text)
    mult = _computation_multipliers(comps, entry)
    out = {c: {"count": 0, "bytes": 0, "wire_bytes": 0} for c in _COLLECTIVES}
    for comp_name, lines in comps.items():
        k = mult.get(comp_name, 1) or 1
        for ls in lines:
            m = re.match(r"(?:ROOT )?%?[\w.\-]+ = .*?\b(all-gather|"
                         r"all-reduce|reduce-scatter|all-to-all|"
                         r"collective-permute)(?:-start|-done)?\(", ls)
            if not m:
                continue
            op = m.group(1)
            if "-done(" in ls:      # avoid double counting start/done pairs
                continue
            eq = ls.index(" = ")
            result_shapes = _SHAPE_RE.findall(ls[eq + 3:ls.index("(", eq)])
            operand_shapes = _SHAPE_RE.findall(ls[ls.index("(", eq):])
            rb = sum(_type_bytes(dt, dims) for dt, dims in result_shapes)
            ob = sum(_type_bytes(dt, dims) for dt, dims in operand_shapes)
            out[op]["count"] += k
            out[op]["bytes"] += k * (ob or rb)
            out[op]["wire_bytes"] += k * (2 * rb if op == "all-reduce"
                                          else max(rb, ob))
    out["total_bytes"] = sum(v["bytes"] for k_, v in out.items()
                             if isinstance(v, dict))
    out["total_wire_bytes"] = sum(v["wire_bytes"] for k_, v in out.items()
                                  if isinstance(v, dict))
    out["total_count"] = sum(v["count"] for k_, v in out.items()
                             if isinstance(v, dict))
    return out


def input_shardings(specs_tree, mesh):
    def visit(v):
        hints = ["data"] + [None] * (len(v.shape) - 1)
        return NamedSharding(mesh, best_spec(v.shape, hints, mesh))

    return jax.tree_util.tree_map(visit, specs_tree)


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool,
                agg: str = "obcsaa", tcfg: TrainConfig = None,
                variant: str = "baseline"):
    """Build + lower + compile one combination. Returns result dict.

    variant="opt" enables the §Perf beyond-paper changes: shard-aligned
    chunking + bf16 MAC symbols (train), flash-decoding sharded-cache
    attention (decode)."""
    import dataclasses
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if variant == "opt":
        if shape_name in ("decode_32k", "long_500k"):
            cfg = dataclasses.replace(cfg, decode_sharded_chunks=16)
        # NOTE: wire_dtype="bfloat16" is the TPU deployment choice, but the
        # XLA *CPU* AllReducePromotion pass crashes on bf16 all-reduce
        # ("Invalid binary instruction opcode copy") — keep f32 on the CPU
        # stand-in and record bf16's 2x saving analytically (EXPERIMENTS §Perf).
        tcfg = tcfg or TrainConfig(aggregation=agg, cs_shard_aligned=True)
    if shape.kind == "decode" and shape_name == "long_500k" \
            and not cfg.supports_long_context:
        return {"status": "skipped",
                "reason": "full-attention arch: long_500k requires "
                          "sub-quadratic attention (DESIGN.md §5)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    tcfg = tcfg or TrainConfig(aggregation=agg)
    t0 = time.time()
    with jax.set_mesh(mesh):
        pshard, pshapes = steps_lib.param_shardings(model, mesh)
        specs = model.input_specs(shape)
        in_shard = input_shardings(specs, mesh)
        if shape.kind == "train":
            step = steps_lib.make_train_step(model, tcfg, mesh)
            opt = steps_lib.make_optimizer(tcfg)
            from repro.dist.sharding import infer_param_sharding
            ostate_shapes = jax.eval_shape(opt.init, pshapes)
            oshard = infer_param_sharding(ostate_shapes, mesh)
            ctx_shapes = steps_lib.round_ctx_specs(mesh)
            ctx_shard = jax.tree_util.tree_map(
                lambda l: NamedSharding(mesh, P()), ctx_shapes)
            fn = jax.jit(step,
                         in_shardings=(pshard, oshard, in_shard, ctx_shard),
                         donate_argnums=(0, 1))
            lowered = fn.lower(pshapes, ostate_shapes, specs, ctx_shapes)
        elif shape.kind == "prefill":
            step = steps_lib.make_prefill_step(model)
            fn = jax.jit(step, in_shardings=(pshard, in_shard))
            lowered = fn.lower(pshapes, specs)
        else:  # decode
            step = steps_lib.make_decode_step(model)
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cshard = steps_lib.cache_shardings(cache_shapes, mesh)
            tok = specs["tokens"]
            tok_shard = NamedSharding(
                mesh, best_spec(tok.shape, ["data", None], mesh))
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            fn = jax.jit(step, in_shardings=(pshard, cshard, tok_shard,
                                             NamedSharding(mesh, P())),
                         donate_argnums=(1,))
            lowered = fn.lower(pshapes, cache_shapes, tok, pos)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    # older jax returns one dict per program; normalise to a flat dict
    if cost and not isinstance(cost, dict):
        cost = cost[0]
    cost = cost or {}
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)
    n_dev = 512 if multi_pod else 256
    result = {
        "status": "ok",
        "variant": variant,
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "device_kind": PRODUCTION_DEVICE_KIND,
        "agg": agg if shape.kind == "train" else None,
        "n_devices": n_dev,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem,
                                            "generated_code_size_in_bytes",
                                            None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        "cost": {k: cost.get(k) for k in
                 ("flops", "bytes accessed", "transcendentals",
                  "optimal_seconds") if k in cost},
        "collectives": coll,
        "param_count": cfg.param_count(),
    }
    return result


def combo_path(arch, shape_name, mesh_tag, agg, variant="baseline"):
    suffix = "" if variant == "baseline" else f"__{variant}"
    return RESULTS_DIR / f"{arch}__{shape_name}__{mesh_tag}__{agg}{suffix}.json"


def run_combo(arch, shape_name, multi_pod, agg="obcsaa", force=False,
              variant="baseline"):
    mesh_tag = "multi" if multi_pod else "single"
    path = combo_path(arch, shape_name, mesh_tag, agg, variant)
    if path.exists() and not force:
        return json.loads(path.read_text())
    try:
        res = lower_combo(arch, shape_name, multi_pod=multi_pod, agg=agg,
                          variant=variant)
    except Exception as e:
        res = {"status": "error", "arch": arch, "shape": shape_name,
               "mesh": mesh_tag, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-3000:]}
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(res, indent=1, default=str))
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--agg", default="obcsaa", choices=["obcsaa", "mean"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "opt"])
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    archs = [args.arch] if args.arch else ASSIGNED_ARCHS
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = "multi" if mp else "single"
                res = run_combo(arch, shape, mp, args.agg, force=args.force,
                                variant=args.variant)
                status = res["status"]
                extra = ""
                if status == "ok":
                    extra = (f"compile={res['compile_s']}s "
                             f"flops={res['cost'].get('flops', 0):.3e} "
                             f"coll={res['collectives']['total_bytes']:.3e}B")
                elif status == "error":
                    extra = res["error"][:160]
                else:
                    extra = res.get("reason", "")[:80]
                print(f"[{status:7s}] {arch:22s} {shape:12s} {tag:6s} {extra}",
                      flush=True)


if __name__ == "__main__":
    main()

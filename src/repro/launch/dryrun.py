import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent for every
(architecture x input-shape x mesh) combination without real hardware.

For each combination this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs ShapeDtypeStruct stand-ins for params, optimizer state,
     batch, and caches (no allocation),
  3. jits the train/prefill/serve step with explicit in/out shardings,
  4. ``.lower()`` + ``.compile()`` — any sharding mismatch, unsupported
     collective, or compile-time OOM is a bug in the framework,
  5. records memory_analysis / cost_analysis / parsed collective ops into
     experiments/dryrun/<arch>__<shape>__<mesh>.json for the roofline
     analysis (benchmarks/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  python -m repro.launch.dryrun --all                  # every pair, 16x16
  python -m repro.launch.dryrun --all --multi-pod      # every pair, 2x16x16
Flags mirroring the §Perf hillclimb levers:
  --seq-parallel    sequence-parallel residual stream (hillclimb 1)
  --window-cache    ring-buffer caches for sliding-window layers
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_arch, list_archs, with_long_variant
from repro.launch.mesh import HW, make_production_mesh
from repro.launch.steps import (
    cache_shapes,
    default_opts,
    input_specs,
    make_prefill_step,
    make_serve_step,
    make_train_step,
    opt_shapes,
    param_shapes,
)
from repro.sharding import batch_specs, cache_specs, param_specs, zero1_specs
from repro.sharding.specs import to_named

COLLECTIVE_RE = re.compile(
    r"=\s+(\w+)\[([\d,]*)\]\S*\s+(all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)\("
)
DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "f64": 8, "s64": 8, "u64": 8,
}


def parse_collectives(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in the post-SPMD module.
    NOTE: ops inside while (scan) bodies appear ONCE — the roofline layer
    scales them by the known trip counts (see benchmarks/roofline.py)."""
    out: dict[str, dict] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = n * DTYPE_BYTES.get(dt, 4)
        rec = out.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += b
    return out


def shape_skip_reason(cfg, shape_name: str, long_variant: bool) -> str | None:
    if shape_name != "long_500k":
        return None
    if cfg.long_context == "native":
        return None
    if cfg.long_context == "window" and long_variant:
        return None
    if cfg.long_context == "window":
        return ("pure full-attention arch: long_500k skipped by policy "
                "(run with --long-variant for the sliding-window variant)")
    return "no 500k analogue for bounded-context enc-dec audio (DESIGN.md)"


def run_one(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    seq_parallel: bool = False,
    window_cache: bool = False,
    long_variant: bool = False,
    ssm_seq_chunk: int = 0,
    moe_constrain: bool = False,
    out_dir: str = "experiments/dryrun",
    tag: str = "",
    **opt_overrides,
) -> dict:
    cfg = get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    skip = shape_skip_reason(cfg, shape_name, long_variant)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "seq_parallel": seq_parallel, "window_cache": window_cache,
        "ssm_seq_chunk": ssm_seq_chunk, "moe_constrain": moe_constrain,
        "tag": tag,
    }
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            suffix = f"__{tag}" if tag else ""
            fname = f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
            with open(os.path.join(out_dir, fname), "w") as f:
                json.dump(rec, f, indent=1)
        return rec
    if long_variant and cfg.long_context == "window" and shape_name == "long_500k":
        cfg = with_long_variant(cfg)
        rec["arch_variant"] = cfg.name

    mesh = make_production_mesh(multi_pod=multi_pod)
    opts = default_opts(
        cfg, mesh, seq_parallel=seq_parallel, window_cache=window_cache,
        ssm_seq_chunk=ssm_seq_chunk, moe_constrain=moe_constrain,
        **opt_overrides,
    )
    t0 = time.time()
    ps = param_shapes(cfg, opts)
    pspec = param_specs(cfg, opts, ps, mesh)
    bspec = batch_specs(cfg, shape.mode, shape.global_batch, mesh)
    ispecs = input_specs(cfg, shape, opts)

    with mesh:
        if shape.mode == "train":
            osh = opt_shapes(ps)
            ospec = {
                "step": P(),
                "m": zero1_specs(pspec, ps, mesh),
                "v": zero1_specs(pspec, ps, mesh),
            }
            step = make_train_step(cfg, opts)
            jitted = jax.jit(
                step,
                in_shardings=(to_named(pspec, mesh), to_named(ospec, mesh),
                              to_named(bspec, mesh)),
                out_shardings=(to_named(pspec, mesh), to_named(ospec, mesh), None),
            )
            args = (ps, osh, ispecs)
        elif shape.mode == "prefill":
            step = make_prefill_step(cfg, opts)
            jitted = jax.jit(
                step,
                in_shardings=(to_named(pspec, mesh), to_named(bspec, mesh)),
            )
            args = (ps, ispecs)
        else:  # decode
            csh = cache_shapes(cfg, opts, shape)
            cspec = cache_specs(cfg, opts, csh, mesh,
                                batch=shape.global_batch, seq=shape.seq_len)
            step = make_serve_step(cfg, opts)
            jitted = jax.jit(
                step,
                in_shardings=(to_named(pspec, mesh), to_named(cspec, mesh),
                              to_named(bspec, mesh)),
                out_shardings=(None, None, to_named(cspec, mesh)),
            )
            args = (ps, csh, ispecs)

        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        coll = parse_collectives(hlo)

    rec.update(
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=int(ma.argument_size_in_bytes),
            output_bytes=int(ma.output_size_in_bytes),
            temp_bytes=int(ma.temp_size_in_bytes),
            generated_code_bytes=int(ma.generated_code_size_in_bytes),
        ),
        cost=dict(
            flops_body_once=float(ca.get("flops", -1.0)),
            bytes_accessed_body_once=float(ca.get("bytes accessed", -1.0)),
        ),
        collectives=coll,
        hw=HW,
        num_devices=int(mesh.size),
    )
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fname = f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--window-cache", action="store_true")
    ap.add_argument("--ssm-chunk", type=int, default=0)
    ap.add_argument("--moe-constrain", action="store_true")
    ap.add_argument("--long-variant", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    pairs = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            pairs.append((a, s))
    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    for mp in meshes:
        for a, s in pairs:
            t0 = time.time()
            try:
                rec = run_one(
                    a, s, multi_pod=mp,
                    seq_parallel=args.seq_parallel,
                    window_cache=args.window_cache,
                    long_variant=args.long_variant,
                    ssm_seq_chunk=args.ssm_chunk,
                    moe_constrain=args.moe_constrain,
                    out_dir=args.out, tag=args.tag,
                )
                if rec["status"] == "ok":
                    m = rec["memory"]
                    print(
                        f"[OK]   {a:24s} {s:12s} {rec['mesh']:8s} "
                        f"lower {rec['lower_s']:6.1f}s compile {rec['compile_s']:6.1f}s "
                        f"arg {m['argument_bytes']/1e9:7.2f}GB temp {m['temp_bytes']/1e9:7.2f}GB",
                        flush=True,
                    )
                else:
                    print(f"[SKIP] {a:24s} {s:12s} {rec['mesh']:8s} {rec['reason']}",
                          flush=True)
            except Exception as e:
                failures += 1
                print(f"[FAIL] {a:24s} {s:12s} mp={mp} {type(e).__name__}: {e}",
                      flush=True)
                traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Kernel contract analyzer: prove Pallas resource contracts without running.

For each kernel in :data:`CONTRACTS` the analyzer verifies, using only
``jax.eval_shape`` (abstract tracing — nothing executes) plus a declared
block-geometry mirror of the source:

* **trace**    — the public entry point traces over the bench shapes from
  ``BENCH_kernels.json`` and produces the contracted output shapes/dtypes;
* **divisibility** — padded dims divide exactly into the block grid, the
  minor two dims of every block are (a multiple of 8 or the full dim, a
  multiple of 128 or the full dim) as the Pallas TPU lowering requires,
  lane blocks respect the kernel's declared lane unit (128 for
  vocab/class-tiled kernels — the TPU f32 tile is (8, 128)), and sublane
  blocks are multiples of 8. These checks read the declared geometry
  mirror below, not the kernels' own ``BlockSpec``s: what the TPU compiler
  accepts is proven by compiling each kernel for a v5e chip
  (tests/test_tpu_compile.py);
* **vmem**     — the per-grid-step VMEM footprint (in/out blocks rounded up
  to (8, 128) tile granularity, double-buffered, plus scratch) fits a
  configurable budget (default 8 MiB of the ~16 MB/core);
* **fp32**     — matmul-bearing kernels accumulate in fp32: every VMEM
  scratch buffer is declared ``jnp.float32`` and the kernel body casts
  operands with ``.astype(jnp.float32)`` (checked on the module AST);
* **vjp**      — batched pair kernels expose a 2-D wrapper whose output is
  the ``B=1`` slice of the batched output, and kernels declared
  differentiable are registered ``jax.custom_vjp`` objects whose gradient
  traces abstractly.

Each failed check is a :class:`~repro.analysis.findings.Finding` with rule
ID ``KRN001``-``KRN005``, merged into the same stream as the AST rules.
Tests corrupt a contract (``dataclasses.replace``) and assert the check
fails — see tests/test_analysis.py.
"""
from __future__ import annotations

import ast
import importlib
import inspect
import json
import os
import re
from dataclasses import dataclass, field
from typing import Callable

from repro.analysis.findings import Finding

LANE = 128  # f32 tile lane width
SUBLANE = 8  # f32 tile sublane height
DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024  # bytes; VMEM is ~16 MB/core

KRN_EXPLAIN = {
    "KRN001": "kernel entry point failed to trace (jax.eval_shape) or "
              "produced shapes/dtypes outside its contract",
    "KRN002": "block grid does not divide the padded bench shape, a "
              "block's minor two dims are not (multiple of 8 or full, "
              "multiple of 128 or full), or a block dimension violates the "
              "kernel's declared (sublane, lane) alignment units — all read "
              "from the declared geometry mirror",
    "KRN003": "estimated per-grid-step VMEM footprint (double-buffered "
              "blocks + scratch at (8,128) tile granularity) exceeds the "
              "budget",
    "KRN004": "matmul-bearing kernel without an fp32 accumulation policy "
              "(non-float32 VMEM scratch, or no .astype(jnp.float32) cast "
              "in the kernel body)",
    "KRN005": "batched kernel's 2-D wrapper / custom-VJP pairing is broken "
              "(missing wrapper, wrapper output is not the B=1 slice, or a "
              "differentiable kernel is not a registered jax.custom_vjp)",
}


def _roundup(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclass
class Geometry:
    """Block-level mirror of one kernel's pallas_call for a bench shape."""

    grid: tuple[int, ...]
    #: name -> (padded dims that the grid tiles, block dims) — same rank
    tiled: dict[str, tuple[tuple[int, ...], tuple[int, ...]]]
    #: per-grid-step scratch shapes (always f32)
    scratch: list[tuple[int, ...]] = field(default_factory=list)
    #: lane-tiled axes that must honor the 128 unit: (name, block_size)
    lane_blocks: list[tuple[str, int]] = field(default_factory=list)
    #: sublane-tiled axes that must honor the 8 unit: (name, block_size)
    sublane_blocks: list[tuple[str, int]] = field(default_factory=list)


@dataclass
class KernelContract:
    name: str
    module: str
    entry: str  # batched / public entry point attribute
    wrapper: str | None  # 2-D B=1 wrapper attribute, if the kernel is batched
    differentiable: bool  # must be a registered jax.custom_vjp
    matmul: bool  # fp32-accumulation policy applies
    kernel_fns: tuple[str, ...]  # Pallas kernel body function names
    geometry: Callable[[dict], Geometry]
    abstract: Callable[[dict], tuple]  # shape -> (fn, arg_specs, out_shapes)
    grad_abstract: Callable[[dict], tuple] | None = None

    def source_path(self) -> str:
        return "src/" + self.module.replace(".", "/") + ".py"


# ---------------------------------------------------------------------------
# Contract table (mirrors the kernel sources; the analyzer cross-checks it
# against reality via eval_shape, so a drifted mirror fails the gate)
# ---------------------------------------------------------------------------


def _specs(*shapes_dtypes):
    import jax

    return tuple(jax.ShapeDtypeStruct(s, d) for s, d in shapes_dtypes)


def _distill_geometry(s: dict) -> Geometry:
    import repro.kernels.distill_loss as m

    B, N, V = s["B"], s["N"], s["V"]
    bn, bv = s.get("block_n", 8), s.get("block_v", 512)
    Np, Vp = _roundup(N, bn), _roundup(V, bv)
    return Geometry(
        grid=(B, Np // bn, Vp // bv),
        tiled={
            "z": ((B, Np, Vp), (1, bn, bv)),
            "t": ((B, Np, Vp), (1, bn, bv)),
            # per-row vectors travel as (B, Np, 1) columns
            "y": ((B, Np, 1), (1, bn, 1)),
            "loss": ((B, Np, 1), (1, bn, 1)),
            "stats": ((B, Np, 2), (1, bn, 2)),
            # bwd pass reuses the fwd tiles plus g-in and dz-out
            "g": ((B, Np, 1), (1, bn, 1)),
            "dz": ((B, Np, Vp), (1, bn, bv)),
        },
        scratch=[(bn, 1)] * 5,
        lane_blocks=[("z", bv)],
        sublane_blocks=[("z", bn)],
    ) if m else None


def _distill_abstract(s: dict):
    import jax.numpy as jnp

    from repro.kernels.distill_loss import distill_loss_batched

    B, N, V = s["B"], s["N"], s["V"]
    fn = lambda z, t, y: distill_loss_batched(z, t, y, 1.5)
    specs = _specs(((B, N, V), jnp.float32), ((B, N, V), jnp.float32),
                   ((B, N), jnp.int32))
    return fn, specs, {"out": (B, N)}


def _distill_grad_abstract(s: dict):
    import jax
    import jax.numpy as jnp

    from repro.kernels.distill_loss import distill_loss_batched

    B, N, V = s["B"], s["N"], s["V"]
    gfn = jax.grad(lambda z, t, y: distill_loss_batched(z, t, y, 1.5).sum())
    specs = _specs(((B, N, V), jnp.float32), ((B, N, V), jnp.float32),
                   ((B, N), jnp.int32))
    return gfn, specs, {"out": (B, N, V)}


def _skr_geometry(s: dict) -> Geometry:
    B, N, C = s["B"], s["N"], s["C"]
    bn, bc = s.get("block_n", 8), s.get("block_c", 128)
    Np, Cp = _roundup(N, bn), _roundup(C, bc)
    return Geometry(
        grid=(B, Np // bn, Cp // bc),
        tiled={
            "p": ((B, Np, Cp), (1, bn, bc)),
            "pc": ((B, Np, 1), (1, bn, 1)),
            "do": ((B, Np, 1), (1, bn, 1)),
            "qb": ((B, Np, 1), (1, bn, 1)),
            "label": ((B, Np, 1), (1, bn, 1)),
            "out": ((B, Np, Cp), (1, bn, bc)),
        },
        lane_blocks=[("p", bc)],
        sublane_blocks=[("p", bn)],
    )


def _skr_abstract(s: dict):
    import jax.numpy as jnp

    from repro.kernels.skr_rectify import skr_rectify_batched

    B, N, C = s["B"], s["N"], s["C"]
    fn = lambda p, lab, q, c: skr_rectify_batched(p, lab, q, c)
    specs = _specs(((B, N, C), jnp.float32), ((B, N), jnp.int32),
                   ((B, C), jnp.float32), ((B, C), jnp.int32))
    return fn, specs, {"out": (B, N, C)}


def _flash_geometry(s: dict) -> Geometry:
    B, S, Nh, H = s["B"], s["S"], s["Nh"], s["H"]
    K = s.get("K", Nh)
    bq = min(s.get("block_q", 128), max(8, S))
    bk = min(s.get("block_k", 128), max(8, S))
    Sq, Sk = _roundup(S, bq), _roundup(S, bk)
    return Geometry(
        grid=(B, Nh, Sq // bq, Sk // bk),
        # the wrapper moves heads out of the minor pair: (B, heads, S, H)
        tiled={
            "q": ((B, Nh, Sq, H), (1, 1, bq, H)),
            "k": ((B, K, Sk, H), (1, 1, bk, H)),
            "v": ((B, K, Sk, H), (1, 1, bk, H)),
            "o": ((B, Nh, Sq, H), (1, 1, bq, H)),
        },
        scratch=[(bq, 1), (bq, 1), (bq, H)],
        # head_dim is the lane axis; MXU-aligned means a multiple of 64
        # (64/128/256 per the kernel docstring) — declared unit 64 here,
        # the VMEM estimate still pads lanes to the full 128 tile
        lane_blocks=[],
        sublane_blocks=[("q", bq), ("k", bk)],
    )


def _flash_abstract(s: dict):
    import jax.numpy as jnp

    from repro.kernels.flash_attention import flash_attention

    B, S, Nh, H = s["B"], s["S"], s["Nh"], s["H"]
    K = s.get("K", Nh)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True)
    specs = _specs(((B, S, Nh, H), jnp.float32), ((B, S, K, H), jnp.float32),
                   ((B, S, K, H), jnp.float32))
    return fn, specs, {"out": (B, S, Nh, H)}


def _rwkv6_geometry(s: dict) -> Geometry:
    B, T, Hh, hd = s["B"], s["T"], s["Hh"], s["hd"]
    chunk = s.get("chunk", 64)
    Tp = _roundup(T, chunk)
    return Geometry(
        grid=(B, Hh, Tp // chunk),
        # the wrapper moves heads out of the minor pair: (B, Hh, T, hd)
        tiled={
            "r": ((B, Hh, Tp, hd), (1, 1, chunk, hd)),
            "k": ((B, Hh, Tp, hd), (1, 1, chunk, hd)),
            "v": ((B, Hh, Tp, hd), (1, 1, chunk, hd)),
            "w": ((B, Hh, Tp, hd), (1, 1, chunk, hd)),
            "u": ((Hh, 1, hd), (1, 1, hd)),
            "s0": ((B, Hh, hd, hd), (1, 1, hd, hd)),
            "y": ((B, Hh, Tp, hd), (1, 1, chunk, hd)),
            "sT": ((B, Hh, hd, hd), (1, 1, hd, hd)),
        },
        scratch=[(hd, hd)],
        lane_blocks=[],
        sublane_blocks=[("r", chunk)],
    )


def _rwkv6_abstract(s: dict):
    import jax.numpy as jnp

    from repro.kernels.rwkv6_scan import rwkv6_scan

    B, T, Hh, hd = s["B"], s["T"], s["Hh"], s["hd"]
    fn = lambda r, k, v, w, u, s0: rwkv6_scan(r, k, v, w, u, s0)
    shp = (B, T, Hh, hd)
    specs = _specs((shp, jnp.float32), (shp, jnp.float32), (shp, jnp.float32),
                   (shp, jnp.float32), ((Hh, hd), jnp.float32),
                   ((B, Hh, hd, hd), jnp.float32))
    return fn, specs, {"out": shp}


CONTRACTS: dict[str, KernelContract] = {
    "distill_loss": KernelContract(
        name="distill_loss",
        module="repro.kernels.distill_loss",
        entry="distill_loss_batched",
        wrapper="distill_loss",
        differentiable=True,
        matmul=False,
        kernel_fns=("_fwd_kernel", "_bwd_kernel"),
        geometry=_distill_geometry,
        abstract=_distill_abstract,
        grad_abstract=_distill_grad_abstract,
    ),
    "skr_rectify": KernelContract(
        name="skr_rectify",
        module="repro.kernels.skr_rectify",
        entry="skr_rectify_batched",
        wrapper="skr_rectify",
        differentiable=False,
        matmul=False,
        kernel_fns=("_kernel",),
        geometry=_skr_geometry,
        abstract=_skr_abstract,
    ),
    "flash_attention": KernelContract(
        name="flash_attention",
        module="repro.kernels.flash_attention",
        entry="flash_attention",
        wrapper=None,
        differentiable=False,
        matmul=True,
        kernel_fns=("_kernel",),
        geometry=_flash_geometry,
        abstract=_flash_abstract,
    ),
    "rwkv6_scan": KernelContract(
        name="rwkv6_scan",
        module="repro.kernels.rwkv6_scan",
        entry="rwkv6_scan",
        wrapper=None,
        differentiable=False,
        matmul=True,
        kernel_fns=("_kernel",),
        geometry=_rwkv6_geometry,
        abstract=_rwkv6_abstract,
    ),
}


# ---------------------------------------------------------------------------
# Bench shapes (BENCH_kernels.json is the source of record)
# ---------------------------------------------------------------------------

_FLASH_RE = re.compile(r"B=(\d+) S=(\d+) H=(\d+)x(\d+)")
_RWKV_RE = re.compile(r"B=(\d+) T=(\d+) H=(\d+)x(\d+)")

DEFAULT_SHAPES = {
    "distill_loss": {"B": 4, "N": 256, "V": 2048},
    "skr_rectify": {"B": 4, "N": 256, "C": 1024},
    "flash_attention": {"B": 2, "S": 512, "Nh": 8, "H": 64, "K": 2},
    "rwkv6_scan": {"B": 2, "T": 256, "Hh": 4, "hd": 32},
}


def bench_shapes(bench_path: str | None = None) -> dict[str, dict]:
    """Per-kernel bench shapes parsed from BENCH_kernels.json, falling back
    to :data:`DEFAULT_SHAPES` for anything the file doesn't pin."""
    shapes = {k: dict(v) for k, v in DEFAULT_SHAPES.items()}
    if bench_path is None or not os.path.exists(bench_path):
        return shapes
    with open(bench_path) as f:
        bench = json.load(f)
    bd = bench.get("batched_dispatch", {})
    for name, keys in (("distill_loss", ("B", "N", "V")),
                       ("skr_rectify", ("B", "N", "C"))):
        rec = bd.get(name)
        if rec and all(k in rec for k in keys):
            shapes[name].update({k: int(rec[k]) for k in keys})
    for row in bench.get("single_kernel", []):
        derived = row.get("derived", "")
        if "flash_attention" in row.get("name", ""):
            m = _FLASH_RE.search(derived)
            if m:
                B, S, Nh, H = map(int, m.groups())
                shapes["flash_attention"].update(B=B, S=S, Nh=Nh, H=H)
        elif "rwkv6_scan" in row.get("name", ""):
            m = _RWKV_RE.search(derived)
            if m:
                B, T, Hh, hd = map(int, m.groups())
                shapes["rwkv6_scan"].update(B=B, T=T, Hh=Hh, hd=hd)
    return shapes


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------


def _entry_line(contract: KernelContract) -> int:
    try:
        mod = importlib.import_module(contract.module)
        obj = getattr(mod, contract.entry)
        obj = getattr(obj, "__wrapped__", obj)
        fun = getattr(obj, "fun", obj)  # custom_vjp wraps the python fn
        return inspect.getsourcelines(fun)[1]
    except Exception:
        return 1


def _tile_bytes(block: tuple[int, ...], itemsize: int = 4) -> int:
    """Bytes of one VMEM block at (8, 128) tile granularity."""
    dims = list(block)
    if len(dims) >= 1:
        dims[-1] = _roundup(dims[-1], LANE)
    if len(dims) >= 2:
        dims[-2] = _roundup(dims[-2], SUBLANE)
    n = 1
    for d in dims:
        n *= d
    return n * itemsize


def check_trace(contract: KernelContract, shape: dict) -> list[Finding]:
    import jax

    path, line = contract.source_path(), _entry_line(contract)
    try:
        fn, specs, expect = contract.abstract(shape)
        out = jax.eval_shape(fn, *specs)
    except Exception as e:  # tracing itself is the check
        return [Finding("KRN001", path, line,
                        f"{contract.entry} failed to trace over {shape}: "
                        f"{type(e).__name__}: {e}", engine="kernel")]
    first = out[0] if isinstance(out, (tuple, list)) else out
    got = tuple(first.shape)
    want = tuple(expect["out"])
    if got != want:
        return [Finding("KRN001", path, line,
                        f"{contract.entry} output shape {got} != contract "
                        f"{want} over {shape}", engine="kernel")]
    return []


def check_divisibility(contract: KernelContract, shape: dict) -> list[Finding]:
    path, line = contract.source_path(), _entry_line(contract)
    out: list[Finding] = []
    geo = contract.geometry(shape)
    for name, (padded, block) in geo.tiled.items():
        if len(padded) != len(block):
            out.append(Finding(
                "KRN002", path, line,
                f"{contract.name}.{name}: padded rank {len(padded)} != "
                f"block rank {len(block)}", engine="kernel"))
            continue
        for axis, (dim, blk) in enumerate(zip(padded, block)):
            if blk <= 0 or dim % blk:
                out.append(Finding(
                    "KRN002", path, line,
                    f"{contract.name}.{name}: axis {axis} padded dim {dim} "
                    f"not divisible by block {blk} (shape {shape})",
                    engine="kernel"))
        # the Pallas TPU lowering's rule for the minor pair of a block
        minor = zip((SUBLANE, LANE), padded[-2:], block[-2:])
        for unit, dim, blk in (minor if len(block) >= 2 else ()):
            if blk % unit and blk != dim:
                out.append(Finding(
                    "KRN002", path, line,
                    f"{contract.name}.{name}: minor block dim {blk} is "
                    f"neither a multiple of {unit} nor the full dim {dim}",
                    engine="kernel"))
    for name, blk in geo.lane_blocks:
        if blk % LANE:
            out.append(Finding(
                "KRN002", path, line,
                f"{contract.name}.{name}: lane block {blk} is not a "
                f"multiple of {LANE}", engine="kernel"))
    for name, blk in geo.sublane_blocks:
        if blk % SUBLANE:
            out.append(Finding(
                "KRN002", path, line,
                f"{contract.name}.{name}: sublane block {blk} is not a "
                f"multiple of {SUBLANE}", engine="kernel"))
    if any(g <= 0 for g in geo.grid):
        out.append(Finding(
            "KRN002", path, line,
            f"{contract.name}: degenerate grid {geo.grid}", engine="kernel"))
    return out


def vmem_bytes(contract: KernelContract, shape: dict) -> int:
    geo = contract.geometry(shape)
    blocks = sum(_tile_bytes(b) for _, b in geo.tiled.values())
    scratch = sum(_tile_bytes(s) for s in geo.scratch)
    return 2 * blocks + scratch  # double-buffered pipeline + live scratch


def check_vmem(contract: KernelContract, shape: dict,
               budget: int = DEFAULT_VMEM_BUDGET) -> list[Finding]:
    got = vmem_bytes(contract, shape)
    if got <= budget:
        return []
    return [Finding(
        "KRN003", contract.source_path(), _entry_line(contract),
        f"{contract.name}: estimated VMEM {got} B exceeds budget {budget} B "
        f"over {shape}", engine="kernel")]


def check_fp32_accum(contract: KernelContract,
                     source: str | None = None) -> list[Finding]:
    """Matmul kernels must keep fp32 accumulators: every pltpu.VMEM scratch
    is float32 and the kernel body casts via .astype(jnp.float32)."""
    if not contract.matmul:
        return []
    path, line = contract.source_path(), _entry_line(contract)
    if source is None:
        mod = importlib.import_module(contract.module)
        source = inspect.getsource(mod)
    tree = ast.parse(source)
    out: list[Finding] = []

    def _is_f32(node: ast.AST) -> bool:
        return isinstance(node, ast.Attribute) and node.attr == "float32"

    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "VMEM"
                and len(node.args) >= 2
                and not _is_f32(node.args[1])):
            out.append(Finding(
                "KRN004", path, getattr(node, "lineno", line),
                f"{contract.name}: VMEM scratch dtype is not jnp.float32 — "
                "matmul kernels must accumulate in fp32", engine="kernel"))

    for fn_name in contract.kernel_fns:
        fn_def = next(
            (n for n in ast.walk(tree)
             if isinstance(n, ast.FunctionDef) and n.name == fn_name), None)
        if fn_def is None:
            out.append(Finding(
                "KRN004", path, line,
                f"{contract.name}: kernel body {fn_name!r} not found in "
                f"{contract.module}", engine="kernel"))
            continue
        casts = any(
            isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr == "astype"
            and n.args and _is_f32(n.args[0])
            for n in ast.walk(fn_def)
        )
        if not casts:
            out.append(Finding(
                "KRN004", path, fn_def.lineno,
                f"{contract.name}: kernel body {fn_name!r} has no "
                ".astype(jnp.float32) operand cast — fp32 accumulation "
                "policy", engine="kernel"))
    return out


def check_vjp_pairing(contract: KernelContract, shape: dict) -> list[Finding]:
    import jax

    path, line = contract.source_path(), _entry_line(contract)
    out: list[Finding] = []
    mod = importlib.import_module(contract.module)
    entry = getattr(mod, contract.entry, None)
    if entry is None:
        return [Finding("KRN005", path, line,
                        f"{contract.module} has no entry {contract.entry!r}",
                        engine="kernel")]
    if contract.wrapper is not None:
        wrapper = getattr(mod, contract.wrapper, None)
        if wrapper is None:
            out.append(Finding(
                "KRN005", path, line,
                f"batched kernel {contract.entry} has no 2-D wrapper "
                f"{contract.wrapper!r}", engine="kernel"))
        else:
            try:
                _, specs, expect = contract.abstract(shape)
                slim = tuple(
                    jax.ShapeDtypeStruct(s.shape[1:], s.dtype) for s in specs
                )
                got = jax.eval_shape(wrapper, *slim)
                first = got[0] if isinstance(got, (tuple, list)) else got
                if tuple(first.shape) != tuple(expect["out"][1:]):
                    out.append(Finding(
                        "KRN005", path, line,
                        f"wrapper {contract.wrapper} output "
                        f"{tuple(first.shape)} is not the B=1 slice "
                        f"{tuple(expect['out'][1:])}", engine="kernel"))
            except Exception as e:
                out.append(Finding(
                    "KRN005", path, line,
                    f"wrapper {contract.wrapper} failed to trace: "
                    f"{type(e).__name__}: {e}", engine="kernel"))
    if contract.differentiable:
        if not isinstance(entry, jax.custom_vjp):
            out.append(Finding(
                "KRN005", path, line,
                f"{contract.entry} is declared differentiable but is not a "
                "registered jax.custom_vjp", engine="kernel"))
        elif contract.grad_abstract is not None:
            try:
                gfn, specs, expect = contract.grad_abstract(shape)
                got = jax.eval_shape(gfn, *specs)
                if tuple(got.shape) != tuple(expect["out"]):
                    out.append(Finding(
                        "KRN005", path, line,
                        f"{contract.entry} VJP output {tuple(got.shape)} != "
                        f"{tuple(expect['out'])}", engine="kernel"))
            except Exception as e:
                out.append(Finding(
                    "KRN005", path, line,
                    f"{contract.entry} VJP failed to trace: "
                    f"{type(e).__name__}: {e}", engine="kernel"))
    return out


def check_kernel(contract: KernelContract, shape: dict,
                 budget: int = DEFAULT_VMEM_BUDGET) -> list[Finding]:
    out = check_trace(contract, shape)
    out += check_divisibility(contract, shape)
    out += check_vmem(contract, shape, budget)
    out += check_fp32_accum(contract)
    out += check_vjp_pairing(contract, shape)
    return out


def check_all(bench_path: str | None = None,
              budget: int = DEFAULT_VMEM_BUDGET,
              contracts: dict[str, KernelContract] | None = None
              ) -> list[Finding]:
    contracts = CONTRACTS if contracts is None else contracts
    shapes = bench_shapes(bench_path)
    findings: list[Finding] = []
    for name in sorted(contracts):
        c = contracts[name]
        findings.extend(check_kernel(c, shapes[name], budget))
    return findings


def contract_table(bench_path: str | None = None,
                   budget: int = DEFAULT_VMEM_BUDGET) -> dict:
    """The tracked-artifact view: per-kernel geometry + check outcomes
    (everything deterministic — no wall clock anywhere)."""
    shapes = bench_shapes(bench_path)
    table: dict[str, dict] = {}
    for name in sorted(CONTRACTS):
        c = CONTRACTS[name]
        shape = shapes[name]
        geo = c.geometry(shape)
        failures = check_kernel(c, shape, budget)
        table[name] = {
            "shape": {k: int(v) for k, v in sorted(shape.items())},
            "grid": list(geo.grid),
            "blocks": {k: list(b) for k, (_, b) in sorted(geo.tiled.items())},
            "vmem_bytes": vmem_bytes(c, shape),
            "fp32_accum": c.matmul,
            "vjp": ("custom_vjp" if c.differentiable
                    else "wrapper-only" if c.wrapper else "forward-only"),
            "ok": not failures,
        }
    return table

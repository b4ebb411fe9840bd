"""Repo-specific determinism and layering rules.

Every rule has a stable ID (``DET``/``ARCH``/``OBS`` families), a path
scope, and an ``explain`` text surfaced by ``python -m repro.analysis
--explain RULE``. The invariants they protect are load-bearing:

* the ``benchmarks/tables/scenarios.json`` gate requires event signatures
  to be a pure function of (scenario, seed) — hence no wall clock, no
  unseeded randomness, no hash-ordered iteration near event emission;
* Pallas compiler params, the interpret decision and mesh axis types
  each have one home (``pallas_compat`` / ``launch.mesh``) — hence no raw
  Pallas/mesh API outside them;
* algorithm dispatch is registry-only (PR 3) — hence no duck-typed
  probing of the ``FLAlgorithm`` surface outside ``fl/api.py``;
* tracing-off must stay zero-overhead and event-log-invisible (PR 7) —
  hence every tracer call site sits behind the ``None`` guard.

Suppress a deliberate exception inline with ``# analysis: allow[ID]`` on
the offending line (or the line above), or grandfather it in the baseline
file — see docs/static-analysis.md.
"""
from __future__ import annotations

import ast
from typing import Iterator

from repro.analysis.findings import Finding
from repro.analysis.visitor import FileContext, canonical, receiver_src

RULES: dict[str, "Rule"] = {}


def register_rule(cls):
    rule = cls()
    if rule.id in RULES:
        raise ValueError(f"duplicate rule id {rule.id}")
    RULES[rule.id] = rule
    return cls


def default_rules() -> list["Rule"]:
    return [RULES[k] for k in sorted(RULES)]


class Rule:
    id: str = ""
    title: str = ""
    explain: str = ""
    #: path prefixes the rule applies to (repo-relative, "/"-separated)
    scope: tuple[str, ...] = ("src/repro/",)
    #: path prefixes/files exempted even inside the scope
    exempt: tuple[str, ...] = ()

    def applies_to(self, path: str) -> bool:
        if not any(path.startswith(p) for p in self.scope):
            return False
        return not any(path.startswith(p) for p in self.exempt)

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, ctx: FileContext, node: ast.AST, msg: str) -> Finding:
        return Finding(self.id, ctx.path, getattr(node, "lineno", 0), msg)


# ---------------------------------------------------------------------------
# DET — determinism (the scenarios.json signature contract)
# ---------------------------------------------------------------------------

#: files whose control flow feeds event emission / signature computation
_SIGNATURE_SCOPE = (
    "src/repro/sim/",
    "src/repro/fl/",
    "src/repro/core/",
)


@register_rule
class Det001WallClock(Rule):
    id = "DET001"
    title = "no wall-clock reads in signature-bearing code"
    scope = _SIGNATURE_SCOPE
    explain = (
        "Simulated time is the only clock the scheduler may consult: event\n"
        "signatures in benchmarks/tables/scenarios.json are a pure function\n"
        "of (scenario, seed), and a time.time()/datetime.now()/perf_counter\n"
        "read that leaks into scheduling or event payloads makes replays\n"
        "diverge. Host-side measurement that stays OUTSIDE the event log\n"
        "(RunResult.wall_s, metrics histograms) is legitimate — annotate\n"
        "those sites with `# analysis: allow[DET001]`."
    )

    _CLOCKS = {
        "time.time", "time.time_ns", "time.perf_counter",
        "time.perf_counter_ns", "time.monotonic", "time.monotonic_ns",
        "time.process_time", "time.process_time_ns",
        "datetime.datetime.now", "datetime.datetime.utcnow",
        "datetime.datetime.today", "datetime.date.today",
    }

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = canonical(ctx, node.func)
            if name in self._CLOCKS:
                yield self.finding(
                    ctx, node,
                    f"wall-clock read `{name}` in signature-bearing code; "
                    "use simulated time, or annotate a host-only "
                    "measurement with `# analysis: allow[DET001]`",
                )


@register_rule
class Det002UnseededRandom(Rule):
    id = "DET002"
    title = "no unseeded randomness"
    scope = ("src/repro/",)
    explain = (
        "All randomness must flow from an explicit seed: numpy through\n"
        "np.random.default_rng(seed) Generators, JAX through PRNGKey(seed).\n"
        "Module-level numpy sampling (np.random.normal, np.random.choice,\n"
        "np.random.seed, ...) and the stdlib `random` module draw from\n"
        "process-global state that any import can perturb, so two runs of\n"
        "the same (scenario, seed) stop being bit-identical. A bare\n"
        "default_rng() with no seed is OS entropy — equally forbidden."
    )

    _NP_ALLOWED = {"default_rng", "Generator", "SeedSequence", "BitGenerator",
                   "PCG64", "Philox"}

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = canonical(ctx, node.func)
            if name is None:
                continue
            if name == "random" or name.startswith("random."):
                yield self.finding(
                    ctx, node,
                    f"stdlib `{name}` draws from process-global RNG state; "
                    "use np.random.default_rng(seed) or jax.random",
                )
            elif name.startswith("numpy.random."):
                leaf = name.rsplit(".", 1)[1]
                if leaf == "default_rng":
                    if not node.args and not node.keywords:
                        yield self.finding(
                            ctx, node,
                            "`default_rng()` without a seed draws OS "
                            "entropy; pass an explicit seed",
                        )
                elif leaf not in self._NP_ALLOWED:
                    yield self.finding(
                        ctx, node,
                        f"module-level `{name}` uses numpy's global RNG; "
                        "use a seeded np.random.default_rng Generator",
                    )


@register_rule
class Det003UnorderedIteration(Rule):
    id = "DET003"
    title = "no hash-ordered iteration near event emission"
    scope = _SIGNATURE_SCOPE
    explain = (
        "Python set iteration order is salted hash order (PYTHONHASHSEED):\n"
        "a `for v in some_set` that feeds event emission or signature\n"
        "computation reorders events between processes. Wrap the iterable\n"
        "in sorted(...) — the scheduler already does this for stragglers,\n"
        "offline windows, and churn draws. dict/.keys() iteration is\n"
        "insertion-ordered but the insertion order itself is rarely part of\n"
        "the determinism contract, so explicit .keys() loops are flagged\n"
        "too; iterate sorted(d) instead."
    )

    def _offending_iter(self, ctx: FileContext, it: ast.AST) -> str | None:
        if isinstance(it, (ast.Set, ast.SetComp)):
            return "a set literal/comprehension"
        if isinstance(it, ast.Call):
            fname = canonical(ctx, it.func)
            if isinstance(it.func, ast.Name) and it.func.id in (
                "set", "frozenset"
            ):
                return f"a {it.func.id}() result"
            if fname in ("builtins.set", "builtins.frozenset"):
                return "a set() result"
            if isinstance(it.func, ast.Attribute) and it.func.attr == "keys":
                return "dict.keys()"
        return None

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        iters: list[ast.AST] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.For):
                iters.append(node.iter)
            elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                   ast.GeneratorExp)):
                iters.extend(gen.iter for gen in node.generators)
        for it in iters:
            what = self._offending_iter(ctx, it)
            if what is not None:
                yield self.finding(
                    ctx, it,
                    f"iteration over {what} is hash/insertion-ordered; "
                    "wrap in sorted(...) so event order is deterministic",
                )


@register_rule
class Det004FaultStreamConstruction(Rule):
    id = "DET004"
    title = "simulator RNGs are constructed once, in __init__"
    scope = ("src/repro/sim/",)
    explain = (
        "Fault/churn/network randomness must come from streams owned by a\n"
        "process object and built exactly once in its __init__ (see\n"
        "FaultProcess: one SeedSequence-derived Generator per concern).\n"
        "Constructing a Generator inside a draw path — default_rng(...),\n"
        "SeedSequence(...), PCG64/Philox(...) in loss_prob, draw_round,\n"
        "plan_attempts, module level, ... — re-keys the stream per call, so\n"
        "the schedule of fault events stops being a pure function of\n"
        "(scenario, seed, plan) and checkpoint-resume (which snapshots the\n"
        "streams' bit-generator state) can no longer replay it. Pre-run\n"
        "one-shot derivations (e.g. byzantine label noise applied before the\n"
        "engine exists) are the deliberate exception — annotate them with\n"
        "`# analysis: allow[DET004]`."
    )

    _CTORS = {
        "numpy.random.default_rng", "numpy.random.Generator",
        "numpy.random.SeedSequence", "numpy.random.PCG64",
        "numpy.random.Philox",
    }

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = canonical(ctx, node.func)
            if name not in self._CTORS:
                continue
            fn = ctx.enclosing_function(node)
            if fn is not None and fn.name == "__init__":
                continue
            where = f"in `{fn.name}`" if fn is not None else "at module level"
            yield self.finding(
                ctx, node,
                f"`{name}` constructed {where}; simulator RNG streams are "
                "built once in __init__ so fault schedules replay "
                "bit-identically (checkpoint-resume snapshots their state)",
            )


# ---------------------------------------------------------------------------
# PERF — population-scale scheduler hot paths
# ---------------------------------------------------------------------------


@register_rule
class Perf001PerNodeLoop(Rule):
    id = "PERF001"
    title = "no per-node Python loops over the population in sim hot paths"
    scope = ("src/repro/sim/",)
    explain = (
        "The simulator core is array-resident (docs/simulator.md): churn,\n"
        "offline windows, and rejoin sweeps are numpy operations over the\n"
        "whole population, because a Python `for v in tree.devices` that\n"
        "runs every round costs O(population) interpreter iterations and\n"
        "caps the engine well below its events/sec budget. Loops (or\n"
        "comprehensions) over `*.devices` / `*.nodes` are allowed only in\n"
        "construction paths (`__init__`), where they run once. Hot-path\n"
        "sites that are deliberately scalar — e.g. a draw loop kept in\n"
        "legacy RNG consumption order for signature compatibility — must\n"
        "say so with `# analysis: allow[PERF001]`."
    )

    _POPULATION_ATTRS = frozenset({"devices", "nodes"})
    #: wrappers that don't change what is being iterated
    _TRANSPARENT = frozenset({"sorted", "list", "tuple", "enumerate",
                              "reversed", "set", "frozenset"})

    def _population_src(self, node: ast.AST) -> str | None:
        """The dotted source of a population-sized iterable, unwrapping
        transparent call wrappers (``sorted(tree.devices)`` still iterates
        the population), else None."""
        if isinstance(node, ast.Call):
            if (isinstance(node.func, ast.Name)
                    and node.func.id in self._TRANSPARENT and node.args):
                return self._population_src(node.args[0])
            return None
        if (isinstance(node, ast.Attribute)
                and node.attr in self._POPULATION_ATTRS):
            return receiver_src(node) or node.attr
        return None

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        iters: list[ast.AST] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.For):
                iters.append(node.iter)
            elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                   ast.GeneratorExp)):
                iters.extend(gen.iter for gen in node.generators)
        for it in iters:
            src = self._population_src(it)
            if src is None:
                continue
            fn = ctx.enclosing_function(it)
            if fn is not None and fn.name == "__init__":
                continue  # construction-time: runs once, not per round
            yield self.finding(
                ctx, it,
                f"per-node Python loop over `{src}` outside __init__; "
                "hot paths sweep the population with array ops "
                "(docs/simulator.md), or annotate a deliberate scalar "
                "path with `# analysis: allow[PERF001]`",
            )


# ---------------------------------------------------------------------------
# ARCH — layering (shim routing + registry-only dispatch)
# ---------------------------------------------------------------------------


@register_rule
class Arch001ShimRouting(Rule):
    id = "ARCH001"
    title = "raw Pallas/mesh APIs only inside their shims"
    scope = ("src/repro/",)
    explain = (
        "Two modules own the raw APIs: repro.kernels.pallas_compat\n"
        "(CompilerParams and the interpret-mode decision) and\n"
        "repro.launch.mesh.auto_mesh (make_mesh with Auto axis types).\n"
        "Kernel modules under src/repro/kernels/ may call pl.pallas_call\n"
        "directly but must import CompilerParams from pallas_compat;\n"
        "everything else goes through the wrappers. A raw\n"
        "pltpu.CompilerParams or jax.make_mesh elsewhere splits those\n"
        "decisions across the tree."
    )

    _PALLAS_CALL_OK = ("src/repro/kernels/",)
    _COMPILER_PARAMS_OK = ("src/repro/kernels/pallas_compat.py",)
    _MAKE_MESH_OK = ("src/repro/launch/mesh.py",)

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                if node.module == "jax.experimental.pallas.tpu" and any(
                    a.name == "CompilerParams" for a in node.names
                ) and not ctx.path.startswith(self._COMPILER_PARAMS_OK):
                    yield self.finding(
                        ctx, node,
                        "import CompilerParams from "
                        "repro.kernels.pallas_compat, not from "
                        "jax.experimental.pallas.tpu",
                    )
                continue
            if not isinstance(node, ast.Attribute):
                continue
            name = canonical(ctx, node)
            if name is None:
                continue
            if name.endswith(".pallas_call") and name.startswith(
                "jax.experimental.pallas"
            ) and not ctx.path.startswith(self._PALLAS_CALL_OK):
                yield self.finding(
                    ctx, node,
                    "pl.pallas_call outside src/repro/kernels/ — kernels "
                    "live there, beside pallas_compat",
                )
            elif name == "jax.experimental.pallas.tpu.CompilerParams" \
                    and not ctx.path.startswith(self._COMPILER_PARAMS_OK):
                yield self.finding(
                    ctx, node,
                    "raw pltpu CompilerParams reference; import it from "
                    "repro.kernels.pallas_compat instead",
                )
            elif name == "jax.make_mesh" and not ctx.path.startswith(
                self._MAKE_MESH_OK
            ):
                yield self.finding(
                    ctx, node,
                    "jax.make_mesh outside repro.launch.mesh; call "
                    "auto_mesh so every mesh gets the same axis types",
                )


@register_rule
class Arch002DuckProbing(Rule):
    id = "ARCH002"
    title = "no duck-typed algorithm probing outside fl/api.py"
    scope = ("src/repro/",)
    exempt = ("src/repro/fl/api.py",)
    explain = (
        "PR 3 replaced hasattr-probing of trainers with the FLAlgorithm\n"
        "ABC + @register_algorithm registry: the scheduler calls the\n"
        "declared surface, never sniffs for it. A hasattr(trainer,\n"
        "'execute_batch') or isinstance(x, FedEEC) outside fl/api.py\n"
        "reintroduces per-algorithm special cases the unified work-item\n"
        "API removed. Extend the FLAlgorithm base class (with a default)\n"
        "instead of probing."
    )

    #: the FLAlgorithm method/attribute surface probing would sniff
    _API_ATTRS = frozenset({
        "work_items", "execute", "execute_batch", "batch_signature",
        "begin_round", "end_round", "set_participation", "participates",
        "train_round", "migrate", "try_migrate", "on_migrate_refused",
        "cloud_params", "cloud_apply", "on_item_failed",
        "state_arrays", "state_meta", "load_state",
    })
    _ALGO_TYPES = frozenset({
        "FLAlgorithm", "FedEEC", "HierarchicalFedAvg", "FlatFedAvg",
    })

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if not isinstance(node.func, ast.Name):
                continue
            if node.func.id == "hasattr" and len(node.args) == 2:
                attr = node.args[1]
                if isinstance(attr, ast.Constant) and attr.value in self._API_ATTRS:
                    yield self.finding(
                        ctx, node,
                        f"hasattr probe for FLAlgorithm API "
                        f"{attr.value!r}; dispatch through the registry / "
                        "base-class default instead",
                    )
            elif node.func.id == "isinstance" and len(node.args) == 2:
                types = node.args[1]
                names = [types] if not isinstance(types, ast.Tuple) else list(
                    types.elts
                )
                for t in names:
                    leaf = t.attr if isinstance(t, ast.Attribute) else (
                        t.id if isinstance(t, ast.Name) else None
                    )
                    if leaf in self._ALGO_TYPES:
                        yield self.finding(
                            ctx, node,
                            f"isinstance check against algorithm type "
                            f"{leaf!r}; algorithms are dispatched via the "
                            "FLAlgorithm surface, not their concrete class",
                        )
                        break


# ---------------------------------------------------------------------------
# OBS — telemetry inertness
# ---------------------------------------------------------------------------


@register_rule
class Obs001UnguardedTracer(Rule):
    id = "OBS001"
    title = "tracer call sites must sit behind the None guard"
    scope = ("src/repro/",)
    exempt = ("src/repro/obs/",)
    explain = (
        "Tracing-off must cost one global read: every call to a tracer's\n"
        ".span()/.add_span()/.instant() outside repro.obs must be reachable\n"
        "only when the tracer is known non-None — an enclosing\n"
        "`if tr is not None:` block, the\n"
        "`tr.span(...) if tr is not None else nullcontext()` with-item\n"
        "idiom, or an early `if tr is None: return ...` in the same\n"
        "function. An unguarded site either crashes with tracing off or\n"
        "silently forces a tracer into a hot path."
    )

    _METHODS = frozenset({"span", "add_span", "instant"})

    @staticmethod
    def _is_tracer_recv(recv: str) -> bool:
        return recv in ("tr", "tracer") or recv.endswith(".tracer")

    @staticmethod
    def _none_test(test: ast.AST, recv: str) -> str | None:
        """'is_none' / 'is_not_none' when ``test`` (or one conjunct of an
        `and`) compares ``recv`` against None; else None."""
        if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
            for sub in test.values:
                got = Obs001UnguardedTracer._none_test(sub, recv)
                if got:
                    return got
            return None
        if not (isinstance(test, ast.Compare) and len(test.ops) == 1):
            return None
        if not (isinstance(test.comparators[0], ast.Constant)
                and test.comparators[0].value is None):
            return None
        if receiver_src(test.left) != recv:
            return None
        if isinstance(test.ops[0], ast.Is):
            return "is_none"
        if isinstance(test.ops[0], ast.IsNot):
            return "is_not_none"
        return None

    def _guarded(self, ctx: FileContext, call: ast.Call, recv: str) -> bool:
        # 1. enclosing If / IfExp with the right branch
        for parent, child in ctx.parent_chain(call):
            if isinstance(parent, ast.IfExp):
                kind = self._none_test(parent.test, recv)
                if kind == "is_not_none" and child is parent.body:
                    return True
                if kind == "is_none" and child is parent.orelse:
                    return True
            elif isinstance(parent, ast.If):
                kind = self._none_test(parent.test, recv)
                if kind == "is_not_none" and child in parent.body:
                    return True
                if kind == "is_none" and child in parent.orelse:
                    return True
        # 2. early-exit guard earlier in the same function:
        #    if recv is None: return/raise/continue
        fn = ctx.enclosing_function(call)
        if fn is not None:
            for node in ast.walk(fn):
                if (isinstance(node, ast.If)
                        and node.lineno < call.lineno
                        and self._none_test(node.test, recv) == "is_none"
                        and node.body
                        and isinstance(node.body[-1],
                                       (ast.Return, ast.Raise, ast.Continue))):
                    return True
        return False

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self._METHODS):
                continue
            recv = receiver_src(node.func.value)
            if not self._is_tracer_recv(recv):
                continue
            if not self._guarded(ctx, node, recv):
                yield self.finding(
                    ctx, node,
                    f"`{recv}.{node.func.attr}(...)` is not guarded by a "
                    f"`{recv} is not None` check — tracing-off must stay "
                    "one None test (docs/observability.md)",
                )

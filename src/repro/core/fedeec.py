"""FedEEC: recursive knowledge agglomeration over the EEC-NET (Algorithm 3).

Two phases per run:
  * Init: every leaf encodes its private data with the frozen encoder and
    sends (ε, y) up the tree; every interior node stores the union of its
    subtree's embeddings.
  * Train rounds: post-order traversal; every (child, parent) pair runs
    BSBODP(+SKR): child-as-student then parent-as-student, distilling over
    bridge samples dec(ε) of the child's subtree embeddings.

FedAgg (the INFOCOM'24 predecessor) is exactly this with SKR disabled
(``use_skr=False``) — the ablation the paper reports in Table III.
"""
from __future__ import annotations

from contextlib import nullcontext
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig
from repro.core import bsbodp
from repro.core.protocols import BSBODP_SKR
from repro.core.skr import skr_init, skr_process_batch
from repro.core.topology import Tree
from repro.fl.api import FLAlgorithm, WorkItem, register_algorithm
from repro.models.autoencoder import decode, encode
from repro.models.registry import get_fl_model
from repro.obs.metrics import global_registry
from repro.obs.trace import active_tracer
from repro.optim import adamw_init, adamw_update

_NO_SPAN = nullcontext()


class FedEEC(FLAlgorithm):
    # BSBODP(+SKR) imposes no structural relation on parent-child model
    # pairs (R = V x V): every migration is legal (Theorem 1)
    protocol = BSBODP_SKR

    def __init__(
        self,
        cfg: FLConfig,
        tree: Tree,
        client_data: dict[str, tuple[np.ndarray, np.ndarray]],
        auto_params,
        *,
        use_skr: bool = True,
        model_of: dict[str, str] | None = None,
        seed: int = 0,
    ):
        super().__init__(cfg, tree)
        self.auto = auto_params
        self.use_skr = use_skr
        self.rng = np.random.default_rng(seed)
        key = jax.random.PRNGKey(seed)

        # tier -> model assignment
        self.model_of: dict[str, str] = {}
        leaves = tree.leaves
        for v in tree.nodes:
            if model_of and v in model_of:
                self.model_of[v] = model_of[v]
            elif tree.is_leaf(v):
                if cfg.end_model_hetero and leaves.index(v) % 2 == 1:
                    self.model_of[v] = cfg.end_model_hetero
                else:
                    self.model_of[v] = cfg.end_model
            elif v == tree.root:
                self.model_of[v] = cfg.cloud_model
            else:
                self.model_of[v] = cfg.edge_model

        # node states
        self.params: dict[str, object] = {}
        self.opt: dict[str, object] = {}
        self.skr: dict[str, object] = {}
        self.apply: dict[str, Callable] = {}
        for i, v in enumerate(tree.nodes):
            init_fn, apply_fn = get_fl_model(self.model_of[v])
            p = init_fn(jax.random.fold_in(key, i), cfg.num_classes, cfg.image_size)
            self.params[v] = p
            self.opt[v] = adamw_init(p)
            self.skr[v] = skr_init(cfg.num_classes, cfg.queue_len)
            self.apply[v] = apply_fn

        self.client_data = client_data
        self.embeddings: dict[str, tuple[np.ndarray, np.ndarray]] = {}
        # per-row provenance of every embedding store: which device each
        # sample came from (index into the sorted device list). Drives
        # cohort-weighted bridge sampling under population-scale
        # scenarios (docs/simulator.md); maintained at the same three
        # sites as the stores themselves (init / gather / migrate)
        self.embed_src: dict[str, np.ndarray] = {}
        self._src_names: list[str] = sorted(client_data)
        self._src_pos: dict[str, int] = {
            v: i for i, v in enumerate(self._src_names)}
        self._bridge_p_cache: dict[str, np.ndarray] = {}
        self._step_cache: dict = {}
        # (node, peer, reason) of BSBODP pairs lost to faults — the
        # knowledge that never agglomerated (docs/robustness.md)
        self.failed_pairs: list[tuple[str, str, str]] = []
        # always-on BSBODP counters (docs/observability.md): calls of the
        # jitted distill steps; calls of a coalesced group's stack and
        # unstack, apart, so that the steps' count stays three a distill
        # step; and the bytes of host arrays sent to the device
        reg = global_registry()
        self._jit_calls = {step: reg.counter("fl_jit_calls_total", step=step)
                           for step in ("decode", "teacher", "student")}
        self._jit_calls |= {step: reg.counter("fl_stack_calls_total", step=step)
                            for step in ("stack", "unstack")}
        self._h2d_bytes = reg.counter("fl_h2d_bytes_total")
        self._init_phase()

    # ------------------------------------------------------------------ init

    def _init_phase(self):
        """Leaves encode private data; embeddings propagate to the root."""
        enc = jax.jit(encode)
        for v in self.tree.post_order():
            if self.tree.is_leaf(v):
                x, y = self.client_data[v]
                eps = np.asarray(enc(self.auto, jnp.asarray(x)))
                self.embeddings[v] = (eps, y.copy())
                self.embed_src[v] = np.full(
                    len(y), self._src_pos[v], dtype=np.int32)
                # upload (ε, y): (|ε| + 1) per sample — Table VII init term
                link = self.comm.link_kind(self.tree, v)
                self.comm.record(link, eps.size + len(y), "init-embed")
            elif v != self.tree.root:
                self._gather_children(v)
        self._gather_children(self.tree.root)

    def _gather_children(self, v):
        es, ys, ss = [], [], []
        for c in self.tree.children[v]:
            e, y = self.embeddings[c]
            es.append(e)
            ys.append(y)
            ss.append(self.embed_src[c])
            if v != self.tree.root:
                link = self.comm.link_kind(self.tree, v)
                self.comm.record(link, e.size + y.size, "relay-embed")
        self.embeddings[v] = (np.concatenate(es), np.concatenate(ys))
        self.embed_src[v] = np.concatenate(ss)

    # -------------------------------------------------------------- jit steps

    def _teacher_core(self, model_name):
        apply_fn = get_fl_model(model_name)[1]
        T = self.cfg.temperature

        def bsbodp_teacher(params, skr_state, bridge_x, labels):
            z = apply_fn(params, bridge_x)
            probs = jax.nn.softmax(z / T, axis=-1)
            new_state, q = skr_process_batch(skr_state, probs, labels)
            return probs, q, new_state

        return bsbodp_teacher

    def _teacher_fn(self, model_name):
        key = ("teacher", model_name)
        if key not in self._step_cache:
            self._step_cache[key] = jax.jit(self._teacher_core(model_name))
        return self._step_cache[key]

    def _teacher_fn_batched(self, model_name):
        """One dispatch for B stacked teachers of the same architecture."""
        key = ("teacher", model_name, "vmap")
        if key not in self._step_cache:
            self._step_cache[key] = jax.jit(
                jax.vmap(self._teacher_core(model_name))
            )
        return self._step_cache[key]

    def _student_core(self, model_name, leaf: bool):
        apply_fn = get_fl_model(model_name)[1]
        beta, gamma, lr = self.cfg.beta, self.cfg.gamma, self.cfg.lr

        if leaf:
            def loss_fn(p, bx, by, tq, lx, ly):
                zl = apply_fn(p, lx)
                zb = apply_fn(p, bx)
                return bsbodp.leaf_loss(zl, ly, zb, by, tq, beta, gamma)

            def bsbodp_student_leaf(params, opt, bx, by, tq, lx, ly):
                l, g = jax.value_and_grad(loss_fn)(params, bx, by, tq, lx, ly)
                params, opt = adamw_update(g, opt, params, lr=lr, weight_decay=0.0)
                return params, opt, l

            return bsbodp_student_leaf

        def loss_fn(p, bx, by, tq):
            zb = apply_fn(p, bx)
            return bsbodp.non_leaf_loss(zb, by, tq, beta)

        def bsbodp_student(params, opt, bx, by, tq):
            l, g = jax.value_and_grad(loss_fn)(params, bx, by, tq)
            params, opt = adamw_update(g, opt, params, lr=lr, weight_decay=0.0)
            return params, opt, l

        return bsbodp_student

    def _student_fn(self, model_name, leaf: bool):
        key = ("student", model_name, leaf)
        if key not in self._step_cache:
            self._step_cache[key] = jax.jit(self._student_core(model_name, leaf))
        return self._step_cache[key]

    def _student_fn_batched(self, model_name, leaf: bool):
        """One fused update step for B stacked same-architecture students."""
        key = ("student", model_name, leaf, "vmap")
        if key not in self._step_cache:
            self._step_cache[key] = jax.jit(
                jax.vmap(self._student_core(model_name, leaf))
            )
        return self._step_cache[key]

    def _stack_fn(self):
        """One compiled copy of a coalesced group's per-node trees into
        stacked ones: ``(P_t, S_t, P_s, O_s)``, each a list of B trees, to
        four trees with a leading axis of B. jit keys on the structures and
        shapes, so the one function serves every model pair and group size."""
        if "stack" not in self._step_cache:
            def bsbodp_stack(*groups):
                return tuple(jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
                             for trees in groups)

            self._step_cache["stack"] = jax.jit(bsbodp_stack)
        return self._step_cache["stack"]

    def _unstack_fn(self):
        """The inverse of ``_stack_fn`` for the trees a direction changes:
        each stacked tree to a list of its B per-node trees, B read from a
        leaf's leading axis while tracing."""
        if "unstack" not in self._step_cache:
            def bsbodp_unstack(*stacked):
                B = jax.tree.leaves(stacked)[0].shape[0]
                return tuple([jax.tree.map(lambda x, b=b: x[b], tree) for b in range(B)]
                             for tree in stacked)

            self._step_cache["unstack"] = jax.jit(bsbodp_unstack)
        return self._step_cache["unstack"]

    def _decode_fn(self):
        if "decode" not in self._step_cache:
            img = self.cfg.image_size

            def bsbodp_decode(e):
                # a coalesced group's (B, bs, E) embeddings decode as one
                # flat batch, reshaped inside the program
                x = decode(self.auto, e.reshape(-1, e.shape[-1]), img)
                return x.reshape(e.shape[:-1] + x.shape[1:])

            self._step_cache["decode"] = jax.jit(bsbodp_decode)
        return self._step_cache["decode"]

    def _h2d(self, x: np.ndarray):
        """``jnp.asarray`` of a host array, counted in ``fl_h2d_bytes_total``."""
        self._h2d_bytes.inc(x.nbytes)
        return jnp.asarray(x)

    # ------------------------------------------------------------- protocol

    def _bsbodp_directional(self, v_s: str, v_t: str):
        """One direction: v_t teaches v_s over bridge samples of the shared
        (= intersection of leaf sets = student∩teacher subtree) embeddings."""
        cfg = self.cfg
        pair_node = v_s if self.tree.parent.get(v_s) == v_t else v_t
        eps, labels = self.embeddings[pair_node]
        n = len(labels)
        if n == 0:  # subtree emptied by migration — nothing to distill over
            return
        bs = min(cfg.batch_size, n)
        dec_fn = self._decode_fn()
        teacher = self._teacher_fn(self.model_of[v_t])
        # "leaf" = data-holding end device; an edge whose clients all
        # migrated away is tree-leaf but must not train on client data
        is_leaf = v_s in self.client_data
        student = self._student_fn(self.model_of[v_s], is_leaf)
        link = self.comm.link_kind(
            self.tree, v_s if self.tree.parent.get(v_s) == v_t else v_t
        )

        # one pass over the pair's embeddings per round (CPU-capped), or a
        # fixed number of steps when cfg.distill_steps > 0 — pair_steps is
        # the single source of truth so the simulator prices what runs
        steps = self.pair_steps(v_s, v_t)
        calls = self._jit_calls
        tr = active_tracer()
        with (tr.span("bsbodp.direction", cat="bsbodp", student=v_s, teacher=v_t,
                      B=1, steps=steps) if tr is not None else _NO_SPAN):
            for _ in range(steps):
                with tr.span("bsbodp.inputs", cat="bsbodp") if tr is not None else _NO_SPAN:
                    idx = self._bridge_choice(pair_node, n, bs)
                    e_b = self._h2d(eps[idx])
                    y_b = self._h2d(labels[idx])
                with tr.span("bsbodp.decode", cat="bsbodp") if tr is not None else _NO_SPAN:
                    bridge = dec_fn(e_b)
                calls["decode"].inc()
                with tr.span("bsbodp.teacher", cat="bsbodp") if tr is not None else _NO_SPAN:
                    probs, q, new_skr = teacher(
                        self.params[v_t], self.skr[v_t], bridge, y_b
                    )
                calls["teacher"].inc()
                self.skr[v_t] = new_skr
                tq = q if self.use_skr else probs
                # teacher -> student: (|z| + 1) per sample (Table VII round term)
                self.comm.record(link, bs * (cfg.num_classes + 1), "logits")
                if is_leaf:
                    with tr.span("bsbodp.inputs", cat="bsbodp") if tr is not None else _NO_SPAN:
                        lx, ly = self.client_data[v_s]
                        li = self.rng.choice(len(ly), size=min(bs, len(ly)),
                                             replace=len(ly) < bs)
                        local = (self._h2d(lx[li]), self._h2d(ly[li]))
                else:
                    local = ()
                with tr.span("bsbodp.student", cat="bsbodp") if tr is not None else _NO_SPAN:
                    self.params[v_s], self.opt[v_s], _ = student(
                        self.params[v_s], self.opt[v_s], bridge, y_b, tq, *local
                    )
                calls["student"].inc()

    def _bridge_choice(self, node: str, n: int, bs: int) -> np.ndarray:
        """Bridge-sample index draw over ``node``'s embedding store. With
        default size-1 cohorts this is the historical uniform draw (same
        call, same rng consumption — signatures untouched); under a
        population-scale scenario rows are drawn proportionally to their
        source device's cohort size, so the bridge distribution matches
        the declared population, not the materialized sample."""
        if not self._cohort_sizes:
            return self.rng.choice(n, size=bs, replace=n < bs)
        return self.rng.choice(n, size=bs, replace=n < bs,
                               p=self._bridge_p(node))

    def _bridge_p(self, node: str) -> np.ndarray:
        p = self._bridge_p_cache.get(node)
        if p is None:
            sizes = np.array([float(self.cohort_size(nm))
                              for nm in self._src_names])
            w = sizes[self.embed_src[node]]
            p = w / w.sum()
            self._bridge_p_cache[node] = p
        return p

    def set_cohort_sizes(self, sizes) -> None:
        super().set_cohort_sizes(sizes)
        self._bridge_p_cache.clear()

    def bsbodp_pair(self, v1: str, v2: str):
        """Algorithm 1/2: both directions."""
        self._bsbodp_directional(v1, v2)
        self._bsbodp_directional(v2, v1)

    def _pair_child(self, v1: str, v2: str) -> str:
        """The child side of pair (v1, v2) — owner of the shared embeddings."""
        return v1 if self.tree.parent.get(v1) == v2 else v2

    def _bsbodp_directional_batched(self, pairs: list[tuple[str, str]]):
        """Batched ``_bsbodp_directional``: B same-signature pairs with
        disjoint node sets run each train step as ONE vmapped dispatch over
        stacked (params, opt, skr) pytrees. Per-pair numerics match serial
        execution given the same per-pair rng draws; only the global rng
        consumption order differs (index draws go pair-major within a step
        instead of step-major within a pair).
        """
        cfg = self.cfg
        v_s0, v_t0 = pairs[0]
        children = [self._pair_child(vs, vt) for vs, vt in pairs]
        embs = [self.embeddings[c] for c in children]
        bs = min(cfg.batch_size, len(embs[0][1]))
        steps = self.pair_steps(v_s0, v_t0)
        is_leaf = v_s0 in self.client_data
        dec_fn = self._decode_fn()
        teacher = self._teacher_fn_batched(self.model_of[v_t0])
        student = self._student_fn_batched(self.model_of[v_s0], is_leaf)
        links = [self.comm.link_kind(self.tree, c) for c in children]

        calls = self._jit_calls
        tr = active_tracer()
        with (tr.span("bsbodp.direction", cat="bsbodp", student=[vs for vs, _ in pairs],
                      teacher=[vt for _, vt in pairs], B=len(pairs), steps=steps)
              if tr is not None else _NO_SPAN):
            with tr.span("bsbodp.stack", cat="bsbodp") if tr is not None else _NO_SPAN:
                P_t, S_t, P_s, O_s = self._stack_fn()(
                    [self.params[vt] for _, vt in pairs], [self.skr[vt] for _, vt in pairs],
                    [self.params[vs] for vs, _ in pairs], [self.opt[vs] for vs, _ in pairs])
            calls["stack"].inc()

            for _ in range(steps):
                with tr.span("bsbodp.inputs", cat="bsbodp") if tr is not None else _NO_SPAN:
                    idx = [self._bridge_choice(c, len(e[1]), bs)
                           for c, e in zip(children, embs)]
                    e_b = self._h2d(np.stack([e[0][i] for e, i in zip(embs, idx)]))
                    y_b = self._h2d(np.stack([e[1][i] for e, i in zip(embs, idx)]))
                with tr.span("bsbodp.decode", cat="bsbodp") if tr is not None else _NO_SPAN:
                    bridge = dec_fn(e_b)
                calls["decode"].inc()
                with tr.span("bsbodp.teacher", cat="bsbodp") if tr is not None else _NO_SPAN:
                    probs, q, S_t = teacher(P_t, S_t, bridge, y_b)
                calls["teacher"].inc()
                tq = q if self.use_skr else probs
                for link in links:
                    self.comm.record(link, bs * (cfg.num_classes + 1), "logits")
                if is_leaf:
                    with tr.span("bsbodp.inputs", cat="bsbodp") if tr is not None else _NO_SPAN:
                        lxs, lys = [], []
                        for vs, _ in pairs:
                            lx, ly = self.client_data[vs]
                            li = self.rng.choice(len(ly), size=min(bs, len(ly)),
                                                 replace=len(ly) < bs)
                            lxs.append(lx[li])
                            lys.append(ly[li])
                        local = (self._h2d(np.stack(lxs)), self._h2d(np.stack(lys)))
                else:
                    local = ()
                with tr.span("bsbodp.student", cat="bsbodp") if tr is not None else _NO_SPAN:
                    P_s, O_s, _ = student(P_s, O_s, bridge, y_b, tq, *local)
                calls["student"].inc()

            with tr.span("bsbodp.unstack", cat="bsbodp") if tr is not None else _NO_SPAN:
                skrs, params, opts = self._unstack_fn()(S_t, P_s, O_s)
                for (vs, vt), skr, p, o in zip(pairs, skrs, params, opts):
                    self.skr[vt], self.params[vs], self.opt[vs] = skr, p, o
            calls["unstack"].inc()

    def pair_steps(self, v1: str, v2: str) -> int:
        """Distill steps one direction of pair (v1, v2) runs — the single
        formula both _bsbodp_directional and the simulator's work-item
        pricing use."""
        pair_node = v1 if self.tree.parent.get(v1) == v2 else v2
        n = len(self.embeddings[pair_node][1])
        if n == 0:
            return 0
        bs = min(self.cfg.batch_size, n)
        return self.cfg.distill_steps or min(
            max(1, (n + bs - 1) // bs), self.cfg.max_distill_steps
        )

    # ------------------------------------------------------------ training

    def round_pairs(self) -> list[tuple[str, str]]:
        """The round's (child, parent) pairs in post-order — the unit
        the discrete-event simulator schedules."""
        return [
            (v, self.tree.parent[v])
            for v in self.tree.post_order()
            if v != self.tree.root
        ]

    def work_items(self, round: int, online) -> list[WorkItem]:
        """One bidirectional BSBODP "pair" item per (child, parent) link,
        in post-order; the scheduler's dependency rule (an item waits for
        the items whose ``peer`` is its ``node``) reproduces Algorithm 3's
        subtree-before-parent ordering."""
        return [
            WorkItem("pair", node=v, peer=p, link=self.link_of(v),
                     steps=self.pair_steps(v, p))
            for v, p in self.round_pairs()
        ]

    def execute(self, item: WorkItem) -> None:
        self.bsbodp_pair(item.node, item.peer)

    def batch_signature(self, item: WorkItem):
        """Pairs coalesce when both sides' architectures, leaf-ness, step
        count, and every per-step batch shape agree — exactly the fields
        that make the stacked vmap dispatch shape-compatible and the
        per-item comm bytes identical."""
        if item.kind != "pair" or item.steps <= 0:
            return None
        v, p = item.node, item.peer
        n = len(self.embeddings[self._pair_child(v, p)][1])
        if n == 0:
            return None
        bs = min(self.cfg.batch_size, n)
        sig = ("pair", self.model_of[v], self.model_of[p],
               v in self.client_data, p in self.client_data, item.steps, bs)
        for u in (v, p):
            if u in self.client_data:
                n_local = len(self.client_data[u][1])
                sig += (min(bs, n_local), n_local < bs)
        return sig

    def execute_batch(self, items: list[WorkItem]) -> None:
        """Coalesced BSBODP: run each direction of every pair in the group
        as stacked vmapped steps (child-as-student for all pairs, then
        parent-as-student — pairs share no nodes, so direction interleaving
        across pairs cannot change any pair's own numerics)."""
        if len(items) == 1:
            self.execute(items[0])
            return
        pairs = [(it.node, it.peer) for it in items]
        self._bsbodp_directional_batched([(v, p) for v, p in pairs])
        self._bsbodp_directional_batched([(p, v) for v, p in pairs])

    def on_item_failed(self, item: WorkItem, reason: str) -> None:
        """A BSBODP pair was lost to faults. The pair never executed:
        neither direction distilled and the teacher's SKR queue never saw
        the bridge batch, so the pair is excluded from this round's
        agglomeration weights by construction — SKR's queue-frequency
        weighting (Eq. 8) only ever counts batches that arrived. Record
        the loss so tests and operators can see what went missing."""
        self.failed_pairs.append((item.node, item.peer, reason))

    # -- checkpoint state (docs/robustness.md) ------------------------------

    def state_arrays(self):
        return {
            "params": self.params,
            "opt": self.opt,
            "skr": self.skr,
            "embeddings": self.embeddings,
        }

    def state_meta(self) -> dict:
        meta = super().state_meta()
        meta["rng"] = self.rng.bit_generator.state
        meta["failed_pairs"] = [list(t) for t in self.failed_pairs]
        return meta

    def load_state(self, meta: dict, arrays) -> None:
        super().load_state(meta, arrays)
        self.rng.bit_generator.state = meta["rng"]
        self.failed_pairs = [
            (str(a), str(b), str(c)) for a, b, c in meta["failed_pairs"]
        ]
        self.params = arrays["params"]
        self.opt = arrays["opt"]
        self.skr = arrays["skr"]
        # embedding stores are host-side numpy (indexed by the rng draws)
        self.embeddings = {
            v: (np.asarray(e), np.asarray(y))
            for v, (e, y) in arrays["embeddings"].items()
        }
        # provenance is derivable from (restored topology, client_data):
        # rebuild instead of checkpointing it, in the same child order
        # the stores concatenate — row i of a store and of its provenance
        # always describe the same sample
        self._rebuild_embed_src()

    def _rebuild_embed_src(self) -> None:
        self.embed_src = {}
        for v in self.tree.post_order():
            if v in self.client_data:
                self.embed_src[v] = np.full(
                    len(self.embeddings[v][1]), self._src_pos[v],
                    dtype=np.int32)
            else:
                parts = [self.embed_src[c] for c in self.tree.children[v]]
                self.embed_src[v] = (np.concatenate(parts) if parts
                                     else np.zeros((0,), dtype=np.int32))
        self._bridge_p_cache.clear()

    def _model_params(self, node: str):
        return self.params[node]

    def _do_migrate(self, node: str, new_parent: str):
        """Dynamic migration (§IV-E): legal for any pair under BSBODP+SKR.

        The moved subtree's embeddings are (a) dropped from the stores on
        the old parent→root path, (b) re-registered up the new path — and
        the re-registration upload is charged on the CommMeter per the
        Table VII init term ((|ε|+1) per sample per hop). Only the two
        affected root paths are recomputed, not the whole tree.
        """
        old_parent = self.tree.parent[node]
        self.tree.migrate(node, new_parent)
        # recompute stores bottom-up along the two affected paths only
        # interior = not a data-holding device (an edge emptied by the move
        # is a tree-leaf but its store must still be rebuilt — to empty)
        affected = {
            v for v in self.tree.path_to_root(old_parent)
            + self.tree.path_to_root(new_parent)
            if v not in self.client_data
        }
        for v in sorted(affected, key=self.tree.tier, reverse=True):
            es, ys, ss = [], [], []
            for c in self.tree.children[v]:
                e, y = self.embeddings[c]
                es.append(e)
                ys.append(y)
                ss.append(self.embed_src[c])
            if es:
                self.embeddings[v] = (np.concatenate(es), np.concatenate(ys))
                self.embed_src[v] = np.concatenate(ss)
            else:
                self.embeddings[v] = (
                    np.zeros((0,) + self.embeddings[node][0].shape[1:],
                             dtype=self.embeddings[node][0].dtype),
                    np.zeros((0,), dtype=self.embeddings[node][1].dtype),
                )
                self.embed_src[v] = np.zeros((0,), dtype=np.int32)
        self._bridge_p_cache.clear()
        # charge the subtree's (ε, y) upload on every hop of the new path
        eps, ys_ = self.embeddings[node]
        hop = node
        while hop != self.tree.root:
            link = self.comm.link_kind(self.tree, hop)
            self.comm.record(link, eps.size + ys_.size, "migrate-embed")
            hop = self.tree.parent[hop]

    def cloud_params(self):
        return self.params[self.tree.root]

    def cloud_apply(self):
        return self.apply[self.tree.root]


@register_algorithm("fedeec")
def _fedeec(cfg, tree, client_data, auto):
    return FedEEC(cfg, tree, client_data, auto, use_skr=True, seed=cfg.seed)


@register_algorithm("fedagg")
def _fedagg(cfg, tree, client_data, auto):
    # the INFOCOM'24 predecessor == FedEEC with SKR disabled (Table III)
    return FedEEC(cfg, tree, client_data, auto, use_skr=False, seed=cfg.seed)

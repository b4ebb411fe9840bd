"""Plain reference of the first FedEEC round, independent of the program.

It imports nothing of ``repro`` and takes nothing the program made. From
the seed, the configuration file and the inputs the benchmark hands to both
sides (``make_inputs``, ``pretrain_autoencoder``) it builds, in
straightforward ``jax.numpy``:

* every node's tier model from the seed, built by its family
  (``bench/families/``: CNN, ResNet with GroupNorm) from the layers here,
  and the leaves' embedding stores gathered up the tree;
* BSBODP's directional steps: bridge decode, the teacher's temperature
  softmax with Self-Knowledge Rectification (Algorithm 2, row by row), the
  student's leaf or non-leaf loss, and AdamW;
* the cloud model's logits on the test split.

``replay`` follows the groups the program's scheduler dispatched in the
first round (which pairs, in what order, alone or coalesced) and draws its
bridge and local rows from its own ``numpy`` generator in the program's
order. Every matrix product runs at ``Precision.HIGHEST`` in float32; with
``dtype=bfloat16`` the same code is the low-precision control.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from bench import check
from bench.spec import family

HIGHEST = jax.lax.Precision.HIGHEST

# the student steps of each node's first direction that the check compares
# step by step (``bench.harness.Probe.STEPS``)
PROBE_STEPS = 3

# -- inputs -----------------------------------------------------------------
#
# The benchmark makes the federation's inputs itself and hands them to the
# program: the images (the program's synthetic CIFAR-10 stand-in, with the
# per-sample roll vectorised), the Dirichlet partition, and the frozen
# bridge autoencoder, pre-trained here in float32 at the highest precision
# (the paper takes it pre-trained from an open dataset). The labels and the
# Dirichlet partition's blocks are drawn once (``PARTITION_SEED``), so every
# seed gives the clients the same set of sizes and the program the same
# shapes, every one in the compile cache after a cell's first run; the run's
# seed deals the blocks to the clients and draws the images, the weights and
# every draw of the rounds.

PARTITION_SEED = 0

DATASET_PARAMS = {
    "synth_svhn": dict(noise=0.25, jitter=1, proto_scale=1.0),
    "synth_cifar10": dict(noise=0.55, jitter=2, proto_scale=0.8),
    "synth_cinic10": dict(noise=0.85, jitter=3, proto_scale=0.65),
}


def _prototypes(rng, num_classes, image, scale):
    base = rng.normal(0, scale, (num_classes, 4, 4, 3))
    protos = np.zeros((num_classes, image, image, 3), np.float32)
    xs = np.linspace(0, 3, image)
    x0 = np.clip(xs.astype(int), 0, 2)
    fx = xs - x0
    for c in range(num_classes):
        row = (base[c, x0] * (1 - fx)[:, None, None]
               + base[c, np.minimum(x0 + 1, 3)] * fx[:, None, None])
        protos[c] = (row[:, x0] * (1 - fx)[None, :, None]
                     + row[:, np.minimum(x0 + 1, 3)] * fx[None, :, None])
    return protos


def _sample(rng, protos, labels, noise, jitter):
    n, image = labels.shape[0], protos.shape[1]
    x = protos[labels]
    if jitter:
        shifts = rng.integers(-jitter, jitter + 1, (n, 2))
        hi = (np.arange(image)[None, :] - shifts[:, :1]) % image
        wi = (np.arange(image)[None, :] - shifts[:, 1:]) % image
        x = x[np.arange(n)[:, None, None], hi[:, :, None], wi[:, None, :]]
    x = x + rng.normal(0, noise, x.shape)
    return (1 / (1 + np.exp(-x))).astype(np.float32)


def dirichlet_partition(labels, num_clients, alpha, rng, min_per_client=2):
    parts: list[list[int]] = [[] for _ in range(num_clients)]
    for c in range(int(labels.max()) + 1):
        idx_c = np.flatnonzero(labels == c)
        rng.shuffle(idx_c)
        props = rng.dirichlet([alpha] * num_clients)
        cuts = (np.cumsum(props) * len(idx_c)).astype(int)[:-1]
        for i, part in enumerate(np.split(idx_c, cuts)):
            parts[i].extend(part.tolist())
    for i in range(num_clients):
        while len(parts[i]) < min_per_client:
            donor = int(np.argmax([len(ix) for ix in parts]))
            parts[i].append(parts[donor].pop())
    return [np.asarray(sorted(ix), np.int64) for ix in parts]


@dataclass
class Inputs:
    x_train: np.ndarray
    y_train: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray
    x_open: np.ndarray
    parts: list


def make_inputs(config: dict, seed: int, num_open: int = 512) -> Inputs:
    fl = config["fl_config"]
    p = DATASET_PARAMS[fl["dataset"]]
    nc, image = fl["num_classes"], fl["image_size"]
    lab = np.random.default_rng(PARTITION_SEED)
    y_tr = lab.integers(0, nc, fl["num_clients"] * fl["samples_per_client"]).astype(np.int32)
    y_te = lab.integers(0, nc, fl["test_samples"]).astype(np.int32)
    parts = dirichlet_partition(y_tr, fl["num_clients"], fl["dirichlet_alpha"], lab)
    rng = np.random.default_rng(seed)
    parts = [parts[i] for i in rng.permutation(len(parts))]
    protos = _prototypes(rng, nc, image, p["proto_scale"])
    x_tr = _sample(rng, protos, y_tr, p["noise"], p["jitter"])
    x_te = _sample(rng, protos, y_te, p["noise"], p["jitter"])
    rng_open = np.random.default_rng(seed + 10_000)
    protos_open = _prototypes(rng_open, nc, image, p["proto_scale"])
    y_open = rng_open.integers(0, nc, num_open).astype(np.int32)
    x_open = _sample(rng_open, protos_open, y_open, p["noise"], p["jitter"])
    return Inputs(x_tr, y_tr, x_te, y_te, x_open, parts)


# -- layers -----------------------------------------------------------------


def conv_init(key, kh, kw, cin, cout):
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * (
        2.0 / (kh * kw * cin)) ** 0.5


def linear_init(key, din, dout):
    return {"w": jax.random.normal(key, (din, dout), jnp.float32) * din ** -0.5,
            "b": jnp.zeros((dout,), jnp.float32)}


def _prec(x):
    return HIGHEST if x.dtype == jnp.float32 else None


def conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_prec(x))


def dense(x, p):
    return jnp.dot(x, p["w"], precision=_prec(x)) + p["b"]


def group_norm(x, scale, bias, groups=4, eps=1e-5):
    n, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(n, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    return xg.reshape(n, h, w, c) * scale + bias


def max_pool2(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


# -- tier models ------------------------------------------------------------


def init_cnn(key, num_classes, image, widths):
    ks = jax.random.split(key, 5)
    c1, c2, c3 = widths
    return {"c1": conv_init(ks[0], 3, 3, 3, c1),
            "c2": conv_init(ks[1], 3, 3, c1, c2),
            "c3": conv_init(ks[2], 3, 3, c2, c3),
            "fc": linear_init(ks[3], (image // 8) ** 2 * c3, num_classes)}


def apply_cnn(p, x):
    for name in ("c1", "c2", "c3"):
        x = max_pool2(jax.nn.relu(conv(x, p[name])))
    return dense(x.reshape(x.shape[0], -1), p["fc"])


def _strides(blocks_per_stage):
    return [2 if (b == 0 and s > 0) else 1
            for s, n in enumerate(blocks_per_stage) for b in range(n)]


def init_resnet(key, num_classes, blocks_per_stage, width):
    ks = jax.random.split(key, 2 + sum(blocks_per_stage))
    params = {"stem": conv_init(ks[0], 3, 3, 3, width), "blocks": []}
    cin = width
    for i, stride in enumerate(_strides(blocks_per_stage)):
        stage = sum(1 for s in np.cumsum(blocks_per_stage) if s <= i)
        cout = width * 2 ** stage
        kb = jax.random.split(ks[1 + i], 3)
        blk = {"conv1": conv_init(kb[0], 3, 3, cin, cout),
               "gn1_s": jnp.ones((cout,)), "gn1_b": jnp.zeros((cout,)),
               "conv2": conv_init(kb[1], 3, 3, cout, cout),
               "gn2_s": jnp.ones((cout,)), "gn2_b": jnp.zeros((cout,))}
        if stride != 1 or cin != cout:
            blk["proj"] = conv_init(kb[2], 1, 1, cin, cout)
        params["blocks"].append(blk)
        cin = cout
    params["fc"] = linear_init(ks[1 + len(params["blocks"])], cin, num_classes)
    return params


def apply_resnet(p, x, blocks_per_stage):
    x = jax.nn.relu(conv(x, p["stem"]))
    for blk, stride in zip(p["blocks"], _strides(blocks_per_stage)):
        h = jax.nn.relu(group_norm(conv(x, blk["conv1"], stride),
                                   blk["gn1_s"], blk["gn1_b"]))
        h = group_norm(conv(h, blk["conv2"]), blk["gn2_s"], blk["gn2_b"])
        sc = conv(x, blk["proj"], stride) if "proj" in blk else x
        x = jax.nn.relu(h + sc)
    return dense(x.mean(axis=(1, 2)), p["fc"])


def tier_model(spec: dict, num_classes: int, image: int):
    """(init(key), apply(params, x)) of one entry of a config's ``models``,
    built by its family (``bench/families/<family>.py``) from the layers
    above."""
    return family(spec["family"]).reference(spec, num_classes, image)


# -- bridge autoencoder -----------------------------------------------------


def init_autoencoder(key, image, embed_dim, width=16):
    ks = jax.random.split(key, 6)
    s = image // 4
    return {"enc": {"c1": conv_init(ks[0], 3, 3, 3, width),
                    "c2": conv_init(ks[1], 3, 3, width, width),
                    "fc": linear_init(ks[2], s * s * width, embed_dim)},
            "dec": {"fc": linear_init(ks[3], embed_dim, s * s * width),
                    "c1": conv_init(ks[4], 3, 3, width, width),
                    "c2": conv_init(ks[5], 3, 3, width, 3)}}


def encode(a, x):
    e = a["enc"]
    h = jax.nn.relu(conv(x, e["c1"], 2))
    h = jax.nn.relu(conv(h, e["c2"], 2))
    return jnp.tanh(dense(h.reshape(h.shape[0], -1), e["fc"]))


def _up2(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def decode(a, e, image):
    d = a["dec"]
    s = image // 4
    h = jax.nn.relu(dense(e, d["fc"])).reshape(e.shape[0], s, s, -1)
    h = jax.nn.relu(conv(_up2(h), d["c1"]))
    return jax.nn.sigmoid(conv(_up2(h), d["c2"]))


# -- optimiser, losses, SKR -------------------------------------------------


def adamw_init(params):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(z, params), "v": jax.tree.map(z, params)}


def adamw_update(g, opt, params, lr, b1=0.9, b2=0.95, eps=1e-8):
    """AdamW without weight decay, moments in float32, parameters updated
    in their own dtype."""
    step = opt["step"] + 1
    t = step.astype(jnp.float32)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                     opt["m"], g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(
        g.astype(jnp.float32)), opt["v"], g)
    new = jax.tree.map(
        lambda p, m, v: (p.astype(jnp.float32) - lr * (m / (1 - b1 ** t)) / (
            jnp.sqrt(v / (1 - b2 ** t)) + eps)).astype(p.dtype), params, m, v)
    return new, {"step": step, "m": m, "v": v}


def _log_probs_of(p):
    return jnp.log(jnp.maximum(p, 1e-12))


def non_leaf_loss(zb, yb, tq, beta):
    """CE of the student's softmax on the bridge labels plus beta times
    KL(student || teacher knowledge) (paper Eq. 3)."""
    sp = jax.nn.softmax(zb.astype(jnp.float32), axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(_log_probs_of(sp), yb[:, None], 1))
    tq = tq.astype(jnp.float32)
    kl = jnp.mean(jnp.sum(jnp.maximum(sp, 1e-12) * (
        _log_probs_of(sp) - _log_probs_of(tq)), axis=-1))
    return ce + beta * kl


def leaf_loss(zl, yl, zb, yb, tq, beta, gamma):
    """Local cross-entropy plus gamma times the non-leaf loss (paper Eq. 5)."""
    zl = zl.astype(jnp.float32)
    ce = jnp.mean(jax.nn.logsumexp(zl, axis=-1)
                  - jnp.take_along_axis(zl, yl[:, None], 1)[:, 0])
    return ce + gamma * non_leaf_loss(zb, yb, tq, beta)


def skr_init(num_classes, queue_len):
    return {"q": jnp.zeros((num_classes, queue_len), jnp.float32),
            "count": jnp.zeros((num_classes,), jnp.int32),
            "head": jnp.zeros((num_classes,), jnp.int32)}


def skr_rows(state, probs, labels):
    """Algorithm 2 row by row: rectify a misattributed row towards its
    class queue's mean, and push the confidence of a correct one."""
    qlen = state["q"].shape[1]

    def row(st, pc):
        p, c = pc
        correct = jnp.argmax(p) == c
        cnt = st["count"][c]
        qbar = jnp.sum(jnp.where(jnp.arange(qlen) < cnt, st["q"][c], 0.0)) / (
            jnp.maximum(cnt, 1))
        rect = (~correct) & (cnt > 0)
        new_c = jnp.where(rect, qbar, p[c])
        out = jnp.where(rect, p * (1.0 - new_c) / jnp.maximum(1.0 - p[c], 1e-12),
                        p).at[c].set(new_c)
        hd = st["head"][c]
        st = {"q": st["q"].at[c, hd].set(jnp.where(correct, p[c], st["q"][c, hd])),
              "count": st["count"].at[c].set(
                  jnp.where(correct, jnp.minimum(cnt + 1, qlen), cnt)),
              "head": st["head"].at[c].set(jnp.where(correct, (hd + 1) % qlen, hd))}
        return st, out

    return jax.lax.scan(row, state, (probs.astype(jnp.float32), labels))


# -- the reference federation -----------------------------------------------


def pretrain_autoencoder(x_open, image, embed_dim, seed, dtype=jnp.float32,
                         steps=1200, lr=2e-3, batch=64):
    """The bridge autoencoder: MSE reconstruction of the open split, AdamW,
    the draws and initial weights of the program's own pre-training."""
    auto = cast(init_autoencoder(jax.random.PRNGKey(seed + 7), image, embed_dim), dtype)
    n = x_open.shape[0]
    rng, idx = jax.random.PRNGKey(1), []
    for _ in range(steps):
        rng, k = jax.random.split(rng)
        idx.append(jax.random.randint(k, (min(batch, n),), 0, n))
    xb = jnp.asarray(x_open, dtype)[jnp.stack(idx)]

    def loss(a, x):
        d = decode(a, encode(a, x), image).astype(jnp.float32)
        return jnp.mean((d - x.astype(jnp.float32)) ** 2)

    def step(carry, x):
        a, opt = carry
        return adamw_update(jax.grad(loss)(a, x), opt, a, lr), None

    with jax.default_matmul_precision("highest"):
        (auto, _), _ = jax.jit(lambda a, xs: jax.lax.scan(
            step, (a, adamw_init(a)), xs))(auto, xb)
    return auto


def cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def _leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).ravel())
                      for x in jax.tree.leaves(tree)])


@dataclass
class Reference:
    """Nodes, stores and steps of one configuration at one seed, on the
    benchmark's inputs. ``auto`` is the float32 autoencoder the program is
    given, cast to ``dtype``; without it the reference pre-trains its own.

    ``fault="half_batch"`` plants a fault in the student step (the loss
    over the first half of each batch only), to read what such a fault
    does to the compared numbers."""

    config: dict
    seed: int
    inputs: Inputs
    auto: object = None
    dtype: object = jnp.float32
    fault: str = ""
    params: dict = field(default_factory=dict)
    opt: dict = field(default_factory=dict)
    skr: dict = field(default_factory=dict)

    def __post_init__(self):
        fl = self.fl = self.config["fl_config"]
        self.image = fl["image_size"]
        inp = self.inputs
        e, c = fl["num_edges"], fl["num_clients"]
        self.root = "cloud"
        self.children = {"cloud": [f"edge{i}" for i in range(e)]}
        for i in range(e):
            self.children[f"edge{i}"] = [f"client{k}" for k in range(c) if k % e == i]
        self.parent = {ch: v for v, chs in self.children.items() for ch in chs}
        self.nodes = ["cloud"] + [f"edge{i}" for i in range(e)] + [
            f"client{k}" for k in range(c)]
        self.data = {f"client{k}": (inp.x_train[inp.parts[k]], inp.y_train[inp.parts[k]])
                     for k in range(c)}
        self.model_of = {}
        for v in self.nodes:
            tier = ("end_model" if v in self.data else
                    "cloud_model" if v == "cloud" else "edge_model")
            self.model_of[v] = fl[tier]
        self.models = {name: tier_model(spec, fl["num_classes"], self.image)
                       for name, spec in self.config["models"].items()}
        if self.auto is None:
            self.auto = pretrain_autoencoder(inp.x_open, self.image, fl["embed_dim"],
                                             self.seed, self.dtype)
        self.auto = cast(self.auto, self.dtype)
        self.stores = self._stores()
        key = jax.random.PRNGKey(self.seed)
        inits = {name: jax.jit(init) for name, (init, _) in self.models.items()}
        for i, v in enumerate(self.nodes):
            p = cast(inits[self.model_of[v]](jax.random.fold_in(key, i)), self.dtype)
            self.params[v] = p
            self.opt[v] = adamw_init(p)
            self.skr[v] = skr_init(fl["num_classes"], fl["queue_len"])
        self.rng = np.random.default_rng(self.seed)
        self._fns: dict = {}

    def _stores(self):
        enc = jax.jit(encode)
        stores = {}

        def fill(v):
            if v in self.data:
                x, y = self.data[v]
                with jax.default_matmul_precision("highest"):
                    eps = np.concatenate([np.asarray(enc(self.auto, jnp.asarray(
                        x[i:i + 2048], self.dtype)), np.float32)
                        for i in range(0, len(y), 2048)])
                stores[v] = (eps, y)
                return
            for ch in self.children[v]:
                fill(ch)
            stores[v] = (np.concatenate([stores[ch][0] for ch in self.children[v]]),
                         np.concatenate([stores[ch][1] for ch in self.children[v]]))

        fill(self.root)
        return stores

    def _direction_fn(self, teacher, student, leaf):
        """All steps of one direction as one scan: decode the bridge rows,
        the teacher's softmax at temperature T with SKR, the student's loss,
        gradient and AdamW step. Returns the teacher's SKR state, the
        student's parameters and optimizer state, the per-leaf norms of the
        student's first gradient, and of its first ``PROBE_STEPS`` steps the
        losses, Adam's ``v`` after the first and the parameters after the
        last. The student's parameters and optimizer state are donated, so
        that the scan's carry takes their place: a direction holds one copy
        of the student's state besides ``v1`` and ``p3``, not two."""
        key = (teacher, student, leaf)
        if key in self._fns:
            return self._fns[key]
        apply_t, apply_s = self.models[teacher][1], self.models[student][1]
        fl, image = self.fl, self.image
        cut = (lambda a: a[: a.shape[0] // 2]) if self.fault == "half_batch" else (lambda a: a)

        def loss(p, bx, by, tq, lx, ly):
            bx, by, tq = cut(bx), cut(by), cut(tq)
            zb = apply_s(p, bx)
            if not leaf:
                return non_leaf_loss(zb, by, tq, fl["beta"])
            return leaf_loss(apply_s(p, cut(lx)), cut(ly), zb, by, tq, fl["beta"], fl["gamma"])

        def fn(auto, pt, skr, ps, opt, e, y, lx, ly):
            last = min(PROBE_STEPS, e.shape[0]) - 1

            def step(carry, xs):
                skr, ps, opt, v1, p3 = carry
                i, e, y, lx, ly = xs
                bridge = decode(auto, e, image)
                z = apply_t(pt, bridge).astype(jnp.float32)
                skr, q = skr_rows(skr, jax.nn.softmax(z / fl["temperature"], axis=-1), y)
                lv, g = jax.value_and_grad(loss)(ps, bridge, y, q, lx, ly)
                ps, opt = adamw_update(g, opt, ps, fl["lr"])
                v1 = jax.tree.map(lambda a, b: jnp.where(i == 0, a, b), opt["v"], v1)
                p3 = jax.tree.map(lambda a, b: jnp.where(i == last, a, b), ps, p3)
                return (skr, ps, opt, v1, p3), (_leaf_norms(g), lv.astype(jnp.float32))

            (skr, ps, opt, v1, p3), (gn, lv) = jax.lax.scan(
                step, (skr, ps, opt, opt["v"], ps), (jnp.arange(e.shape[0]), e, y, lx, ly))
            return skr, ps, opt, gn[0], lv[:last + 1], v1, p3

        self._fns[key] = jax.jit(fn, donate_argnums=(3, 4))
        return self._fns[key]

    def _child(self, a, b):
        return a if self.parent.get(a) == b else b

    def _group(self, pairs, steps, init, grad_norms, probe):
        """Both directions of a dispatch group: each pair's node learns from
        its peer, then each peer from its node. Rows are drawn step by step,
        all pairs' bridge rows first and then, for data-holding students,
        all pairs' local rows: the program's order for a coalesced group,
        and for a group of one that of a serial pair. A node's first
        direction as student is reduced to its norms at once (``check``)."""
        bsz = self.fl["batch_size"]
        for dirs in (list(pairs), [(b, a) for a, b in pairs]):
            rows = [([], [], [], []) for _ in dirs]
            for _ in range(steps):
                sizes = []
                for (vs, vt), r in zip(dirs, rows):
                    eps, lab = self.stores[self._child(vs, vt)]
                    bs = min(bsz, len(lab))
                    idx = self.rng.choice(len(lab), size=bs, replace=len(lab) < bs)
                    r[0].append(eps[idx])
                    r[1].append(lab[idx])
                    sizes.append(bs)
                for (vs, _), r, bs in zip(dirs, rows, sizes):
                    if vs in self.data:
                        lx, ly = self.data[vs]
                        li = self.rng.choice(len(ly), size=min(bs, len(ly)),
                                             replace=len(ly) < bs)
                        r[2].append(lx[li])
                        r[3].append(ly[li])
                    else:
                        r[2].append(np.zeros((1, self.image, self.image, 3), np.float32))
                        r[3].append(np.zeros((1,), np.int32))
            for (vs, vt), r in zip(dirs, rows):
                e, y, lx, ly = (np.stack(a) for a in r)
                fn = self._direction_fn(self.model_of[vt], self.model_of[vs], vs in self.data)
                with jax.default_matmul_precision("highest"):
                    self.skr[vt], self.params[vs], self.opt[vs], gn, lv, v1, p3 = fn(
                        self.auto, self.params[vt], self.skr[vt], self.params[vs],
                        self.opt[vs], jnp.asarray(e, self.dtype), jnp.asarray(y),
                        jnp.asarray(lx, self.dtype), jnp.asarray(ly))
                if vs not in probe:
                    grad_norms[vs] = np.asarray(gn, np.float64)
                    probe[vs] = (np.asarray(lv, np.float64).tolist(), check.v_norms(v1),
                                 check.change_norms(p3, init[vs]))

    def replay(self, groups, init):
        """Run the recorded dispatch groups of the first round. Returns, per
        node and reduced against ``init`` (host copies of the parameters
        before the round), the change norms after the first group it took
        part in, the per-leaf norms of its first gradient, and the first
        steps of its first direction as student (``_direction_fn``)."""
        first, grad_norms, probe = {}, {}, {}
        for pairs, steps in groups:
            self._group(pairs, steps, init, grad_norms, probe)
            for a, b in pairs:
                for v in (a, b):
                    if v not in first:
                        first[v] = check.change_norms(self.params[v], init[v])
        return first, grad_norms, probe

    def logits(self, params, batch=256):
        apply = self.models[self.model_of[self.root]][1]
        fn = self._fns.setdefault("eval", jax.jit(apply))
        with jax.default_matmul_precision("highest"):
            return np.concatenate([
                np.asarray(fn(params, jnp.asarray(self.inputs.x_test[i:i + batch], self.dtype)),
                           np.float32) for i in range(0, len(self.inputs.y_test), batch)])

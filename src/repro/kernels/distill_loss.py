"""Pallas TPU kernel: fused distillation loss over the vocabulary axis.

Computes, per row i (one token's logits z and teacher log-probs t):

    L_i = lw * CE(softmax(z_i), y_i) + beta * KL(softmax(z_i) || exp(t_i))

WITHOUT materializing softmax(z) in HBM — a flash-softmax style online
reduction over vocab tiles. This is BSBODP's Eq. (3)/(32) hot loop at LM
scale (vocab up to 262k: the (tokens, vocab) probability tensor would be
GBs per layer step). beta=0 degenerates to plain fused softmax-xent (used
for the LM training loss).

The native layout is batched: stacked inputs ``(B, N, V)`` where B indexes
independent distillation pairs coalesced into one dispatch (the simulator
stacks same-shape BSBODP pairs that become ready at the same sim time).
The batch axis is an extra *parallel* grid dimension — per-row scratch is
unchanged because the vocab axis stays the innermost sequential one. The
2-D ``distill_loss`` entry point is a thin B=1 wrapper.

Forward accumulators per row (running across vocab tiles j):
    m  = running max of z
    l  = sum exp(z - m)
    sz = sum exp(z - m) * z
    st = sum exp(z - m) * t
    zy = logit of the gold label
Final: logZ = m + log l;  CE = logZ - zy;
       KL = sz/l - logZ - st/l.

Backward (custom VJP, second kernel, elementwise over tiles; one dispatch
for the whole batch):
    dz = g * [ lw*(softmax(z) - onehot_y)
               + beta * softmax(z) * ((z - logZ - t) - KL) ]

Block shapes: lane dim (vocab) tiles of `block_v` (multiple of 128),
sublane (rows) tiles of `block_n` (multiple of 8), batch blocks of 1.
Per-row vectors (labels, losses, upstream grads) travel as (B, N, 1)
columns so their blocks keep the TPU's minor-pair tiling. The running
stats live in (block_n, 1) VMEM scratch and persist across the sequential
vocab grid axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_compat import CompilerParams, resolve_interpret

NEG = -1e30


def _fwd_kernel(
    z_ref, t_ref, y_ref, loss_ref, stats_ref,
    m_s, l_s, sz_s, st_s, zy_s,
    *, block_v: int, n_v: int, beta: float, label_weight: float,
):
    j = pl.program_id(2)  # vocab tile (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        sz_s[...] = jnp.zeros_like(sz_s)
        st_s[...] = jnp.zeros_like(st_s)
        zy_s[...] = jnp.zeros_like(zy_s)

    z = z_ref[0].astype(jnp.float32)  # (bn, bv)
    t = t_ref[0].astype(jnp.float32)
    y = y_ref[0]  # (bn, 1)

    m_old = m_s[...]
    m_new = jnp.maximum(m_old, z.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    e = jnp.exp(z - m_new)
    l_s[...] = l_s[...] * alpha + e.sum(-1, keepdims=True)
    sz_s[...] = sz_s[...] * alpha + (e * z).sum(-1, keepdims=True)
    st_s[...] = st_s[...] * alpha + (e * t).sum(-1, keepdims=True)
    m_s[...] = m_new

    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    hit = (col == y).astype(jnp.float32)
    zy_s[...] = zy_s[...] + (hit * z).sum(-1, keepdims=True)

    @pl.when(j == n_v - 1)
    def _fin():
        m, l = m_s[...], l_s[...]
        logz = m + jnp.log(jnp.maximum(l, 1e-38))
        ce = logz - zy_s[...]
        kl = sz_s[...] / l - logz - st_s[...] / l
        loss_ref[0] = label_weight * ce + beta * kl
        stats_ref[0] = jnp.concatenate([logz, kl], axis=-1)


def _bwd_kernel(
    z_ref, t_ref, y_ref, stats_ref, g_ref, dz_ref,
    *, block_v: int, beta: float, label_weight: float,
):
    j = pl.program_id(2)
    z = z_ref[0].astype(jnp.float32)
    t = t_ref[0].astype(jnp.float32)
    y = y_ref[0]  # (bn, 1)
    stats = stats_ref[0]
    logz = stats[:, 0:1]
    kl = stats[:, 1:2]
    g = g_ref[0]
    sp = jnp.exp(z - logz)
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    onehot = (col == y).astype(jnp.float32)
    dz = label_weight * (sp - onehot) + beta * sp * ((z - logz - t) - kl)
    dz_ref[0] = (g * dz).astype(dz_ref.dtype)


def _pad(z, t, y, block_n, block_v):
    B, N, V = z.shape
    n_pad = (-N) % block_n
    v_pad = (-V) % block_v
    z = jnp.pad(z, ((0, 0), (0, n_pad), (0, v_pad)), constant_values=NEG)
    t = jnp.pad(t, ((0, 0), (0, n_pad), (0, v_pad)))
    # per-row vectors travel as (B, N, 1) columns: a (1, block_n, 1) block
    # keeps the minor pair at (multiple of 8, full dim) for the TPU tiling
    y = jnp.pad(y, ((0, 0), (0, n_pad)))[..., None]
    return z, t, y, N, V


@functools.partial(
    jax.jit, static_argnames=("beta", "label_weight", "block_n", "block_v", "interpret")
)
def _distill_loss_fwd(
    logits, teacher_logprobs, labels, *, beta, label_weight,
    block_n=8, block_v=512, interpret=None,
):
    interpret = resolve_interpret(interpret)
    z, t, y, N, V = _pad(logits, teacher_logprobs, labels, block_n, block_v)
    B, Np, Vp = z.shape
    n_v = Vp // block_v
    grid = (B, Np // block_n, n_v)
    loss, stats = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_v=block_v, n_v=n_v, beta=beta,
            label_weight=label_weight,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, block_v), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, block_n, block_v), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, block_n, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_n, 2), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Np, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Np, 2), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, 1), jnp.float32) for _ in range(5)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(z, t, y)
    return loss[:, :N, 0], stats[:, :N]


@functools.partial(
    jax.jit, static_argnames=("beta", "label_weight", "block_n", "block_v", "interpret")
)
def _distill_loss_bwd(
    logits, teacher_logprobs, labels, stats, g, *, beta, label_weight,
    block_n=8, block_v=512, interpret=None,
):
    interpret = resolve_interpret(interpret)
    z, t, y, N, V = _pad(logits, teacher_logprobs, labels, block_n, block_v)
    B, Np, Vp = z.shape
    stats_p = jnp.pad(stats, ((0, 0), (0, Np - N), (0, 0)))
    g_p = jnp.pad(g, ((0, 0), (0, Np - N)))[..., None]
    grid = (B, Np // block_n, Vp // block_v)
    dz = pl.pallas_call(
        functools.partial(
            _bwd_kernel, block_v=block_v, beta=beta, label_weight=label_weight
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, block_v), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, block_n, block_v), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, block_n, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_n, 2), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_n, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n, block_v), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, Np, Vp), logits.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(z, t, y, stats_p, g_p)
    return dz[:, :N, :V]


# ---------------------------------------------------------------------------
# public custom-VJP ops: batched (B, N, V) native, 2-D thin wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def distill_loss_batched(logits, teacher_logprobs, labels, beta=1.0,
                         label_weight=1.0, interpret=None):
    """Per-row fused CE + beta*KL over stacked pairs.

    logits/teacher_logprobs: (B, N, V); labels: (B, N). Returns (B, N)
    losses from ONE kernel dispatch (forward and backward each). B indexes
    independent coalesced pairs. Differentiable w.r.t. ``logits`` only
    (the teacher is a constant under online distillation)."""
    loss, _ = _distill_loss_fwd(
        logits, teacher_logprobs, labels, beta=beta, label_weight=label_weight,
        interpret=interpret,
    )
    return loss


def _vjp_fwd(logits, teacher_logprobs, labels, beta, label_weight, interpret):
    loss, stats = _distill_loss_fwd(
        logits, teacher_logprobs, labels, beta=beta, label_weight=label_weight,
        interpret=interpret,
    )
    return loss, (logits, teacher_logprobs, labels, stats)


def _vjp_bwd(beta, label_weight, interpret, res, g):
    logits, t, labels, stats = res
    dz = _distill_loss_bwd(
        logits, t, labels, stats, g, beta=beta, label_weight=label_weight,
        interpret=interpret,
    )
    return dz, None, None


distill_loss_batched.defvjp(_vjp_fwd, _vjp_bwd)


def distill_loss(logits, teacher_logprobs, labels, beta=1.0, label_weight=1.0,
                 interpret=None):
    """2-D (N, V) entry point: B=1 slice of the batched kernel."""
    return distill_loss_batched(
        logits[None], teacher_logprobs[None], labels[None],
        beta, label_weight, interpret,
    )[0]

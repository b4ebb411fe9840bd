"""Pallas TPU kernel: batched SKR rectification (paper Eq. 31).

Given temperature-softmax probabilities P (N, C), per-row label-class
probability p_c, the misattribution flag, and the queue-mean q̄ of the label
class, produce the rectified knowledge Q:

    Q[i, j] = q̄_i                           if rectify_i and j == label_i
            = P[i, j]·(1-q̄_i)/(1-p_c_i)     if rectify_i and j != label_i
            = P[i, j]                        otherwise

The native layout is stacked pairs ``(B, N, C)`` with per-pair ``qbar`` /
``counts`` of shape ``(B, C)`` — B independent teachers rectifying their
batches in ONE dispatch (the pair-coalescing path). The batch axis is an
extra parallel grid dimension of block 1; the 2-D ``skr_rectify`` entry
point is a thin B=1 wrapper.

The kernel is tiled (1 x block_n x block_c) over the (B, N, C) probability
tensor — at LM scale C is the vocabulary (up to 262k), so the whole matrix
never sits in VMEM; row scalars are broadcast per tile. Lane dim (C) tiles
are multiples of 128; sublane (N) tiles multiples of 8 (fp32 VREG tiling).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pallas_compat import CompilerParams, resolve_interpret


def _kernel(p_ref, pc_ref, do_ref, qb_ref, label_ref, out_ref, *, block_c: int):
    j = pl.program_id(2)
    p = p_ref[0]  # (bn, bc)
    pc = pc_ref[0]  # (bn, 1) row columns
    do = do_ref[0]
    qb = qb_ref[0]
    label = label_ref[0]
    scale = (1.0 - qb) / jnp.maximum(1.0 - pc, 1e-12)
    rect = p * scale
    col = j * block_c + jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    rect = jnp.where(col == label, qb, rect)
    out_ref[0] = jnp.where(do > 0, rect, p)


@functools.partial(jax.jit, static_argnames=("block_n", "block_c", "interpret"))
def skr_rectify_batched(
    probs,
    labels,
    qbar,
    counts,
    *,
    block_n: int = 8,
    block_c: int = 128,
    interpret: bool | None = None,
):
    """probs (B, N, C) fp32; labels (B, N) int32; qbar/counts (B, C).

    Returns rectified (B, N, C) from a single kernel dispatch. Row
    statistics (p_c, misattribution flag) are jnp reductions; the O(B·N·C)
    rescale/select is the Pallas kernel.
    """
    interpret = resolve_interpret(interpret)
    B, N, C = probs.shape
    p_c = jnp.take_along_axis(probs, labels[..., None], axis=-1)[..., 0]
    mis = jnp.argmax(probs, axis=-1) != labels
    cnt = jnp.take_along_axis(counts, labels, axis=-1)  # (B, N)
    do = (mis & (cnt > 0)).astype(jnp.int32)
    qb = jnp.take_along_axis(qbar, labels, axis=-1)

    # pad to tile multiples (batch blocks are 1 — no batch padding); row
    # scalars travel as (B, N, 1) columns so their (block_n, 1) blocks keep
    # the minor pair at (multiple of 8, full dim) for the TPU tiling
    n_pad = (-N) % block_n
    c_pad = (-C) % block_c
    p_in = jnp.pad(probs, ((0, 0), (0, n_pad), (0, c_pad)))
    col = lambda x, fill=0: jnp.pad(
        x, ((0, 0), (0, n_pad)), constant_values=fill)[..., None]
    pc_in, do_in, qb_in = col(p_c), col(do), col(qb)
    lb_in = col(labels, -1)
    _, Np, Cp = p_in.shape

    grid = (B, Np // block_n, Cp // block_c)
    out = pl.pallas_call(
        functools.partial(_kernel, block_c=block_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, block_c), lambda b, i, j: (b, i, j)),
        ] + [pl.BlockSpec((1, block_n, 1), lambda b, i, j: (b, i, 0))] * 4,
        out_specs=pl.BlockSpec((1, block_n, block_c), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, Np, Cp), probs.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
    )(p_in, pc_in, do_in, qb_in, lb_in)
    return out[:, :N, :C]


def skr_rectify(
    probs,
    labels,
    qbar,
    counts,
    *,
    block_n: int = 8,
    block_c: int = 128,
    interpret: bool | None = None,
):
    """2-D (N, C) entry point: B=1 slice of the batched kernel."""
    return skr_rectify_batched(
        probs[None], labels[None], qbar[None], counts[None],
        block_n=block_n, block_c=block_c, interpret=interpret,
    )[0]

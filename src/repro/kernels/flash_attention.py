"""Pallas TPU kernel: GQA flash attention (causal / sliding-window).

Grid: (batch, q_heads, q_blocks, kv_blocks); the kv axis is the innermost,
sequential ("arbitrary") dimension — running max / normalizer / output
accumulator persist in VMEM scratch across kv steps (flash-attention v2
style). GQA is expressed in the BlockSpec index map: the kv-head block index
is q_head // group_size, so no KV replication materializes in VMEM.

Causality and the sliding window are enforced by absolute-position masks
computed from the grid coordinates; fully-masked kv blocks short-circuit
(pl.when) so the causal upper triangle costs no MXU work — this is the
advantage over the rectangle-shaped jnp fallback in models/attention.py
(see EXPERIMENTS.md §Perf).

Block shapes default to (128 q x 128 kv) tiles with head_dim lanes —
MXU-aligned for head_dim in {64, 128, 256}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_compat import CompilerParams, resolve_interpret

NEG = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s,
    *, block_q: int, block_k: int, n_k: int, causal: bool, window: int,
    q_offset: int, scale: float, k_len: int,
):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q_start = i * block_q + q_offset
    k_start = j * block_k

    # block-level reachability: skip kv blocks that are entirely masked
    reachable = True
    if causal:
        reachable = k_start <= q_start + block_q - 1
    if window:
        reachable = jnp.logical_and(
            reachable, k_start + block_k - 1 > q_start - window
        )

    @pl.when(reachable)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, H)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, H)
        v = v_ref[0, 0].astype(jnp.float32)
        s = q @ k.T  # (bq, bk)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < k_len  # padded kv columns never contribute
        if causal:
            mask &= rows >= cols
        if window:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG)
        m_old = m_s[...]  # (bq, 1) row columns
        m_new = jnp.maximum(m_old, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_s[...] = l_s[...] * alpha + p.sum(-1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + p @ v
        m_s[...] = m_new

    @pl.when(j == n_k - 1)
    def _fin():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
):
    """q: (B, Sq, N, H); k/v: (B, Sk, K, H); N % K == 0. Returns (B, Sq, N, H).

    Sq/Sk are padded to block multiples internally; padded kv positions are
    masked explicitly (cols >= Sk never contribute). ``interpret=None``
    auto-detects: compiled on TPU, interpreter elsewhere.
    """
    interpret = resolve_interpret(interpret)
    B, Sq, N, H = q.shape
    K = k.shape[2]
    G = N // K
    Sk = k.shape[1]
    bq = min(block_q, max(8, Sq))
    bk = min(block_k, max(8, Sk))
    # heads move out of the minor pair: the kernel sees (B, heads, S, H),
    # so every block's minor pair is (sequence tile, full head_dim)
    q_pad = (-Sq) % bq
    k_pad = (-Sk) % bk
    qp = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kp = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vp = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    n_q = qp.shape[2] // bq
    n_k = kp.shape[2] // bk

    grid = (B, N, n_q, n_k)
    out = pl.pallas_call(
        functools.partial(
            _kernel, block_q=bq, block_k=bk, n_k=n_k, causal=causal,
            window=window, q_offset=q_offset, scale=H**-0.5, k_len=Sk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, H), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, H), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, H), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, H), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, H), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qp, kp, vp)
    return out.transpose(0, 2, 1, 3)[:, :Sq]

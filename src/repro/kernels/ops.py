"""Public wrappers around the Pallas kernels.

Every wrapper runs the Pallas kernel itself: there is no fall-through to
the jnp oracle. The kernels default to ``interpret=None``, which
``repro.kernels.pallas_compat.resolve_interpret`` turns into compiled on a
TPU and the Pallas interpreter (the same kernel body) on the CPU. The
oracles in ref.py are the numerics ground truth for tests and benchmarks.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref as R
from repro.kernels.distill_loss import (
    distill_loss as _distill_loss,
    distill_loss_batched as _distill_loss_batched,
)
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv6
from repro.kernels.skr_rectify import (
    skr_rectify as _skr,
    skr_rectify_batched as _skr_batched,
)


# --- public ops --------------------------------------------------------------


def fused_softmax_xent(logits, labels):
    """Per-row CE without materializing softmax (beta=0 distill_loss)."""
    zeros = jnp.zeros_like(logits)
    return _distill_loss(logits, zeros, labels, 0.0, 1.0, None)


def fused_distill_loss(logits, teacher_logprobs, labels, *, beta: float,
                       label_weight: float = 1.0):
    """Fused Eq.(3)/(32): CE + beta*KL per row (custom VJP, vocab-tiled)."""
    return _distill_loss(logits, teacher_logprobs, labels, beta, label_weight, None)


def fused_distill_loss_batched(logits, teacher_logprobs, labels, *,
                               beta: float, label_weight: float = 1.0):
    """Batched Eq.(3)/(32) over stacked pairs (B, N, V) — one kernel
    dispatch forward and backward for the whole coalesced group."""
    return _distill_loss_batched(logits, teacher_logprobs, labels, beta, label_weight,
                                 None)


def skr_rectify(probs, labels, qbar, counts):
    return _skr(probs, labels, qbar, counts)


def skr_rectify_batched(probs, labels, qbar, counts):
    """Stacked (B, N, C) rectification with per-pair (B, C) queue stats."""
    return _skr_batched(probs, labels, qbar, counts)


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    block_q=128, block_k=128):
    return _flash(q, k, v, causal=causal, window=window, q_offset=q_offset,
                  block_q=block_q, block_k=block_k)


def rwkv6_scan(r, k, v, w, u, s0, *, chunk: int = 64):
    return _rwkv6(r, k, v, w, u, s0, chunk=chunk)


# Re-export oracles for tests/benchmarks
ref = R

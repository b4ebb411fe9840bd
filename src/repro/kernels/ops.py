"""Traced public wrappers around the Pallas kernels.

Every wrapper runs the Pallas kernel itself: there is no fall-through to
the jnp oracle. The kernels default to ``interpret=None``, which
``repro.kernels.pallas_compat.resolve_interpret`` turns into compiled on a
TPU and the Pallas interpreter (the same kernel body) on the CPU. The
oracles in ref.py are the numerics ground truth for tests and benchmarks.
"""
from __future__ import annotations

import time

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


def _traced(kernel: str, fn, *args):
    """Run a kernel entry point under the active tracer (no-op — a single
    global read — when tracing is off). Records a host span plus a
    ``kernel_dispatch_seconds{kernel=...}`` latency histogram in the global
    metrics registry. Under ``jax.jit`` the wrapper observes trace-time
    once per compilation (dispatches inside compiled code are invisible
    to host tracing by construction)."""
    from repro.obs.trace import active_tracer

    tr = active_tracer()
    if tr is None:
        return fn(*args)
    from repro.obs.metrics import global_registry

    t0 = time.perf_counter()
    with tr.span(f"kernel.{kernel}", cat="kernel"):
        out = fn(*args)
    global_registry().histogram(
        "kernel_dispatch_seconds", kernel=kernel
    ).observe(time.perf_counter() - t0)
    return out


# --- public ops --------------------------------------------------------------


def fused_softmax_xent(logits, labels):
    """Per-row CE without materializing softmax (beta=0 distill_loss)."""
    zeros = jnp.zeros_like(logits)
    return _traced(
        "softmax_xent", _distill_loss, logits, zeros, labels, 0.0, 1.0, None
    )


def fused_distill_loss(logits, teacher_logprobs, labels, *, beta: float,
                       label_weight: float = 1.0):
    """Fused Eq.(3)/(32): CE + beta*KL per row (custom VJP, vocab-tiled)."""
    return _traced(
        "distill_loss", _distill_loss,
        logits, teacher_logprobs, labels, beta, label_weight, None,
    )


def fused_distill_loss_batched(logits, teacher_logprobs, labels, *,
                               beta: float, label_weight: float = 1.0):
    """Batched Eq.(3)/(32) over stacked pairs (B, N, V) — one kernel
    dispatch forward and backward for the whole coalesced group."""
    return _traced(
        "distill_loss_batched", _distill_loss_batched,
        logits, teacher_logprobs, labels, beta, label_weight, None,
    )


def skr_rectify(probs, labels, qbar, counts):
    return _traced("skr_rectify", _skr, probs, labels, qbar, counts)


def skr_rectify_batched(probs, labels, qbar, counts):
    """Stacked (B, N, C) rectification with per-pair (B, C) queue stats."""
    return _traced(
        "skr_rectify_batched", _skr_batched, probs, labels, qbar, counts
    )


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    block_q=128, block_k=128):
    return _traced(
        "flash_attention",
        lambda q, k, v: _flash(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            block_q=block_q, block_k=block_k,
        ),
        q, k, v,
    )


def rwkv6_scan(r, k, v, w, u, s0, *, chunk: int = 64):
    return _traced(
        "rwkv6_scan",
        lambda *a: _rwkv6(*a, chunk=chunk),
        r, k, v, w, u, s0,
    )


# Re-export oracles for tests/benchmarks
ref = R

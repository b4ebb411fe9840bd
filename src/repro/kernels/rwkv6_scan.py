"""Pallas TPU kernel: RWKV6 ("Finch") time-mix recurrence.

    out_t = r_t · (S_{t-1} + diag(u) k_t v_tᵀ)
    S_t   = diag(w_t) S_{t-1} + k_t v_tᵀ          (w_t: data-dependent decay)

Grid: (batch, heads, time_chunks); the time axis is sequential
("arbitrary") with the (head_dim x head_dim) state carried in VMEM scratch
across chunks — the HBM traffic is exactly one read of (r,k,v,w) and one
write of y per token, with the state resident on-chip (the TPU-native
adaptation of RWKV's CUDA kernel, which keeps state in registers/smem).
Inside a chunk the recurrence is stepped with a fori_loop over VMEM tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_compat import CompilerParams, resolve_interpret


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref, s_s,
            *, chunk: int, n_t: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        s_s[...] = s0_ref[0, 0].astype(jnp.float32)

    # (1, hd) x (1, hd) -> aᵀb on the MXU, at full f32 precision: the
    # elementwise outer product it replaces was exact
    outer = lambda a, b: jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (1, hd)
    u_rows = outer(u, jnp.ones_like(u))  # diag(u) as a row scale: u_i in row i

    def step(i, s):
        row = pl.ds(i, 1)
        r_i = r_ref[0, 0, row, :].astype(jnp.float32)  # (1, hd)
        k_i = k_ref[0, 0, row, :].astype(jnp.float32)
        v_i = v_ref[0, 0, row, :].astype(jnp.float32)
        w_i = w_ref[0, 0, row, :].astype(jnp.float32)
        kv = outer(k_i, v_i)  # (hd, hd)
        out = jnp.dot(r_i, s + u_rows * kv, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)  # (1, hd)
        y_ref[0, 0, row, :] = out.astype(y_ref.dtype)
        return outer(w_i, jnp.ones_like(w_i)) * s + kv

    s = jax.lax.fori_loop(0, chunk, step, s_s[...])
    s_s[...] = s

    @pl.when(t == n_t - 1)
    def _fin():
        sT_ref[0, 0] = s.astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, w, u, s0, *, chunk: int = 64,
               interpret: bool | None = None):
    """r/k/v/w: (B, T, H, hd); u: (H, hd); s0: (B, H, hd, hd).

    Returns (y (B, T, H, hd) fp32, sT (B, H, hd, hd) fp32). T is padded to a
    chunk multiple with k=0 and w=1, so padded steps leave the state
    unchanged and their outputs are dropped. ``interpret=None`` compiles
    the kernel unless the backend is the CPU.
    """
    B, T, H, hd = r.shape
    t_pad = (-T) % chunk
    if t_pad:
        zpad = lambda x: jnp.pad(x, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        r, k, v = zpad(r), zpad(k), zpad(v)
        w = jnp.pad(w, ((0, 0), (0, t_pad), (0, 0), (0, 0)), constant_values=1.0)
    Tp = r.shape[1]
    n_t = Tp // chunk
    grid = (B, H, n_t)
    # heads move out of the minor pair: the kernel sees (B, H, T, hd) and a
    # (H, 1, hd) bonus, so every block's minor pair is (chunk, full hd)
    r, k, v, w = (x.transpose(0, 2, 1, 3) for x in (r, k, v, w))
    u = u[:, None, :]
    y, sT = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_t=n_t),
        grid=grid,
        in_specs=[
        ] + [pl.BlockSpec((1, 1, chunk, hd), lambda b, h, t: (b, h, t, 0))] * 4 + [
            pl.BlockSpec((1, 1, hd), lambda b, h, t: (h, 0, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=resolve_interpret(interpret),
    )(r, k, v, w, u, s0)
    return y.transpose(0, 2, 1, 3)[:, :T], sT

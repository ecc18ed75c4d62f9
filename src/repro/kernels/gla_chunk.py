"""Chunked gated-linear-attention Pallas kernel (RWKV6 / Mamba2 hot loop).

Implements one head's chunk sweep: per grid step (bh, chunk c) the kernel
computes the intra-chunk attention (three MXU matmuls) and carries the
recurrent state S [Dk, Dv] in a VMEM scratch across the sequential chunk
dimension.  This is the TPU-native adaptation of the GPU recurrent kernels:
sequential work is restructured into MXU-sized matmuls with the state as a
VMEM-resident accumulator (the paper's narrow-random-access-span idea
applied to the recurrence).

Decay convention matches repro.models.linear_attention.chunked_gla:
    S_t = diag(exp(w_t)) S_{t-1} + k_t v_t^T
    y_t = q_t S_t                          (include_current=True, Mamba2)
    y_t = q_t S_{t-1} + (q_t.(u*k_t)) v_t  (include_current=False, RWKV6)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.csr_spmv import default_interpret


def _kernel(q_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_out_ref, s_scratch,
            *, chunk: int, include_current: bool, has_bonus: bool,
            n_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_scratch[...] = jnp.zeros_like(s_scratch)

    q = q_ref[0].astype(jnp.float32)          # [L, Dk]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)          # [L, Dv]
    w = w_ref[0].astype(jnp.float32)          # [L, Dk] log decay
    s_in = s_scratch[...]                     # [Dk, Dv]

    lc = jnp.cumsum(w, axis=0)                # inclusive cumulative log decay
    lq = lc if include_current else lc - w
    l_last = lc[-1:, :]                       # [1, Dk]

    # inter-chunk: y += (q * exp(lq)) @ S_in
    y = (q * jnp.exp(lq)) @ s_in              # [L, Dv]

    # intra-chunk: A[t,s] = sum_d q_td k_sd exp(lq_t,d - lc_s,d), masked
    row = jax.lax.iota(jnp.int32, chunk)
    tri = (row[:, None] >= row[None, :]) if include_current else \
        (row[:, None] > row[None, :])
    diff = lq[:, None, :] - lc[None, :, :]    # [L, L, Dk]
    diff = jnp.where(tri[:, :, None], diff, -jnp.inf)
    a = jnp.einsum("td,sd,tsd->ts", q, k, jnp.exp(diff))
    if has_bonus:
        u = u_ref[0].astype(jnp.float32)      # [Dk] (row vector block)
        diag = jnp.sum(q * u[None, :] * k, axis=1)          # [L]
        a = a + jnp.where(row[:, None] == row[None, :],
                          diag[:, None], 0.0)
    y = y + a @ v
    y_ref[...] = y[None].astype(y_ref.dtype)

    # state update: S = exp(l_last)^T * S_in + (k * exp(l_last - lc))^T v
    s_new = jnp.exp(l_last).T * s_in + (k * jnp.exp(l_last - lc)).T @ v
    s_scratch[...] = s_new

    @pl.when(c == n_chunks - 1)
    def _final():
        s_out_ref[...] = s_new[None].astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "include_current",
                                             "interpret"))
def gla_chunked(q, k, v, w, u=None, *, chunk: int = 64,
                include_current: bool = True, interpret: bool | None = None):
    """q/k/w: [BH, T, Dk]; v: [BH, T, Dv]; u: [BH, Dk] bonus or None.
    Returns (y [BH, T, Dv], final_state [BH, Dk, Dv])."""
    if interpret is None:
        interpret = default_interpret()
    bh, t, dk = q.shape
    dv = v.shape[-1]
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk
    has_bonus = u is not None
    if u is None:
        u = jnp.zeros((bh, dk), jnp.float32)

    kern = functools.partial(_kernel, chunk=chunk,
                             include_current=include_current,
                             has_bonus=has_bonus, n_chunks=n_chunks)
    y, s = pl.pallas_call(
        kern,
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dk), lambda b, c: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(q, k, v, w, u)
    return y, s

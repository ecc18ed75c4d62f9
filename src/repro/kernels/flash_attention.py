"""Flash attention Pallas kernel (TPU target, interpret-validated).

Online-softmax over KV blocks with causal, sliding-window, and softcap
support — the LM stack's attention hot loop.  Grid: (batch*heads, q blocks);
each step holds one q block + running (m, l, acc) in registers/VMEM and
streams KV blocks HBM->VMEM.

BlockSpec layout: q/o blocks [1, bq, d]; k/v are resident per (b*h) slice
[1, S, d] (fits VMEM for the shapes we target per-device after sharding:
e.g. 32k x 128 x 2B = 8 MiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.csr_spmv import default_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bkv: int, seq_kv: int,
            causal: bool, window: int, softcap: float, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # [bq, d]
    d = q.shape[-1]

    m = jnp.full((bq,), NEG_INF, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    q_pos = qi * bq + jax.lax.iota(jnp.int32, bq)

    def body(kv_i, carry):
        m, l, acc = carry
        k = pl.load(k_ref, (pl.dslice(0, 1), pl.dslice(kv_i * bkv, bkv),
                            slice(None)))[0].astype(jnp.float32)
        v = pl.load(v_ref, (pl.dslice(0, 1), pl.dslice(kv_i * bkv, bkv),
                            slice(None)))[0].astype(jnp.float32)
        s = q @ k.T                                    # [bq, bkv]
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        kv_pos = kv_i * bkv + jax.lax.iota(jnp.int32, bkv)
        mask = jnp.ones((bq, bkv), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    n_kv = seq_kv // bkv
    if causal:
        # only blocks at or before the diagonal contribute
        hi = jnp.minimum(n_kv, (qi + 1) * bq // bkv + 1)
    else:
        hi = n_kv
    m, l, acc = jax.lax.fori_loop(0, hi, body, (m, l, acc))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None])[None].astype(
        o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "bq", "bkv",
                              "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, bq: int = 128, bkv: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q: [BH, Sq, D]; k/v: [BH, Skv, D].  Returns [BH, Sq, D]."""
    if interpret is None:
        interpret = default_interpret()
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (sq, bq, skv, bkv)
    scale = d ** -0.5

    kern = functools.partial(_kernel, bq=bq, bkv=bkv, seq_kv=skv,
                             causal=causal, window=window, softcap=softcap,
                             scale=scale)
    return pl.pallas_call(
        kern,
        grid=(bh, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, skv, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, skv, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(q, k, v)

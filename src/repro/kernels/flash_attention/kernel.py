"""Pallas TPU kernel: causal flash attention (GQA-aware).

TPU adaptation of FlashAttention: grid = (batch, kv_head, q_block,
kv_block); the q block (bq, G*D) sits in VMEM, k/v stream through the
innermost (sequential) kv-grid dimension in (bk, D) blocks; the
online-softmax running max/denominator/accumulator live in VMEM scratch
across that dimension.  Causal kv blocks beyond the q block's diagonal are
skipped via pl.when — the MXU sees only lower-triangle block pairs, and the
O(S^2) scores never touch HBM (this is exactly the traffic that dominates
the baseline jnp prefill roofline; see EXPERIMENTS.md §Perf).

Block sizes: bq=bk=128 align with the 128x128 MXU; head_dim 64/80/128 all
lower cleanly.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  bq: int, bk: int, G: int, D: int, scale: float):
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # process only kv blocks that intersect the causal lower triangle of
    # this q block (supports bq != bk)
    @pl.when(jk * bk < (iq + 1) * bq)
    def _step():
        q = q_ref[...].reshape(bq * G, D).astype(jnp.float32)   # (bq*G, D)
        k = k_ref[...].reshape(bk, D).astype(jnp.float32)
        v = v_ref[...].reshape(bk, D).astype(jnp.float32)
        s = jnp.dot(q, k.T) * scale                             # (bq*G, bk)
        # causal mask in global positions (exact for any bq/bk ratio)
        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, G, bk), 0)
        kpos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, G, bk), 2)
        tri = (kpos <= qpos).reshape(bq * G, bk)
        s = jnp.where(tri, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(p, v)
        m_scr[...] = m_new

    @pl.when(jk == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).reshape(1, 1, bq, G * D) \
            .astype(o_ref.dtype)


def flash_attention(q, k, v, *, bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B,S,H,D); k,v: (B,S,KH,D), causal. S % bq == 0 == S % bk."""
    B, S, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = 1.0 / math.sqrt(D)
    # layout: (B, KH, S, G*D) for q; (B, KH, S, D) for k/v
    qr = jnp.moveaxis(q.reshape(B, S, KH, G, D), 1, 2).reshape(B, KH, S, G * D)
    kr = jnp.moveaxis(k, 1, 2)                                  # (B, KH, S, D)
    vr = jnp.moveaxis(v, 1, 2)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, G=G, D=D,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B, KH, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, G * D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, G * D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KH, S, G * D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qr, kr, vr)
    out = out.reshape(B, KH, S, G, D)
    return jnp.moveaxis(out, 2, 1).reshape(B, S, H, D)

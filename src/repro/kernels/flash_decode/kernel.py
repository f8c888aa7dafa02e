"""Pallas TPU kernel: flash-decode — single-token attention over a long KV
cache (the §Perf cell C "next lever").

TPU adaptation: grid = (batch, kv_head, kv_block); the (G, D) query tile
sits in VMEM, cache blocks (bk, D) stream through the sequential innermost
grid axis in their STORAGE dtype (bf16 — no f32 cache copy ever exists,
matching the mixed-precision jnp path), online-softmax state in VMEM
scratch.  Blocks entirely beyond `pos` are skipped with pl.when — the
kernel reads exactly ceil((pos+1)/bk) cache blocks, which is the
irreducible decode traffic.  The masked tail inside the boundary block is
handled with a positional mask.
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


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, bk: int, G: int, D: int, scale: float):
    jk = pl.program_id(2)
    nk = pl.num_programs(2)
    pos = pos_ref[0]

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip cache blocks entirely beyond the current position
    @pl.when(jk * bk <= pos)
    def _step():
        q = q_ref[...].reshape(G, D)
        k = k_ref[...].reshape(bk, D)
        v = v_ref[...].reshape(bk, D)
        s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T) * scale
        kpos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p.astype(v.dtype), v.astype(jnp.float32))
        m_scr[...] = m_new

    @pl.when(jk == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).reshape(1, 1, G, D).astype(o_ref.dtype)


def flash_decode(q, k_cache, v_cache, pos, *, bk: int = 512,
                 interpret: Optional[bool] = None):
    """q: (B,H,D); caches: (B,S,KH,D) in storage dtype; pos: () int32."""
    B, H, D = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)
    scale = 1.0 / math.sqrt(D)
    qr = q.reshape(B, KH, G * D)
    kr = jnp.moveaxis(k_cache, 1, 2)          # (B, KH, S, D)
    vr = jnp.moveaxis(v_cache, 1, 2)
    pos_arr = jnp.asarray([pos], jnp.int32)
    kernel = functools.partial(_decode_kernel, bk=bk, G=G, D=D, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B, KH, S // bk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, j: (0,)),
            pl.BlockSpec((1, 1, G * D), lambda b, h, j: (b, h, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(pos_arr, qr, kr, vr)
    return out.reshape(B, H, D)

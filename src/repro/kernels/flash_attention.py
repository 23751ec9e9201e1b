"""Flash attention for TPU (Pallas): online-softmax, GQA, causal + sliding
window.

TPU adaptation (vs. the CUDA algorithm): the kernel is expressed as a 4-D
grid (batch, q_head, q_block, kv_block) whose LAST dimension is sequential
("arbitrary" semantics) — the online-softmax running max / denominator /
accumulator live in VMEM scratch that persists across kv-block steps, and
the MXU sees (block_q x d) @ (d x block_k) tiles with d and block sizes in
multiples of 128. GQA is handled in the BlockSpec index maps (q head h reads
kv head h // G) — no head replication in memory.

Fully-masked kv blocks are skipped with ``pl.when`` (saves MXU issue slots;
the DMA still runs — hiding it needs block-sparse index maps, noted in
EXPERIMENTS.md SPerf as a further step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, block_q, block_k, num_kv_blocks):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    # block reachability: any (t, s) with t >= s (causal) and t-s < window?
    reachable = True
    if causal:
        reachable = (q_start + block_q - 1) >= k_start
    if window is not None:
        reachable = jnp.logical_and(
            reachable, (k_start + block_k - 1) > (q_start - window))

    @pl.when(reachable)
    def _compute():
        q = q_ref[...].astype(jnp.float32)               # (bq, d)
        k = k_ref[...].astype(jnp.float32)               # (bk, d)
        v = v_ref[...].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                     # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * alpha
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    block_q=128, block_k=128, interpret=None):
    """q: (B,T,H,d); k/v: (B,S,K,d), H % K == 0. Returns (B,T,H,d)."""
    B, T, H, d = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    assert T % block_q == 0 and S % block_k == 0, (T, S, block_q, block_k)
    nq, nk = T // block_q, S // block_k
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk)

    # heads go ahead of time and the batch and head dims are squeezed out
    # of every block, so the kernel sees (block, d) tiles whose last two
    # dims meet the (8, 128) tiling rule
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    grid = (B, H, nq, nk)
    sq = pl.squeezed
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sq, sq, block_q, d),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((sq, sq, block_k, d),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((sq, sq, block_k, d),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((sq, sq, block_q, d),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max
            pltpu.VMEM((block_q, 1), jnp.float32),     # running denominator
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)

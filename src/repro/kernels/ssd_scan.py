"""Mamba-2 SSD chunk scan for TPU (Pallas).

TPU adaptation of the SSD algorithm (arXiv:2405.21060 §6): grid is
(batch, head-block, chunk) with the CHUNK dimension sequential — the
inter-chunk recurrent state (heads_blk, N, P) lives in f32 VMEM scratch and
is carried across chunk steps, while the intra-chunk quadratic term runs on
the MXU as (Q x N)(N x Q) and (Q x Q)(Q x P) tiles. This replaces the GPU
formulation's separate state-passing kernel + atomics with grid-sequential
scratch carry, which is the idiomatic TPU pattern.

Mosaic lowers neither ``cumsum`` nor matmuls with more than two dims, so
the wrapper takes the within-chunk prefix sums of ``dt * a`` in XLA (the
oracle's own arithmetic), lays heads ahead of time, and passes ``dt`` and
those sums both as rows and as columns and ``B`` both time-major and
transposed. The kernel then works one head at a time on 2-D tiles and
transposes none of them.

Shapes match models/ssm.ssd_chunked (the oracle): x (B,L,H,P), dt (B,L,H),
A_log (H,), B/C (B,L,N) -> y (B,L,H,P), final_state (B,H,P,N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _last_as_col(row, rows):
    """The last entry of a (1, Q) row as a (rows, 1) column. Mosaic cannot
    broadcast a (1, 1) value along sublanes and lanes at once, so the
    entry is picked by a masked lane sum over the sublane-broadcast row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, row.shape[1]), 1)
    return jnp.sum(jnp.where(lane == row.shape[1] - 1, row, 0.0), axis=1,
                   keepdims=True)


def _kernel(x_ref, dtr_ref, dtc_ref, cumr_ref, cumc_ref, b_ref, bt_ref,
            c_ref, y_ref, state_ref, h_scr, *, nchunks, chunk, block_h):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    Bm = b_ref[...].astype(jnp.float32)        # (Q, N)
    Bt = bt_ref[...].astype(jnp.float32)       # (N, Q)
    Cm = c_ref[...].astype(jnp.float32)        # (Q, N)
    N = Bt.shape[0]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    CB = _dot(Cm, Bm, ((1,), (1,)))            # (Q, Q)

    for j in range(block_h):
        x = x_ref[j].astype(jnp.float32)       # (Q, P)
        dt_row = dtr_ref[j]                    # (1, Q)
        dt_col = dtc_ref[j]                    # (Q, 1)
        cum_row = cumr_ref[j]                  # (1, Q)
        cum_col = cumc_ref[j]                  # (Q, 1)

        # intra-chunk: y[t] = sum_{s<=t} CB[t,s] exp(cum[t]-cum[s]) dt[s] x[s]
        # (mask before exp: t<s diffs are positive and can overflow)
        L = jnp.exp(jnp.where(tri, cum_col - cum_row, -1e30))  # (Q, Q)
        y = _dot(CB * L * dt_row, x)                       # (Q, P)

        # inter-chunk: y[t] += exp(cum[t]) C[t] . h_prev
        h_prev = h_scr[j]                                  # (N, P)
        y = y + jnp.exp(cum_col) * _dot(Cm, h_prev)

        # state update: h = h_prev exp(cum[-1]) + sum_s w[s] B[s] x[s]
        w_col = jnp.exp(_last_as_col(cum_row, chunk) - cum_col) * dt_col
        decay = jnp.exp(_last_as_col(cum_row, N))          # (N, 1)
        h_new = h_prev * decay + _dot(Bt, x * w_col)
        h_scr[j] = h_new
        y_ref[j] = y.astype(y_ref.dtype)

        @pl.when(ic == nchunks - 1)
        def _final():
            state_ref[j] = h_new.astype(state_ref.dtype)


def ssd_scan(x, dt, A_log, B_mat, C_mat, chunk, *, block_h=None,
             interpret=None):
    """Pallas SSD. Returns (y (B,L,H,P), final_state (B,H,P,N)).
    ``block_h`` heads share one grid step (default 1)."""
    Bb, L, H, P = x.shape
    N = B_mat.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0
    nc = L // Q
    bh = block_h or 1
    assert H % bh == 0
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    sq = pl.squeezed

    f32 = jnp.float32
    a = -jnp.exp(A_log.astype(f32))
    dth = dt.astype(f32).transpose(0, 2, 1)            # (B, H, L)
    cum = jnp.cumsum((dth * a[None, :, None]).reshape(Bb, H, nc, Q),
                     axis=-1).reshape(Bb, H, L)        # within-chunk sums
    xh = x.transpose(0, 2, 1, 3)                       # (B, H, L, P)
    B_t = B_mat.transpose(0, 2, 1)                     # (B, N, L)
    row = pl.BlockSpec((sq, bh, 1, Q), lambda b, h, c: (b, h, 0, c))
    col = pl.BlockSpec((sq, bh, Q, 1), lambda b, h, c: (b, h, c, 0))

    kernel = functools.partial(_kernel, nchunks=nc, chunk=Q, block_h=bh)
    y, state = pl.pallas_call(
        kernel,
        grid=(Bb, H // bh, nc),
        in_specs=[
            pl.BlockSpec((sq, bh, Q, P), lambda b, h, c: (b, h, c, 0)),
            row, col, row, col,
            pl.BlockSpec((sq, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((sq, N, Q), lambda b, h, c: (b, 0, c)),
            pl.BlockSpec((sq, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((sq, bh, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((sq, bh, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, L, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bh, N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh, dth[:, :, None, :], dth[:, :, :, None], cum[:, :, None, :],
      cum[:, :, :, None], B_mat, B_t, C_mat)
    return y.transpose(0, 2, 1, 3), state.transpose(0, 1, 3, 2)

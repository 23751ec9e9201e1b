"""RG-LRU linear recurrence for TPU (Pallas).

h_t = a_t * h_{t-1} + b_t, per channel (diagonal). The recurrence is
memory-bound, so the TPU kernel streams (time-chunk x channel-block) tiles
through VMEM: grid (batch, channel-block, time-chunk) with the time
dimension sequential, carrying h in f32 scratch. Within a chunk the scan is
a fori_loop over rows — each step is a (block_w,)-wide VPU vector op, which
is the idiomatic TPU shape for diagonal recurrences (cf. the RecurrentGemma
TPU kernel); the log-depth associative scan used by the jnp oracle would
waste bandwidth re-materializing O(log T) intermediates.

Inputs log_a, b: (B, T, W) float32. Returns (y (B,T,W), h_last (B,W)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(loga_ref, b_ref, y_ref, hlast_ref, h_scr, *, nchunks, chunk):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def body(t, h):
        # one (1, bw) row per step, read and written through the refs:
        # Mosaic lowers no dynamic index into a loaded value
        a = jnp.exp(loga_ref[pl.ds(t, 1), :].astype(jnp.float32))
        h = a * h + b_ref[pl.ds(t, 1), :].astype(jnp.float32)
        y_ref[pl.ds(t, 1), :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, body, h_scr[...])
    h_scr[...] = h

    @pl.when(ic == nchunks - 1)
    def _final():
        hlast_ref[...] = h.astype(hlast_ref.dtype)


def rglru_scan(log_a, b, *, chunk=256, block_w=None, interpret=None):
    B, T, W = log_a.shape
    Q = min(chunk, T)
    assert T % Q == 0
    nc = T // Q
    bw = block_w or W
    assert W % bw == 0
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kernel = functools.partial(_kernel, nchunks=nc, chunk=Q)
    sq = pl.squeezed
    y, hlast = pl.pallas_call(
        kernel,
        grid=(B, W // bw, nc),
        in_specs=[
            pl.BlockSpec((sq, Q, bw), lambda bb, w, c: (bb, c, w)),
            pl.BlockSpec((sq, Q, bw), lambda bb, w, c: (bb, c, w)),
        ],
        out_specs=[
            pl.BlockSpec((sq, Q, bw), lambda bb, w, c: (bb, c, w)),
            pl.BlockSpec((sq, 1, bw), lambda bb, w, c: (bb, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, W), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(log_a, b)
    return y, hlast[:, 0, :]

"""Cold-start scan for the batched workflow simulator (Pallas).

The one genuinely sequential piece of the simulator's request-axis
recurrence: whether request ``k`` finds its (step, platform) instance cold
depends on request ``k-1``'s end time, which depends on whether *that*
request was cold. Given the node's per-request end times under both
hypotheses (``warm_end[k] <= cold_end[k]``, the cold draw is nonnegative),
the mask obeys

    last[-1] = -inf                      (fresh experiment)
    mask[k]  = (t0[k] - last[k-1]) > keep_warm
    last[k]  = cold_end[k] if mask[k] else warm_end[k]

Two device implementations of the same recurrence:

``cold_scan``           the TPU kernel. The recurrence is memory-bound and
                        diagonal across the batch axis (independent rows),
                        so the kernel streams (time-chunk x batch-block)
                        tiles through VMEM — grid (batch-block, time-chunk)
                        with time sequential, carrying the previous mask
                        row in int32 scratch; within a chunk the scan is a
                        fori_loop over rows, each step a (1, block_b) VPU
                        select (the rglru scan shape). Both gaps are
                        compared before the kernel, in the inputs' dtype,
                        so the kernel selects bits and never rounds a time.
                        Time is the sublane dimension so the per-step store
                        is a full lane row. Rows are lanes: under ``vmap``
                        (the simulator's seeds x placements, one row each)
                        a batching rule folds the batch into the row axis,
                        so a whole sweep is one call with its rows side by
                        side in 128-lane blocks, not a grid of one-row
                        tiles. On non-TPU backends it runs in interpret
                        mode.

``cold_scan_parallel``  the same mask with the sequential dependence
                        factored out, for XLA on any backend: mask[k] is a
                        1-bit affine function of mask[k-1] —
                        ``s = a XOR (b AND s_prev)`` with (a, b) determined
                        by which of the two gaps clears ``keep_warm`` — and
                        affine maps over GF(2) compose associatively, so the
                        whole mask is a log-depth parallel (Hillis–Steele)
                        scan with no per-request loop. The composition runs
                        under ``lax.while_loop`` keyed on ``any(b)``: the
                        "flip" bit ``b`` marks requests whose status depends
                        on the previous one, its true-runs halve every
                        doubling step, and in the paper's regimes
                        (interarrival far from ``keep_warm`` on either side)
                        it is all-false from the start — zero iterations,
                        mirroring the numpy scan's candidate short-circuit.
                        This is what the jax simulator backend uses where
                        Pallas isn't lowered.

The pure-jnp oracle both are validated against is ``ref.cold_scan_ref``
(tests/test_kernels.py, interpret mode on CPU), which mirrors the numpy
``WorkflowSimulator._cold_scan`` semantics exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(code_ref, mask_ref, prev_scr, *, chunk):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        prev_scr[...] = jnp.zeros_like(prev_scr)

    def body(t, prev):
        # rows are read and written through the refs (one (1, block_b)
        # sublane row per step): Mosaic lowers no dynamic index into a
        # loaded value
        code = code_ref[pl.ds(t, 1), :]
        m = jnp.where(prev > 0, code >> 1, code & 1)
        mask_ref[pl.ds(t, 1), :] = m
        return m

    prev_scr[...] = jax.lax.fori_loop(0, chunk, body, prev_scr[...])


def _gap_bits(t0, warm_end, cold_end, keep_warm):
    """Per-request bools along the last axis: ``warm_bit[k]`` says request
    k finds its instance cold if request k-1 ended warm, ``cold_bit[k]``
    if it ended cold. Request 0 measures against ``last = -inf``: cold
    under either hypothesis unless ``keep_warm`` is inf. Both gaps are
    compared in the inputs' own dtype, so every consumer of the bits is
    exact in any dtype."""
    t0, warm_end, cold_end = jnp.broadcast_arrays(t0, warm_end, cold_end)
    warm_gap = t0[..., 1:] - warm_end[..., :-1] > keep_warm
    cold_gap = t0[..., 1:] - cold_end[..., :-1] > keep_warm
    first = jnp.broadcast_to(keep_warm < jnp.inf, t0[..., :1].shape)
    return (
        jnp.concatenate([first, warm_gap], axis=-1),
        jnp.concatenate([first, cold_gap], axis=-1),
    )


def kernel_lanes(rows, block_b=128):
    """Lane width of the kernel call over ``rows`` rows: whole blocks of
    ``block_b``."""
    return -(-rows // block_b) * block_b


def _scan_rows(code, *, chunk, block_b, interpret):
    """The kernel over a (B, T) int32 code plane: rows padded to lane
    blocks of ``block_b``, time to chunks of ``chunk``. Returns (B, T)
    bool."""
    B, T = code.shape
    # pad to tile multiples; the scan runs forward so padded time steps
    # never influence real outputs, and padded rows are sliced away
    Tp = -(-T // chunk) * chunk
    Bp = kernel_lanes(B, block_b)
    codep = jnp.zeros((Tp, Bp), jnp.int32).at[:T, :B].set(code.T)

    kernel = functools.partial(_kernel, chunk=chunk)
    mask = pl.pallas_call(
        kernel,
        grid=(Bp // block_b, Tp // chunk),
        in_specs=[pl.BlockSpec((chunk, block_b), lambda b, c: (c, b))],
        out_specs=pl.BlockSpec((chunk, block_b), lambda b, c: (c, b)),
        out_shape=jax.ShapeDtypeStruct((Tp, Bp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, block_b), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        # the kernel's instruction in the device trace, under any transform
        name="cold_scan",
    )(codep)
    return mask[:T, :B].T > 0


@functools.cache
def _folded_scan(chunk, block_b, interpret):
    """``_scan_rows`` with a batching rule that folds each vmapped axis into
    the row (lane) axis: under ``vmap`` an (axis_size, B, T) plane becomes
    one (axis_size * B, T) call instead of a grid of one tile per batch
    member. Rows are independent (the recurrence runs along time only), so
    the mask is the same; nested vmaps fold again, one axis per level."""

    @jax.custom_batching.custom_vmap
    def scan(code):
        return _scan_rows(code, chunk=chunk, block_b=block_b, interpret=interpret)

    @scan.def_vmap
    def _fold(axis_size, in_batched, code):
        del in_batched  # the one input: a rule only runs on a batched input
        _, B, T = code.shape
        return scan(code.reshape(axis_size * B, T)).reshape(axis_size, B, T), True

    return scan


def cold_scan(
    t0, warm_end, cold_end, keep_warm, *, chunk=256, block_b=128, interpret=None
):
    """Boolean cold mask, request-major. ``t0``: (T,) arrival times shared
    by every row; ``warm_end``/``cold_end``: (B, T) per-row end times under
    the warm / cold hypothesis; ``keep_warm``: scalar idle horizon (may be
    +inf: never cold). Returns (B, T) bool. The gaps are compared in the
    inputs' dtype outside the kernel (``_gap_bits``), packed as the code
    ``warm_bit | cold_bit << 1``; the kernel only selects bits, so the
    mask is exact for f32 and f64 inputs alike. Under ``vmap`` (per-row
    ``keep_warm`` included) every batch member's rows join the one kernel
    call's lanes (``_folded_scan``)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    warm_bit, cold_bit = _gap_bits(t0, warm_end, cold_end, keep_warm)
    code = warm_bit.astype(jnp.int32) | (cold_bit.astype(jnp.int32) << 1)
    return _folded_scan(chunk, block_b, interpret)(code)


def cold_scan_parallel(t0, warm_end, cold_end, keep_warm):
    """The same mask as ``cold_scan`` as a log-depth parallel scan along
    the last axis (no Pallas, any backend, any dtype). ``t0``,
    ``warm_end`` and ``cold_end`` broadcast against each other; the scan
    runs over the trailing (request) axis; ``keep_warm`` is scalar.

    Derivation: request k can be cold regardless of history iff even the
    LATE previous end (cold) left a gap past keep_warm; it is warm
    regardless iff even the EARLY one (warm) did not. In between, the mask
    flips the previous one. All three cases are ``s = a ^ (b & s_prev)``:
    definitely-cold (1, 0), definitely-warm (0, 0), flip (1, 1) — affine
    over GF(2), hence associative under composition. The Hillis–Steele
    doubling runs under ``while_loop`` gated on ``any(b)``: once no flip
    bit survives, ``a`` IS the mask and the loop exits — zero iterations
    in regimes where every request is decidable from its own gap (the
    batched analogue of the numpy scan walking only its candidate list).
    Under ``vmap`` the gate becomes "any lane still flipping", so batch
    members that converge early ride along for free."""
    warm_bit, cold_bit = _gap_bits(t0, warm_end, cold_end, keep_warm)
    a = warm_bit
    b = warm_bit & ~cold_bit
    n = a.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, (n,), 0)

    def keep_going(state):
        _, b, d = state
        return jnp.any(b) & (d < n)

    def double(state):
        a, b, d = state
        # compose each element with the affine map d steps back (elements
        # with no predecessor that far compose with identity (0, 0))
        behind = idx >= d
        a_s = jnp.where(behind, jnp.roll(a, d, axis=-1), False)
        b_s = jnp.where(behind, jnp.roll(b, d, axis=-1), False)
        return a ^ (b & a_s), b & b_s, d * 2

    a, _, _ = jax.lax.while_loop(keep_going, double, (a, b, jnp.int32(1)))
    return a

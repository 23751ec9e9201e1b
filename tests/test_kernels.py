"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes/dtypes.

Kernels run in interpret mode on CPU (the TPU lowering is exercised by the
same pallas_call with interpret=False on real hardware).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.cold_scan import _gap_bits, cold_scan_parallel

KEY = jax.random.PRNGKey(0)


def tol(dtype):
    return (
        dict(rtol=2e-2, atol=2e-2)
        if dtype == jnp.bfloat16
        else dict(rtol=2e-5, atol=2e-5)
    )


# -- flash attention ----------------------------------------------------------
@pytest.mark.parametrize(
    "B,T,S,H,K,d",
    [
        (1, 128, 128, 4, 4, 64),  # MHA
        (2, 256, 256, 8, 2, 64),  # GQA 4:1
        (1, 128, 256, 4, 1, 128),  # MQA, T != S
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 96), (False, None)])
def test_flash_attention_sweep(B, T, S, H, K, d, dtype, causal, window):
    ks = jax.random.split(jax.random.fold_in(KEY, T * H + d), 3)
    q = jax.random.normal(ks[0], (B, T, H, d), dtype)
    k = jax.random.normal(ks[1], (B, S, K, d), dtype)
    v = jax.random.normal(ks[2], (B, S, K, d), dtype)
    out = ops.flash_attention(
        q, k, v, causal=causal, window=window, block_q=64, block_k=64
    )
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


@given(st.sampled_from([32, 64, 128]), st.sampled_from([16, 32, 64]))
@settings(max_examples=10, deadline=None)
def test_flash_attention_block_shape_invariance(bq, bk):
    """Output must not depend on the BlockSpec tiling."""
    q = jax.random.normal(KEY, (1, 128, 2, 32))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 128, 2, 32))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 128, 2, 32))
    out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


# -- ssd scan -------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,L,H,P,N,Q,bh",
    [
        (1, 64, 2, 16, 8, 16, 2),
        (2, 128, 4, 32, 16, 32, 2),  # head-blocked
        (1, 96, 3, 16, 8, 32, 1),  # H not a power of two
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, L, H, P, N, Q, bh, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, L + H), 5)
    x = jax.random.normal(ks[0], (B, L, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))).astype(dtype)
    A_log = jnp.log(jax.random.uniform(ks[2], (H,), minval=1.0, maxval=8.0))
    Bm = jax.random.normal(ks[3], (B, L, N), dtype)
    Cm = jax.random.normal(ks[4], (B, L, N), dtype)
    y, s = ops.ssd_scan(x, dt, A_log, Bm, Cm, Q, block_h=bh)
    yr, sr = ref.ssd_scan_ref(x, dt, A_log, Bm, Cm, Q)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), **tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), **tol(dtype))


# -- rg-lru scan ------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,T,W,chunk,bw", [(1, 64, 32, 16, 32), (2, 128, 64, 64, 16), (1, 256, 16, 256, 16)]
)
def test_rglru_scan_sweep(B, T, W, chunk, bw):
    ks = jax.random.split(jax.random.fold_in(KEY, T + W), 2)
    log_a = -jax.nn.softplus(jax.random.normal(ks[0], (B, T, W)))
    b = jax.random.normal(ks[1], (B, T, W))
    y, h = ops.rglru_scan(log_a, b, chunk=chunk, block_w=bw)
    yr, hr = ref.rglru_scan_ref(log_a, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-5, atol=1e-5)


# -- rmsnorm ----------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(7, 64), (3, 5, 128), (1, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (shape[-1],)) * 0.1
    out = ops.rmsnorm(x, w)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


def test_model_paths_agree_with_pallas():
    """cfg.use_pallas=True must reproduce the jnp model end to end."""
    from repro.configs.registry import smoke_config
    from repro.models import model as M

    for arch in ("qwen3-1.7b", "mamba2-370m", "recurrentgemma-9b"):
        cfg = smoke_config(arch).replace(attn_chunk_q=0)
        params = M.init_params(cfg, jax.random.PRNGKey(11))
        batch = {
            "tokens": jax.random.randint(KEY, (2, 32), 1, 255),
            "labels": jax.random.randint(KEY, (2, 32), 0, 255),
        }
        l_jnp, _ = M.forward_train(cfg, params, batch)
        l_pls, _ = M.forward_train(cfg.replace(use_pallas=True), params, batch)
        np.testing.assert_allclose(float(l_jnp), float(l_pls), rtol=5e-3), arch


# -- cold-start scan (simulator) -----------------------------------------------
def _cold_case(key, B, T, interarrival, keep_warm, spread=0.3):
    """Arrival times plus warm/cold end-time hypotheses around them."""
    k1, k2, k3 = jax.random.split(key, 3)
    gaps = interarrival * (0.5 + jax.random.uniform(k1, (T,)))
    t0 = jnp.cumsum(gaps)
    dur = spread * jax.random.uniform(k2, (B, T))
    cold_extra = spread * jax.random.uniform(k3, (B, T))
    warm_end = t0[None, :] + dur
    return t0, warm_end, warm_end + cold_extra, jnp.float32(keep_warm)


@pytest.mark.parametrize("B,T", [(1, 64), (3, 257), (130, 300)])
@pytest.mark.parametrize(
    "interarrival,keep_warm",
    [
        (1.0, 900.0),  # paper regime: warm after request 0
        (10.0, 1.0),  # every request cold
        (1.0, 0.95),  # straddling: the mask genuinely recurses
        (1.0, jnp.inf),  # never cold
    ],
)
def test_cold_scan_kernel_and_parallel_match_ref(B, T, interarrival, keep_warm):
    t0, warm, cold, kw = _cold_case(
        jax.random.PRNGKey(7), B, T, interarrival, keep_warm
    )
    want = ref.cold_scan_ref(t0, warm, cold, kw)
    got_pl = ops.cold_scan(t0, warm, cold, kw)  # interpret mode on CPU
    got_par = cold_scan_parallel(t0, warm, cold, kw)
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_par), np.asarray(want))


def test_cold_scan_flip_heavy_regime():
    """keep_warm between the warm and cold gaps on most requests: the
    affine maps are nearly all 'flip', the worst case for the early-out
    doubling loop (it must run to full depth and still be exact)."""
    T = 97
    t0 = 0.7 * jnp.arange(T, dtype=jnp.float32)
    warm = t0[None, :] + 0.02
    cold = warm + 0.5  # warm gap 0.68 > kw=0.6, cold gap 0.18 < kw -> flip
    kw = jnp.float32(0.6)
    want = ref.cold_scan_ref(t0, warm, cold, kw)
    np.testing.assert_array_equal(
        np.asarray(cold_scan_parallel(t0, warm, cold, kw)), np.asarray(want)
    )
    np.testing.assert_array_equal(
        np.asarray(ops.cold_scan(t0, warm, cold, kw)), np.asarray(want)
    )


def test_cold_scan_parallel_under_vmap():
    """The while_loop gate must lift over vmap (any lane still flipping)."""
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    cases = [_cold_case(k, 2, 50, 1.0, 0.95) for k in keys]
    t0 = jnp.stack([c[0] for c in cases])
    warm = jnp.stack([c[1] for c in cases])
    cold = jnp.stack([c[2] for c in cases])
    got = jax.vmap(lambda a, b, c: cold_scan_parallel(a, b, c, 0.95))(t0, warm, cold)
    for i in range(4):
        want = ref.cold_scan_ref(t0[i], warm[i], cold[i], 0.95)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


def test_cold_scan_kernel_under_nested_vmap_folds_rows_into_lanes():
    """As the simulator's sweep calls the kernel: one (1, T) row per (seed,
    placement) under vmap(vmap(...)), arrivals shared, each row with its
    own keep_warm, in the straddling regime. The 3 x 50 rows fold into one
    kernel call over two 128-lane blocks, and every row's mask is the
    reference's bit for bit."""
    n_s, n_p, T = 3, 50, 300
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(9), 4)
    t0 = jnp.cumsum(0.5 + jax.random.uniform(k1, (T,)))
    warm = t0 + 0.3 * jax.random.uniform(k2, (n_s, n_p, T))
    cold = warm + 0.3 * jax.random.uniform(k3, (n_s, n_p, T))
    kw = 0.85 + 0.2 * jax.random.uniform(k4, (n_s, n_p))

    def row(w, c, k):
        return ops.cold_scan(t0, w[None, :], c[None, :], k)[0]

    sweep = jax.vmap(jax.vmap(row))
    program = str(jax.make_jaxpr(sweep)(warm, cold, kw))
    assert program.count("pallas_call") == 1
    assert "i32[512,256] = pallas_call" in program
    got = np.asarray(sweep(warm, cold, kw))
    assert got.shape == (n_s, n_p, T)
    # the reference's recurrence runs on every row at once, each row
    # against its own keep_warm (broadcast over the rows' carry)
    want = np.asarray(ref.cold_scan_ref(t0, warm, cold, kw))
    np.testing.assert_array_equal(got, want)
    warm_bit, cold_bit = _gap_bits(t0, warm, cold, kw[..., None])
    flips = np.asarray(warm_bit & ~cold_bit).sum(axis=-1)
    assert 0.05 < got[:, :, 1:].mean() < 0.95  # both states occur
    assert (flips > 0).all()  # every row has requests whose state follows

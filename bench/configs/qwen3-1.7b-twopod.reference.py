"""Plain reference of Qwen3's decoder, in float32 ``jax.numpy``.

Written from the published architecture (Qwen3 ``config.json`` keys) and
importing nothing of the program: token embedding; per layer a pre-norm
grouped-query attention block with RMS-normed queries and keys (per head,
before the rotary embedding) and rotary embedding on the two halves of
each head, then a pre-norm SwiGLU MLP; a final RMS norm; logits from the
tied embedding. Every matmul runs at ``highest`` precision. No cache and
no batching: each sequence is run whole, causally, one at a time.

The weights are the ones the benchmark drew (never the program's), laid
out as the program stores them: per-layer tensors stacked on a leading
layer axis under ``blocks/cycle/p0``, projections as (in, heads, head_dim)
and (heads, head_dim, out), and every RMS-norm gain stored as an offset
from 1 (gain = 1 + w), which is the published norm with its weight
reparameterised.

``served_logit_err`` is the comparison that decides ``correct`` for a
served cell: with the served tokens fed back in, how far the logit the
program served each token with lies from the reference's logit of that
token, in standard deviations of that position's reference logits. A
served token that is not the reference's choice, or a logit off by more
than the precision explains, shows in it; so does any change of the
logits that leaves the argmax alone. ``control_logit_err`` reads the same
number for a float8 (e4m3, per-tensor scaled) evaluation of the same
reference, at each position of the same prompts and tokens, for the token
it puts first: the precision step below the configuration's bfloat16.
"""

from __future__ import annotations

import numpy as np

_CACHE = {}


def _quant_fp8(x):
    """Per-tensor scaled float8 e4m3 round trip (the control's precision)."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _forward(hf, quant: bool):
    """jitted (params, tokens (L,), read (P,)) -> (P, vocab) float32 logits
    at the positions ``read``."""
    key = (hf["num_hidden_layers"], hf["hidden_size"], hf["vocab_size"], quant)
    if key in _CACHE:
        return _CACHE[key]
    import jax
    import jax.numpy as jnp

    H, K, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    eps, theta = hf["rms_norm_eps"], float(hf["rope_theta"])
    q8 = _quant_fp8 if quant else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)

    def rope(x, pos):
        half = hd // 2
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos[:, None].astype(jnp.float32) * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def layer(x, w):
        L = x.shape[0]
        pos = jnp.arange(L)
        a = w["mixer"]
        h = rms(x, w["norm1"])
        q = rms(mm("ld,dhk->lhk", h, a["wq"]), a["q_norm"])
        k = rms(mm("ld,dhk->lhk", h, a["wk"]), a["k_norm"])
        v = mm("ld,dhk->lhk", h, a["wv"])
        q, k = rope(q, pos), rope(k, pos)
        k = jnp.repeat(k, H // K, axis=1)  # query head h reads kv head h // (H/K)
        v = jnp.repeat(v, H // K, axis=1)
        s = mm("lhk,mhk->hlm", q, k) * hd**-0.5
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        o = mm("hlm,mhk->lhk", jax.nn.softmax(s, -1), v)
        x = x + mm("lhk,hkd->ld", o, a["wo"])
        f = w["ffn"]
        h = rms(x, w["norm2"])
        g = jax.nn.silu(mm("ld,df->lf", h, f["w_gate"])) * mm("ld,df->lf", h, f["w_up"])
        return x + mm("lf,fd->ld", g, f["w_down"]), None

    @jax.jit
    def fwd(params, tokens, read):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens]
            x, _ = jax.lax.scan(layer, x, params["blocks"]["cycle"]["p0"])
            x = rms(x[read], params["final_norm"])
            return mm("pd,vd->pv", x, params["embed"])

    _CACHE[key] = fwd
    return fwd


def _logits(hf, params, prompt, tokens, shape, quant=False):
    """Reference logits at the positions that produced ``tokens`` after
    ``prompt``: the prompt's last and each fed-back served token but the
    last. ``shape`` = (padded length, read positions) is fixed per cell, so
    one program serves every request; padding after a position never
    reaches it (causal), and surplus reads are dropped."""
    length, n_read = shape
    seq = np.concatenate(
        [np.asarray(prompt, np.int32), np.asarray(tokens[:-1], np.int32)]
    )
    T, n = len(prompt), len(tokens)
    if len(seq) > length or n > n_read:
        raise ValueError(f"{len(seq)} tokens, {n} reads over the padded {shape}")
    padded = np.zeros(length, np.int32)
    padded[: len(seq)] = seq
    read = np.full(n_read, T - 1, np.int32)
    read[:n] = np.arange(T - 1, T - 1 + n)
    out = _forward(hf, quant)(params, padded, read)
    return np.asarray(out, np.float64)[:n]


def _err(ref, picked, values):
    """Per position: how far the value served for ``picked`` lies from the
    reference's logit of it, in SDs of the reference's logits there."""
    got = ref[np.arange(len(picked)), picked]
    return np.abs(np.asarray(values, np.float64) - got) / ref.std(-1)


def served_logit_err(hf, params, requests, shape) -> list:
    """The widest error of each request's served logits (the list the
    comparison takes its maximum over). ``requests``: (prompt, tokens,
    logits); ``shape``: (padded length, read positions) of the cell."""
    out = []
    for prompt, tokens, logits in requests:
        ref = _logits(hf, params, prompt, tokens, shape)
        out.append(float(_err(ref, np.asarray(tokens), logits).max()))
    return out


def control_logit_err(hf, params, requests, shape) -> list:
    """The same reading for the float8 evaluation's own first token and
    logit at each position of the same prompts and served tokens."""
    out = []
    for prompt, tokens, _ in requests:
        ref = _logits(hf, params, prompt, tokens, shape)
        low = _logits(hf, params, prompt, tokens, shape, quant=True)
        pick = low.argmax(-1)
        out.append(float(_err(ref, pick, low[np.arange(len(pick)), pick]).max()))
    return out

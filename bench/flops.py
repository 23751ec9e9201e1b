"""Operations and bytes the algorithms need, from shapes alone.

These are the numerators of the roofline and utilization metrics: model
FLOPs from a decoder's published sizes (``hidden_size``, heads, ...), and
the work of one kernel call from the shapes of that call. Nothing here
reads the program; a later change to the program cannot move them.
"""

from __future__ import annotations


def decoder_matmul_params(hf: dict) -> tuple:
    """(layer weights, head weights) that one token multiplies through: the
    q/k/v/o projections and the gated MLP of every layer, and the
    unembedding (tied or not, one hidden x vocab product)."""
    d, h, k = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    hd, f = hf["head_dim"], hf["intermediate_size"]
    per_layer = d * h * hd * 2 + d * k * hd * 2 + 3 * d * f
    return hf["num_hidden_layers"] * per_layer, d * hf["vocab_size"]


def attention_flops(hf: dict, key_pairs: int) -> float:
    """QK^T and PV over ``key_pairs`` (query, key) pairs, every layer."""
    heads = hf["num_hidden_layers"] * hf["num_attention_heads"]
    return 4.0 * heads * hf["head_dim"] * key_pairs


def prefill_flops(hf: dict, prompt_len: int) -> float:
    """Model FLOPs of one causal prefill of ``prompt_len`` tokens that
    returns the logits of the last position."""
    layers, head = decoder_matmul_params(hf)
    pairs = prompt_len * (prompt_len + 1) // 2
    return (
        2.0 * layers * prompt_len
        + 2.0 * head
        + attention_flops(hf, pairs)
    )


def decode_flops(hf: dict, context: int) -> float:
    """Model FLOPs of one decode step whose token attends to ``context``
    positions (itself included)."""
    layers, head = decoder_matmul_params(hf)
    return 2.0 * (layers + head) + attention_flops(hf, context)


def flash_attention_work(q_shape, k_shape, causal: bool = True, itemsize: int = 2):
    """(flops, bytes) of one flash-attention call on q (B, H, T, d) and k, v
    (B, K, S, d): both matmuls over the pairs the mask keeps, and q, k, v
    read and the output written once."""
    b, h, t, d = q_shape
    _, k, s, _ = k_shape
    pairs = t * (t + 1) // 2 if causal and t == s else t * s
    flops = 4.0 * b * h * d * pairs
    nbytes = itemsize * (2 * b * h * t * d + 2 * b * k * s * d)
    return flops, nbytes


def cold_scan_work(rows: int, requests: int, itemsize: int = 4):
    """(flops, bytes) of the cold scans over ``rows`` x ``requests``
    elements: the code plane in and the mask out, unpadded. A sweep's scans
    cover nodes x (seed, placement) rows; a program may split them over
    several kernel calls, so a reader spreads a sweep's work over the calls
    one sweep makes. A select per element is no matrix work, so the scan is
    counted as memory traffic alone."""
    return 0.0, 2 * itemsize * rows * requests

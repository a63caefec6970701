"""Operations and bytes that the work needs, reckoned from the configuration
and the live state alone, at the configuration's dtype.  Whatever program
does the work, these numbers stay the same, so a share built on them moves
only with time.

``m`` is a configuration's ``model`` block (``configs/<config>.json``).
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable

HERE = os.path.dirname(os.path.abspath(__file__))
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    try:
        return table["kinds"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table['kinds'])}") from None


def elem_bytes(m: dict) -> int:
    return DTYPE_BYTES[m["dtype"]]


def layer_matmul_params(m: dict) -> int:
    d, h, kv, hd, f = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    return d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f


def matmul_params(m: dict) -> int:
    """Parameters that a token multiplies: every layer's projections and
    the output head (the embedding lookup is a gather, not a matmul)."""
    return m["n_layers"] * layer_matmul_params(m) + m["vocab"] * m["d_model"]


def kv_row_bytes(m: dict) -> int:
    """Bytes of one position's keys and values over all layers."""
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * elem_bytes(m)


def decode_flops(m: dict, contexts: Iterable[int]) -> int:
    """Model FLOPs of decoding one token per entry of ``contexts``, each
    the number of positions that token attends to (itself included)."""
    ctx = list(contexts)
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"]
    return 2 * matmul_params(m) * len(ctx) + attn * sum(ctx)


def decode_bytes(m: dict, steps: int, contexts: Iterable[int]) -> int:
    """Bytes that ``steps`` decode steps must move at the least: every
    matmul weight read once a step, each earlier live KV row read once and
    each new row written once."""
    w = matmul_params(m) * elem_bytes(m)
    return steps * w + sum(contexts) * kv_row_bytes(m)


def decode_blocks(m: dict, slots: int, window: int) -> Dict[str, Dict[str, int]]:
    """FLOPs and bytes of one call of each served decode block, from the
    block's own index space: 2 x the product of the index ranges of a
    contraction, and each operand and result moved once at the dtype."""
    d, h, kv, hd, f = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    g, b, t, e = h // kv, slots, window, elem_bytes(m)
    return {
        "qkv": {"flops": 2 * b * d * (h + 2 * kv) * hd,
                "bytes": e * (b * d + d * (h + 2 * kv) * hd + b * (h + 2 * kv) * hd)},
        "attn_out": {"flops": 2 * b * h * hd * d,
                     "bytes": e * (b * h * hd + h * hd * d + 2 * b * d)},
        "mlp": {"flops": 2 * b * d * f * 3,
                "bytes": e * (2 * b * d + 3 * d * f + b * d)},
        "attn_scores": {"flops": 2 * b * kv * g * t * hd,
                        "bytes": e * (b * kv * g * hd + b * kv * t * hd + b * kv * g * t)},
        "attn_values": {"flops": 2 * b * kv * g * t * hd,
                        "bytes": e * (b * kv * g * t + b * kv * t * hd + b * kv * g * hd)},
    }


def least_seconds(flops: float, nbytes: float, pk: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])

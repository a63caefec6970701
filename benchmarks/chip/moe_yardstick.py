"""Operations and bytes of a routed-expert decode step, reckoned from the
configuration, the live contexts and the engine's expert counters, at the
configuration's dtype.  Whatever program does the work these numbers stay
the same, so a share built on them moves only with time.

What a step must do: every token through the attention projections, the
router and the output head; each (token, held expert) pair through that
expert's SiLU-GLU FFN, ``6 d f`` FLOPs; the attention over each token's
live context.  What it must move: the attention weights, the router and
the head once a step, the weights of each expert that some token meets
once a step, each earlier live KV row read once and each new row written
once.

``m`` is a configuration's ``model`` block; its ``d_ff`` is the expert
width.  ``rows`` and ``hits`` count over all layers: the engine's
``serve.moe.decode_rows`` (pairs) and ``serve.moe.decode_experts_hit``
(held experts with a pair).
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Tuple

import yardstick

COUNTERS = ("decode_steps", "decode_rows", "decode_experts_hit")


def counters() -> Optional[Dict[str, float]]:
    """The engine's ``serve.moe.*`` decode totals in the process-wide
    registry; None where the program keeps no such counter."""
    try:
        from repro.obs import metrics
    except ImportError:
        return None
    got = metrics.snapshot().get("counters", {})
    out = {n: got.get(f"serve.moe.{n}") for n in COUNTERS}
    if any(v is None for v in out.values()) or not out["decode_steps"]:
        return None
    return out


def per_step(c: Dict[str, float]) -> Tuple[float, float]:
    """(pairs, experts hit) a decode step, over all layers: run totals,
    which warm-up hardly moves in a backlog."""
    steps = c["decode_steps"]
    return c["decode_rows"] / steps, c["decode_experts_hit"] / steps


def token_params(m: dict) -> int:
    """Parameters every token multiplies: each layer's attention
    projections and router, and the output head."""
    d, h, kv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    layer = d * (h + 2 * kv) * hd + h * hd * d + d * m["n_experts"]
    return m["n_layers"] * layer + m["vocab"] * d


def expert_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def decode_flops(m: dict, contexts: Iterable[int], rows: float) -> float:
    """Model FLOPs of decoding one token per entry of ``contexts`` (each
    the positions that token attends to), whose steps route ``rows``
    pairs in all."""
    ctx = list(contexts)
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"]
    return 2 * token_params(m) * len(ctx) + 2 * expert_params(m) * rows + attn * sum(ctx)


def decode_bytes(m: dict, steps: int, contexts: Iterable[int], hits: float) -> float:
    """Bytes that ``steps`` decode steps must move at the least, their
    steps meeting ``hits`` held experts in all (layers counted apart)."""
    e = yardstick.elem_bytes(m)
    weights = steps * token_params(m) + hits * expert_params(m)
    return weights * e + sum(contexts) * yardstick.kv_row_bytes(m)


def expert_block(m: dict, rows: float, hits: float) -> Dict[str, float]:
    """FLOPs and bytes of the grouped expert kernels in one decode step:
    each pair through its expert, the hit experts' weights read once, and
    each pair's row read and its result written once."""
    e = yardstick.elem_bytes(m)
    return {"flops": 2 * expert_params(m) * rows,
            "bytes": e * (hits * expert_params(m) + 2 * rows * m["d_model"])}

"""Mixture-of-Experts layer for training: top-k routing with
capacity-bounded scatter/gather dispatch (Switch-style) — expert weights
are stacked on a leading expert axis so EP shards them over the ``model``
mesh axis.  The served path (``serving.paged``) drops no token and has
its own dispatch."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .core import Params, dense_init


def moe_init(key, cfg, dtype) -> Params:
    """The router over all ``n_experts`` (float32) and the weights of the
    experts the layer holds (``MoECfg.held``), stacked on a leading expert
    axis."""
    d = cfg.d_model
    e, f = cfg.moe.held, cfg.moe.d_ff_expert
    ks = jax.random.split(key, 4)
    scale = 1.0 / np.sqrt(d)
    fscale = 1.0 / np.sqrt(f)
    return {
        "router": dense_init(ks[0], d, cfg.moe.n_experts, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32) * fscale).astype(dtype),
    }


MOE_EXPERT_MAJOR = True


def moe_apply(p: Params, x: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D).  Returns (out, aux_loss)."""
    if cfg.moe.is_share:
        raise ValueError(
            f"{cfg.name}: the layer holds {cfg.moe.held} of the "
            f"{cfg.moe.n_experts} experts it routes over; training needs "
            f"every expert (train the whole model's config)")
    b, s, d = x.shape
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    t = b * s
    cap = int(np.ceil(cfg.moe.capacity_factor * t * k / e))
    cap = max(cap, 4)

    xt = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)           # (t, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # load-balancing auxiliary loss (Switch): E * sum(frac_tokens * frac_prob)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)

    # position of each (token, choice) within its expert's capacity
    eid = gate_idx.reshape(-1)                               # (t*k,)
    onehot = jax.nn.one_hot(eid, e, dtype=jnp.int32)         # (t*k, e)
    pos = jnp.cumsum(onehot, axis=0) - 1                     # running count
    pos_in_e = jnp.take_along_axis(pos, eid[:, None], axis=1)[:, 0]
    keep = pos_in_e < cap
    slot = jnp.where(keep, eid * cap + pos_in_e, e * cap)    # overflow slot

    # scatter tokens into (e*cap+1, d), compute experts, gather back
    from ..parallel.constrain import constrain

    src = jnp.repeat(xt, k, axis=0)                          # (t*k, d)
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].add(src * keep[:, None].astype(x.dtype))
    h = buf[: e * cap].reshape(e, cap, d)
    # EP: keep expert-major tensors sharded on 'model' so the expert FFN
    # einsums stay local (the dispatch becomes an all-to-all instead of
    # GSPMD all-gathering the expert weights -- see EXPERIMENTS.md §Perf)
    h = constrain(h, "model", "data", None) if MOE_EXPERT_MAJOR else h
    a = cfg.act.split("_")[0] if cfg.act.endswith("_glu") else None
    if cfg.act.endswith("_glu"):
        act_fn = jax.nn.silu if a == "silu" else (lambda z: jax.nn.gelu(z, approximate=True))
        g = act_fn(jnp.einsum("ecd,edf->ecf", h, p["w_gate"]))
        u = jnp.einsum("ecd,edf->ecf", h, p["w_up"])
        o = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"])
    else:
        u = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", h, p["w_up"])))
        o = jnp.einsum("ecf,efd->ecd", u, p["w_down"])
    o = constrain(o, "model", "data", None) if MOE_EXPERT_MAJOR else o
    flat = jnp.concatenate([o.reshape(e * cap, d), jnp.zeros((1, d), o.dtype)], axis=0)
    # ---- combine: weight in expert-major layout, then ONE scatter-add back
    # to token-major (t, d).  (The naive flat[slot] gather materializes a
    # replicated (t*k, d) f32 tensor that GSPMD all-reduces — 103 GB/chip
    # on dbrx prefill; see EXPERIMENTS.md §Perf iteration 1.)
    w_buf = jnp.zeros((e * cap + 1,), jnp.float32).at[slot].add(
        gate_vals.reshape(-1) * keep)
    ow = flat * w_buf[:, None].astype(flat.dtype)            # (e*cap+1, d)
    tok_ids = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)  # (t*k,)
    tok_of_slot = (
        jnp.full((e * cap + 1,), -1, jnp.int32).at[slot].max(jnp.where(keep, tok_ids, -1))
    )
    dest = jnp.where(tok_of_slot >= 0, tok_of_slot, t)       # sink row for empty
    out = jnp.zeros((t + 1, d), flat.dtype).at[dest].add(ow)[:t]
    out = constrain(out, ("data",), None)
    return out.reshape(b, s, d), aux

"""Core NN building blocks (pure-functional JAX, params = pytrees).

All dense projections route through ``repro.core.oplib.linear`` — the
Stripe-compiled op layer (einsum on the jnp backend so GSPMD shards it;
the Stripe-generated Pallas kernel on TPU backends).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec

from ..core import oplib

Params = Dict[str, Any]


def param_dtype(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else (1.0 / np.sqrt(d_in))
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def linear(x: jnp.ndarray, w: jnp.ndarray, bias: Optional[jnp.ndarray] = None,
           act: Optional[str] = None) -> jnp.ndarray:
    if oplib.get_backend() == "jnp":
        out = jnp.einsum("...k,kn->...n", x, w)
        if bias is not None:
            out = out + bias
        if act is not None:
            out = _ACT[act](out)
        return out
    return oplib.linear(x, w, bias, act)


_ACT = {
    "relu": jax.nn.relu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


# ---------------------------------------------------------------- norms
def norm_init(d: int, kind: str, dtype) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p: Params, x: jnp.ndarray, kind: str, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        nrm = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
        out = xf * nrm * p["scale"].astype(jnp.float32)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
        out = out + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def rms_head_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """qk-norm: RMS over the head dim."""
    xf = x.astype(jnp.float32)
    nrm = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (xf * nrm * scale.astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------- RoPE
def rope_freqs(hd_rot: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd_rot, 2, dtype=jnp.float32) / hd_rot))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, mode: str, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: (..., S) int32.  mode: full|half|none."""
    if mode == "none":
        return x
    hd = x.shape[-1]
    rot = hd if mode == "full" else hd // 2
    freqs = rope_freqs(rot, theta)  # (rot/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, rot/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., : rot // 2], x_rot[..., rot // 2 :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    r1 = xf1 * cos - xf2 * sin
    r2 = xf2 * cos + xf1 * sin
    out = jnp.concatenate([r1.astype(x.dtype), r2.astype(x.dtype), x_pass], axis=-1)
    return out


# ------------------------------------------------------------------ MLP
def mlp_init(key, d: int, d_ff: int, act: str, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    if act.endswith("_glu"):
        return {
            "w_gate": dense_init(k1, d, d_ff, dtype),
            "w_up": dense_init(k2, d, d_ff, dtype),
            "w_down": dense_init(k3, d_ff, d, dtype),
        }
    return {"w_up": dense_init(k1, d, d_ff, dtype), "w_down": dense_init(k2, d_ff, d, dtype)}


def mlp_apply(p: Params, x: jnp.ndarray, act: str) -> jnp.ndarray:
    if act.endswith("_glu"):
        a = act.split("_")[0]
        g = linear(x, p["w_gate"], act=a)
        u = linear(x, p["w_up"])
        return linear(g * u, p["w_down"])
    h = linear(x, p["w_up"], act=act)
    return linear(h, p["w_down"])


# ----------------------------------------------------------- embeddings
def embed_init(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


def embed_lookup(table: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    sharding = jax.typeof(tokens).sharding
    if AxisType.Explicit not in sharding.mesh.axis_types:
        return jnp.take(table, tokens, axis=0)
    # under explicit-axis sharding the gather's output sharding is
    # ambiguous (table rows vs ids): the looked-up rows follow the ids
    return table.at[tokens].get(out_sharding=NamedSharding(
        sharding.mesh, PartitionSpec(*sharding.spec, None)))


def unembed(x: jnp.ndarray, table_or_w: jnp.ndarray, tied: bool) -> jnp.ndarray:
    if tied:
        return jnp.einsum("...d,vd->...v", x, table_or_w)
    return jnp.einsum("...d,dv->...v", x, table_or_w)


def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray, real_vocab: int) -> jnp.ndarray:
    """Mean token cross-entropy; logits over the padded vocab are masked."""
    lf = logits.astype(jnp.float32)
    pad = lf.shape[-1] - real_vocab
    if pad > 0:
        neg = jnp.full((pad,), -1e30, jnp.float32)
        lf = lf.at[..., real_vocab:].set(neg)
    logz = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ----------------------------------------------------- causal conv (ssm)
def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, state: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv.  x: (B, S, C); w: (W, C).  Returns (y, new
    state (B, W-1, C))."""
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], W - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    parts = [xp[:, i : i + x.shape[1], :] * w[i] for i in range(W)]
    y = sum(parts)
    new_state = xp[:, -(W - 1):, :] if W > 1 else state
    return y, new_state

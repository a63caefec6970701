"""Plain reference of a dense decoder-only LM, and its seeded weights.

Written from the published description of the architecture (pre-norm
RMSNorm blocks, grouped-query attention with rotary positions, SiLU-GLU
feed-forward, optional per-head q/k RMSNorm, tied or untied output head).
It imports nothing of the system under test.  The shapes and names of the
weights follow the served parameter tree, which the harness hands over;
the weights themselves are made here, from the seed.

Everything is float32 at ``HIGHEST`` matmul precision, one layer at a
time, so that it fits on the chip beside the bf16 weights.  The control
(``precision="fp8"``) is the same computation with every matmul operand
rounded to float8 e4m3 under a per-row / per-column scale: the step below
bfloat16 that a quantised serving path would take.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------- weights
def _leaf_init(path: str, shape, key, dtype):
    """How each named leaf of the parameter tree is drawn.  Norm scales
    are drawn around 1 (not exactly 1) so that a path that drops one is
    seen; embeddings at std 0.02; projections at 1/sqrt(fan-in)."""
    name = path.split("/")[-1]
    if name in ("scale", "q_norm", "k_norm"):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if name in ("embed", "unembed"):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if len(shape) == 3:  # stacked per-layer matrices: one layer at a time
        keys = jax.random.split(key, shape[0])
        std = 1.0 / math.sqrt(shape[1])
        return jax.lax.map(
            lambda k: (std * jax.random.normal(k, shape[1:], jnp.float32)).astype(dtype),
            keys)
    raise ValueError(f"no initialiser for parameter {path} {shape}")


def _paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    return names, [v for _, v in flat], treedef


def make_weights(shapes, key):
    """All weights in one jitted call on the device, from ``key``, in the
    dtype of ``shapes`` (a tree of ``ShapeDtypeStruct``)."""
    names, leaves, treedef = _paths(shapes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return [_leaf_init(n, s.shape, k, s.dtype)
                for n, s, k in zip(names, leaves, keys)]

    return jax.tree_util.tree_unflatten(treedef, make(key))


# ------------------------------------------------------------ reference
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _fp8(x, axis):
    """Round to float8 e4m3 under a scale taken along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, precision):
    """x (..., k) @ w (k, n) in float32, or with fp8-rounded operands."""
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w)


def _rope(x, pos, rot, theta):
    """Rotary on the first ``rot`` dims, halves paired (x_i with x_{i+rot/2})."""
    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _layer(x, w, m, precision):
    """One pre-norm block over x (B, L, D) float32 with causal attention."""
    b, l, _ = x.shape
    h, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    a = _rms(x, f32(w["ln1"]), eps)
    q = _mm(a, f32(w["wq"]), precision).reshape(b, l, h, hd)
    k = _mm(a, f32(w["wk"]), precision).reshape(b, l, kv, hd)
    v = _mm(a, f32(w["wv"]), precision).reshape(b, l, kv, hd)
    if m["qk_norm"]:
        q = _rms(q, f32(w["q_norm"]), eps)
        k = _rms(k, f32(w["k_norm"]), eps)
    pos = jnp.arange(l)
    q = _rope(q, pos, m["rotary_dims"], m["rope_theta"])
    k = _rope(k, pos, m["rotary_dims"], m["rope_theta"])
    qg = q.reshape(b, l, kv, h // kv, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((l, l), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v).reshape(b, l, h * hd)
    x = x + _mm(o, f32(w["wo"]), precision)
    a = _rms(x, f32(w["ln2"]), eps)
    g = jax.nn.silu(_mm(a, f32(w["w_gate"]), precision)) * _mm(a, f32(w["w_up"]), precision)
    return x + _mm(g, f32(w["w_down"]), precision)


def _layer_weights(params, i):
    blk = params["blocks"]
    attn = blk["attn"]
    w = {"ln1": blk["ln1"]["scale"][i], "ln2": blk["ln2"]["scale"][i],
         "wq": attn["wq"][i], "wk": attn["wk"][i], "wv": attn["wv"][i],
         "wo": attn["wo"][i], "w_gate": blk["mlp"]["w_gate"][i],
         "w_up": blk["mlp"]["w_up"][i], "w_down": blk["mlp"]["w_down"][i]}
    if "q_norm" in attn:
        w["q_norm"], w["k_norm"] = attn["q_norm"][i], attn["k_norm"][i]
    return w


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _head(h, final_scale, table, m, precision):
    """Logits over the published vocabulary for hidden rows h (N, D)."""
    h = _rms(h, final_scale.astype(jnp.float32), m["norm_eps"])
    w = table.astype(jnp.float32)
    w = w[: m["vocab"]].T if m["tie_embeddings"] else w[:, : m["vocab"]]
    return _mm(h, w, precision)


def hidden_at(params, m, seqs, rows, precision="f32", block=4):
    """Final hidden states (before the last norm) of each sequence in
    ``seqs`` (lists of token ids) at positions ``rows[i]``; returns one
    (sum of len(rows[i]), D) float32 array.  Sequences are run ``block``
    at a time, padded to one power-of-two length."""
    m = _frozen(m)
    longest = max(len(s) for s in seqs)
    l = 1 << max(4, (longest - 1).bit_length())
    out = []
    with jax.default_matmul_precision("highest"):
        for b0 in range(0, len(seqs), block):
            part = seqs[b0: b0 + block]
            toks = np.zeros((block, l), np.int32)
            for j, s in enumerate(part):
                toks[j, : len(s)] = s
            x = jnp.take(params["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
            for i in range(m["n_layers"]):
                x = _layer(x, _layer_weights(params, i), m, precision)
            for j in range(len(part)):
                out.append(x[j, np.asarray(rows[b0 + j])])
    return jnp.concatenate(out, axis=0)


def logits_reduce(params, m, hid, want, precision="f32", chunk=256):
    """For hidden rows ``hid`` (N, D): the best logit, the logit of each
    token in ``want`` (a list of (N,) int arrays), and the argmax, as
    numpy arrays.  The head runs ``chunk`` rows at a time."""
    m = _frozen(m)
    table = params["embed"] if m["tie_embeddings"] else params["unembed"]
    best, top, picked = [], [], [[] for _ in want]
    with jax.default_matmul_precision("highest"):
        for c0 in range(0, hid.shape[0], chunk):
            h = hid[c0: c0 + chunk]
            n = h.shape[0]
            if n < chunk:
                h = jnp.concatenate([h, jnp.zeros((chunk - n, h.shape[1]), h.dtype)])
            lg = _head(h, params["final_norm"]["scale"], table, m, precision)[:n]
            best.append(np.asarray(jnp.max(lg, axis=-1)))
            top.append(np.asarray(jnp.argmax(lg, axis=-1)))
            for k, w in enumerate(want):
                idx = jnp.asarray(np.asarray(w[c0: c0 + n], np.int32))
                picked[k].append(np.asarray(jnp.take_along_axis(lg, idx[:, None], 1)[:, 0]))
    return (np.concatenate(best), np.concatenate(top),
            [np.concatenate(p) for p in picked])


def served_gaps(params, m, prompts, outputs, control=None):
    """Teacher-forced check of served greedy tokens.

    For each request, the reference runs once over the prompt and its
    served tokens; at the position that produced served token j it reads
    how far that token's logit lies below the best logit.  Returns the
    gaps of all served tokens (one flat array), and with ``control`` set
    (``"fp8"``) also the gaps of the tokens that the control ranks first
    at the same positions."""
    seqs = [list(p) + list(o[:-1]) for p, o in zip(prompts, outputs)]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(o)) for p, o in zip(prompts, outputs)]
    served = np.concatenate([np.asarray(o, np.int64) for o in outputs])
    ctrl_top = None
    if control is not None:
        hc = hidden_at(params, m, seqs, rows, precision=control)
        _, ctrl_top, _ = logits_reduce(params, m, hc, [], precision=control)
        del hc
    hid = hidden_at(params, m, seqs, rows)
    want = [served] + ([ctrl_top] if ctrl_top is not None else [])
    best, _, picked = logits_reduce(params, m, hid, want)
    gaps = best - picked[0]
    return (gaps, best - picked[1]) if control is not None else gaps


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _frozen(m):
    return m if isinstance(m, _Frozen) else _Frozen(m)

"""Plain reference of a decoder-only LM whose feed-forward blocks are routed
experts, of which this chip holds a share; and its seeded weights.

Written from the published description of the architecture: each layer is
``dense_decoder``'s pre-norm attention block (imported from that file, with
its rotary, norms and head; nothing of the system under test), then a
routed-expert block in place of the dense FFN:

* the router's softmax over all ``n_experts``, in float32;
* the ``top_k`` largest probabilities, renormalised to sum to 1
  (``norm_topk_prob``);
* of the picks, those of the ``n_held`` experts from ``held_offset`` on
  (one chip's share of an expert-parallel deployment) each add their gate
  weight times their SiLU-GLU FFN; the picks of experts held on other
  chips add nothing here;
* the residual.

Everything is float32 at ``HIGHEST``, one layer at a time.  Each held
expert runs over all tokens and is weighted by its gate weight for each
token, zero where the token did not pick it: the same sum as adding the
picks token by token.  The control (``precision="fp8"``) rounds every
matmul operand, the router's included, to float8 e4m3, as the dense
reference does.

Routing is discontinuous: where a token's 8th and 9th probabilities lie
close, the served path's rounding can pick the other expert, and the two
outputs then differ by that expert's part, which is a property of MoE
and not a fault.  Such a flip moves the logits of a few positions, by a
heavy-tailed amount, and how far any error moves the served tokens off
the reference's best depends on how flat the seed's logits lie.  So
``served_gaps`` gives one number: the served tokens' mean logit gap as a
share of the control's mean gap at the same positions.  The flips of a
sound program barely move a mean, and the control's rounding, which
moves every position, sets the seed's scale; a program that rounds as
coarsely as the control reads about 1.  It also reports the router
margins (8th less 9th probability) of the compared positions: how many
(position, layer) picks are near-ties, and those of the position with
the largest gap beside the typical position's, in ``last_report`` (with
the per-token gaps) and on standard error.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np


def _load_dense():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "dense_decoder.py")
    spec = importlib.util.spec_from_file_location("moe_reference_dense_decoder", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dense = _load_dense()

# a pick whose margin over the next expert is below this share of its own
# probability is a near-tie: rounding of the order of bf16 can flip it
NEAR_TIE = 0.01

# the latest served_gaps call's router margins, for calibration scripts
last_report: dict = {}


# ------------------------------------------------------------- weights
def _leaf_init(path: str, shape, key, dtype):
    """``dense_decoder``'s draws, plus the 4-D expert stacks ``(layers,
    experts, fan-in, fan-out)`` at 1/sqrt(fan-in), one layer at a time."""
    if len(shape) != 4:
        return dense._leaf_init(path, shape, key, dtype)
    std = 1.0 / math.sqrt(shape[2])
    return jax.lax.map(
        lambda k: (std * jax.random.normal(k, shape[1:], jnp.float32)).astype(dtype),
        jax.random.split(key, shape[0]))


def make_weights(shapes, key):
    """All weights in one jitted call on the device, from ``key``, in the
    dtype of ``shapes`` (a tree of ``ShapeDtypeStruct``)."""
    names, leaves, treedef = dense._paths(shapes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return [_leaf_init(n, s.shape, k, s.dtype)
                for n, s, k in zip(names, leaves, keys)]

    return jax.tree_util.tree_unflatten(treedef, make(key))


# ------------------------------------------------------------ reference
@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _experts(x, w, m, precision):
    """x plus the held experts' part of the routed FFN, for x (B, L, D)
    float32; also each position's router margin (8th less 9th
    probability) over its 8th probability, and the picked experts
    (B, L, top_k)."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    k, off = m["top_k"], m["held_offset"]
    a = dense._rms(x, f32(w["ln2"]), m["norm_eps"])
    probs = jax.nn.softmax(dense._mm(a, f32(w["router"]), precision), axis=-1)
    top, idx = jax.lax.top_k(probs, k + 1)
    rel = (top[..., k - 1] - top[..., k]) / top[..., k - 1]
    gate = top[..., :k] / jnp.sum(top[..., :k], axis=-1, keepdims=True)

    def expert(y, e_w):
        e, wg, wu, wd = e_w
        c = jnp.sum(jnp.where(idx[..., :k] == off + e, gate, 0.0), axis=-1)
        h = jax.nn.silu(dense._mm(a, f32(wg), precision)) * dense._mm(a, f32(wu), precision)
        return y + c[..., None] * dense._mm(h, f32(wd), precision), None

    held = w["w_gate"].shape[0]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (jnp.arange(held), w["w_gate"], w["w_up"], w["w_down"]))
    return x + y, rel, idx[..., :k]


def _layer_weights(params, i, d):
    """Layer ``i``'s attention weights as ``dense_decoder._layer`` takes
    them (a zero FFN, so it returns x plus attention), and its experts."""
    blk = params["blocks"]
    attn, moe = blk["attn"], blk["moe"]
    zero = jnp.zeros((d, 1), jnp.float32)
    w = {"ln1": blk["ln1"]["scale"][i], "ln2": blk["ln2"]["scale"][i],
         "wq": attn["wq"][i], "wk": attn["wk"][i], "wv": attn["wv"][i],
         "wo": attn["wo"][i], "w_gate": zero, "w_up": zero, "w_down": zero.T}
    if "q_norm" in attn:
        w["q_norm"], w["k_norm"] = attn["q_norm"][i], attn["k_norm"][i]
    e = {"ln2": blk["ln2"]["scale"][i], "router": moe["router"][i],
         "w_gate": moe["w_gate"][i], "w_up": moe["w_up"][i], "w_down": moe["w_down"][i]}
    return w, e


def _check_shapes(params, m):
    moe = params["blocks"]["moe"]
    got = (moe["router"].shape[-1], moe["w_gate"].shape[1], moe["w_gate"].shape[-1])
    want = (m["n_experts"], m["n_held"], m["d_ff"])
    if got != want:
        raise ValueError(f"served experts (routed, held, width) {got} != the "
                         f"configuration's {want}")


def hidden_at(params, m, seqs, rows, precision="f32", block=4, picks=None):
    """Final hidden states (before the last norm) of each sequence in
    ``seqs`` at positions ``rows[i]``, as one (N, D) float32 array, and at
    those positions the smallest router margin over the layers relative
    to the 8th probability and the number of layers whose pick is a
    near-tie (two (N,) arrays).  A list given
    as ``picks`` gets, per sequence, the experts each layer picked at
    each of its positions, ``(layers, len(seq), top_k)``."""
    m = dense._frozen(m)
    _check_shapes(params, m)
    longest = max(len(s) for s in seqs)
    l = 1 << max(4, (longest - 1).bit_length())
    out, rel, ties = [], [], []
    with jax.default_matmul_precision("highest"):
        for b0 in range(0, len(seqs), block):
            part = seqs[b0: b0 + block]
            toks = np.zeros((block, l), np.int32)
            for j, s in enumerate(part):
                toks[j, : len(s)] = s
            x = jnp.take(params["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
            low = jnp.full((block, l), jnp.inf)
            near = jnp.zeros((block, l), jnp.int32)
            chosen = []
            for i in range(m["n_layers"]):
                w, e = _layer_weights(params, i, m["d_model"])
                x = dense._layer(x, w, m, precision)
                x, mr, idx = _experts(x, e, m, precision)
                low, near = jnp.minimum(low, mr), near + (mr < NEAR_TIE)
                chosen.append(idx)
            if picks is not None:
                chosen = np.stack([np.asarray(c) for c in chosen], axis=1)
                picks += [chosen[j, :, : len(s)] for j, s in enumerate(part)]
            for j in range(len(part)):
                r = np.asarray(rows[b0 + j])
                out.append(x[j, r])
                rel.append(np.asarray(low[j, r]))
                ties.append(np.asarray(near[j, r]))
    return jnp.concatenate(out, axis=0), np.concatenate(rel), np.concatenate(ties)


def served_gaps(params, m, prompts, outputs, control=None):
    """Teacher-forced check of served greedy tokens.  At the position that
    produced each served token the reference reads the gap by which that
    token's logit lies below its best logit, as
    ``dense_decoder.served_gaps`` does, and the gap of the token that the
    control (``control``, else ``"fp8"``) ranks first there.  Returns the
    served tokens' mean gap over the control's mean gap as a one-element
    array; with ``control`` given, also the control's own reading (1, or
    0 where it never leaves the best).  ``last_report`` keeps the means,
    the per-token gaps and the router margins of the compared positions;
    a summary goes to standard error."""
    seqs = [list(p) + list(o[:-1]) for p, o in zip(prompts, outputs)]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(o)) for p, o in zip(prompts, outputs)]
    served = np.concatenate([np.asarray(o, np.int64) for o in outputs])
    hc, _, _ = hidden_at(params, m, seqs, rows, precision=control or "fp8")
    _, ctrl_top, _ = dense.logits_reduce(params, m, hc, [], precision=control or "fp8")
    del hc
    hid, rel, ties = hidden_at(params, m, seqs, rows)
    best, _, picked = dense.logits_reduce(params, m, hid, [served, ctrl_top])
    gaps, cgaps = np.asarray(best - picked[0]), np.asarray(best - picked[1])
    worst = int(np.argmax(gaps))
    last_report.clear()
    last_report.update({
        "positions": int(gaps.shape[0]), "layers": int(m["n_layers"]),
        "mean_gap": float(np.mean(gaps)), "control_mean_gap": float(np.mean(cgaps)),
        "largest_gap": float(gaps[worst]), "control_largest_gap": float(np.max(cgaps)),
        "tokens_off_best": int(np.sum(gaps > 0)),
        "control_tokens_off_best": int(np.sum(cgaps > 0)),
        "near_ties": int(np.sum(ties)),
        "positions_with_near_tie": int(np.sum(ties > 0)),
        "median_smallest_margin": float(np.median(rel)),
        "largest_gap_near_ties": int(ties[worst]),
        "largest_gap_smallest_margin": float(rel[worst]),
    })
    print("router margins: " + ", ".join(f"{k} {v}" for k, v in last_report.items()),
          file=sys.stderr, flush=True)
    last_report.update({"gaps": gaps, "control_gaps": cgaps,
                        "near_ties_at": np.asarray(ties), "smallest_margin_at": np.asarray(rel)})
    scale = max(float(np.mean(cgaps)), 1e-30)
    share = np.asarray([np.mean(gaps) / scale])
    return share if control is None else (share, np.asarray([np.mean(cgaps) / scale]))

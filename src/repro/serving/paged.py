"""Block-paged KV cache with static shapes, plus the paged decode/prefill
steps for dense-attention LMs.

Layout: the physical KV store is ``(n_layers, n_pages, page_size, kv_heads,
head_dim)``.  A slot's logical KV window is ``pages_per_slot =
ceil(max_len / page_size)`` pages, mapped through a ``page_table`` row of
physical page ids; the logical window length is ``T = pages_per_slot *
page_size``.  Every shape is static — slots grow and shrink purely by
rewriting the (tiny, host-side) page table and per-slot ``pos``.

Physical pages ``[0, pool_pages)`` form the shared allocation pool;
pages ``[pool_pages, pool_pages + slots)`` are per-slot *garbage pages*:
an idle slot's page-table row points at its own garbage page, so the
always-full-batch decode step's KV writes from dead slots land in
disjoint junk rows (never a scatter collision with a live slot, which
keeps runs deterministic) and are never read.

Decode attention reads the pool in place.  A slot's history is its rows
at positions ``< pos``; a page is *live* while it holds one of them, so a
slot has ``ceil(pos / page_size)`` live pages and an empty slot (``pos ==
0``) none.  The score and value programs take the step's whole input
pool as ``Paged(pool, layer, page_table, pos)``: their kernel DMAs a
slot's live pages, and only those, through the page table into VMEM, at
the stored dtype, and widens them to f32 after the load
(``lower_pallas._emit_paged``).  This step's own key and value join as
one more score column and ``p_new * v_new``.  A score past ``pos`` is
unspecified and is selected away (``jnp.where``), never multiplied; the
values kernel selects the rows past ``pos`` to zero before its dot, so a
skipped page, or a recycled page's stale or NaN rows, add exactly
nothing.  Recycled pages therefore need no zeroing, and greedy decode
through the paged path reproduces the dense-cache reference decode
token-for-token (asserted in tests/test_serving_engine.py and
tests/test_paged_attention.py).  The layer scan carries no pages: it
returns each layer's new rows, and after it each slot's rows of every
layer go into the pool in one in-place update.  The engine jits the step
with the pools donated, so the output pools are the input buffers with
the new rows written (no copy of either pool); the input arrays are
consumed by the call.

Both steps run one layer body (``_run_layers``) over the ``(batch,
rows)`` layout, decode's ``(S, 1)`` and prefill's ``(1, Lb)``; they
differ only in their attention and in where the new KV rows go (decode:
into the output pool after the layer scan; prefill: into the layer's
pages inside it).  The body calls its blocks through one interface
(``run_qkv``, ``run_attn_out``, ``run_mlp``, ``run_experts``), which the
Stripe-compiled programs of :mod:`repro.serving.stripe_decode` offer and,
in plain jnp, :class:`PlainBlocks` (the prefill of a bucket whose compile
is quarantined).  Both compute in float32, matching the reference
attention path's upcast.  The layer loop scans the layer index and the
norm scales (prefill's also the KV pages); the matmul weights stay whole,
stacked ``(n_layers, ...)`` in their stored dtype, and reach the blocks as
``Stacked(weight, layer)``: the kernels read the layer's slice in place
and promote it to f32 tile by tile, so a step copies no weight.

Routed experts (``cfg.moe``) take the FFN's place: the router's softmax
over all experts in f32, top-k, the k gate weights renormalised to sum
to 1; the (token, expert) pairs whose expert this layer holds are sorted
by expert into a row buffer in blocks of one expert each
(``stripe_decode.moe_rows``), the grouped program runs the held experts'
FFN over it, reading each block's expert weights in place from the
``(n_layers * held, ...)`` stack (``Stacked`` with one index per block),
and each row, scaled by its gate weight, is added back to its token.  No
pair is dropped.  Padding tokens (prefill positions past the prompt,
decode slots with no request, ``pos == 0``) route nowhere.  The MoE
steps also return, after their tokens, the step's (token, held expert)
pairs and held experts with a pair, summed over layers.
"""
from __future__ import annotations

from typing import List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.lower_jnp import Paged, Stacked
from ..models import lm
from ..nn.attention import NEG_INF, causal_mask, mha
from ..nn.core import _ACT, apply_norm, apply_rope, embed_lookup, rms_head_norm
from ..reliability import faults
from .stripe_decode import DecodePrograms, Weight, moe_rows


# --------------------------------------------------------------- page pool
class PagePool:
    """Host-side allocator over the shared physical page pool.

    Allocation and release are O(pages) list ops on python ints — the
    device never sees the free list, only the rewritten page tables.
    LIFO reuse keeps the hot pages hot and is deterministic.
    """

    def __init__(self, pool_pages: int, slots: int):
        self.pool_pages = int(pool_pages)
        self.slots = int(slots)
        self._free: List[int] = list(range(self.pool_pages))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def total_pages(self) -> int:
        """Physical pages including the per-slot garbage pages."""
        return self.pool_pages + self.slots

    def garbage_page(self, slot: int) -> int:
        return self.pool_pages + slot

    def can_alloc(self, n: int) -> bool:
        return len(self._free) >= n

    def alloc(self, n: int) -> Optional[List[int]]:
        if faults.fires("paged.alloc", n=n, free=len(self._free)):
            # injected transient allocation failure: report exhaustion;
            # the engine defers the admission instead of crashing
            return None
        if len(self._free) < n:
            return None
        got = self._free[-n:]
        del self._free[-n:]
        return got

    def release(self, pages: List[int]) -> None:
        for p in pages:
            if not (0 <= p < self.pool_pages):
                raise ValueError(f"released page {p} outside the pool")
        self._free.extend(pages)


def pages_needed(plen: int, new_tokens: int, page_size: int) -> int:
    """Pages a request occupies over its whole lifetime: KV rows are
    written for positions ``[0, plen + new_tokens - 1)`` (the last
    emitted token is never written back)."""
    rows = plen + max(new_tokens, 1) - 1
    return -(-rows // page_size)


def init_pages(cfg, total_pages: int, page_size: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    dtype = jnp.dtype(cfg.dtype)
    shape = (cfg.n_layers, total_pages, page_size, cfg.n_kv_heads, cfg.hd)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


# ----------------------------------------------------------- plain blocks
def _f32(w: Weight) -> jnp.ndarray:
    return (w.select() if isinstance(w, Stacked) else w).astype(jnp.float32)


def _proj(x2d: jnp.ndarray, w: Weight) -> jnp.ndarray:
    return jnp.einsum("bd,de->be", x2d.astype(jnp.float32), _f32(w))


class PlainBlocks:
    """The layer's blocks in plain jnp, called as the programs are
    (:class:`~repro.serving.stripe_decode.DecodePrograms`): each weight,
    as stored, is selected and cast to f32 outside the dot.  A prefill
    bucket whose compile is quarantined serves through these."""

    def __init__(self, act: str):
        self.act = act

    def run_qkv(self, x2d, wq: Weight, wk: Weight, wv: Weight):
        return _proj(x2d, wq), _proj(x2d, wk), _proj(x2d, wv)

    def run_attn_out(self, attn2d, resid2d, wo: Weight):
        return _proj(attn2d, wo) + resid2d.astype(jnp.float32)

    def run_mlp(self, x2d, resid2d, p):
        x2d = x2d.astype(jnp.float32)
        if "w_gate" in p:
            a = _ACT[self.act.split("_")[0]](_proj(x2d, p["w_gate"])) * _proj(x2d, p["w_up"])
        else:
            a = _ACT[self.act](_proj(x2d, p["w_up"]))
        return _proj(a, p["w_down"]) + resid2d.astype(jnp.float32)

    def run_experts(self, rows, wg: Stacked, wu: Stacked, wd: Stacked):
        g = jnp.einsum("nrd,ndf->nrf", rows, _f32(wg))
        u = jnp.einsum("nrd,ndf->nrf", rows, _f32(wu))
        return jnp.einsum("nrf,nfd->nrd", _ACT[self.act.split("_")[0]](g) * u, _f32(wd))


Blocks = Union[DecodePrograms, PlainBlocks]


# ----------------------------------------------------------- layer weights
# per-layer matmul weights, read in place from the stacked parameter
_MATMUL = {"attn": ("wq", "wk", "wv", "wo"), "mlp": ("w_gate", "w_up", "w_down"),
           "moe": ("w_gate", "w_up", "w_down")}


def _split_blocks(blocks):
    """(matmul weights, everything else) of the stacked per-layer tree:
    the weights stay out of the layer scan's inputs, so no per-layer slice
    of them is made; the rest (norm scales) is scanned as before."""
    mats = {g: {k: v for k, v in blocks[g].items() if k in names}
            for g, names in _MATMUL.items() if g in blocks}
    rest = {g: ({k: v for k, v in sub.items() if k not in _MATMUL[g]}
                if g in _MATMUL else sub)
            for g, sub in blocks.items()}
    return mats, rest


def _layer_params(mats, rest_i, i):
    """Layer ``i``'s parameter tree: ``rest_i`` (the scanned slices) with
    each matmul weight as ``Stacked(weight, i)``."""
    p_i = {g: dict(sub) for g, sub in rest_i.items()}
    for g, sub in mats.items():
        if g == "moe":  # selected per block of rows (_moe_ffn)
            continue
        for k, w in sub.items():
            p_i[g][k] = Stacked(w, i)
    return p_i


# ----------------------------------------------------------- routed experts
_HI = jax.lax.Precision.HIGHEST


def _route(cfg, x2: jnp.ndarray, router: jnp.ndarray, valid: jnp.ndarray):
    """Gate weights ``(m, k)`` and the held expert of each pick (``held``
    where the expert is held elsewhere or the token is padding)."""
    moe = cfg.moe
    logits = jnp.einsum("md,de->me", x2, router.astype(jnp.float32), precision=_HI)
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), moe.top_k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    local = idx - moe.held_offset
    keep = (local >= 0) & (local < moe.held) & valid[:, None]
    return gate, jnp.where(keep, local, moe.held).astype(jnp.int32)


def _dispatch(expert: jnp.ndarray, held: int, bm: int, nb: int):
    """Sort the pairs by expert into ``nb`` blocks of ``bm`` rows, each
    expert's rows padded to whole blocks.  Returns each pair's row (the
    sink row ``nb * bm`` for pairs not held), the pair feeding each row,
    each block's expert and whether it holds a row, and each expert's
    pair count."""
    flat = expert.reshape(-1)
    n_rows = nb * bm
    onehot = (flat[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    counts = jnp.sum(onehot, axis=0)
    e = jnp.minimum(flat, held - 1)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), e[:, None], axis=1)[:, 0] - 1
    padded = (counts + bm - 1) // bm * bm
    ends = jnp.cumsum(padded)
    row = jnp.where(flat < held, ends[e] - padded[e] + rank, n_rows)
    pair = jnp.zeros(n_rows + 1, jnp.int32).at[row].set(
        jnp.arange(flat.shape[0], dtype=jnp.int32))[:n_rows]
    start = jnp.arange(nb, dtype=jnp.int32) * bm
    block_expert = jnp.minimum(jnp.searchsorted(ends, start, side="right"), held - 1)
    return (row.reshape(expert.shape), pair, block_expert.astype(jnp.int32),
            start < ends[-1], counts)


def _moe_ffn(cfg, blocks: Blocks, x2: jnp.ndarray, resid: jnp.ndarray, experts,
             router: jnp.ndarray, layer, valid: jnp.ndarray):
    """Routed experts plus the residual, ``(m, d)`` float32, and the
    layer's (pairs, experts hit).  ``experts`` holds the whole
    ``(n_layers, held, ...)`` stacks; each block reads its expert in
    place through ``Stacked(stack, layer * held + expert, live)``."""
    held, k = cfg.moe.held, cfg.moe.top_k
    m = x2.shape[0]
    bm, nb = moe_rows(cfg, m)
    x2 = x2.astype(jnp.float32)
    gate, expert = _route(cfg, x2, router, valid)
    row, pair, block_expert, live, counts = _dispatch(expert, held, bm, nb)
    rows = x2[pair // k].reshape(nb, bm, x2.shape[1])
    w = {n: Stacked(a.reshape((-1,) + a.shape[2:]), layer * held + block_expert, live)
         for n, a in experts.items()}
    y = blocks.run_experts(rows, w["w_gate"], w["w_up"], w["w_down"])
    # a block with no row holds nothing defined: only rows of pairs (and
    # the zero sink row) are read back
    y = jnp.concatenate([y.reshape(nb * bm, -1), jnp.zeros((1, y.shape[-1]), y.dtype)])
    out = jnp.einsum("mk,mkd->md", gate, y[row], precision=_HI)
    stats = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0)]).astype(jnp.int32)
    return out + resid.astype(jnp.float32), stats


# ------------------------------------------------------------- the layers
def _run_layers(cfg, blocks: Blocks, params, x, positions, valid, attend, per_layer=()):
    """The served transformer layers over ``x`` ``(batch, rows, d)``,
    decode's ``(S, 1)`` or prefill's ``(1, Lb)``: norm, qkv, qk-norm, rope
    at ``positions`` ``(batch * rows,)``, attention, ``attn_out`` plus the
    residual, norm, then the MLP or the routed experts plus the residual,
    every block through ``blocks``.

    ``attend(i, q, k, v, *per_layer_i)`` is the step's own attention: it
    returns the attention rows ``(batch * rows, heads * hd)`` and what the
    step keeps of layer ``i`` (its new KV rows, or its pages).
    ``per_layer`` are arrays with a leading layer axis, scanned beside the
    layers and handed to ``attend``.  ``valid()`` gives the rows ``(batch *
    rows,)`` that route to experts, computed in the layer beside the
    routing that reads it.  Returns ``x``, the stacked kept values, and
    each layer's (pairs, experts hit), or None without routed experts."""
    b, r = x.shape[:2]
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    mats, rest = _split_blocks(params["blocks"])
    # the blocks take rows (batch * rows, d): index away the axis of size
    # one (decode's rows, prefill's batch), and put it back
    unit = (slice(None),) * (0 if b == 1 else 1)
    flat = lambda a: a[unit + (0,)]  # noqa: E731
    wide = lambda a: a[unit + (None,)]  # noqa: E731

    def layer(x, scanned):
        i, rest_i, *mine = scanned
        p = _layer_params(mats, rest_i, i)
        ap = p["attn"]
        xn = apply_norm(p["ln1"], x, cfg.norm)
        q2, k2, v2 = blocks.run_qkv(flat(xn), ap["wq"], ap["wk"], ap["wv"])
        q = q2.reshape(b, r, h, hd)
        k = k2.reshape(b, r, kv, hd)
        v = v2.reshape(b, r, kv, hd)
        if cfg.qk_norm:
            q = rms_head_norm(q, ap["q_norm"])
            k = rms_head_norm(k, ap["k_norm"])
        q = apply_rope(q, wide(positions), cfg.rope, cfg.rope_theta)
        k = apply_rope(k, wide(positions), cfg.rope, cfg.rope_theta)
        a2, kept = attend(i, q, k, v, *mine)
        x1 = blocks.run_attn_out(a2, flat(x), ap["wo"]).astype(x.dtype)
        xn2 = apply_norm(p["ln2"], wide(x1), cfg.norm)
        if cfg.moe:
            y, st = _moe_ffn(cfg, blocks, flat(xn2), x1, mats["moe"], p["moe"]["router"],
                             i, valid())
            kept += (st,)
        else:
            y = blocks.run_mlp(flat(xn2), x1, p["mlp"])
        return wide(y.astype(x.dtype)), kept

    x, kept = jax.lax.scan(
        layer, x, (jnp.arange(cfg.n_layers, dtype=jnp.int32), rest, *per_layer))
    if cfg.moe:
        return x, kept[:-1], kept[-1]
    return x, kept, None


# ------------------------------------------------------------ decode step
def make_decode_step(cfg, progs: DecodePrograms, page_size: int):
    """Build the (jit-friendly) continuous decode step.

    Signature: ``fn(params, pages_k, pages_v, page_table, pos, tok) ->
    (next_tok, pages_k, pages_v)`` with ``page_table (S, PPS) int32``,
    ``pos (S,) int32`` (per-slot lengths; 0 for a slot with no request),
    ``tok (S,) int32``.  With routed experts ``next_tok`` is ``(S + 2,)``:
    the tokens, then the step's pairs and experts hit (module docstring).
    The returned pools are the input pools with one row a slot written;
    jitted with ``donate_argnums=(1, 2)`` (as the engine does) the write
    is in place and the inputs are consumed, without donation XLA first
    copies both pools.
    """
    ps = int(page_size)
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    g = h // kv
    sm_scale = 1.0 / np.sqrt(hd)

    def step(params, pages_k, pages_v, page_table, pos, tok):
        s = page_table.shape[0]
        x = embed_lookup(params["embed"], tok[:, None])  # (S, 1, D)
        cur_page = jnp.take_along_axis(page_table, (pos // ps)[:, None], axis=1)[:, 0]
        kpos = jnp.arange(page_table.shape[1] * ps, dtype=jnp.int32)
        history = (kpos[None, :] < pos[:, None])[:, None, None, :]

        def attend(i, q, k, v):
            # history (positions < pos) read in place from the step's input
            # pool, live pages only; this position's row joins as one more
            # score column
            k_row = k[:, 0].astype(pages_k.dtype)  # as the pool stores them
            v_row = v[:, 0].astype(pages_v.dtype)
            qg = q[:, 0].reshape(s, kv, g, hd).astype(jnp.float32)
            k_new, v_new = k_row.astype(jnp.float32), v_row.astype(jnp.float32)
            hist_k = Paged(pages_k, i, table=page_table, lengths=pos)
            hist_v = Paged(pages_v, i, table=page_table, lengths=pos)
            s_hist = progs.paged_scores({"Q": qg, "K": hist_k})["S"] * sm_scale
            s_new = jnp.einsum("bkgd,bkd->bkg", qg, k_new, precision=_HI) * sm_scale
            # a row past pos holds no defined score: select, never scale
            s_hist = jnp.where(history, s_hist, NEG_INF)
            top = jnp.maximum(jnp.max(s_hist, axis=-1), s_new)
            e_hist = jnp.where(history, jnp.exp(s_hist - top[..., None]), 0.0)
            e_new = jnp.exp(s_new - top)
            total = jnp.sum(e_hist, axis=-1) + e_new
            o = (progs.paged_values({"P": e_hist / total[..., None], "V": hist_v})["O"]
                 + (e_new / total)[..., None] * v_new[:, :, None, :])
            return o.reshape(s, h * hd), (k_row, v_row)

        x, (k_rows, v_rows), st = _run_layers(cfg, progs, params, x, pos,
                                              lambda: pos > 0, attend)
        # every layer's new row of each slot written at once, one in-place
        # update a slot (dead slots into their garbage pages: disjoint)
        for j in range(s):
            at = (0, cur_page[j], pos[j] % ps, 0, 0)
            pages_k = jax.lax.dynamic_update_slice(pages_k, k_rows[:, j, None, None], at)
            pages_v = jax.lax.dynamic_update_slice(pages_v, v_rows[:, j, None, None], at)
        logits = lm._logits(params, cfg, x)  # (S, 1, V)
        nxt = jnp.argmax(logits[:, -1, : cfg.vocab], axis=-1).astype(jnp.int32)
        if st is not None:
            nxt = jnp.concatenate([nxt, jnp.sum(st, axis=0)])
        return nxt, pages_k, pages_v

    return step


# ----------------------------------------------------------------- prefill
def make_prefill_step(cfg, blocks: Blocks, page_size: int, bucket_len: int):
    """Build the batch-1 paged prefill for one compile bucket.

    Signature: ``fn(params, tokens (1, Lb), length (int32 scalar),
    page_row (PPS,) int32, pages_k, pages_v) -> (first_tok scalar,
    pages_k, pages_v)``; with routed experts ``first_tok`` is ``(3,)``:
    the token, then the prefill's pairs and experts hit.  Tokens are
    right-padded to the bucket; rows at positions ``>= length`` scatter
    junk into the slot's own allocated / garbage pages, which attention
    masks, and which decode overwrites in-place before each position ever
    becomes visible.
    """
    ps = int(page_size)
    lb = int(bucket_len)
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    sm_scale = 1.0 / np.sqrt(hd)

    def step(params, tokens, length, page_row, pages_k, pages_v):
        n_phys = pages_k.shape[1]
        x = embed_lookup(params["embed"], tokens)  # (1, Lb, D)
        t = jnp.arange(lb, dtype=jnp.int32)
        write_rows = page_row[t // ps] * ps + t % ps  # (Lb,)
        cmask = causal_mask(lb)

        def attend(i, q, k, v, pk, pv):
            flat_k = pk.reshape(n_phys * ps, kv, hd).at[write_rows].set(
                k[0].astype(pk.dtype))
            flat_v = pv.reshape(n_phys * ps, kv, hd).at[write_rows].set(
                v[0].astype(pv.dtype))
            out = mha(q, k, v, cmask, sm_scale)  # causal full-sequence
            return out.reshape(lb, h * hd), (flat_k.reshape(pk.shape),
                                             flat_v.reshape(pv.shape))

        x, (pages_k, pages_v), st = _run_layers(
            cfg, blocks, params, x, t, lambda: t < length, attend, (pages_k, pages_v))
        x_last = jax.lax.dynamic_slice(x, (0, length - 1, 0), (1, 1, x.shape[-1]))
        logits = lm._logits(params, cfg, x_last)  # (1, 1, V)
        tok = jnp.argmax(logits[0, 0, : cfg.vocab]).astype(jnp.int32)
        if st is not None:
            tok = jnp.concatenate([tok[None], jnp.sum(st, axis=0)])
        return tok, pages_k, pages_v

    return step

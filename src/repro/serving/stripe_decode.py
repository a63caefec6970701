"""Decode-time attention/MLP blocks as Stripe programs.

The serving engine's decode step is not one opaque ``jax.jit`` over the
model: its dense blocks are expressed in the Tile frontend and compiled
through ``stripe_jit`` — frontend → fusion groups → memory planning →
backend — so decode traffic exercises the whole compiler, and every
compile leaves a :class:`~repro.core.driver.CompileRecord` (fusion
groups, kernel counts, per-block backend choices and fallback reasons)
that the engine surfaces via ``compile_records()``.

The programs cover one transformer layer at decode time (``m`` = rows
flowing through the block: the slot count for decode, the padded bucket
length for prefill):

* ``qkv``    — the three attention input projections sharing one operand;
* ``paged_scores`` — the GQA score contraction ``S[b,k,g,t] += Q·K``
  over a slot's KV history (decode only; softmax stays outside — it is
  not a contraction);
* ``paged_values`` — the GQA value contraction ``O[b,k,g,d] += P·V``;

  both declare K or V ``paged`` in the pool's own row order ``(b, t, k,
  d)`` and take it as ``Paged(pool, layer, page_table, lengths)``: the
  kernel reads each slot's live pages where they lie, at the stored
  dtype, and nothing past the slot's length (``lower_pallas._emit_paged``;
  the cost model picks its pages per block).  ``build_scores_program`` /
  ``build_values_program`` keep the earlier form over a head-major f32
  window ``(b, k, t, d)``, which decode no longer runs;
* ``attn_out`` — output projection fused with the residual add;
* ``mlp``    — the FFN with its activation chain fused between the
  matmuls when the activation is exactly representable as Stripe
  intrinsics (``silu``/``relu``/``relu2`` and their GLU forms); for
  activations whose framework semantics differ from the intrinsic
  (tanh-approximated ``gelu``), the matmuls compile through Stripe and
  the activation runs outside, recorded in ``act_outside``;
* ``moe``    — in place of ``mlp`` for routed experts: the GLU FFN of the
  held experts over a buffer of rows sorted by expert, in blocks of
  ``bm`` rows that each meet one expert (``moe_rows``).  The expert
  weights are declared ``indexed`` with one row per block and handed as
  ``Stacked(experts, index per block, live per block)``, so each block
  reads its expert's weights where they lie and a block with no row
  computes and fetches nothing.

Operands: activations, residuals and outputs are float32 (matching the
reference attention path, which upcasts for scores/values), and so is
every accumulator.  The matmul weights (``WEIGHT_INPUTS``) are declared
at the configuration's dtype, the dtype they are stored in: a bf16
weight crosses HBM and the DMA as bf16 and the kernel promotes each tile
to f32 after the load.  A caller may hand a weight in place as
``Stacked(stacked, layer)``, the layer loop's ``(n_layers, ...)``
parameter plus the layer index: the kernels read the layer's slice where
it lies, so no per-step copy of any weight is made.  Both show in each
program's ``CompileRecord.stored_reads``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax.numpy as jnp

from ..core import cache as _cache
from ..core.driver import CompiledProgram, CompileRecord, stripe_jit
from ..core.frontend import TileProgram
from ..core.hwconfig import HardwareConfig
from ..core.lower_jnp import Stacked

# activations whose Stripe intrinsic chain is semantically identical to
# the framework's nn.core._ACT implementation (see module docstring)
_FUSABLE_ACT = {
    "silu": "silu({x})",
    "relu": "relu({x})",
    "relu2": "square(relu({x}))",
}

# the programs' matmul weight inputs, declared at the stored dtype
WEIGHT_INPUTS = ("WQ", "WK", "WV", "WO", "Wg", "Wu", "Wd")

Weight = Union[jnp.ndarray, Stacked]


def _jit_opts(cfg: "EngineLikeConfig") -> Dict:
    return dict(backend=cfg.backend, interpret=cfg.interpret,
                use_disk=cfg.use_disk, cache=cfg.cache, profile=cfg.profile,
                tune=cfg.tune)


@dataclasses.dataclass
class EngineLikeConfig:
    """The compile-relevant knobs, decoupled from EngineConfig."""

    hw: HardwareConfig
    backend: str = "jnp"
    interpret: Optional[bool] = None  # None: compiled on a TPU only
    use_disk: bool = True
    cache: Optional[_cache.CompilationCache] = None
    profile: bool = False
    tune: Any = None  # a repro.tune.TuningDB, or None


@dataclasses.dataclass
class DecodePrograms:
    """Stripe-compiled callables for one row-count ``m`` plus records.

    The served layer (``paged._run_layers``) calls its blocks through
    ``run_qkv``, ``run_attn_out`` and ``run_mlp`` (each with the residual
    where the block has one) or ``run_experts``; ``paged.PlainBlocks``
    offers the same four calls in plain jnp.  Weights go in as stored (an
    array at the declared dtype, or ``Stacked``); activations and
    residuals are cast to the programs' f32."""

    m: int
    qkv: Callable
    attn_out: Callable
    mlp: Optional[Callable]     # None where the FFN is routed experts
    act_outside: Optional[str]  # activation applied outside the program, if any
    records: Dict[str, CompileRecord]
    # decode only: attention over the KV pages in place (window T, page)
    paged_scores: Optional[CompiledProgram] = None
    paged_values: Optional[CompiledProgram] = None
    moe: Optional[CompiledProgram] = None  # routed experts, in place of mlp

    def run_qkv(self, x2d: jnp.ndarray, wq: Weight, wk: Weight, wv: Weight):
        out = self.qkv({"X": x2d.astype(jnp.float32), "WQ": wq, "WK": wk, "WV": wv})
        return out["Q"], out["K"], out["V"]

    def run_attn_out(self, attn2d: jnp.ndarray, resid2d: jnp.ndarray, wo: Weight):
        out = self.attn_out({"A": attn2d.astype(jnp.float32),
                             "R": resid2d.astype(jnp.float32), "WO": wo})
        return out["Y"]

    def run_mlp(self, x2d: jnp.ndarray, resid2d: jnp.ndarray, p):
        """The (possibly split) MLP program plus the residual, matching
        nn.core.mlp_apply; ``p`` holds the weights as stored (``w_gate``
        for a GLU)."""
        from ..nn.core import _ACT

        x2d = x2d.astype(jnp.float32)
        resid2d = resid2d.astype(jnp.float32)
        mlp = self.mlp
        if isinstance(mlp, _SplitMLP):
            if mlp.glu:
                got = mlp.up({"X": x2d, "Wg": p["w_gate"], "Wu": p["w_up"]})
                a = _ACT[self.act_outside](got["G"]) * got["U"]
            else:
                got = mlp.up({"X": x2d, "Wu": p["w_up"]})
                a = _ACT[self.act_outside](got["H"])
            return mlp.down({"A": a, "R": resid2d, "Wd": p["w_down"]})["Y"]
        arrays = {"X": x2d, "R": resid2d, "Wd": p["w_down"], "Wu": p["w_up"]}
        if "w_gate" in p:
            arrays["Wg"] = p["w_gate"]
        return mlp(arrays)["Y"]

    def run_experts(self, rows: jnp.ndarray, wg: Stacked, wu: Stacked,
                    wd: Stacked) -> jnp.ndarray:
        """The held experts' FFN over the row buffer ``(blocks, bm, d)``;
        the weights as ``Stacked`` with one expert per block."""
        return self.moe({"X": rows.astype(jnp.float32), "Wg": wg, "Wu": wu,
                         "Wd": wd})["Y"]


def build_qkv_program(cfg, m: int, jc: EngineLikeConfig) -> CompiledProgram:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    tp = TileProgram(f"serve_qkv_m{m}")
    tp.input("X", (m, d))
    tp.input("WQ", (d, h * hd), cfg.dtype)
    tp.input("WK", (d, kv * hd), cfg.dtype)
    tp.input("WV", (d, kv * hd), cfg.dtype)
    tp.output("Q", (m, h * hd))
    tp.output("K", (m, kv * hd))
    tp.output("V", (m, kv * hd))
    tp.op("Q[b, e] += X[b, d] * WQ[d, e]", name="proj_q")
    tp.op("K[b, e] += X[b, d] * WK[d, e]", name="proj_k")
    tp.op("V[b, e] += X[b, d] * WV[d, e]", name="proj_v")
    return stripe_jit(tp.build(), jc.hw, **_jit_opts(jc))


def build_attn_out_program(cfg, m: int, jc: EngineLikeConfig) -> CompiledProgram:
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.hd
    tp = TileProgram(f"serve_attn_out_m{m}")
    tp.input("A", (m, h * hd))
    tp.input("R", (m, d))
    tp.input("WO", (h * hd, d), cfg.dtype)
    tp.temp("T", (m, d))
    tp.output("Y", (m, d))
    tp.op("T[b, d2] += A[b, e] * WO[e, d2]", name="proj_o")
    tp.op("Y[b, d2] = T[b, d2] + R[b, d2]", name="resid")
    return stripe_jit(tp.build(), jc.hw, **_jit_opts(jc))


def build_mlp_program(cfg, m: int, jc: EngineLikeConfig):
    """Returns (compiled, act_outside).  The activation chain is fused
    into the program when exactly representable; otherwise the program
    carries the matmuls and the caller applies the activation between
    ``H`` (and ``G`` for GLU) and the down-projection."""
    d, f = cfg.d_model, cfg.d_ff
    act = cfg.act
    glu = act.endswith("_glu")
    base = act.split("_")[0] if glu else act
    fused = base in _FUSABLE_ACT
    tp = TileProgram(f"serve_mlp_m{m}")
    tp.input("X", (m, d))
    tp.input("R", (m, d))
    tp.input("Wd", (f, d), cfg.dtype)
    if glu:
        tp.input("Wg", (d, f), cfg.dtype)
        tp.input("Wu", (d, f), cfg.dtype)
        if fused:
            tp.temp("G", (m, f))
            tp.temp("U", (m, f))
            tp.temp("A", (m, f))
            tp.op("G[b, f] += X[b, d] * Wg[d, f]", name="mm_gate")
            tp.op("U[b, f] += X[b, d] * Wu[d, f]", name="mm_up")
            gexpr = _FUSABLE_ACT[base].format(x="G[b, f]")
            tp.op(f"A[b, f] = {gexpr} * U[b, f]", name="glu")
            inner = "A"
        else:
            # matmuls through Stripe, activation outside: split programs
            return _split_glu_programs(cfg, m, jc), base
    else:
        tp.input("Wu", (d, f), cfg.dtype)
        if fused:
            tp.temp("H", (m, f))
            tp.temp("A", (m, f))
            tp.op("H[b, f] += X[b, d] * Wu[d, f]", name="mm_up")
            tp.op(f"A[b, f] = {_FUSABLE_ACT[base].format(x='H[b, f]')}", name="act")
            inner = "A"
        else:
            return _split_plain_programs(cfg, m, jc), base
    tp.temp("O", (m, d))
    tp.output("Y", (m, d))
    tp.op(f"O[b, d2] += {inner}[b, f] * Wd[f, d2]", name="mm_down")
    tp.op("Y[b, d2] = O[b, d2] + R[b, d2]", name="resid")
    return stripe_jit(tp.build(), jc.hw, **_jit_opts(jc)), None


def _split_glu_programs(cfg, m: int, jc: EngineLikeConfig):
    """GLU MLP with the activation outside: an up program producing G and
    U, and a down program applying Wd + residual."""
    d, f = cfg.d_model, cfg.d_ff
    up = TileProgram(f"serve_mlp_up_m{m}")
    up.input("X", (m, d)); up.input("Wg", (d, f), cfg.dtype)
    up.input("Wu", (d, f), cfg.dtype)
    up.output("G", (m, f)); up.output("U", (m, f))
    up.op("G[b, f] += X[b, d] * Wg[d, f]", name="mm_gate")
    up.op("U[b, f] += X[b, d] * Wu[d, f]", name="mm_up")
    down = _down_program(cfg, m, jc)
    cup = stripe_jit(up.build(), jc.hw, **_jit_opts(jc))
    return _SplitMLP(cup, down, glu=True)


def _split_plain_programs(cfg, m: int, jc: EngineLikeConfig):
    d, f = cfg.d_model, cfg.d_ff
    up = TileProgram(f"serve_mlp_up_m{m}")
    up.input("X", (m, d)); up.input("Wu", (d, f), cfg.dtype)
    up.output("H", (m, f))
    up.op("H[b, f] += X[b, d] * Wu[d, f]", name="mm_up")
    cup = stripe_jit(up.build(), jc.hw, **_jit_opts(jc))
    return _SplitMLP(cup, _down_program(cfg, m, jc), glu=False)


def _down_program(cfg, m: int, jc: EngineLikeConfig) -> CompiledProgram:
    d, f = cfg.d_model, cfg.d_ff
    tp = TileProgram(f"serve_mlp_down_m{m}")
    tp.input("A", (m, f)); tp.input("R", (m, d)); tp.input("Wd", (f, d), cfg.dtype)
    tp.temp("O", (m, d))
    tp.output("Y", (m, d))
    tp.op("O[b, d2] += A[b, f] * Wd[f, d2]", name="mm_down")
    tp.op("Y[b, d2] = O[b, d2] + R[b, d2]", name="resid")
    return stripe_jit(tp.build(), jc.hw, **_jit_opts(jc))


@dataclasses.dataclass
class _SplitMLP:
    """Two stripe programs with the activation applied by the caller."""

    up: CompiledProgram
    down: CompiledProgram
    glu: bool

    @property
    def records(self):
        return {"mlp_up": self.up.record, "mlp_down": self.down.record}


def moe_rows(cfg, m: int) -> Tuple[int, int]:
    """(rows per block ``bm``, blocks) of the grouped expert program for
    ``m`` token rows.  A block meets one expert; ``bm`` is the rows an
    expert meets on average (8 to 128).  The buffer holds every pair of a
    token and a held expert it picks (at most ``m * min(top_k, held)``)
    plus one block of padding per held expert, so no token is dropped
    however the router sends them."""
    moe = cfg.moe
    bm, want = 8, -(-m * moe.top_k // moe.n_experts)
    while bm < min(want, 128):
        bm *= 2
    pairs = m * min(moe.top_k, moe.held)
    return bm, -(-pairs // bm) + moe.held


def build_moe_program(cfg, m: int, jc: EngineLikeConfig) -> CompiledProgram:
    """The held experts' GLU FFN over the sorted row buffer: ``G = X·Wg``,
    ``U = X·Wu``, ``A = act(G)·U``, ``Y = A·Wd``, one expert per block of
    ``bm`` rows (``moe_rows``)."""
    d, f = cfg.d_model, cfg.moe.d_ff_expert
    base = cfg.act.split("_")[0]
    if not cfg.act.endswith("_glu") or base not in _FUSABLE_ACT:
        raise ValueError(f"{cfg.name}: served experts need a fusable GLU "
                         f"activation, not {cfg.act!r}")
    bm, nb = moe_rows(cfg, m)
    tp = TileProgram(f"serve_moe_m{m}")
    tp.input("X", (nb, bm, d))
    tp.input("Wg", (nb, d, f), cfg.dtype, indexed=True)
    tp.input("Wu", (nb, d, f), cfg.dtype, indexed=True)
    tp.input("Wd", (nb, f, d), cfg.dtype, indexed=True)
    tp.temp("G", (nb, bm, f))
    tp.temp("U", (nb, bm, f))
    tp.temp("A", (nb, bm, f))
    tp.output("Y", (nb, bm, d))
    tp.op("G[n, r, f] += X[n, r, d] * Wg[n, d, f]", name="mm_gate")
    tp.op("U[n, r, f] += X[n, r, d] * Wu[n, d, f]", name="mm_up")
    gexpr = _FUSABLE_ACT[base].format(x="G[n, r, f]")
    tp.op(f"A[n, r, f] = {gexpr} * U[n, r, f]", name="glu")
    tp.op("Y[n, r, d2] += A[n, r, f] * Wd[n, f, d2]", name="mm_down")
    return stripe_jit(tp.build(), jc.hw, **_jit_opts(jc))


def build_scores_program(cfg, m: int, t: int, jc: EngineLikeConfig) -> CompiledProgram:
    kv, hd = cfg.n_kv_heads, cfg.hd
    g = cfg.n_heads // kv
    tp = TileProgram(f"serve_scores_m{m}_t{t}")
    tp.input("Q", (m, kv, g, hd))
    tp.input("K", (m, kv, t, hd))
    tp.output("S", (m, kv, g, t))
    tp.op("S[b, k, g, t] += Q[b, k, g, d] * K[b, k, t, d]", name="scores")
    return stripe_jit(tp.build(), jc.hw, **_jit_opts(jc))


def build_values_program(cfg, m: int, t: int, jc: EngineLikeConfig) -> CompiledProgram:
    kv, hd = cfg.n_kv_heads, cfg.hd
    g = cfg.n_heads // kv
    tp = TileProgram(f"serve_values_m{m}_t{t}")
    tp.input("P", (m, kv, g, t))
    tp.input("V", (m, kv, t, hd))
    tp.output("O", (m, kv, g, hd))
    tp.op("O[b, k, g, d] += P[b, k, g, t] * V[b, k, t, d]", name="values")
    return stripe_jit(tp.build(), jc.hw, **_jit_opts(jc))


def build_paged_scores_program(cfg, m: int, t: int, page_size: int,
                               jc: EngineLikeConfig) -> CompiledProgram:
    """``S[b, k, g, t] += Q[b, k, g, d] * K[b, t, k, d]`` with ``K`` the
    slots' keys in the page pool (``paged``, at the stored dtype, handed
    as ``Paged``): only each slot's live pages are read, and the scores of
    rows past its length are unspecified."""
    kv, hd = cfg.n_kv_heads, cfg.hd
    g = cfg.n_heads // kv
    tp = TileProgram(f"serve_paged_scores_m{m}_t{t}")
    tp.input("Q", (m, kv, g, hd))
    tp.input("K", (m, t, kv, hd), cfg.dtype, paged=page_size)
    tp.output("S", (m, kv, g, t))
    tp.op("S[b, k, g, t] += Q[b, k, g, d] * K[b, t, k, d]", name="paged_scores")
    return stripe_jit(tp.build(), jc.hw, **_jit_opts(jc))


def build_paged_values_program(cfg, m: int, t: int, page_size: int,
                               jc: EngineLikeConfig) -> CompiledProgram:
    """``O[b, k, g, d] += P[b, k, g, t] * V[b, t, k, d]`` with ``V`` in the
    page pool (``Paged``): rows past a slot's length add nothing, whatever
    their ``P`` and whatever a recycled page holds there."""
    kv, hd = cfg.n_kv_heads, cfg.hd
    g = cfg.n_heads // kv
    tp = TileProgram(f"serve_paged_values_m{m}_t{t}")
    tp.input("P", (m, kv, g, t))
    tp.input("V", (m, t, kv, hd), cfg.dtype, paged=page_size)
    tp.output("O", (m, kv, g, hd))
    tp.op("O[b, k, g, d] += P[b, k, g, t] * V[b, t, k, d]", name="paged_values")
    return stripe_jit(tp.build(), jc.hw, **_jit_opts(jc))


def build_programs(cfg, m: int, jc: EngineLikeConfig,
                   kv_window: Optional[int] = None,
                   page_size: Optional[int] = None) -> DecodePrograms:
    """Compile the serving block programs for row count ``m``.

    ``kv_window`` and ``page_size`` (the logical paged-KV length T and
    the rows per page) add the decode-only paged score/value
    contractions; prefill callers leave them None (their attention is
    the causal full-sequence einsum).
    """
    qkv = build_qkv_program(cfg, m, jc)
    attn_out = build_attn_out_program(cfg, m, jc)
    records: Dict[str, CompileRecord] = {
        "qkv": qkv.record, "attn_out": attn_out.record,
    }
    mlp = moe = act_outside = None
    if cfg.moe:
        moe = build_moe_program(cfg, m, jc)
        records["moe"] = moe.record
    else:
        mlp, act_outside = build_mlp_program(cfg, m, jc)
        if isinstance(mlp, _SplitMLP):
            records.update(mlp.records)
        else:
            records["mlp"] = mlp.record
    scores = values = None
    if kv_window is not None:
        scores = build_paged_scores_program(cfg, m, kv_window, page_size, jc)
        values = build_paged_values_program(cfg, m, kv_window, page_size, jc)
        records["paged_scores"] = scores.record
        records["paged_values"] = values.record
    return DecodePrograms(m=m, qkv=qkv, attn_out=attn_out, mlp=mlp,
                          act_outside=act_outside, records=records,
                          paged_scores=scores, paged_values=values, moe=moe)

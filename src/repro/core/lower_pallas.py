"""Pallas backend: lower optimized (tiled/stenciled/fused) Stripe blocks to
``pl.pallas_call`` with explicit ``BlockSpec`` VMEM tiling.

TPU adaptation of Stripe's hardware lowering (see DESIGN.md): Stripe's
refinement-with-location (explicit DMA between memory units) maps to the
declarative BlockSpec (block shape + index_map); the optimization passes
*choose* the BlockSpec parameters:

* the grid = the outer ("grid") block's iteration space, ordered so
  reduction indices vary fastest (output block revisiting => VMEM-resident
  accumulation in an accumulator-dtype scratch); parallel output dimensions
  are declared via ``dimension_semantics`` so Mosaic may reorder/parallelize
  them;
* each refinement of the grid block becomes one BlockSpec: a view whose
  per-dimension offsets step in whole blocks indexes the operand directly;
  a **halo window** (offset step < block dim, or a non-zero base — the
  conv views of paper Fig. 5b) is emitted over a *materialized* operand:
  the overlapping tiles are gathered once per input (pad + strided gather,
  halo rows duplicated by the margin/step ratio) and indexed with an
  aligned BlockSpec over the gathered array;
* a whole **fusion group** (fuse.py) executes inside a single
  ``pallas_call`` as a tile-compute graph: elementwise *prologue* DAGs
  transform the input tiles, the MXU contraction runs via
  ``jax.lax.dot_general`` with f32 accumulation kept in a VMEM scratch
  across reduction grid steps, and the *epilogue* DAG (bias/activation
  chains, diamond joins — second elementwise inputs become extra
  BlockSpecs) is applied when the final reduction step completes
  (``pl.when``);
* plain elementwise blocks lower to a map kernel (no scratch);
* **constraint-carrying blocks** (conv halos, boundary remainders from
  non-dividing tiles) take the *windowed* path: window vars (e.g. the
  3x3 filter taps) are enumerated as unrolled kernel steps, each step
  contracts a shifted slice of the input tile, and the block's
  constraints become masks over the output tile (+ ``pl.program_id`` for
  grid-var terms) — a **masked store** writes the aggregation identity at
  constrained-out points.  Blocks the ``boundary`` pass proved
  constraint-free (tag ``interior``) skip the masks and lower densely.

``lower_program_hybrid`` lowers every op block / fusion group to Pallas
**independently**: a unit that cannot lower falls back to the jnp backend
for just that unit (``lower_jnp.lower_group_jnp`` on its semantic member
blocks), and units are composed in wavefront order.  One bad block no
longer costs the whole program its kernels.  ``lower_program_pallas``
keeps the strict contract (any unsupported block raises
``UnsupportedPallas``).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import re
import time
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import memplan
from .ir import (Block, Constant, Intrinsic, Load, Program, Refinement,
                 RefDir, Store, TensorDecl)
from .lower_jnp import _J_BINARY, _J_UNARY, Paged, Stacked, _acc_dtype, select_stacked

MAX_WINDOW_STEPS = 512           # unrolled kernel steps per grid point
MAX_HALO_BYTES = 256 * 2**20     # materialized (gathered) operand budget
# scoped VMEM Mosaic gives a kernel that asks for no more; a kernel is
# never asked to make do with less
MOSAIC_DEFAULT_VMEM = 16 * 2**20
# Mosaic's own scratch on top of the planned arena (intermediates of the
# kernel body, the output's second pipeline buffer): a quarter of the
# arena plus this much
VMEM_SLACK = 2 * 2**20


class UnsupportedPallas(Exception):
    pass


class _ProgramFallback(UnsupportedPallas):
    """A structural hazard no per-unit fallback can fix (e.g. two units
    accumulating into one buffer — composition by region placement would
    silently drop contributions, and the per-group jnp executor would
    clobber them the same way).  Propagates out of the hybrid composer so
    the driver falls back wholesale."""


# --------------------------------------------------------------------------
# Pattern extraction
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DimSpec:
    """One dimension of a grid-block refinement: ``base + step*var`` start,
    ``size`` extent.  ``step < size`` (or ``base != 0``) is a halo window."""

    var: Optional[str]
    step: int
    base: int
    size: int

    @property
    def is_halo(self) -> bool:
        if self.var is None:
            return self.base != 0
        return self.step != self.size or self.base != 0


@dataclasses.dataclass
class GridRef:
    ref: Refinement
    block_shape: Tuple[int, ...]
    dim_vars: Tuple[Optional[str], ...]  # grid var addressing each dim
    dims: Tuple[DimSpec, ...] = ()

    @property
    def base(self) -> Tuple[int, ...]:
        return tuple(d.base for d in self.dims)

    @property
    def halo(self) -> bool:
        return any(d.is_halo for d in self.dims)


def _grid_ref(ref: Refinement, grid_ranges: Mapping[str, int],
              allow_base: bool = False, allow_halo: bool = False) -> GridRef:
    """Parse a grid-block refinement into per-dim (var, step, base, size).

    Default (strict) mode accepts only block-aligned views (step == size,
    base == 0) — the shape a plain BlockSpec can index.  ``allow_base``
    admits a constant base (the composer places the kernel's output region
    into the buffer); ``allow_halo`` admits overlapping windows (emitted
    over a materialized operand by the windowed path)."""
    dim_vars: List[Optional[str]] = []
    dims: List[DimSpec] = []
    for e, size in zip(ref.offsets, ref.shape):
        if e.is_const():
            if e.const != 0 and not (allow_base or allow_halo):
                raise UnsupportedPallas(f"non-zero const offset {e}")
            dim_vars.append(None)
            dims.append(DimSpec(None, 0, e.const, size))
        elif len(e.terms) == 1:
            (v, c) = e.terms[0]
            if v not in grid_ranges:
                raise UnsupportedPallas(f"offset var {v} is not a grid index")
            if c <= 0:
                raise UnsupportedPallas(f"non-positive offset step in {e}")
            if not allow_halo:
                if c != size:
                    raise UnsupportedPallas(
                        f"halo view: offset step {c} != block dim {size}")
                if e.const != 0 and not allow_base:
                    raise UnsupportedPallas(f"offset base {e.const} in {e}")
            dim_vars.append(v)
            dims.append(DimSpec(v, c, e.const, size))
        else:
            raise UnsupportedPallas(f"unsupported offset {e}")
    return GridRef(ref=ref, block_shape=tuple(ref.shape),
                   dim_vars=tuple(dim_vars), dims=tuple(dims))


@dataclasses.dataclass
class _TNode:
    """A node of the tile-compute graph (prologue/elementwise DAGs).

    Deliberately mirrors ``lower_jnp._Node`` (same kinds, same intrinsic
    tables) — the two walkers must stay in sync when intrinsics or DAG
    shapes are added, but operate at different granularities (whole-tile
    arrays here vs broadcast-materialized operands there)."""

    kind: str  # 'load' | 'const' | 'op'
    buf: str = ""
    value: float = 0.0
    op: str = ""
    args: Tuple["_TNode", ...] = ()

    def loads(self):
        if self.kind == "load":
            yield self
        for a in self.args:
            yield from a.loads()


def _leaf_root(stmts) -> _TNode:
    """Rebuild the expression DAG of a leaf statement list; returns the
    node stored by the (single) Store."""
    env: Dict[str, _TNode] = {}
    root: Optional[_TNode] = None
    for s in stmts:
        if isinstance(s, Load):
            env[s.into] = _TNode("load", buf=s.buf)
        elif isinstance(s, Constant):
            env[s.into] = _TNode("const", value=s.value)
        elif isinstance(s, Intrinsic):
            try:
                args = tuple(env[a] for a in s.args)
            except KeyError as e:
                raise UnsupportedPallas(f"undefined scalar {e} in leaf")
            env[s.into] = _TNode("op", op=s.op, args=args)
        elif isinstance(s, Store):
            root = env.get(s.scalar)
        elif isinstance(s, Block):
            raise UnsupportedPallas("nested block inside leaf")
    if root is None:
        raise UnsupportedPallas("leaf has no store")
    return root


def _split_sides(root: _TNode, sig_of: Mapping[str, Tuple]
                 ) -> Tuple[List[_TNode], float]:
    """Split the stored DAG into operand sides + a constant scale:
    top-level ``mul`` factors are grouped by the index pattern of their
    loads, so an elementwise prologue (e.g. ``gelu(A[i,c]) * B[c,j]``)
    stays attached to its operand side.  Returns 1 or 2 sides."""
    factors: List[_TNode] = []
    scale = 1.0
    stack = [root]
    while stack:
        n = stack.pop(0)
        if n.kind == "op" and n.op == "mul":
            stack = list(n.args) + stack
        elif n.kind == "const":
            scale *= n.value
        else:
            factors.append(n)
    groups: Dict[Tuple, List[_TNode]] = {}
    order: List[Tuple] = []
    for n in factors:
        sigs = set()
        for l in n.loads():
            if l.buf not in sig_of:
                raise UnsupportedPallas(f"leaf operand {l.buf} is not a grid input")
            sigs.add(sig_of[l.buf])
        if len(sigs) != 1:
            raise UnsupportedPallas("mixed index patterns inside one operand")
        sig = sigs.pop()
        if sig not in groups:
            groups[sig] = []
            order.append(sig)
        groups[sig].append(n)
    if not 1 <= len(order) <= 2:
        raise UnsupportedPallas(f"{len(order)} distinct operand groups (need 1 or 2)")

    def fold(ns: List[_TNode]) -> _TNode:
        out = ns[0]
        for n in ns[1:]:
            out = _TNode("op", op="mul", args=(out, n))
        return out

    return [fold(groups[s]) for s in order], scale


def _split_contraction(root: _TNode, sig_of: Mapping[str, Tuple]) -> Tuple[_TNode, _TNode, float]:
    sides, scale = _split_sides(root, sig_of)
    if len(sides) != 2:
        raise UnsupportedPallas(f"{len(sides)} distinct operand groups (need 2)")
    return sides[0], sides[1], scale


@dataclasses.dataclass
class ContractionPlan:
    grid_order: List[str]
    grid_sizes: Dict[str, int]
    in_refs: List[GridRef]
    out_ref: GridRef
    red_vars: List[str]
    lhs: _TNode
    rhs: _TNode
    lhs_bufs: List[str]  # grid-input names feeding each side, in spec order
    rhs_bufs: List[str]
    scale: float
    lhs_contract: Tuple[int, ...]
    rhs_contract: Tuple[int, ...]
    # operand tile shapes the kernel contracts: unit dims ahead of the
    # batch dims dropped, the batch dims (shared with the output, leading
    # in both operands) merged into the one batch dim the TPU matmul takes
    lhs_shape: Tuple[int, ...]
    rhs_shape: Tuple[int, ...]
    n_batch: int
    epilogue: List[object]
    acc_scalar: Optional[str]


@dataclasses.dataclass
class ElementwisePlan:
    grid_order: List[str]
    grid_sizes: Dict[str, int]
    in_refs: List[GridRef]
    out_ref: GridRef
    root: _TNode


def _leaf_of(block: Block) -> Block:
    cur = block
    while True:
        subs = cur.sub_blocks()
        if not subs:
            return cur
        if len(subs) != 1:
            raise UnsupportedPallas("multiple inner blocks")
        cur = subs[0]


def _is_constrained(block: Block) -> bool:
    """Does any block of this tree carry constraints?  The emitter trusts
    the passes' proofs instead of re-deriving them: ``boundary`` tags the
    pieces whose constraints ``prune_constraints`` fully discharged with
    ``interior`` (the whole tree is clean — skip the walk), and
    ``stencil`` tags the tiles whose stencil fit it established on an
    unconstrained body with ``dense`` (skip that block's check)."""
    if "interior" in block.tags:
        return False
    return any(b.constraints for b in block.walk() if "dense" not in b.tags)


def _check_no_constraints(block: Block) -> None:
    for b in block.walk():
        if b.constraints:
            raise UnsupportedPallas(
                f"constraints in block {b.name} (halo/overflow tiles)")


def _ensure_grid(outer: Block) -> Block:
    """Canonicalize a flat (``fits_inner``) or per-point fused block into
    the grid->tile shape the emitter expects, by splitting its output
    indices at full range (a 1-step grid per output dim)."""
    if "grid" in outer.tags:
        return outer
    from .tiling import split_block

    out_ref = next((r for r in outer.refs if r.dir in (RefDir.OUT, RefDir.INOUT)), None)
    if out_ref is None:
        raise UnsupportedPallas("no output ref")
    free = outer.idx_ranges()
    out_vars = [n for e in out_ref.offsets for n in e.names() if n in free]
    tiles = {v: free[v] for v in out_vars}
    if not tiles:
        raise UnsupportedPallas("no output indices to grid over")
    grid = split_block(outer, tiles, name_suffix="g", full_tiles=True)
    # the split is a pure canonicalization: proofs about the flat block
    # (boundary's interior tag) hold for its grid form
    if "interior" in outer.tags:
        grid.add_tag("interior")
    return grid


def _collect(outer: Block):
    """Common scaffolding: grid refs, local allocs, the leaf block and its
    statements, epilogue."""
    grid_ranges = {i.name: i.range for i in outer.idxs if not i.is_passthrough()}
    ins: List[GridRef] = []
    out: Optional[GridRef] = None
    local_alloc: Dict[str, Refinement] = {}
    for r in outer.refs:
        if r.dir == RefDir.IN:
            ins.append(_grid_ref(r, grid_ranges))
        elif r.dir in (RefDir.OUT, RefDir.INOUT):
            if out is not None:
                raise UnsupportedPallas("multiple outputs")
            out = _grid_ref(r, grid_ranges, allow_base=True)
        elif r.dir == RefDir.NONE:
            local_alloc[r.into] = r
    if out is None:
        raise UnsupportedPallas("no output ref")

    sub_blocks = outer.sub_blocks()
    epilogue: List[object] = []
    if sub_blocks:
        for b in sub_blocks[0].walk():
            for r in b.refs:
                if r.dir == RefDir.NONE:
                    local_alloc.setdefault(r.into, r)
        # Descend levels; at each level, trailing leaf statements after a
        # sub-block are the (pure elementwise) fused epilogue, which lifts
        # soundly from per-point to per-tile granularity.
        cur: Block = outer
        while True:
            msubs = cur.sub_blocks()
            trailing = []
            seen = False
            for s in cur.stmts:
                if isinstance(s, Block):
                    seen = True
                elif seen:
                    trailing.append(s)
            if msubs and trailing:
                epilogue = trailing
                leaf = _leaf_of(msubs[0])
                break
            if not msubs:
                leaf = cur
                break
            if len(msubs) != 1:
                raise UnsupportedPallas("multiple inner blocks")
            cur = msubs[0]
    else:
        leaf = outer
    return grid_ranges, ins, out, local_alloc, leaf, epilogue


def _dim_names(ref: Refinement, tag: str) -> List[str]:
    """Name each dim of a leaf refinement by the index variable that
    addresses it; a constant offset (a unit dim) gets a name of its own."""
    names: List[str] = []
    for d, e in enumerate(ref.offsets):
        if e.is_const():
            names.append(f"{tag}#{d}")
        elif len(e.terms) == 1:
            names.append(e.terms[0][0])
        else:
            raise UnsupportedPallas(f"multi-index tile access {e}")
    return names


def _leaf_ref(leaf: Block, buf: str) -> Refinement:
    for r in leaf.refs:
        if r.from_buf == buf or r.into == buf:
            return r
    raise UnsupportedPallas(f"leaf block does not address {buf}")


def extract_contraction(outer: Block) -> ContractionPlan:
    """Plan one MXU contraction.  Operand and output dims are identified by
    the leaf block's index variables: a variable on both operands and not
    on the output is contracted, one on both operands and the output is a
    batch dim, one on a single operand and the output is free."""
    grid_ranges, ins, out, local_alloc, leaf, epilogue = _collect(outer)
    if (out.ref.agg or "assign") not in ("add", "assign"):
        # dot_general + the scratch accumulation only realize a SUM
        raise UnsupportedPallas(
            f"contraction aggregates with '{out.ref.agg}' (only add)")
    out_vars = {v for v in out.dim_vars if v}
    red_vars = [v for v in grid_ranges if v not in out_vars]
    grid_order = [v for v in grid_ranges if v in out_vars] + red_vars

    root = _leaf_root(leaf.stmts)
    sig_of = {g.ref.into: (g.dim_vars, g.block_shape) for g in ins}
    lhs, rhs, scale = _split_contraction(root, sig_of)

    acc_scalar: Optional[str] = None
    for s in epilogue:
        if isinstance(s, Load) and s.buf in local_alloc:
            acc_scalar = s.into

    def side_bufs(node: _TNode) -> List[str]:
        seen: List[str] = []
        for l in node.loads():
            if l.buf not in seen:
                seen.append(l.buf)
        return seen

    lhs_bufs, rhs_bufs = side_bufs(lhs), side_bufs(rhs)
    lhs_gr = next(g for g in ins if g.ref.into == lhs_bufs[0])
    rhs_gr = next(g for g in ins if g.ref.into == rhs_bufs[0])
    leaf_out = next((r for r in leaf.refs
                     if r.dir in (RefDir.OUT, RefDir.INOUT)), None)
    if leaf_out is None:
        raise UnsupportedPallas("leaf block has no output")
    ln = _dim_names(_leaf_ref(leaf, lhs_bufs[0]), "lhs")
    rn = _dim_names(_leaf_ref(leaf, rhs_bufs[0]), "rhs")
    on = _dim_names(leaf_out, "out")
    if len(on) != len(out.block_shape):
        raise UnsupportedPallas("output tile rank differs from its leaf view")

    contract = [n for n in ln if n in rn and n not in on]
    batch = [n for n in ln if n in rn and n in on]
    for names, gr in ((ln, lhs_gr), (rn, rhs_gr)):
        for n, size in zip(names, gr.block_shape):
            if n not in contract and n not in on and "#" not in n:
                raise UnsupportedPallas(f"one-sided reduction over {n}")
    if not contract:
        raise UnsupportedPallas("no contraction dims found")
    nb = len(batch)
    lshape, rshape = tuple(lhs_gr.block_shape), tuple(rhs_gr.block_shape)
    if nb:
        def lead(names, shape):
            k = 0
            while k < len(names) and "#" in names[k] and shape[k] == 1:
                k += 1
            if names[k:k + nb] != batch:
                raise UnsupportedPallas(
                    f"batch dims {batch} are not leading in {names}")
            if len(names) - k - nb < 2:
                raise UnsupportedPallas("batch dims reach the minor tile dims")
            batch_elems = 1
            for d in shape[k:k + nb]:
                batch_elems *= d
            return names[k + nb:], (batch_elems,) + shape[k + nb:]

        ln, lshape = lead(ln, lshape)
        rn, rshape = lead(rn, rshape)
        on = lead(on, tuple(out.block_shape))[0]
    real = lambda names: [n for n in names if "#" not in n]  # noqa: E731
    result = real([n for n in ln if n not in contract]
                  + [n for n in rn if n not in contract])
    if result != real(on):
        raise UnsupportedPallas(
            f"dot result dims {result} are not the output tile's {real(on)}")
    off = 1 if nb else 0
    return ContractionPlan(
        grid_order=grid_order, grid_sizes=grid_ranges, in_refs=ins, out_ref=out,
        red_vars=red_vars, lhs=lhs, rhs=rhs, lhs_bufs=lhs_bufs, rhs_bufs=rhs_bufs,
        scale=scale,
        lhs_contract=tuple(off + ln.index(n) for n in contract),
        rhs_contract=tuple(off + rn.index(n) for n in contract),
        lhs_shape=lshape, rhs_shape=rshape, n_batch=nb,
        epilogue=epilogue, acc_scalar=acc_scalar,
    )


def extract_elementwise(outer: Block) -> ElementwisePlan:
    grid_ranges, ins, out, _local, leaf, epilogue = _collect(outer)
    if epilogue:
        raise UnsupportedPallas("elementwise block with trailing epilogue")
    root = _leaf_root(leaf.stmts)
    # broadcast legality: each input's addressed dims must line up with the
    # trailing dims of the output tile (numpy broadcasting in the kernel)
    out_dv = list(out.dim_vars)
    for g in ins:
        dv = list(g.dim_vars)
        tail = out_dv[len(out_dv) - len(dv):] if len(dv) <= len(out_dv) else None
        if tail is None:
            raise UnsupportedPallas(f"input {g.ref.into} has higher rank than output")
        for d, v in enumerate(dv):
            if v is None and g.block_shape[d] == 1:
                continue
            if v != tail[d] and g.block_shape[d] != 1:
                raise UnsupportedPallas(
                    f"input {g.ref.into} dim {d} does not broadcast against the output")
    grid_order = [v for v in grid_ranges]
    if any(v not in {d for d in out.dim_vars if d} for v in grid_order):
        raise UnsupportedPallas("elementwise block with reduction index")
    return ElementwisePlan(grid_order=grid_order, grid_sizes=grid_ranges,
                           in_refs=ins, out_ref=out, root=root)


# --------------------------------------------------------------------------
# Windowed (halo / masked) extraction
# --------------------------------------------------------------------------
@dataclasses.dataclass
class WindowedPlan:
    """A constraint- or halo-carrying block as the windowed kernel sees it:
    grid refs (halo views allowed), the tile-level addressing of each
    input, enumerated window vars, and the constraint exprs that become
    masks over the output tile."""

    grid_order: List[str]
    grid_sizes: Dict[str, int]
    in_refs: List[GridRef]
    out_ref: GridRef
    red_vars: List[str]                      # grid vars revisiting the output
    tile_ranges: Dict[str, int]
    out_axis_vars: Tuple[Optional[str], ...]  # tile var per output dim
    inner_offsets: Dict[str, Tuple]          # ref.into -> tile-level offsets
    window_vars: List[str]
    agg: str                                 # "add" | "assign"
    sides: Optional[List[_TNode]]            # contraction sides (agg=add)
    root: Optional[_TNode]                   # full DAG (agg=assign)
    scale: float
    constraint_exprs: List                   # affine exprs, each ">= 0"


def extract_windowed(outer: Block) -> WindowedPlan:
    grid_ranges = {i.name: i.range for i in outer.idxs if not i.is_passthrough()}
    subs = outer.sub_blocks()
    if len(subs) != 1:
        raise UnsupportedPallas("windowed path needs exactly one tile block")
    if any(not isinstance(s, Block) for s in outer.stmts):
        raise UnsupportedPallas("windowed path does not support fused epilogues")
    tile = subs[0]
    if tile.sub_blocks():
        raise UnsupportedPallas("windowed path needs a flat tile block")

    ins: List[GridRef] = []
    out: Optional[GridRef] = None
    for r in outer.refs:
        if r.dir == RefDir.IN:
            ins.append(_grid_ref(r, grid_ranges, allow_halo=True))
        elif r.dir in (RefDir.OUT, RefDir.INOUT):
            if out is not None:
                raise UnsupportedPallas("multiple outputs")
            out = _grid_ref(r, grid_ranges, allow_base=True)
        elif r.dir == RefDir.NONE and not r.is_scalar_view():
            raise UnsupportedPallas("windowed path with non-scalar local view")
    if out is None:
        raise UnsupportedPallas("no output ref")
    agg = out.ref.agg or "assign"
    if agg not in ("add", "assign"):
        raise UnsupportedPallas(f"windowed path cannot aggregate with '{agg}'")

    tile_ranges = tile.idx_ranges()
    inner = {r.from_buf: r for r in tile.refs}

    # output tile addressing: one plain tile var (or const 0) per dim
    oref = inner.get(out.ref.into)
    if oref is None:
        raise UnsupportedPallas("tile block does not address the output view")
    out_axis_vars: List[Optional[str]] = []
    for e in oref.offsets:
        if e.is_const():
            if e.const != 0:
                raise UnsupportedPallas(f"non-zero inner output offset {e}")
            out_axis_vars.append(None)
        elif len(e.terms) == 1 and e.const == 0 and e.terms[0][1] == 1:
            out_axis_vars.append(e.terms[0][0])
        else:
            raise UnsupportedPallas(f"output tile offset {e} is not a plain index")
    out_vars = {v for v in out_axis_vars if v}

    # tile addressing of each input + window-var discovery
    inner_offsets: Dict[str, Tuple] = {}
    window: set = set()
    for gr in ins:
        ir = inner.get(gr.ref.into)
        if ir is None:
            raise UnsupportedPallas(f"tile block does not address input {gr.ref.into}")
        for e in ir.offsets:
            for n, c in e.terms:
                if n not in tile_ranges:
                    raise UnsupportedPallas(f"inner offset var {n} is not a tile index")
                if c <= 0:
                    raise UnsupportedPallas(f"negative inner offset step in {e}")
            names = [n for n in e.names() if tile_ranges.get(n, 1) > 1]
            if len(names) > 1:
                carriers = [n for n in names if n in out_vars] or names
                carrier = max(carriers, key=lambda n: tile_ranges[n])
                window.update(n for n in names if n != carrier)
        inner_offsets[gr.ref.into] = tuple(ir.offsets)

    # constraints close over window vars: any constraint var that is
    # neither an output-tile coordinate nor a grid index must be enumerated
    exprs = [c.expr for c in outer.constraints] + [c.expr for c in tile.constraints]
    for _ in range(4):
        extra = set()
        for e in exprs:
            for n in e.names():
                if n in out_vars or n in grid_ranges or n in window:
                    continue
                if n in tile_ranges:
                    extra.add(n)
                else:
                    raise UnsupportedPallas(f"constraint var {n} is not in scope")
        if not extra:
            break
        window |= extra
    if window & out_vars:
        raise UnsupportedPallas(
            f"window vars {sorted(window & out_vars)} address the output")
    window_vars = sorted(window)
    n_steps = 1
    for v in window_vars:
        n_steps *= tile_ranges[v]
    if n_steps > MAX_WINDOW_STEPS:
        raise UnsupportedPallas(f"window too large ({n_steps} unrolled steps)")

    out_grid_vars = {v for v in out.dim_vars if v}
    red_vars = [v for v in grid_ranges if v not in out_grid_vars]
    grid_order = [v for v in grid_ranges if v in out_grid_vars] + red_vars

    root = _leaf_root(tile.stmts)
    sides: Optional[List[_TNode]] = None
    scale = 1.0
    if agg == "add":
        sig_of = {gr.ref.into: tuple(str(e) for e in inner_offsets[gr.ref.into])
                  for gr in ins}
        sides, scale = _split_sides(root, sig_of)
        root = None
    else:
        # assign must be a pure per-point map: no enumerated windows, no
        # leftover reduction axes (a raced overwrite otherwise)
        if window_vars:
            raise UnsupportedPallas("assign block with window vars")
        if red_vars:
            raise UnsupportedPallas("assign block with grid reduction vars")
        leftover = [v for v, r in tile_ranges.items()
                    if r > 1 and v not in out_vars]
        if leftover:
            raise UnsupportedPallas(f"assign block with reduction tile vars {leftover}")

    return WindowedPlan(
        grid_order=grid_order, grid_sizes=grid_ranges, in_refs=ins, out_ref=out,
        red_vars=red_vars, tile_ranges=tile_ranges,
        out_axis_vars=tuple(out_axis_vars), inner_offsets=inner_offsets,
        window_vars=window_vars, agg=agg, sides=sides, root=root, scale=scale,
        constraint_exprs=exprs,
    )


# --------------------------------------------------------------------------
# Kernel emission
# --------------------------------------------------------------------------
def _eval_tnode(n: _TNode, tiles: Mapping[str, jnp.ndarray], dtype=None):
    if n.kind == "load":
        return tiles[n.buf]
    if n.kind == "const":
        return jnp.asarray(n.value, dtype or jnp.float32)
    args = [_eval_tnode(a, tiles, dtype) for a in n.args]
    fn = _J_UNARY[n.op] if len(args) == 1 and n.op in _J_UNARY else _J_BINARY[n.op]
    return fn(*args)


def _apply_epilogue(plan: ContractionPlan, acc, tile_args: Dict[str, jnp.ndarray]):
    env: Dict[str, jnp.ndarray] = {}
    result = acc
    for s in plan.epilogue:
        if isinstance(s, Load):
            env[s.into] = acc if s.into == plan.acc_scalar else tile_args[s.buf]
        elif isinstance(s, Constant):
            env[s.into] = jnp.asarray(s.value, acc.dtype)
        elif isinstance(s, Intrinsic):
            args = [env[a] for a in s.args]
            fn = _J_UNARY[s.op] if len(args) == 1 and s.op in _J_UNARY else _J_BINARY[s.op]
            env[s.into] = fn(*args)
        elif isinstance(s, Store):
            result = env[s.scalar]
    return result


def _compiler_params(grid_order: List[str], red_vars,
                     mp: Optional[memplan.BlockPlan],
                     vmem_cap: Optional[int],
                     blocks: Sequence[Tuple[Sequence[int], Optional[Sequence[int]],
                                            Refinement]],
                     buffers: Optional[Mapping[str, TensorDecl]]
                     ) -> "pltpu.CompilerParams":
    """Mosaic parameters of one kernel, after checking that every block
    ``(block shape, array shape, refinement)`` is aligned to the TPU
    tiling; an array shape of None is the declared buffer's.  Parallel (output-streaming) grid axes may be reordered or
    split across cores; reduction axes are 'arbitrary' because the scratch
    accumulation carries state across their steps.  The scoped VMEM limit
    is what the memory plan needs, so the planner and the compiler agree;
    a kernel needing more than the chip grants (``vmem_cap``) is refused
    here, at lowering time."""
    for shape, full, ref in blocks:
        if full is None and buffers is not None and ref.from_buf in buffers:
            full = buffers[ref.from_buf].shape
        if full is not None:
            _check_tpu_block(shape, full, ref.dtype, ref.from_buf)
    red = set(red_vars)
    sem = tuple("arbitrary" if v in red else "parallel" for v in grid_order)
    if mp is None:
        return pltpu.CompilerParams(dimension_semantics=sem)
    need = mp.peak_bytes + mp.peak_bytes // 4 + VMEM_SLACK
    if vmem_cap is not None and need > vmem_cap:
        raise UnsupportedPallas(
            f"kernel needs {need}B of VMEM (planned arena {mp.peak_bytes}B); "
            f"a kernel is granted {vmem_cap}B")
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=max(need, MOSAIC_DEFAULT_VMEM))


def _check_tpu_block(block: Sequence[int], full: Sequence[int], dtype,
                     what: str) -> None:
    """Mosaic tiles the two minor dims of every block by (sublane, 128
    lanes): each must be a multiple of the tile or span the whole array."""
    sub = 8 * max(1, 4 // np.dtype(dtype).itemsize)
    for k, tile in ((1, 128), (2, sub)):
        if len(block) >= k and block[-k] % tile and block[-k] != full[-k]:
            raise UnsupportedPallas(
                f"{what}: block {tuple(block)} of {tuple(full)} is not aligned "
                f"to the ({sub}, 128) TPU tiling")


def _index_map_for(gr: GridRef, gpos: Mapping[str, int]):
    def imap(*gidx):
        return tuple(gidx[gpos[v]] if v is not None else 0 for v in gr.dim_vars)
    return imap


def _operand(arrays: Mapping[str, object], buf: str):
    """(array, :class:`Stacked` or None) of one kernel input: a
    ``Stacked`` input is handed whole, with the indices that select it."""
    a = arrays[buf]
    if isinstance(a, Stacked):
        return jnp.asarray(a.array), a
    return jnp.asarray(a), None


def _lead_axis(gr: GridRef, gpos: Mapping[str, int]) -> Optional[int]:
    """The grid axis whose index addresses the leading dim of a view."""
    v = gr.dim_vars[0] if gr.dim_vars else None
    return gpos[v] if v is not None else None


def _live_source(live: jnp.ndarray) -> jnp.ndarray:
    """For each block, the block whose operands a step reads: itself where
    live, else the last live block before it, else the first after it
    (0 where none is live)."""
    k = live.shape[0]
    at = jnp.arange(k, dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(live, at, -1), axis=0)
    nxt = jax.lax.cummin(jnp.where(live, at, k), axis=0, reverse=True)
    return jnp.where(prev >= 0, prev, jnp.where(nxt < k, nxt, 0)).astype(jnp.int32)


def _promote_pair(a: jnp.ndarray, b: jnp.ndarray):
    """Two float contraction operands of different dtypes meet at the
    wider one, in the kernel body after the load: a weight stored in
    bf16 crosses HBM and the DMA as bf16 and the dot sees the same f32
    values a cast outside the kernel would have made."""
    if (a.dtype == b.dtype or not jnp.issubdtype(a.dtype, jnp.floating)
            or not jnp.issubdtype(b.dtype, jnp.floating)):
        return a, b
    wide = jnp.promote_types(a.dtype, b.dtype)
    return a.astype(wide), b.astype(wide)


def _kernel_launcher(kernel: Callable, grid: Tuple[int, ...],
                     in_blocks: Sequence[Tuple[Tuple[int, ...], Callable, Optional[int]]],
                     out_block: Tuple[int, ...], out_imap: Callable,
                     out_shape: jax.ShapeDtypeStruct, scratch: Sequence,
                     interpret: bool, name: Optional[str], kwargs: Mapping):
    """``launch(operands)`` for one kernel, ``operands`` being one
    ``(array, Stacked or None)`` per ``in_blocks`` entry (block shape,
    index map, the grid axis that addresses the leading dim or None).
    With no ``Stacked`` operand it is the plain ``pallas_call``.  A
    selected operand is handed whole and its indices ride in scalar
    prefetch (``PrefetchScalarGridSpec``), so the kernel reads its tiles
    where they lie and no slice is ever copied:

    * a scalar index (``"one"``): the block gains a squeezed leading dim
      and the index map leads with the index;
    * an index vector (``"each"``): the block's leading dim is 1 and its
      block index ``j`` becomes ``index[j]``.  With ``live`` flags, a step
      of a dead block runs no kernel body, and every index map of it
      (inputs and output) points where a neighbouring live step points,
      the last step of the live block before it or the first of the one
      after, so the pipeline fetches and writes back nothing new.  That
      needs the selected axis to be the outermost one that varies, and
      a launch with live flags on any other axis raises.

    One ``pallas_call`` is built per pattern of selected operands."""
    built: Dict[Tuple, Callable] = {}
    n_grid = len(grid)

    def build(kinds: Tuple[Optional[str], ...], live_axis: Optional[int]) -> Callable:
        if not any(kinds):
            return pl.pallas_call(
                kernel, grid=grid,
                in_specs=[pl.BlockSpec(b, m) for b, m, _ in in_blocks],
                out_specs=pl.BlockSpec(out_block, out_imap), out_shape=out_shape,
                scratch_shapes=list(scratch), interpret=interpret, name=name,
                **kwargs)
        # prefetch: the scalar indices stacked in one vector, then one
        # vector per "each" operand, then the live blocks' sources and flags
        ones = "one" in kinds
        slots, k = [], int(ones)
        for kind in kinds:
            if kind == "each":
                slots.append(k)
                k += 1
            else:
                slots.append(None)
        n_pre = k + (2 if live_axis is not None else 0)
        one_at = iter(range(kinds.count("one")))

        def steps(gidx, pre):
            if live_axis is None:
                return gidx
            src, live = pre[-2], pre[-1]
            j = gidx[live_axis]
            s = src[j]
            on = live[j] != 0
            before = s > j
            return tuple(
                s if a == live_axis else
                g if a < live_axis else
                jnp.where(on, g, jnp.where(before, 0, grid[a] - 1))
                for a, g in enumerate(gidx))

        def spec(block, imap, kind=None, slot=None, one=None):
            def index(*a):
                gidx, pre = a[:n_grid], a[n_grid:]
                bi = imap(*steps(gidx, pre))
                if kind == "one":
                    return (pre[0][one], *bi)
                if kind == "each":
                    return (pre[slot][bi[0]], *bi[1:])
                return bi
            blk = (pl.squeezed, *block) if kind == "one" else block
            return pl.BlockSpec(blk, index)

        in_specs = [spec(b, m, kind, slot, next(one_at) if kind == "one" else None)
                    for (b, m, _), kind, slot in zip(in_blocks, kinds, slots)]

        def body(*args):
            pre, refs = args[:n_pre], args[n_pre:]
            if live_axis is None:
                kernel(*refs)
                return
            pl.when(pre[-1][pl.program_id(live_axis)] != 0)(lambda: kernel(*refs))

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre, grid=grid, in_specs=in_specs,
            out_specs=spec(out_block, out_imap), scratch_shapes=list(scratch))
        return pl.pallas_call(body, grid_spec=grid_spec, out_shape=out_shape,
                              interpret=interpret, name=name, **kwargs)

    def live_axis_of(operands, kinds) -> Tuple[Optional[int], object]:
        axis, live = None, None
        for (block, _, lead), (_, st), kind in zip(in_blocks, operands, kinds):
            if kind != "each":
                continue
            if lead is None or block[0] != 1:
                raise ValueError(
                    f"kernel {name}: an operand selected per block needs its "
                    f"leading dim on the grid in blocks of 1 (declare it "
                    f"indexed); got block {tuple(block)}")
            if st.live is not None and live is None:
                axis, live = lead, st.live
        # a dead block repeats its live neighbour's indices, which holds
        # only where the selected axis is the outermost that varies
        if axis is not None and any(grid[a] > 1 for a in range(axis)):
            raise ValueError(
                f"kernel {name}: live flags need the per-block axis outermost "
                f"on the grid; it is axis {axis} of grid {tuple(grid)}")
        return axis, live

    def launch(operands: Sequence[Tuple[jnp.ndarray, object]]) -> jnp.ndarray:
        kinds = tuple(None if st is None else
                      ("one" if jnp.ndim(st.index) == 0 else "each")
                      for _, st in operands)
        live_axis, live = live_axis_of(operands, kinds)
        key = (kinds, live_axis)
        if key not in built:
            built[key] = build(*key)
        arrays = [a for a, _ in operands]
        if not any(kinds):
            return built[key](*arrays)
        pre = []
        if "one" in kinds:
            pre.append(jnp.stack([jnp.asarray(st.index, jnp.int32).reshape(())
                                  for (_, st), kind in zip(operands, kinds)
                                  if kind == "one"]))
        pre += [jnp.asarray(st.index, jnp.int32)
                for (_, st), kind in zip(operands, kinds) if kind == "each"]
        if live_axis is not None:
            flags = jnp.asarray(live) != 0
            pre += [_live_source(flags), flags.astype(jnp.int32)]
        return built[key](*pre, *arrays)

    return launch


def _halo_spec(gr: GridRef, grid_sizes: Mapping[str, int],
               buf_shape: Tuple[int, ...], gpos: Mapping[str, int]):
    """Emission plan for a halo-windowed input: ``prepare`` gathers the
    overlapping tiles once per input (pad to cover the base/overflow, then
    a strided gather per grid-addressed dim — halo rows materialized once,
    duplicated by the margin/step ratio), and the returned BlockSpec
    indexes the gathered array block-aligned (leading grid axes of extent
    1)."""
    dims = gr.dims
    lead_vars = [d.var for d in dims if d.var is not None]
    pads = []
    total = 1
    for d, bdim in zip(dims, buf_shape):
        g = grid_sizes[d.var] if d.var is not None else 1
        lo = d.base
        hi = d.base + (d.step * (g - 1) if d.var is not None else 0) + d.size
        pads.append((max(0, -lo), max(0, hi - bdim)))
        total *= g * d.size if d.var is not None else d.size
    if total * np.dtype(gr.ref.dtype).itemsize > MAX_HALO_BYTES:
        raise UnsupportedPallas(
            f"materialized halo view of {gr.ref.from_buf} too large "
            f"({total} elems)")

    def prepare(arr: jnp.ndarray) -> jnp.ndarray:
        if any(p != (0, 0) for p in pads):
            arr = jnp.pad(arr, pads)
        lead = 0
        for i, d in enumerate(dims):
            start = d.base + pads[i][0]
            if d.var is None:
                arr = jax.lax.slice_in_dim(arr, start, start + d.size,
                                           axis=lead + i)
            else:
                g = grid_sizes[d.var]
                idx = start + d.step * jnp.arange(g)[:, None] + jnp.arange(d.size)[None, :]
                arr = jnp.take(arr, idx, axis=lead + i)
                arr = jnp.moveaxis(arr, lead + i, lead)
                lead += 1
        return arr

    block_shape = (1,) * len(lead_vars) + tuple(d.size for d in dims)

    def imap(*gidx):
        return tuple(gidx[gpos[v]] for v in lead_vars) + (0,) * len(dims)

    return prepare, block_shape, imap


def _tile_slice(arr: jnp.ndarray, exprs, tile_ranges: Mapping[str, int],
                wenv: Mapping[str, int]) -> Tuple[jnp.ndarray, List[str]]:
    """Static slice of a tile for one window position: each offset expr,
    after substituting the window vars, must reduce to ``c*v + k`` or a
    constant.  Returns (sliced array, axis var names)."""
    index: List[object] = []
    axes: List[str] = []
    for e in exprs:
        ep = e.partial_eval(wenv)
        if ep.is_const():
            index.append(ep.const)
            continue
        if len(ep.terms) != 1:
            raise UnsupportedPallas(f"multi-var tile access {ep} after windowing")
        (v, c), k = ep.terms[0], ep.const
        r = tile_ranges[v]
        index.append(slice(k, k + c * (r - 1) + 1, c))
        axes.append(v)
    return arr[tuple(index)], axes


def _eval_plain(n: _TNode, sliced: Mapping[str, Tuple], dtype):
    """Evaluate a one-sided DAG on sliced tiles (all loads of a side share
    one index signature, so shapes agree elementwise)."""
    if n.kind == "load":
        return sliced[n.buf][0]
    if n.kind == "const":
        return jnp.asarray(n.value, dtype)
    args = [_eval_plain(a, sliced, dtype) for a in n.args]
    fn = _J_UNARY[n.op] if len(args) == 1 and n.op in _J_UNARY else _J_BINARY[n.op]
    return fn(*args)


def _eval_dag_axes(n: _TNode, sliced: Mapping[str, Tuple],
                   tile_ranges: Mapping[str, int], dtype):
    """Evaluate a full (assign) DAG on sliced tiles, threading axis names
    and broadcasting args onto the union axis order."""
    if n.kind == "load":
        return sliced[n.buf]
    if n.kind == "const":
        return jnp.asarray(n.value, dtype), []
    vals = [_eval_dag_axes(a, sliced, tile_ranges, dtype) for a in n.args]
    union: List[str] = []
    for _, ax in vals:
        for v in ax:
            if v not in union:
                union.append(v)
    bargs = []
    for arr, ax in vals:
        if not ax:
            bargs.append(arr)
            continue
        perm = [ax.index(v) for v in union if v in ax]
        a = jnp.transpose(arr, perm)
        a = a.reshape([tile_ranges[v] if v in ax else 1 for v in union])
        bargs.append(a)
    fn = _J_UNARY[n.op] if len(bargs) == 1 and n.op in _J_UNARY else _J_BINARY[n.op]
    return fn(*bargs), union


def _contract_sides(sides_vals: List[Tuple[jnp.ndarray, List[str]]],
                    out_vars: set, acc_dtype) -> Tuple[jnp.ndarray, List[str]]:
    """Contract 1 or 2 evaluated sides: shared non-output axes feed
    ``dot_general`` (shared output axes batch), leftover non-output axes
    are summed out."""
    if len(sides_vals) == 1:
        val, axes = sides_vals[0]
        val = val.astype(acc_dtype)
    else:
        (la, lax), (ra, rax) = sides_vals
        shared = [v for v in lax if v in rax]
        contract = [v for v in shared if v not in out_vars]
        batch = [v for v in shared if v in out_vars]
        dn = ((tuple(lax.index(v) for v in contract),
               tuple(rax.index(v) for v in contract)),
              (tuple(lax.index(v) for v in batch),
               tuple(rax.index(v) for v in batch)))
        la, ra = _promote_pair(la, ra)
        val = jax.lax.dot_general(la, ra, dn, preferred_element_type=acc_dtype)
        axes = batch + [v for v in lax if v not in shared] + \
            [v for v in rax if v not in shared]
    extra = [v for v in axes if v not in out_vars]
    if extra:
        val = jnp.sum(val, axis=tuple(axes.index(v) for v in extra))
        axes = [v for v in axes if v in out_vars]
    return val, axes


def _emit_windowed(plan: WindowedPlan, interpret: bool,
                   mp: Optional[memplan.BlockPlan] = None,
                   buffers: Optional[Mapping[str, TensorDecl]] = None,
                   vmem_cap: Optional[int] = None,
                   name: Optional[str] = None) -> Callable:
    grid = tuple(plan.grid_sizes[v] for v in plan.grid_order)
    gpos = {v: i for i, v in enumerate(plan.grid_order)}
    out_block = plan.out_ref.block_shape
    out_dtype = np.dtype(plan.out_ref.ref.dtype)
    acc_dtype = _acc_dtype(plan.out_ref.ref.dtype)
    has_red = bool(plan.red_vars)
    if mp is not None and ((mp.acc_bytes > 0) != has_red
                           or set(mp.red_vars) != set(plan.red_vars)):
        raise UnsupportedPallas(
            f"memory plan disagrees with emitter: plan acc={mp.acc_bytes}B "
            f"red={sorted(mp.red_vars)} vs emitter red={sorted(plan.red_vars)}")

    preps: List[Tuple[Optional[Callable], Tuple[int, ...]]] = []
    in_blocks = []
    for gr in plan.in_refs:
        if gr.halo:
            if buffers is None or gr.ref.from_buf not in buffers:
                raise UnsupportedPallas(
                    f"halo view of {gr.ref.from_buf} needs the buffer shape")
            prep, bshape, imap = _halo_spec(
                gr, plan.grid_sizes, tuple(buffers[gr.ref.from_buf].shape), gpos)
            preps.append((prep, bshape))
            in_blocks.append((bshape, imap, None))
        else:
            preps.append((None, gr.block_shape))
            in_blocks.append((gr.block_shape, _index_map_for(gr, gpos),
                              _lead_axis(gr, gpos)))
    out_full_shape = tuple(
        s * (plan.grid_sizes[v] if v else 1)
        for s, v in zip(out_block, plan.out_ref.dim_vars))

    combos = list(itertools.product(
        *[range(plan.tile_ranges[v]) for v in plan.window_vars])) or [()]
    out_vars = {v for v in plan.out_axis_vars if v}
    out_axis_pos = {v: d for d, v in enumerate(plan.out_axis_vars) if v}
    cast_ints = np.dtype(out_dtype).kind in "iu"
    has_mask = bool(plan.constraint_exprs)

    def to_out_block(val: jnp.ndarray, axes: List[str]) -> jnp.ndarray:
        target = [v for v in plan.out_axis_vars if v is not None and v in axes]
        perm = [axes.index(v) for v in target]
        if perm != list(range(len(axes))):
            val = jnp.transpose(val, perm)
        shape = [plan.tile_ranges[v] if (v is not None and v in axes) else 1
                 for v in plan.out_axis_vars]
        return jnp.broadcast_to(val.reshape(shape), out_block)

    def step_mask(wenv: Mapping[str, int]):
        mask = None
        for e in plan.constraint_exprs:
            ep = e.partial_eval(wenv)
            if ep.is_const():
                if ep.const >= 0:
                    continue
                m = jnp.zeros(out_block, jnp.bool_)
            else:
                acc = jnp.full(out_block, ep.const, jnp.int32)
                for n, c in ep.terms:
                    if n in out_axis_pos:
                        acc = acc + c * jax.lax.broadcasted_iota(
                            jnp.int32, out_block, out_axis_pos[n])
                    else:
                        acc = acc + c * pl.program_id(gpos[n])
                m = acc >= 0
            mask = m if mask is None else mask & m
        return mask

    def kernel(*refs):
        if has_red:
            *ins, out_ref, acc_ref = refs
        else:
            *ins, out_ref = refs
            acc_ref = None
        tiles = {}
        for (prep, _bshape), gr, ref in zip(preps, plan.in_refs, ins):
            t = ref[...]
            if t.shape != gr.block_shape:
                t = t.reshape(gr.block_shape)
            tiles[gr.ref.into] = t
        total = None
        for combo in combos:
            wenv = dict(zip(plan.window_vars, combo))
            sliced = {}
            for gr in plan.in_refs:
                arr, axes = _tile_slice(tiles[gr.ref.into],
                                        plan.inner_offsets[gr.ref.into],
                                        plan.tile_ranges, wenv)
                if cast_ints:
                    arr = arr.astype(acc_dtype)
                sliced[gr.ref.into] = (arr, axes)
            if plan.sides is not None:
                vals = []
                for side in plan.sides:
                    axes = next((sliced[l.buf][1] for l in side.loads()), [])
                    vals.append((_eval_plain(side, sliced, acc_dtype), axes))
                val, axes = _contract_sides(vals, out_vars, acc_dtype)
            else:
                val, axes = _eval_dag_axes(plan.root, sliced,
                                           plan.tile_ranges, acc_dtype)
            val = to_out_block(val, axes).astype(acc_dtype)
            if plan.scale != 1.0:
                val = val * jnp.asarray(plan.scale, acc_dtype)
            if has_mask:
                mask = step_mask(wenv)
                if mask is not None:
                    # masked store: constrained-out points contribute the
                    # aggregation identity (0 for add; assign buffers are
                    # zero-initialized, paper Fig. 4's "overflow elements
                    # removed by constraints")
                    val = jnp.where(mask, val, jnp.zeros_like(val))
            total = val if total is None else total + val
        if has_red:
            first = functools.reduce(
                jnp.logical_and,
                [pl.program_id(gpos[v]) == 0 for v in plan.red_vars])
            last = functools.reduce(
                jnp.logical_and,
                [pl.program_id(gpos[v]) == plan.grid_sizes[v] - 1
                 for v in plan.red_vars])

            @pl.when(first)
            def _init():
                acc_ref[...] = jnp.zeros(out_block, acc_dtype)

            acc_ref[...] += total

            @pl.when(last)
            def _flush():
                out_ref[...] = acc_ref[...].astype(out_ref.dtype)
        else:
            out_ref[...] = total.astype(out_ref.dtype)

    kwargs = {}
    if not interpret:
        # a materialized halo operand is the kernel's own array: only the
        # views taken straight from a buffer are checked against it
        blocks = [(bshape, None, gr.ref) for (prep, bshape), gr
                  in zip(preps, plan.in_refs) if prep is None]
        kwargs["compiler_params"] = _compiler_params(
            plan.grid_order, mp.red_vars if mp is not None else plan.red_vars,
            mp, vmem_cap, blocks + [(out_block, out_full_shape, plan.out_ref.ref)],
            buffers)
    scratch = [pltpu.VMEM(out_block, acc_dtype)] if has_red else []
    launch = _kernel_launcher(
        kernel, grid, in_blocks, out_block, _index_map_for(plan.out_ref, gpos),
        jax.ShapeDtypeStruct(out_full_shape, out_dtype), scratch, interpret,
        name, kwargs)

    def fn(arrays: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
        operands = []
        for (prep, _), gr in zip(preps, plan.in_refs):
            if prep is None:
                operands.append(_operand(arrays, gr.ref.from_buf))
            else:
                # a halo view gathers its tiles from the slice itself
                a = arrays[gr.ref.from_buf]
                a = a.select() if isinstance(a, Stacked) else jnp.asarray(a)
                operands.append((prep(a), None))
        return launch(operands)

    fn.out_shape = out_full_shape
    fn.out_dtype = out_dtype
    fn.out_base = plan.out_ref.base
    fn.in_bufs = [g.ref.from_buf for g in plan.in_refs]
    return fn


def _emit_contraction(plan: ContractionPlan, interpret: bool,
                      mp: Optional[memplan.BlockPlan] = None,
                      buffers: Optional[Mapping[str, TensorDecl]] = None,
                      vmem_cap: Optional[int] = None,
                      name: Optional[str] = None) -> Callable:
    grid = tuple(plan.grid_sizes[v] for v in plan.grid_order)
    gpos = {v: i for i, v in enumerate(plan.grid_order)}

    side = set(plan.lhs_bufs) | set(plan.rhs_bufs)
    operand_grs = [g for g in plan.in_refs if g.ref.into in side]
    extra = [g for g in plan.in_refs if g.ref.into not in side]
    order = operand_grs + extra

    batch = ((0,), (0,)) if plan.n_batch else ((), ())
    dnums = ((plan.lhs_contract, plan.rhs_contract), batch)
    out_dtype = np.dtype(plan.out_ref.ref.dtype)
    acc_dtype = _acc_dtype(plan.out_ref.ref.dtype)
    cast_ints = np.dtype(out_dtype).kind in "iu"
    out_block = plan.out_ref.block_shape
    has_red = bool(plan.red_vars)
    # The memory plan decides scratch residency: a revisited output plans
    # one partial-sum tile that must agree with the emitter's own
    # reduction analysis — a mismatch means the schedule placed the
    # accumulator differently than this kernel would use it.
    if mp is not None:
        if (mp.acc_bytes > 0) != has_red or set(mp.red_vars) != set(plan.red_vars):
            raise UnsupportedPallas(
                f"memory plan disagrees with emitter: plan acc={mp.acc_bytes}B "
                f"red={sorted(mp.red_vars)} vs emitter red={sorted(plan.red_vars)}")
        out_elems = 1
        for s in out_block:
            out_elems *= s
        if has_red and mp.acc_bytes != out_elems * 4:
            raise UnsupportedPallas(
                f"planned scratch {mp.acc_bytes}B != f32 out tile {out_elems * 4}B")

    def kernel(*refs):
        if has_red:
            *ins, out_ref, acc_ref = refs
        else:
            *ins, out_ref = refs
            acc_ref = None
        tiles = {g.ref.into: ins[i][...] for i, g in enumerate(order)}
        if cast_ints:
            tiles = {k: v.astype(acc_dtype) for k, v in tiles.items()}
        lhs, rhs = _promote_pair(_eval_tnode(plan.lhs, tiles),
                                 _eval_tnode(plan.rhs, tiles))
        if plan.n_batch:
            lhs = lhs.reshape(plan.lhs_shape)
            rhs = rhs.reshape(plan.rhs_shape)
        part = jax.lax.dot_general(lhs, rhs, dnums, preferred_element_type=acc_dtype)
        part = part.reshape(out_block)
        if plan.scale != 1.0:
            part = part * jnp.asarray(plan.scale, part.dtype)
        tile_args = {g.ref.into: tiles[g.ref.into] for g in extra}
        if has_red:
            first = functools.reduce(
                jnp.logical_and, [pl.program_id(gpos[v]) == 0 for v in plan.red_vars]
            )
            last = functools.reduce(
                jnp.logical_and,
                [pl.program_id(gpos[v]) == plan.grid_sizes[v] - 1 for v in plan.red_vars],
            )

            @pl.when(first)
            def _init():
                acc_ref[...] = jnp.zeros(out_block, acc_dtype)

            acc_ref[...] += part

            @pl.when(last)
            def _flush():
                val = acc_ref[...]
                if plan.epilogue:
                    val = _apply_epilogue(plan, val, tile_args)
                out_ref[...] = val.astype(out_ref.dtype)
        else:
            val = part
            if plan.epilogue:
                val = _apply_epilogue(plan, val, tile_args)
            out_ref[...] = val.astype(out_ref.dtype)

    out_full_shape = tuple(
        s * (plan.grid_sizes[v] if v else 1)
        for s, v in zip(out_block, plan.out_ref.dim_vars)
    )

    kwargs = {}
    if not interpret:
        # planned slots gate the semantics: grid axes that stream the
        # output may be reordered/parallelized by Mosaic; axes that
        # revisit the planned accumulator carry state and stay arbitrary
        kwargs["compiler_params"] = _compiler_params(
            plan.grid_order, mp.red_vars if mp is not None else plan.red_vars,
            mp, vmem_cap, [(g.block_shape, None, g.ref) for g in order]
            + [(out_block, out_full_shape, plan.out_ref.ref)], buffers)
    scratch = []
    if has_red:
        # sized by the memory plan when available (acc_bytes == f32 out
        # tile, verified above), else by the emitter's own analysis
        scratch = [pltpu.VMEM(out_block, acc_dtype)]
    launch = _kernel_launcher(
        kernel, grid,
        [(g.block_shape, _index_map_for(g, gpos), _lead_axis(g, gpos)) for g in order],
        out_block, _index_map_for(plan.out_ref, gpos),
        jax.ShapeDtypeStruct(out_full_shape, out_dtype), scratch, interpret,
        name, kwargs)

    def fn(arrays: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
        return launch([_operand(arrays, g.ref.from_buf) for g in order])

    fn.out_shape = out_full_shape
    fn.out_dtype = out_dtype
    fn.out_base = plan.out_ref.base
    fn.in_bufs = [g.ref.from_buf for g in order]
    return fn


def _emit_elementwise(plan: ElementwisePlan, interpret: bool,
                      mp: Optional[memplan.BlockPlan] = None,
                      buffers: Optional[Mapping[str, TensorDecl]] = None,
                      vmem_cap: Optional[int] = None,
                      name: Optional[str] = None) -> Callable:
    grid = tuple(plan.grid_sizes[v] for v in plan.grid_order)
    gpos = {v: i for i, v in enumerate(plan.grid_order)}
    out_block = plan.out_ref.block_shape
    out_dtype = np.dtype(plan.out_ref.ref.dtype)
    out_full_shape = tuple(s * (plan.grid_sizes[v] if v else 1)
                           for s, v in zip(out_block, plan.out_ref.dim_vars))

    def kernel(*refs):
        *ins, out_ref = refs
        tiles = {g.ref.into: ins[i][...] for i, g in enumerate(plan.in_refs)}
        val = _eval_tnode(plan.root, tiles, jnp.dtype(out_dtype))
        out_ref[...] = jnp.broadcast_to(val, out_block).astype(out_ref.dtype)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = _compiler_params(
            plan.grid_order, (), mp, vmem_cap,
            [(g.block_shape, None, g.ref) for g in plan.in_refs]
            + [(out_block, out_full_shape, plan.out_ref.ref)], buffers)
    launch = _kernel_launcher(
        kernel, grid,
        [(g.block_shape, _index_map_for(g, gpos), _lead_axis(g, gpos))
         for g in plan.in_refs],
        out_block, _index_map_for(plan.out_ref, gpos),
        jax.ShapeDtypeStruct(out_full_shape, out_dtype), (), interpret, name,
        kwargs)

    def fn(arrays: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
        return launch([_operand(arrays, g.ref.from_buf) for g in plan.in_refs])

    fn.out_shape = out_full_shape
    fn.out_dtype = out_dtype
    fn.out_base = plan.out_ref.base
    fn.in_bufs = [g.ref.from_buf for g in plan.in_refs]
    return fn


# --------------------------------------------------------------------------
# Paged operands: a contraction over rows kept in a page pool
# --------------------------------------------------------------------------
@dataclasses.dataclass
class PagedPlan:
    """A contraction of a dense operand ``X[b, h, g, x]`` with an input
    kept in a page pool, ``Y[b, r, h, f]`` (slot, row, head, feature),
    into ``O[b, h, g, y]``: ``x = f, y = r`` puts the rows on the output
    (``"rows_out"``, attention scores), ``x = r, y = f`` sums them away
    (``"rows_summed"``, attention values).  One grid step per slot."""

    n_slots: int
    paged: GridRef
    dense: GridRef
    out_ref: GridRef
    mode: str
    page: int                    # rows per page
    pages: int                   # pages per block (the cost model's choice)
    row_shape: Tuple[int, ...]   # one row of the pool: (heads, features)
    dtype: np.dtype              # the pool's, as declared
    scale: float


def _reads_paged(outer: Block, buffers: Optional[Mapping[str, TensorDecl]]) -> bool:
    return buffers is not None and any(
        r.dir == RefDir.IN and getattr(buffers.get(r.from_buf), "paged", 0)
        for r in outer.refs)


def extract_paged(outer: Block, buffers: Mapping[str, TensorDecl]) -> PagedPlan:
    grid_ranges, ins, out, _local, leaf, epilogue = _collect(outer)
    if epilogue or (out.ref.agg or "assign") != "add":
        raise UnsupportedPallas("paged operand outside a plain sum of products")
    paged = [g for g in ins if getattr(buffers.get(g.ref.from_buf), "paged", 0)]
    if len(ins) != 2 or len(paged) != 1:
        raise UnsupportedPallas("a paged contraction takes one paged and one dense input")
    pg = paged[0]
    dense = next(g for g in ins if g is not pg)
    sides, scale = _split_sides(_leaf_root(leaf.stmts),
                                {g.ref.into: (g.dim_vars, g.block_shape) for g in ins})
    if len(sides) != 2 or any(sd.kind != "load" for sd in sides):
        raise UnsupportedPallas("paged contraction operands carry a prologue")
    pn = _dim_names(_leaf_ref(leaf, pg.ref.into), "p")
    dn = _dim_names(_leaf_ref(leaf, dense.ref.into), "x")
    on = _dim_names(next(r for r in leaf.refs if r.dir in (RefDir.OUT, RefDir.INOUT)), "o")
    if not len(pn) == len(dn) == len(on) == 4:
        raise UnsupportedPallas("paged contraction needs rank-4 operands")
    slot, row, head, feat = pn
    if dn[:2] != [slot, head] or on[:3] != dn[:3]:
        raise UnsupportedPallas(f"paged operand dims {pn} do not lead as (slot, row, "
                                f"head, feature) against {dn} -> {on}")
    if (dn[3], on[3]) == (feat, row):
        mode = "rows_out"
    elif (dn[3], on[3]) == (row, feat):
        mode = "rows_summed"
    else:
        raise UnsupportedPallas(f"paged contraction {dn} x {pn} -> {on}")
    slot_var = pg.dim_vars[0]
    n_slots = grid_ranges[slot_var] if slot_var else 1
    for g in (pg, dense, out):
        full = buffers[g.ref.from_buf].shape
        if (g.block_shape[0] != 1 or g.dim_vars[0] != slot_var or n_slots != full[0]
                or tuple(g.block_shape[1:]) != tuple(full[1:])
                or any(v is not None and grid_ranges[v] > 1 for v in g.dim_vars[1:])):
            raise UnsupportedPallas(
                f"paged contraction needs one slot per grid step and the rest "
                f"whole; {g.ref.from_buf} has block {g.block_shape} of {full}")
    if any(r > 1 for v, r in grid_ranges.items() if v != slot_var):
        raise UnsupportedPallas("paged contraction with a grid axis besides the slot")
    decl = buffers[pg.ref.from_buf]
    page = decl.paged
    pps = decl.shape[1] // page
    if decl.shape[1] % page:
        raise UnsupportedPallas(f"window {decl.shape[1]} is not whole pages of {page}")
    pages = next((int(t.split(":", 1)[1]) for t in outer.tags
                  if t.startswith("paged_pages:")), pps)
    if pps % pages:
        raise UnsupportedPallas(f"{pages} pages a block do not divide {pps}")
    heads, dt = decl.shape[2], np.dtype(decl.dtype)
    if not (dt == np.dtype(jnp.float32) or (dt == np.dtype(jnp.bfloat16) and heads % 2 == 0)):
        raise UnsupportedPallas(f"paged rows of {heads} heads at {decl.dtype}")
    return PagedPlan(n_slots=n_slots, paged=pg, dense=dense, out_ref=out, mode=mode,
                     page=page, pages=pages, row_shape=tuple(decl.shape[2:]),
                     dtype=dt, scale=scale)


def _head_rows(buf2d, head: int, heads: int, rows: int) -> jnp.ndarray:
    """Rows of one head from a VMEM block of pages viewed as ``(rows *
    heads, features)``, in float32.  A float32 pool is read with a
    strided load; a bfloat16 one through its 32-bit words, each holding
    one row's even and odd head side by side: a bfloat16 in the high half
    of a 32-bit word is that number as a float32, the value
    ``astype(float32)`` makes."""
    if np.dtype(buf2d.dtype).itemsize == 4:
        return buf2d[pl.ds(head, rows, stride=heads), :].astype(jnp.float32)
    words = buf2d.bitcast(jnp.uint32)
    w = (words[pl.ds(head // 2, rows, stride=heads // 2), :] if heads > 2
         else words[...])
    w = w << 16 if head % 2 == 0 else w & jnp.uint32(0xFFFF0000)
    return pltpu.bitcast(w, jnp.float32)


def _emit_paged(plan: PagedPlan, interpret: bool, vmem_cap: Optional[int] = None,
                name: Optional[str] = None) -> Callable:
    """One kernel, one grid step per slot, the pool left in HBM
    (``pl.ANY``).  The layer, the page table and the slots' lengths ride
    in scalar prefetch; each step walks its slot's live pages in blocks
    of ``plan.pages``, DMAing each block's pages into one of two VMEM
    buffers while the other is computed on (the next slot's first block
    is started under this slot's last), and never fetches a page with no
    live row.  Rows at or past the slot's length are unspecified on the
    output (``rows_out``) or add exactly nothing (``rows_summed``: they
    are selected to zero before the dot, so a recycled page's stale or
    NaN rows cannot reach the sum)."""
    m, page, c_pages = plan.n_slots, plan.page, plan.pages
    heads, feat = plan.row_shape[0], int(np.prod(plan.row_shape[1:]))
    x_block, out_block = plan.dense.block_shape, plan.out_ref.block_shape
    window = plan.paged.block_shape[1]
    n_blocks = window // (page * c_pages)
    w = page * c_pages  # rows a block
    out_dtype = np.dtype(plan.out_ref.ref.dtype)
    summed = plan.mode == "rows_summed"

    def kernel(layer_ref, table_ref, len_ref, x_ref, pool_ref, o_ref, buf, sem, state):
        b = pl.program_id(0)
        nb = pl.num_programs(0)

        def n_pages(s):
            return (len_ref[s] + page - 1) // page

        def each_page(s, blk, p, act):
            """``act`` on the DMA of each live page of block ``blk``."""
            def page(j, carry):
                act(pltpu.make_async_copy(
                    pool_ref.at[layer_ref[0], table_ref[s, blk * c_pages + j]],
                    buf.at[p, j], sem.at[p]))
                return carry

            live = jnp.clip(n_pages(s) - blk * c_pages, 0, c_pages)
            jax.lax.fori_loop(0, live, page, 0)

        @pl.when(b == 0)
        def _():
            state[0] = 0
            state[1] = 0

        p0 = state[0]
        n = (len_ref[b] + w - 1) // w
        nxt = jnp.minimum(b + 1, nb - 1)
        go = (b + 1 < nb) & (len_ref[nxt] > 0)

        @pl.when((state[1] == 0) & (n > 0))
        def _():
            each_page(b, 0, p0, lambda cp: cp.start())

        if summed:
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
        for blk in range(n_blocks):
            @pl.when(blk < n)
            def _():
                p = (p0 + blk) % 2

                @pl.when(blk + 1 < n)
                def _():
                    each_page(b, blk + 1, 1 - p, lambda cp: cp.start())

                @pl.when((blk + 1 == n) & go)
                def _():
                    each_page(nxt, 0, 1 - p, lambda cp: cp.start())

                each_page(b, blk, p, lambda cp: cp.wait())
                rows2d = buf.at[p].reshape(w * heads, feat)
                lo = blk * w
                for h in range(heads):
                    y = _head_rows(rows2d, h, heads, w)
                    if summed:
                        live = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0) < len_ref[b] - lo
                        y = jnp.where(live, y, 0.0)
                        part = jax.lax.dot_general(
                            x_ref[0, h, :, lo:lo + w].astype(jnp.float32), y,
                            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                    else:
                        part = jax.lax.dot_general(
                            x_ref[0, h].astype(jnp.float32), y,
                            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                    if plan.scale != 1.0:
                        part = part * jnp.float32(plan.scale)
                    if summed:
                        o_ref[0, h] += part.astype(o_ref.dtype)
                    else:
                        o_ref[0, h, :, lo:lo + w] = part.astype(o_ref.dtype)

        @pl.when((n == 0) & go)
        def _():
            each_page(nxt, 0, p0, lambda cp: cp.start())

        state[0] = (p0 + n) % 2
        state[1] = go.astype(jnp.int32)

    x_bytes = int(np.prod(x_block)) * np.dtype(plan.dense.ref.dtype).itemsize
    o_bytes = int(np.prod(out_block)) * out_dtype.itemsize
    need = 2 * w * heads * feat * plan.dtype.itemsize + 2 * (x_bytes + o_bytes) + VMEM_SLACK
    kwargs = {}
    if not interpret:
        if vmem_cap is not None and need > vmem_cap:
            raise UnsupportedPallas(
                f"paged kernel needs {need}B of VMEM; a kernel is granted {vmem_cap}B")
        # the slots run in order: each step starts the next one's DMA
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(need, MOSAIC_DEFAULT_VMEM))
    spec = lambda blk: pl.BlockSpec(blk, lambda i, *_: (i,) + (0,) * (len(blk) - 1))  # noqa: E731
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(m,),
            in_specs=[spec(x_block), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=spec(out_block),
            scratch_shapes=[pltpu.VMEM((2, c_pages, page) + plan.row_shape, plan.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((2,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((m,) + tuple(out_block[1:]), out_dtype),
        interpret=interpret, name=name, **kwargs)

    def fn(arrays: Mapping[str, object]) -> jnp.ndarray:
        y = arrays[plan.paged.ref.from_buf]
        if not isinstance(y, Paged):
            raise TypeError(f"{name}: input {plan.paged.ref.from_buf} is declared "
                            f"paged; hand it as Paged(pool, layer, table, lengths)")
        x = arrays[plan.dense.ref.from_buf]
        x = x.select() if isinstance(x, Stacked) else jnp.asarray(x)
        return call(jnp.asarray(y.index, jnp.int32).reshape(1),
                    jnp.asarray(y.table, jnp.int32), jnp.asarray(y.lengths, jnp.int32),
                    x, jnp.asarray(y.array))

    fn.out_shape = (m,) + tuple(out_block[1:])
    fn.out_base = plan.out_ref.base
    return fn


def lower_op_pallas(outer: Block, interpret: bool = False,
                    pipeline_depth: int = 2,
                    buffers: Optional[Mapping[str, TensorDecl]] = None,
                    vmem_cap: Optional[int] = None,
                    name: Optional[str] = None) -> Callable:
    """Returns fn(arrays: dict) -> output array for one optimized op block
    or fusion group (a single ``pallas_call``).  ``pipeline_depth`` is the
    hardware's DMA-pipeline depth (``HardwareConfig.pipeline_depth``),
    threaded into the memory plan so its slot figures match the schedule's;
    ``buffers`` (the program's declarations) sizes the padded operand of
    halo views and checks block alignment; ``vmem_cap`` is the scoped VMEM
    one kernel may be granted (the hardware config's inner memory);
    ``name`` is the kernel's name (``pallas_call(name=)``), which the TPU
    compiler gives the kernel's operation and a profile shows.

    Emission paths are tried in order — dense contraction / elementwise
    for constraint-free aligned blocks, then the windowed (halo + masked
    store) path — and when *every* path rejects the block, the raised
    ``UnsupportedPallas`` carries each path's reason (the per-block
    fallback trace the driver records)."""
    outer = _ensure_grid(outer)
    out_ref = next((r for r in outer.refs if r.dir in (RefDir.OUT, RefDir.INOUT)), None)
    if out_ref is None:
        raise UnsupportedPallas("no output ref")
    # the memory plan of this kernel's grid block: slot classification
    # (streamed / resident / halo / accumulator) that sizes the VMEM
    # scratch and gates dimension_semantics below
    mp = memplan.plan_block(outer, depth=pipeline_depth)
    agg = out_ref.agg or "assign"
    constrained = _is_constrained(outer)

    fn: Optional[Callable] = None
    errors: List[str] = []
    if _reads_paged(outer, buffers):
        # only the paged kernel reads a pool in place; a refusal falls
        # back to the jnp lowering, which gathers the live window
        fn = _emit_paged(extract_paged(outer, buffers), interpret, vmem_cap, name)
        fn.out_buf = out_ref.from_buf
        return fn

    def attempt(name: str, build: Callable[[], Callable]) -> None:
        nonlocal fn
        if fn is not None:
            return
        try:
            fn = build()
        except UnsupportedPallas as e:
            errors.append(f"{name}: {e}")

    if not constrained:
        if agg == "assign" and not outer.sub_blocks():
            attempt("elementwise",
                    lambda: _emit_elementwise(extract_elementwise(outer), interpret,
                                              mp, buffers, vmem_cap, name))
        elif agg == "assign":
            # a fused group's outer agg is on its local accumulator; decide
            # by whether a reduction sub-structure exists — both reasons
            # are recorded when neither path fits
            attempt("contraction",
                    lambda: _emit_contraction(extract_contraction(outer), interpret,
                                              mp, buffers, vmem_cap, name))
            attempt("elementwise",
                    lambda: _emit_elementwise(extract_elementwise(outer), interpret,
                                              mp, buffers, vmem_cap, name))
        else:
            attempt("contraction",
                    lambda: _emit_contraction(extract_contraction(outer), interpret,
                                              mp, buffers, vmem_cap, name))
    # the general halo/masked path: constraint-carrying blocks (boundary
    # remainders, conv halos) and halo views of constraint-free interiors
    attempt("windowed",
            lambda: _emit_windowed(extract_windowed(outer), interpret,
                                   mp, buffers, vmem_cap, name))
    if fn is None:
        raise UnsupportedPallas("; ".join(errors))
    fn.out_buf = out_ref.from_buf
    return fn


# --------------------------------------------------------------------------
# Program composition: per-block hybrid lowering
# --------------------------------------------------------------------------
@dataclasses.dataclass
class _Unit:
    """One lowering unit: the top-level blocks sharing a semantic member
    set (a fusion group, or the boundary pieces of one op — pieces
    partition an iteration space and must lower, or fall back, together)."""

    members: List[str]
    blocks: List[Block]
    first: int
    level: int

    @property
    def name(self) -> str:
        return "+".join(self.members)


def kernel_name(prog: Program, unit: _Unit, piece: int = 0) -> str:
    """The stable name of one unit's kernel: the program's name and the
    unit's member ops (``serve_mlp_m16.mm_gate``), with ``_p<i>`` for the
    i-th boundary piece after the first, reduced to ``[A-Za-z0-9_.]``.
    It depends on nothing but names, so it stays the same across runs
    and across edits elsewhere."""
    suffix = f"_p{piece}" if piece else ""
    return re.sub(r"[^A-Za-z0-9_.]", "_",
                  f"{prog.entry.name}.{'_'.join(unit.members)}{suffix}")


def _units_of(prog: Program) -> List[_Unit]:
    from .passes.fuse import members_of

    units: Dict[Tuple[str, ...], _Unit] = {}
    order: List[Tuple[str, ...]] = []
    for i, s in enumerate(prog.entry.stmts):
        if not isinstance(s, Block):
            continue
        key = tuple(members_of(s))
        if key not in units:
            units[key] = _Unit(members=list(key), blocks=[], first=i, level=1 << 30)
            order.append(key)
        u = units[key]
        u.blocks.append(s)
        for t in s.tags:
            if t.startswith("sched:"):
                u.level = min(u.level, int(t.split(":", 1)[1]))
    for u in units.values():
        if u.level == 1 << 30:
            u.level = u.first
    return [units[k] for k in order]


def _clip_extents(fn, decl: TensorDecl, block_name: str) -> Tuple[int, ...]:
    """In-bounds extent of the kernel's output region (an overflow-rounded
    boundary piece writes a view whose tail rows the constraints proved
    dead — they are sliced off before placement)."""
    base = getattr(fn, "out_base", (0,) * len(fn.out_shape))
    if len(base) != len(decl.shape) or len(fn.out_shape) != len(decl.shape):
        raise UnsupportedPallas(
            f"{block_name}: kernel writes rank-{len(fn.out_shape)} region "
            f"into rank-{len(decl.shape)} buffer {decl.name}")
    clip = []
    for b, s, d in zip(base, fn.out_shape, decl.shape):
        if b < 0 or b >= d:
            raise UnsupportedPallas(
                f"{block_name}: output region base {base} outside buffer "
                f"{decl.name}{decl.shape}")
        clip.append(min(s, d - b))
    return tuple(clip)


def _place(env: Dict[str, jnp.ndarray], decl: TensorDecl, fn,
           out: jnp.ndarray) -> jnp.ndarray:
    """Place a kernel's output region into its buffer (identity when the
    kernel covers the whole buffer)."""
    base = getattr(fn, "out_base", (0,) * len(fn.out_shape))
    if all(b == 0 for b in base) and tuple(fn.out_shape) == tuple(decl.shape):
        return out
    clip = fn.out_clip
    if clip != tuple(fn.out_shape):
        out = out[tuple(slice(0, c) for c in clip)]
    cur = env.get(decl.name)
    if cur is None:
        cur = jnp.zeros(decl.shape, np.dtype(decl.dtype))
    return jax.lax.dynamic_update_slice(cur, out.astype(cur.dtype), base)


def lower_program_hybrid(prog: Program, interpret: bool = False,
                         pipeline_depth: int = 2,
                         strict: bool = False,
                         profile: bool = False,
                         force_jnp_units: Optional[set] = None,
                         vmem_cap: Optional[int] = None) -> Callable:
    """Lower every op block / fusion group to one Pallas kernel and
    compose the units in wavefront order; intermediates between groups
    live in outer memory (HBM).

    The backend degrades **per unit**: a unit whose blocks cannot lower
    falls back to the jnp backend for just those semantic ops
    (``lower_group_jnp``), the reason is recorded on the returned
    callable (``block_backends`` / ``block_reasons``), and every other
    unit keeps its kernels.  ``strict=True`` restores the all-or-nothing
    contract (raise on the first unsupported block — the
    ``lower_program_pallas`` entry point).

    ``profile=True`` wall-times every unit per dispatch (synchronizing on
    the unit's outputs with ``jax.block_until_ready``), keeping the best
    observation per unit in ``run.unit_times`` ({unit name: seconds}) —
    the measured side of the cost-model residual log.

    ``force_jnp_units`` (unit names, the "+"-joined member form) skips
    the Pallas attempt for those units — the tuning DB's replay of a
    measured per-unit backend choice (a unit that *measured* faster on
    the jnp path is not re-lowered to Pallas just because it legally
    could be).

    Each kernel is named by :func:`kernel_name`; the returned callable's
    ``kernel_names`` lists them in lowering order."""
    blocks = [s for s in prog.entry.stmts if isinstance(s, Block)]
    if not blocks:
        raise UnsupportedPallas("no op blocks")
    units = _units_of(prog)
    semantic = prog.source

    steps: List[Tuple[_Unit, str, object]] = []
    backends: Dict[str, str] = {}
    reasons: Dict[str, str] = {}
    written_regions: Dict[str, List[Tuple[Tuple[int, ...], Tuple[int, ...]]]] = {}
    written: set = set()
    n_pallas = 0
    kernel_names: List[str] = []
    for u in units:
        try:
            if force_jnp_units and u.name in force_jnp_units:
                raise UnsupportedPallas("tuned: measured faster on jnp")
            kernels = []
            regions = []
            names = [kernel_name(prog, u, piece) for piece in range(len(u.blocks))]
            for b, name in zip(u.blocks, names):
                fn = lower_op_pallas(b, interpret=interpret,
                                     pipeline_depth=pipeline_depth,
                                     buffers=prog.buffers, vmem_cap=vmem_cap,
                                     name=name)
                decl = prog.buffers.get(fn.out_buf)
                if decl is None:
                    raise UnsupportedPallas(
                        f"{b.name}: kernel writes unknown buffer {fn.out_buf}")
                fn.out_clip = _clip_extents(fn, decl, b.name)
                base = getattr(fn, "out_base", (0,) * len(fn.out_shape))
                for obase, oclip in written_regions.get(fn.out_buf, []) + regions:
                    if all(b0 < o0 + c0 and o0 < b0 + c1 for b0, c1, o0, c0 in
                           zip(base, fn.out_clip, obase, oclip)):
                        # two writers of one region cannot be composed by
                        # placement (and the jnp group executor would
                        # clobber, not accumulate) — refuse the program
                        raise _ProgramFallback(
                            f"{b.name}: overlapping writes to {fn.out_buf}")
                regions.append((base, fn.out_clip))
                kernels.append(fn)
            for fn, region in zip(kernels, regions):
                written_regions.setdefault(fn.out_buf, []).append(region)
                written.add(fn.out_buf)
            steps.append((u, "pallas", kernels))
            backends[u.name] = "pallas"
            n_pallas += len(kernels)
            kernel_names += names
        except _ProgramFallback:
            raise
        except UnsupportedPallas as e:
            if strict:
                raise UnsupportedPallas(f"{u.blocks[0].name}: {e}")
            if semantic is None:
                raise UnsupportedPallas(
                    f"{u.blocks[0].name}: {e} (and no semantic source for a "
                    f"per-block jnp fallback)")
            from .lower_jnp import lower_group_jnp

            gfn = lower_group_jnp(semantic, u.members)
            steps.append((u, "jnp", gfn))
            backends[u.name] = "jnp"
            reasons[u.name] = str(e)
            for n in u.members:
                for s in semantic.entry.stmts:
                    if isinstance(s, Block) and s.name == n:
                        for r in s.refs:
                            if r.dir in (RefDir.OUT, RefDir.INOUT):
                                if r.from_buf in written:
                                    raise _ProgramFallback(
                                        f"{s.name}: multiple units write "
                                        f"{r.from_buf}")
                                written.add(r.from_buf)
                                # a jnp unit writes the whole buffer: any
                                # later writer overlaps by construction
                                d = prog.buffers.get(r.from_buf)
                                if d is not None:
                                    written_regions.setdefault(
                                        r.from_buf, []).append(
                                        ((0,) * len(d.shape), tuple(d.shape)))

    missing = [o for o in prog.outputs if o not in written]
    if missing:
        raise UnsupportedPallas(f"outputs {missing} not produced by any kernel")
    # wavefront composition: units ordered by schedule level (ties by
    # program order) — the order the pipelined cost model prices
    steps.sort(key=lambda s: (s[0].level, s[0].first))
    outs = list(prog.outputs)
    buffers = prog.buffers

    unit_times: Dict[str, float] = {}

    def run(arrays: Mapping[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        env: Dict[str, jnp.ndarray] = {
            k: v if isinstance(v, Stacked) else jnp.asarray(v)
            for k, v in arrays.items()}
        for u, kind, obj in steps:
            if profile:
                t0 = time.perf_counter()
            if kind == "pallas":
                for fn in obj:
                    env[fn.out_buf] = _place(env, buffers[fn.out_buf], fn, fn(env))
                if profile:
                    jax.block_until_ready([env[fn.out_buf] for fn in obj])
            else:
                # a jnp unit reads the slice of an input handed in place
                updates = obj(select_stacked(
                    {b: env[b] for b in obj.needed if b in env}))
                env.update(updates)
                if profile:
                    jax.block_until_ready(list(updates.values()))
            if profile:
                dt = time.perf_counter() - t0
                prev = unit_times.get(u.name)
                unit_times[u.name] = dt if prev is None or dt < prev else prev
        return {n: env[n] for n in outs}

    run.n_kernels = n_pallas + sum(1 for _, kind, _ in steps if kind == "jnp")
    run.n_pallas = n_pallas
    run.kernel_names = kernel_names
    run.takes_stacked = True
    run.block_backends = backends
    run.block_reasons = reasons
    run.unit_times = unit_times
    return run


def lower_program_pallas(prog: Program, interpret: bool = False,
                         pipeline_depth: int = 2) -> Callable:
    """Strict whole-program lowering: every op block / fusion group must
    lower to a Pallas kernel, else ``UnsupportedPallas`` (the caller
    falls back to the jnp backend wholesale)."""
    return lower_program_hybrid(prog, interpret=interpret,
                                pipeline_depth=pipeline_depth, strict=True)

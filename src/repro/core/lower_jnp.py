"""Lowering Stripe programs to jax.numpy (the "reference backend").

The jnp backend consumes *frontend-shaped* flat blocks (one polyhedron, a
scalar-view load/compute/store body) and emits vectorized JAX:

* pure-index contractions        -> ``jnp.einsum``
* windowed contractions (convs,
  strided/offset accesses)       -> pad + shifted-slice + einsum per window
  point, aggregated with the block's aggregation op, with halo constraints
  materialized as masks on the output grid (the paper's Fig. 4 "accesses to
  overflow elements are removed by constraints in execution")
* elementwise DAGs               -> broadcast + intrinsic table

This is the execution path used on CPU (tests, smoke training) and the
oracle for the Pallas backend.  Optimization passes do not change this
lowering's semantics — they restructure blocks for the Pallas/TPU backend
and for the cost model; `lower_program_jnp` always lowers from the
semantic (flat) form, which passes preserve via the ``frontend`` tag.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from functools import partial
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .affine import Affine
from .ir import (
    AGG_IDENTITY,
    Block,
    Constant,
    Intrinsic,
    Load,
    Program,
    RefDir,
    Refinement,
    Store,
)

_EINSUM_LETTERS = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"

_J_UNARY = {
    "neg": jnp.negative, "exp": jnp.exp, "log": jnp.log, "tanh": jnp.tanh,
    "sqrt": jnp.sqrt, "rsqrt": jax.lax.rsqrt, "sigmoid": jax.nn.sigmoid,
    "relu": jax.nn.relu, "abs": jnp.abs, "square": jnp.square,
    "erf": jax.lax.erf, "gelu": partial(jax.nn.gelu, approximate=False),
    "silu": jax.nn.silu, "sign": jnp.sign, "floor": jnp.floor, "cast": lambda a: a,
}
_J_BINARY = {
    "add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply,
    "div": jnp.divide, "max": jnp.maximum, "min": jnp.minimum, "pow": jnp.power,
}

_AGG_JNP = {
    "add": jnp.add, "max": jnp.maximum, "min": jnp.minimum, "mul": jnp.multiply,
}


# --------------------------------------------------------------------------
# Inputs handed in place
# --------------------------------------------------------------------------
@partial(jax.tree_util.register_dataclass,
         data_fields=["array", "index", "live"], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Stacked:
    """A program input handed in place.

    With a scalar ``index`` (traced or not), ``array`` carries one leading
    axis more than the declared operand and the index selects the operand
    along it: the stacked ``(n_layers, d, f)`` weight of a layer loop plus
    the layer.  With an index vector ``(k,)``, ``array`` has the operand's
    rank and the operand is ``array[index]``: block ``j`` of the operand's
    leading dim (declared ``indexed``, so tiled by 1) is row ``index[j]``
    of ``array``, e.g. the expert whose weights a block of rows meets.
    ``live`` (``(k,)``, with an index vector only) marks the blocks whose
    results matter: the Pallas kernels skip the others, whose results are
    then unspecified, and fetch nothing new for them.

    The Pallas kernels read the selected rows where they lie (the indices
    ride in scalar prefetch); every other lowering takes :meth:`select`."""

    array: object
    index: object
    live: object = None

    def select(self) -> jnp.ndarray:
        a, idx = jnp.asarray(self.array), jnp.asarray(self.index)
        if idx.ndim == 0:
            return jax.lax.dynamic_index_in_dim(a, idx, axis=0, keepdims=False)
        return jnp.take(a, idx, axis=0, mode="clip")


@partial(jax.tree_util.register_dataclass,
         data_fields=["array", "index", "live", "table", "lengths"], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Paged(Stacked):
    """An input kept in a page pool (declared ``paged``), handed in place.

    ``array`` is the whole pool ``(n_layers, n_pages, page, ...)`` and
    ``index`` the layer; ``table`` ``(slots, pages_per_slot)`` names the
    physical page of each logical page of a slot, and ``lengths``
    ``(slots,)`` each slot's live rows.  Row ``t`` of slot ``b`` is
    ``array[index, table[b, t // page], t % page]``; a page that holds no
    row below ``lengths[b]`` is not live.  The Pallas kernel fetches only
    live pages (``lower_pallas._emit_paged``); every other lowering takes
    :meth:`select`."""

    table: object = None
    lengths: object = None

    def select(self) -> jnp.ndarray:
        """The gathered window ``(slots, pages_per_slot * page, ...)``, rows
        at or past a slot's length set to zero, so they add nothing to a
        sum whatever the recycled page held."""
        layer = jax.lax.dynamic_index_in_dim(jnp.asarray(self.array),
                                             jnp.asarray(self.index), keepdims=False)
        table = jnp.asarray(self.table)
        rows = table.shape[1] * layer.shape[1]
        win = layer[table].reshape((table.shape[0], rows) + layer.shape[2:])
        live = jnp.arange(rows)[None, :] < jnp.asarray(self.lengths)[:, None]
        live = live.reshape(live.shape + (1,) * (win.ndim - 2))
        return jnp.where(live, win, jnp.zeros((), win.dtype))


def select_stacked(arrays: Mapping[str, object]) -> Dict[str, object]:
    """``arrays`` with every :class:`Stacked` input replaced by its slice."""
    return {k: v.select() if isinstance(v, Stacked) else v
            for k, v in arrays.items()}


# --------------------------------------------------------------------------
# Block analysis: rebuild the expression DAG from the statement list
# --------------------------------------------------------------------------
@dataclasses.dataclass
class _Node:
    kind: str  # 'load' | 'const' | 'op'
    ref: Optional[Refinement] = None
    value: float = 0.0
    op: str = ""
    args: Tuple["_Node", ...] = ()


@dataclasses.dataclass
class FlatOp:
    block: Block
    out_ref: Refinement
    agg: str
    root: _Node
    ranges: Dict[str, int]
    out_vars: List[str]  # one per non-degenerate output dim


def analyze_flat(block: Block) -> FlatOp:
    env: Dict[str, _Node] = {}
    out_ref = None
    root = None
    for s in block.stmts:
        if isinstance(s, Load):
            env[s.into] = _Node("load", ref=block.ref(s.buf))
        elif isinstance(s, Constant):
            env[s.into] = _Node("const", value=s.value)
        elif isinstance(s, Intrinsic):
            env[s.into] = _Node("op", op=s.op, args=tuple(env[a] for a in s.args))
        elif isinstance(s, Store):
            out_ref = block.ref(s.buf)
            root = env[s.scalar]
        elif isinstance(s, Block):
            raise ValueError("analyze_flat: nested block (not frontend-shaped)")
    if out_ref is None or root is None:
        raise ValueError("analyze_flat: no store")
    out_vars = []
    for e in out_ref.offsets:
        if len(e.terms) == 1 and e.const == 0 and e.terms[0][1] == 1:
            out_vars.append(e.terms[0][0])
        elif e.is_const():
            out_vars.append(None)  # degenerate dim, fixed position
        else:
            raise ValueError(f"output access must be plain index, got {e}")
    return FlatOp(
        block=block, out_ref=out_ref, agg=out_ref.agg or "assign", root=root,
        ranges=block.idx_ranges(), out_vars=[v for v in out_vars if v is not None],
    )


def _product_leaves(n: _Node) -> Optional[Tuple[List[_Node], float]]:
    if n.kind == "load":
        return [n], 1.0
    if n.kind == "const":
        return [], n.value
    if n.kind == "op" and n.op == "mul":
        leaves: List[_Node] = []
        scale = 1.0
        for a in n.args:
            sub = _product_leaves(a)
            if sub is None:
                return None
            leaves.extend(sub[0])
            scale *= sub[1]
        return leaves, scale
    return None


# --------------------------------------------------------------------------
# Operand materialization
# --------------------------------------------------------------------------
def _materialize(arr: jnp.ndarray, exprs: Sequence[Affine], ranges: Mapping[str, int], wenv: Mapping[str, int]) -> Tuple[jnp.ndarray, List[str]]:
    """Slice ``arr`` so each remaining axis corresponds to one index var.

    Every expr must reduce (after substituting ``wenv``) to ``c*v + k`` or a
    constant.  Returns (array, axis var names)."""
    var_axes: List[str] = []
    index: List[object] = []
    pads: List[Tuple[int, int]] = []
    need_pad = False
    for d, e in enumerate(exprs):
        e = e.partial_eval(wenv)
        size = arr.shape[d]
        if e.is_const():
            k = e.const
            pl = max(0, -k)
            ph = max(0, k - (size - 1))
            pads.append((pl, ph))
            need_pad = need_pad or pl or ph
            index.append(k + pl)
        else:
            if len(e.terms) != 1:
                raise ValueError(f"unwindowed multi-var access {e}")
            (v, c), k = e.terms[0], e.const
            rv = ranges[v]
            lo = min(k, k + c * (rv - 1))
            hi = max(k, k + c * (rv - 1))
            pl = max(0, -lo)
            ph = max(0, hi - (size - 1))
            pads.append((pl, ph))
            need_pad = need_pad or pl or ph
            start = k + pl
            if c > 0:
                index.append(slice(start, start + c * (rv - 1) + 1, c))
            else:
                stop = start + c * (rv - 1) - 1
                index.append(slice(start, None if stop < 0 else stop, c))
            var_axes.append(v)
    if need_pad:
        arr = jnp.pad(arr, pads)
    return arr[tuple(index)], var_axes


def _mask_on_grid(constraints, grid_vars: List[str], ranges, wenv, dtype=bool):
    """AND of ``expr >= 0`` over the grid spanned by grid_vars."""
    shape = tuple(ranges[v] for v in grid_vars)
    mask = None
    for c in constraints:
        e = c.expr.partial_eval(wenv)
        if e.is_const():
            val = e.const >= 0
            m = jnp.full(shape, val)
        else:
            acc = jnp.full(shape, e.const, dtype=jnp.int32)
            for n, coef in e.terms:
                ax = grid_vars.index(n)
                iota = jax.lax.broadcasted_iota(jnp.int32, shape, ax)
                acc = acc + coef * iota
            m = acc >= 0
        mask = m if mask is None else (mask & m)
    return mask


def _unhandled_constraint_vars(constraints, wenv, allowed):
    out = set()
    for c in constraints:
        e = c.expr.partial_eval(wenv)
        for n in e.names():
            if n not in allowed:
                out.add(n)
    return out


# --------------------------------------------------------------------------
# Lowering paths
# --------------------------------------------------------------------------
def _acc_dtype(out_dtype: str) -> jnp.dtype:
    d = np.dtype(out_dtype)
    if d.kind in "iu":
        return jnp.int32
    if d == np.float64:
        return jnp.float64
    return jnp.float32


def _window_vars(op: FlatOp, leaves: List[_Node]) -> List[str]:
    """Vars that must be enumerated: every var beyond the first carrier in a
    multi-var access dim, plus constraint vars that are not output vars."""
    window: set = set()
    out_set = set(op.out_vars)
    if op.agg not in ("add", "assign"):
        # einsum can only sum; other aggregations enumerate every reduction
        # point and combine with the aggregation op across steps.
        window.update(v for v, r in op.ranges.items() if v not in out_set and r > 1)
    for leaf in leaves:
        for e in leaf.ref.offsets:
            names = [n for n in e.names() if op.ranges.get(n, 1) > 1]
            if len(names) <= 1:
                continue
            carriers = [n for n in names if n in out_set] or names
            carrier = max(carriers, key=lambda n: op.ranges[n])
            window.update(n for n in names if n != carrier)
    # constraints must end up over output vars only
    for _ in range(4):
        extra = _unhandled_constraint_vars(op.block.constraints, {w: 0 for w in window}, out_set)
        if not extra:
            break
        window.update(extra)
    return sorted(window)


def lower_contraction(op: FlatOp, leaves: List[_Node], scale: float) -> Callable:
    wvars = _window_vars(op, leaves)
    wsizes = [op.ranges[v] for v in wvars]
    n_steps = int(np.prod(wsizes)) if wvars else 1
    if n_steps > 16384:
        raise ValueError(f"window too large ({n_steps} steps)")
    out_shape = tuple(op.ranges[v] for v in op.out_vars)
    agg = op.agg
    identity = AGG_IDENTITY.get(agg, 0.0)
    out_dtype = np.dtype(op.out_ref.dtype)
    acc_dtype = _acc_dtype(op.out_ref.dtype)

    def fn(arrays: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
        acc = None
        for combo in itertools.product(*[range(s) for s in wsizes]):
            wenv = dict(zip(wvars, combo))
            ops, labels = [], []
            for leaf in leaves:
                arr = arrays[leaf.ref.from_buf].astype(acc_dtype)
                mat, axes = _materialize(arr, leaf.ref.offsets, op.ranges, wenv)
                ops.append(mat)
                labels.append(axes)
            var_letter: Dict[str, str] = {}
            for axes in labels + [op.out_vars]:
                for v in axes:
                    var_letter.setdefault(v, _EINSUM_LETTERS[len(var_letter)])
            eq = ",".join("".join(var_letter[v] for v in axes) for axes in labels)
            eq += "->" + "".join(var_letter[v] for v in op.out_vars)
            term = jnp.einsum(eq, *ops) if leaves else jnp.full(out_shape, 1.0, acc_dtype)
            if scale != 1.0:
                term = term * jnp.asarray(scale, acc_dtype)
            mask = _mask_on_grid(op.block.constraints, op.out_vars, op.ranges, wenv)
            if mask is not None:
                term = jnp.where(mask, term, jnp.asarray(identity, acc_dtype))
            if acc is None:
                acc = term
            else:
                acc = _AGG_JNP[agg](acc, term) if agg != "assign" else term
        return acc.astype(out_dtype)

    return fn


def _eval_dag(n: _Node, arrays, op: FlatOp, cache) -> Tuple[jnp.ndarray, List[str]]:
    key = id(n)
    if key in cache:
        return cache[key]
    if n.kind == "load":
        arr = arrays[n.ref.from_buf]
        mat, axes = _materialize(arr, n.ref.offsets, op.ranges, {})
        res = (mat, axes)
    elif n.kind == "const":
        res = (jnp.asarray(n.value), [])
    else:
        vals = [_eval_dag(a, arrays, op, cache) for a in n.args]
        # broadcast all args onto the union var order (output order first)
        union: List[str] = [v for v in op.out_vars]
        for _, axes in vals:
            for v in axes:
                if v not in union:
                    union.append(v)
        used = [v for v in union if any(v in axes for _, axes in vals)]
        bargs = []
        for val, axes in vals:
            if not axes:
                bargs.append(val)
                continue
            perm = [axes.index(v) for v in used if v in axes]
            a = jnp.transpose(val, perm)
            shape = [op.ranges[v] if v in axes else 1 for v in used]
            bargs.append(a.reshape(shape))
        fn = _J_UNARY[n.op] if len(bargs) == 1 and n.op in _J_UNARY else _J_BINARY[n.op]
        res = (fn(*bargs), used)
    cache[key] = res
    return res


def lower_general(op: FlatOp) -> Callable:
    """Elementwise DAGs (assign) and reductions of general DAGs."""
    out_shape = tuple(op.ranges[v] for v in op.out_vars)
    out_dtype = np.dtype(op.out_ref.dtype)
    red_vars = [v for v in sorted(op.ranges) if v not in op.out_vars and op.ranges[v] > 1]
    identity = AGG_IDENTITY.get(op.agg, 0.0)

    def fn(arrays: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
        cache: Dict[int, Tuple[jnp.ndarray, List[str]]] = {}
        val, axes = _eval_dag(op.root, arrays, op, cache)
        grid = [v for v in (*op.out_vars, *red_vars)]
        # expand to full grid order
        if axes:
            perm = [axes.index(v) for v in grid if v in axes]
            val = jnp.transpose(val, perm)
            val = val.reshape([op.ranges[v] if v in axes else 1 for v in grid])
            val = jnp.broadcast_to(val, [op.ranges[v] for v in grid])
        else:
            val = jnp.broadcast_to(val, [op.ranges[v] for v in grid])
        mask = _mask_on_grid(op.block.constraints, grid, op.ranges, {})
        if mask is not None:
            val = jnp.where(mask, val, jnp.asarray(identity, val.dtype))
        if red_vars:
            axis = tuple(range(len(op.out_vars), len(grid)))
            red = {"add": jnp.sum, "max": jnp.max, "min": jnp.min, "mul": jnp.prod}[op.agg]
            val = red(val, axis=axis)
        return val.astype(out_dtype)

    return fn


def lower_block_jnp(block: Block) -> Callable[[Mapping[str, jnp.ndarray]], jnp.ndarray]:
    op = analyze_flat(block)
    prod = _product_leaves(op.root)
    if op.agg != "assign" and prod is not None:
        leaves, scale = prod
        return lower_contraction(op, leaves, scale)
    if op.agg != "assign":
        return lower_general(op)
    # assign: no reduction vars allowed (would be a nondeterministic race)
    return lower_general(op)


def _out_region(op: FlatOp, buf_shape: Tuple[int, ...]) -> Tuple[Tuple[int, int], ...]:
    region = []
    vi = 0
    for e in op.out_ref.offsets:
        if e.is_const():
            region.append((e.const, e.const + 1))
        else:
            v = op.out_vars[vi]
            vi += 1
            region.append((0, op.ranges[v]))
    return tuple(region)


class _LazyZeros(dict):
    """Array environment that materializes a zero buffer on first read —
    a fully-overwritten buffer never pays an init dispatch."""

    def __init__(self, base: Mapping, buffers: Mapping):
        super().__init__(base)
        self._buffers = buffers

    def __missing__(self, key):
        d = self._buffers[key]
        v = jnp.zeros(d.shape, np.dtype(d.dtype))
        self[key] = v
        return v


def _group_executor(prog: Program, plans: Mapping[str, Tuple[Block, FlatOp, Callable]],
                    g: Sequence[str], internal: frozenset) -> Callable:
    """One fusion group as an executable unit: fn(arrays) -> updates dict.
    Group-internal intermediates never leave the unit."""

    def group_fn(arrays, g=tuple(g), internal=frozenset(internal)):
        local = _LazyZeros(arrays, prog.buffers)
        updates: Dict[str, jnp.ndarray] = {}
        for name in g:
            blk, op, fn = plans[name]
            val = fn(local)
            if op.agg != "assign" and len(g) > 1 and jax.default_backend() == "cpu":
                # Keep XLA CPU's library gemm: loop-fusing an expensive
                # elementwise epilogue (erf/gelu) into a dot consumer
                # drops the contraction off the fast gemm runtime.  The
                # barrier pins the dot, while the group's elementwise
                # members still fuse with each other.
                val = jax.lax.optimization_barrier(val)
            buf = op.out_ref.from_buf
            full = local.get(buf)
            decl_shape = prog.buffers[buf].shape
            region = _out_region(op, decl_shape)
            out_shape_full = tuple(hi - lo for lo, hi in region)
            val = val.reshape(out_shape_full)
            if out_shape_full == decl_shape:
                if op.agg != "assign" and full is not None:
                    # a previous writer's contribution is in the buffer:
                    # aggregate with it (each lowering computes its own
                    # complete reduction from the identity, so combining
                    # results with the agg op matches the reference's
                    # single accumulating buffer)
                    new = _AGG_JNP[op.agg](full, val.astype(full.dtype))
                else:
                    new = val
            else:
                if full is None:  # partially-written buffer: zero base
                    full = jnp.zeros(decl_shape,
                                     np.dtype(prog.buffers[buf].dtype))
                starts = tuple(lo for lo, _ in region)
                if op.agg != "assign":
                    cur = jax.lax.dynamic_slice(full, starts, out_shape_full)
                    val = _AGG_JNP[op.agg](cur, val.astype(full.dtype))
                new = jax.lax.dynamic_update_slice(
                    full, val.astype(full.dtype), starts)
            local[buf] = new
            if buf not in internal:
                updates[buf] = new
        return updates

    return group_fn


def _group_needed(plans, g: Sequence[str]) -> frozenset:
    """Buffers a group's jit signature must cover: everything it reads or
    writes — passing the whole program environment would add O(total
    buffers) pytree flattening per dispatch."""
    needed = set()
    for n in g:
        blk, op, _fn = plans[n]
        needed.add(op.out_ref.from_buf)
        for r in blk.refs:
            if r.dir in (RefDir.IN, RefDir.INOUT):
                needed.add(r.from_buf)
    return frozenset(needed)


def lower_group_jnp(prog: Program, names: Sequence[str],
                    jit_scope: Optional[str] = "group") -> Callable:
    """Lower the named semantic (frontend-shaped) op blocks as ONE jnp
    compile unit: fn(arrays) -> {buffer: full array} updates.

    This is the per-unit fallback of the hybrid Pallas composer
    (``lower_pallas.lower_program_hybrid``): when one fusion group cannot
    lower to a kernel, only its member ops take the jnp path, jitted as a
    single dispatch, while the rest of the program keeps its kernels."""
    plans: Dict[str, Tuple[Block, FlatOp, Callable]] = {}
    want = set(names)
    for s in prog.entry.stmts:
        if isinstance(s, Block) and s.name in want:
            plans[s.name] = (s, analyze_flat(s), lower_block_jnp(s))
    missing = [n for n in names if n not in plans]
    if missing:
        raise KeyError(f"op blocks {missing} not in program")
    fn = _group_executor(prog, plans, list(names), frozenset())
    if jit_scope in ("op", "group"):
        fn = jax.jit(fn)
    needed = _group_needed(plans, list(names))

    def run(arrays: Mapping[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        return fn({b: arrays[b] for b in needed if b in arrays})

    run.needed = needed
    return run


def lower_program_jnp(prog: Program, groups: Optional[List[List[str]]] = None,
                      jit_scope: Optional[str] = None,
                      profile: bool = False
                      ) -> Callable[[Mapping[str, jnp.ndarray]], Dict[str, jnp.ndarray]]:
    """Lower every op block; returns fn(inputs)->outputs dict.

    ``groups`` switches to **per-group lowering** (fusion groups from the
    pass pipeline): each group of semantic op-block names becomes one
    compiled unit, its internal intermediates stay local to the group
    (never entering the program-level array environment or the returned
    dict), and — with ``jit_scope="group"`` (or ``"op"`` for per-op
    units) — each unit is wrapped in its own ``jax.jit``, so the group is
    the dispatch granularity, mirroring the Pallas backend's
    one-kernel-per-group contract.

    ``profile=True`` wall-times each group dispatch (synchronizing on its
    updates), keeping the best observation per unit in ``run.unit_times``
    keyed by the "+"-joined group member names; callers wanting
    meaningful per-unit times should pair it with ``jit_scope="group"``
    and no outer jit, so dispatch boundaries survive.
    """
    plans: Dict[str, Tuple[Block, FlatOp, Callable]] = {}
    order: List[str] = []
    for s in prog.entry.stmts:
        if not isinstance(s, Block):
            continue
        op = analyze_flat(s)
        fn = lower_block_jnp(s)
        plans[s.name] = (s, op, fn)
        order.append(s.name)

    if groups is None or sorted(n for g in groups for n in g) != sorted(order):
        groups = [[n] for n in order]

    # who reads each buffer, by op-block name (for internal-buffer elision)
    readers: Dict[str, set] = {}
    for name in order:
        for r in plans[name][0].refs:
            if r.dir in (RefDir.IN, RefDir.INOUT):
                readers.setdefault(r.from_buf, set()).add(name)

    elided: set = set()
    group_fns = []
    for g in groups:
        written = {plans[n][1].out_ref.from_buf for n in g}
        internal = {b for b in written
                    if b not in prog.outputs
                    and readers.get(b, set()) <= set(g)
                    and b != plans[g[-1]][1].out_ref.from_buf}
        elided |= internal
        # the group's jit signature covers only what it touches
        needed = _group_needed(plans, g) | set(written)
        group_fn = _group_executor(prog, plans, g, frozenset(internal))
        if jit_scope in ("op", "group"):
            group_fn = jax.jit(group_fn)
        group_fns.append(("+".join(g), group_fn, frozenset(needed)))

    unit_times: Dict[str, float] = {}

    def run(inputs: Mapping[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        # Buffers are materialized lazily: a fully-overwriting producer
        # needs no zero-init dispatch; partially-written buffers start
        # from zeros inside their group.
        arrays: Dict[str, jnp.ndarray] = {
            name: jnp.asarray(inputs[name]) for name in prog.inputs}
        for gname, gfn, needed in group_fns:
            if profile:
                t0 = time.perf_counter()
            updates = gfn({b: arrays[b] for b in needed if b in arrays})
            arrays.update(updates)
            if profile:
                jax.block_until_ready(list(updates.values()))
                dt = time.perf_counter() - t0
                prev = unit_times.get(gname)
                unit_times[gname] = dt if prev is None or dt < prev else prev
        for name, d in prog.buffers.items():
            if name not in arrays and name not in prog.inputs and name not in elided:
                arrays[name] = jnp.zeros(d.shape, np.dtype(d.dtype))
        return {n: arrays[n] for n in prog.buffers
                if n not in prog.inputs and n not in elided}

    run.n_kernels = len(group_fns)
    run.unit_times = unit_times
    return run

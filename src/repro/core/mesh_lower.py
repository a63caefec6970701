"""Multi-device emission: play a :class:`~repro.core.shardplan.ShardPlan`
inside ``shard_map``.

The driver compiles each of the plan's segments with the ordinary
single-device ``stripe_jit`` pipeline (per-block hybrid Pallas/jnp
composer, cache, tuning DB — everything), then :func:`emit` stitches
the compiled segments together with the plan's explicit collectives:

* ``halo`` — a ``ppermute`` pair moving each shard's boundary slabs to
  its neighbors, concatenated as padding.  The permutation is
  deliberately *not* cyclic: ranks that receive nothing are zero-filled
  by ``ppermute``, which is exactly the boundary masking the dropped
  frontend constraints used to provide.
* ``psum`` / ``all_gather`` — reduction-split partials and sharded
  program outputs.
* ``slice`` — localize a replicated buffer to this shard (no traffic).
* ``ring`` — ``parallel.collective_matmul``'s reduce-scatter matmul,
  the overlap primitive the cost model chose over a plain psum.

Execution always runs on a **flat 1-D mesh** (one ring axis over all
devices); a multi-dim mesh *shape* changes only the cost model's link
bandwidth, not the emitted program.  ``count_collectives`` /
``expected_primitive_counts`` close the loop: tests and the bench leg
assert that the collectives the plan predicted are the collectives the
jaxpr actually contains.
"""
from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Tuple

from .shardplan import Segment, ShardPlan

_COLLECTIVE_PRIMS = ("psum", "all_gather", "ppermute", "all_to_all",
                     "reduce_scatter")


def resolve_mesh(mesh):
    """Normalize a ``mesh=`` argument (device count, mesh shape tuple,
    or ``jax.sharding.Mesh``) to ``(flat 1-D Mesh, axis name, model
    shape)``.  Returns ``None`` for a trivial (size-1 or ``None``)
    mesh — the caller should compile single-device."""
    if mesh is None:
        return None
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if isinstance(mesh, Mesh):
        shape = tuple(int(s) for s in mesh.devices.shape)
        devs = np.asarray(mesh.devices).reshape(-1)
        if devs.size <= 1:
            return None
        axis = mesh.axis_names[0] if len(mesh.axis_names) == 1 else "x"
        return Mesh(devs, (axis,)), str(axis), shape
    shape = (int(mesh),) if isinstance(mesh, int) else tuple(int(s) for s in mesh)
    n = 1
    for s in shape:
        n *= s
    if n <= 1:
        return None
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"mesh {shape} needs {n} devices; only {len(devs)} available "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N for "
            "emulated host devices)")
    return Mesh(np.array(devs[:n]), ("x",)), "x", shape


def _halo_pad(x, dim: int, lo: int, hi: int, axis: str, n: int):
    import jax
    import jax.numpy as jnp

    parts = []
    if lo:
        tail = jax.lax.slice_in_dim(x, x.shape[dim] - lo, x.shape[dim],
                                    axis=dim)
        parts.append(jax.lax.ppermute(
            tail, axis, [(i, i + 1) for i in range(n - 1)]))
    parts.append(x)
    if hi:
        head = jax.lax.slice_in_dim(x, 0, hi, axis=dim)
        parts.append(jax.lax.ppermute(
            head, axis, [(i + 1, i) for i in range(n - 1)]))
    return jnp.concatenate(parts, axis=dim)


def emit(prog, plan: ShardPlan, segments: List[Segment], compiled: List,
         jmesh, axis: str, jit: bool = True):
    """Build the whole-program callable: ``shard_map`` over the plan's
    emission script, inner segments already compiled.  Takes and returns
    global (unsharded) arrays keyed like the single-device driver."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n = plan.n
    in_order = list(prog.inputs)
    out_order = list(prog.outputs)
    in_specs = []
    for name in in_order:
        d = plan.in_specs.get(name, -1)
        rank = len(prog.buffers[name].shape)
        in_specs.append(
            P(*[axis if i == d else None for i in range(rank)])
            if d >= 0 else P())

    def body(*args):
        env = dict(zip(in_order, args))
        for step in plan.steps:
            kind = step[0]
            if kind == "segment":
                seg = segments[step[1]]
                outs = compiled[step[1]]({k: env[k] for k in seg.inputs})
                env.update(outs)
            elif kind == "halo":
                _, buf, dim, lo, hi = step
                env[buf] = _halo_pad(env[buf], dim, lo, hi, axis, n)
            elif kind == "gather":
                _, buf, dim = step
                env[buf] = jax.lax.all_gather(env[buf], axis, axis=dim,
                                              tiled=True)
            elif kind == "slice":
                _, buf, dim, size = step
                i = jax.lax.axis_index(axis)
                env[buf] = jax.lax.dynamic_slice_in_dim(
                    env[buf], i * size, size, axis=dim)
            elif kind == "psum":
                env[step[1]] = jax.lax.psum(env[step[1]], axis)
            elif kind == "ring":
                from ..parallel.collective_matmul import (
                    ring_matmul_reduce_scatter,
                )

                info = step[2]
                acc = ring_matmul_reduce_scatter(
                    env[info["x"]], env[info["w"]], axis)
                full = jax.lax.all_gather(acc, axis, axis=1, tiled=True)
                env[info["out"]] = full.astype(info["out_dtype"])
            else:
                raise ValueError(f"unknown plan step {step!r}")
        return tuple(env[o] for o in out_order)

    sharded = jax.shard_map(body, mesh=jmesh, in_specs=tuple(in_specs),
                            out_specs=tuple(P() for _ in out_order),
                            check_vma=False)
    if jit:
        sharded = jax.jit(sharded)

    def call(arrays: Mapping[str, Any]) -> Dict[str, Any]:
        outs = sharded(*[jnp.asarray(arrays[k]) for k in in_order])
        return dict(zip(out_order, outs))

    call._sharded = sharded
    call._in_order = in_order
    return call


# --------------------------------------------------------------------------
# predicted-vs-emitted collective accounting
# --------------------------------------------------------------------------
def _count_jaxpr(jaxpr, counts: Dict[str, int]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if any(name.startswith(p) for p in _COLLECTIVE_PRIMS):
            counts[name] = counts.get(name, 0) + 1
        for v in eqn.params.values():
            _walk(v, counts)


def _walk(v, counts: Dict[str, int]) -> None:
    if hasattr(v, "eqns"):           # raw Jaxpr (e.g. shard_map's param)
        _count_jaxpr(v, counts)
    elif hasattr(v, "jaxpr"):        # ClosedJaxpr
        _count_jaxpr(v.jaxpr, counts)
    elif isinstance(v, (list, tuple)):
        for x in v:
            _walk(x, counts)


def count_collectives(fn, arrays: Mapping[str, Any]) -> Dict[str, int]:
    """Static collective-primitive counts in ``fn``'s jaxpr (recursing
    through shard_map / scan / jit sub-jaxprs).  ``fn`` may be the
    dict-calling convention returned by :func:`emit` (or the driver) or
    any positional callable."""
    import jax
    import jax.numpy as jnp

    fn = getattr(fn, "_fn", fn)  # unwrap the driver's CompiledProgram
    target = getattr(fn, "_sharded", None)
    if target is not None:
        order = fn._in_order
        jaxpr = jax.make_jaxpr(target)(
            *[jnp.asarray(arrays[k]) for k in order])
    else:
        jaxpr = jax.make_jaxpr(fn)(*arrays.values())
    counts: Dict[str, int] = {}
    _count_jaxpr(jaxpr.jaxpr, counts)
    return counts


def expected_primitive_counts(plan: ShardPlan) -> Dict[str, int]:
    """The static primitive counts :func:`emit` produces for ``plan`` —
    what :func:`count_collectives` must report back.  A halo step is one
    ppermute per nonzero margin; a ring step is one ppermute (inside the
    fori_loop body — static count, n dynamic trips) plus the epilogue
    all-gather."""
    counts: Dict[str, int] = {}

    def add(k: str, m: int = 1):
        if m:
            counts[k] = counts.get(k, 0) + m

    for step in plan.steps:
        kind = step[0]
        if kind == "halo":
            _, _, _, lo, hi = step
            add("ppermute", (1 if lo else 0) + (1 if hi else 0))
        elif kind == "gather":
            add("all_gather")
        elif kind == "psum":
            add("psum")
        elif kind == "ring":
            add("ppermute")
            add("all_gather")
    return counts


def expected_primitive_counts_from_record(mesh_info: Mapping[str, Any]) -> Dict[str, int]:
    """Same accounting as :func:`expected_primitive_counts`, but from the
    ``CompileRecord.mesh`` provenance dict (JSON round-trippable) — so a
    cached or persisted record can still be checked against a jaxpr."""
    counts: Dict[str, int] = {}

    def add(k: str, m: int = 1):
        if m:
            counts[k] = counts.get(k, 0) + m

    for c in mesh_info.get("collectives", ()):
        op = c["collective"]
        if op == "halo":
            add("ppermute", (1 if c.get("lo") else 0) + (1 if c.get("hi") else 0))
        elif op == "ring_matmul":
            add("ppermute")
            add("all_gather")
        else:
            add(op)
    return counts

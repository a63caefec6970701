"""The unified compile driver: frontend -> passes -> lowering, cached.

``stripe_jit`` is the single entry point tying the pieces together behind
the two-level compilation cache (``cache.py``):

1. the input (a ``Program``, ``TileProgram``, Tile contraction string, or
   a callable producing one) is built into a Stripe ``Program``;
2. a content key is computed from the canonical IR, the hardware config
   fingerprint, and the backend;
3. **memory hit** — the live ``CompiledProgram`` is returned immediately;
   **disk hit** — the persisted tilings replay through the pass pipeline
   via a ``TilingOracle`` (no autotile search); **miss** — the full
   pipeline runs (optionally with the parallel autotuner) and both cache
   levels are populated;
4. the optimized program is lowered by the requested backend:
   ``jnp`` (XLA via the reference lowering, jit'd), ``pallas`` (the tiled
   TPU kernel, falling back to jnp when the block shape is unsupported),
   or ``reference`` (the exact numpy interpreter).
"""
from __future__ import annotations

import copy
import dataclasses
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple, Union

import jax.numpy as jnp
import numpy as np

from ..obs import profile as obs_profile
from ..obs import trace as obs_trace
from ..reliability import faults
from . import cache as _cache
from .frontend import TileProgram, single_op_program
from .hwconfig import HardwareConfig
from .interp import execute_reference
from .ir import Block, Program, RefDir, ir_fingerprint
from .lower_jnp import Stacked, _acc_dtype, lower_program_jnp, select_stacked
from .passes import PassManager, TilingOracle
from .platform import resolve_interpret

DRIVER_VERSION = 1

BACKENDS = ("jnp", "pallas", "reference")


@dataclasses.dataclass
class CompileRecord:
    """What happened during one ``stripe_jit`` call."""

    key: str
    backend: str  # backend actually used (may record a pallas->jnp fallback)
    hw_name: str
    cache_hit: bool = False  # in-memory (same-process) hit
    disk_hit: bool = False  # tilings replayed from the on-disk store
    compile_time_s: float = 0.0
    tilings: Dict[str, Dict[str, int]] = dataclasses.field(default_factory=dict)
    pass_trace: List = dataclasses.field(default_factory=list)
    fallback_reason: str = ""
    # Per-group lowering: the semantic op-block names each fusion group
    # absorbed, and the kernel count — for the pallas backend this is the
    # actual pallas_call count per invocation plus one dispatch per
    # jnp-fallback unit; for jnp it is the fusion-group (compile-unit)
    # count, though the driver still wraps the whole program in one outer
    # jax.jit (use lower_program_jnp(jit_scope="group") for per-group
    # dispatch, as the fusion bench does); the reference interpreter
    # launches no kernels and reports 0.
    n_kernels: int = 0
    groups: List[List[str]] = dataclasses.field(default_factory=list)
    # Per-block hybrid lowering (pallas backend): which backend each
    # lowering unit (fusion group / boundary-piece set, keyed by its
    # "+"-joined member names) actually took, and why the jnp units fell
    # back.  Empty for whole-program backends.
    block_backends: Dict[str, str] = dataclasses.field(default_factory=dict)
    block_fallbacks: Dict[str, str] = dataclasses.field(default_factory=dict)
    # Compile-failure quarantine: True when this compile served the jnp
    # fallback because the Pallas lowering *crashed* (not a legality
    # fallback) now or within the backoff embargo; ``quarantine`` carries
    # the negative-cache entry (reason, fail_count, backoff_s, expired).
    quarantined: bool = False
    quarantine: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Kernel profiling (``stripe_jit(..., profile=True)``): per lowering
    # unit, the cost model's predicted latency (autotile roofline,
    # summed over the unit's blocks) and the best measured wall time
    # observed across dispatches.  ``measured_latency_s`` fills in as the
    # compiled program runs (the dict is shared across cache-hit records
    # of the same artifact); (predicted, measured) pairs are appended to
    # the residual JSONL under the cache dir on the first dispatch.
    profiled: bool = False
    ir_fingerprint: str = ""
    hw_fingerprint: str = ""
    predicted_latency_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    measured_latency_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    # Per-unit roofline terms behind predicted_latency_s (latency_s,
    # t_mem/t_compute and their raw uncalibrated counterparts) — the
    # residual log carries them so the calibration fit regresses on raw
    # terms even after the model is already calibrated.
    predicted_terms: Dict[str, Dict[str, float]] = dataclasses.field(default_factory=dict)
    # Where the tilings came from: "analytic" (the autotile search or a
    # plain disk replay of its choice), "tuned" (a measured-best entry
    # served by the tuning DB — ``tuned`` carries the entry's provenance:
    # candidate id, measured latency, measurement source/rounds/age), or
    # "replay" (caller-supplied tilings via ``compile_with_tilings``).
    decision_source: str = "analytic"
    tuned: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Multi-device provenance (``stripe_jit(..., mesh=)``): mesh shape /
    # axis / device count, the shard plan's split decisions, the emitted
    # collectives with their modelled bytes and overlap choices, and a
    # per-segment summary (each segment is its own cached single-device
    # compile).  ``{"fallback": reason, ...}`` when the partitioner found
    # no legal split and the program compiled single-device instead.
    mesh: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Inputs read at their stored dtype where it is narrower than the
    # accumulator of a contraction that reads them (the kernel promotes
    # the tile after the load), and inputs a caller handed in place
    # (``Stacked``: a stacked array plus the index of the slice, which the
    # Pallas kernels read where it lies):
    # ``{input: {"dtype", "narrow", "in_place"}}``.  Narrow entries are
    # fixed at compile time; ``in_place`` fills in as the program is
    # called (the dict is shared with cache-hit records of the artifact).
    stored_reads: Dict[str, Dict[str, Any]] = dataclasses.field(default_factory=dict)

    def fusion_decisions(self) -> List[Dict]:
        """Accepted/rejected merges recorded by the fusion pass."""
        for entry in self.pass_trace:
            if entry[0] == "fuse" and len(entry) > 2:
                return list(entry[2])
        return []

    def fallback_reasons(self) -> Dict[str, str]:
        """Every recorded Pallas fallback: per-unit reasons from the
        hybrid lowering, plus the whole-program reason (key
        ``"<program>"``) when the backend fell back wholesale."""
        out = dict(self.block_fallbacks)
        if self.fallback_reason:
            out["<program>"] = self.fallback_reason
        return out

    def latency_residuals(self) -> List[Dict[str, Any]]:
        """Per-unit (predicted, measured) latency pairs of a profiled
        compile — empty until the compiled program has dispatched."""
        return [{"block": u,
                 "predicted_s": self.predicted_latency_s.get(u),
                 "measured_s": m}
                for u, m in sorted(self.measured_latency_s.items())]


class CompiledProgram:
    """A compiled Stripe program: callable on a dict of input arrays,
    returning a dict of output arrays."""

    def __init__(self, program: Program, fn: Callable[[Mapping[str, Any]], Dict[str, Any]],
                 hw: HardwareConfig, record: CompileRecord):
        self.program = program
        self.hw = hw
        self.record = record
        self._fn = fn

    @property
    def outputs(self) -> List[str]:
        return list(self.program.outputs)

    def __call__(self, arrays: Mapping[str, Any]) -> Dict[str, Any]:
        stacked = [k for k, v in arrays.items() if isinstance(v, Stacked)]
        if not stacked:
            return self._fn(arrays)
        for k in stacked:
            entry = self.record.stored_reads.setdefault(k, {
                "dtype": str(np.dtype(arrays[k].array.dtype)), "narrow": False})
            entry["in_place"] = True
        if not getattr(self._fn, "takes_stacked", False):
            arrays = select_stacked(arrays)
        return self._fn(arrays)


def stored_reads(prog: Program) -> Dict[str, Dict[str, Any]]:
    """The narrow entries of ``CompileRecord.stored_reads``: each float
    input that a contraction reads at a dtype narrower than its
    accumulator."""
    out: Dict[str, Dict[str, Any]] = {}
    for blk in prog.entry.stmts:
        if not isinstance(blk, Block):
            continue
        outs = [r for r in blk.refs if r.dir in (RefDir.OUT, RefDir.INOUT)]
        if len(outs) != 1 or (outs[0].agg or "assign") == "assign":
            continue
        acc = np.dtype(_acc_dtype(outs[0].dtype))
        for r in blk.refs:
            d = np.dtype(r.dtype)
            if (r.dir == RefDir.IN and r.from_buf in prog.inputs
                    and jnp.issubdtype(d, jnp.floating) and d.itemsize < acc.itemsize):
                out[r.from_buf] = {"dtype": str(d), "narrow": True, "in_place": False}
    return out


# --------------------------------------------------------------------------
# Input normalization
# --------------------------------------------------------------------------
def _as_program(fn_or_contraction, tensors=None, out=None, ranges=None, name="op") -> Program:
    obj = fn_or_contraction
    if callable(obj) and not isinstance(obj, (Program, TileProgram)):
        obj = obj()
    if isinstance(obj, TileProgram):
        obj = obj.build()
    if isinstance(obj, str):
        if tensors is None or out is None:
            raise ValueError("contraction-string input needs tensors= and out=")
        obj = single_op_program(obj, tensors, out=out, ranges=ranges, name=name)
    if not isinstance(obj, Program):
        raise TypeError(f"cannot compile {type(obj).__name__}; "
                        "expected Program, TileProgram, contraction str, or a callable producing one")
    return obj


# --------------------------------------------------------------------------
# Lowering
# --------------------------------------------------------------------------
def _semantic_groups(opt: Program) -> Optional[List[List[str]]]:
    """Fusion groups of the optimized program as lists of *semantic*
    op-block names (from each block's ``members:`` tag), or None when the
    mapping does not cover the semantic program exactly (e.g. after
    transpose-pass block insertion the driver lowers per op)."""
    from .passes.fuse import members_of

    semantic = opt.source
    if semantic is None:
        return None
    sem_names = {s.name for s in semantic.entry.stmts if isinstance(s, Block)}
    groups: List[List[str]] = []
    seen: set = set()
    for s in opt.entry.stmts:
        if not isinstance(s, Block):
            continue
        g = [n for n in members_of(s) if n in sem_names and n not in seen]
        if g:
            groups.append(g)
            seen.update(g)
    if seen != sem_names:
        return None
    return groups


def _program_groups(opt: Program) -> List[List[str]]:
    """Fusion groups (semantic-op name lists) of an optimized program,
    falling back to one group per semantic op when the mapping is not
    exact — the dispatch-unit count without any backend lowering."""
    semantic = opt.source or opt
    return _semantic_groups(opt) or [
        [s.name] for s in semantic.entry.stmts if isinstance(s, Block)]


@dataclasses.dataclass
class _Lowered:
    """What one backend lowering produced, for the CompileRecord."""

    fn: Callable
    backend: str
    fallback: str = ""
    n_kernels: int = 0
    groups: List[List[str]] = dataclasses.field(default_factory=list)
    block_backends: Dict[str, str] = dataclasses.field(default_factory=dict)
    block_fallbacks: Dict[str, str] = dataclasses.field(default_factory=dict)
    quarantined: bool = False
    quarantine: Dict[str, Any] = dataclasses.field(default_factory=dict)


def _lower(opt: Program, backend: str, interpret: bool, jit: bool,
           hw: Optional[HardwareConfig] = None,
           quarantine: Optional[_cache.QuarantineStore] = None,
           key: str = "", profile: bool = False,
           force_jnp_units: Optional[set] = None) -> _Lowered:
    """Lower the optimized program.  For the pallas backend, a *crash*
    during lowering (as opposed to a known-unsupported legality fallback)
    degrades to the jnp path and negative-caches the key in
    ``quarantine`` with exponential backoff, so a bad (config, program)
    point serves degraded instead of failing the caller — and is not
    re-attempted until the embargo lapses."""
    semantic = opt.source or opt
    groups = _program_groups(opt)
    if backend == "reference":
        # the interpreter launches no kernels and ignores grouping
        fn = lambda arrays: execute_reference(semantic, arrays)  # noqa: E731
        return _Lowered(fn, backend, groups=groups)
    fallback = ""
    blk_backends: Dict[str, str] = {}
    blk_falls: Dict[str, str] = {}
    quarantined = False
    quar_info: Dict[str, Any] = {}
    if backend == "pallas":
        from .lower_pallas import UnsupportedPallas, lower_program_hybrid

        if quarantine is not None and quarantine.active(key):
            entry = quarantine.get(key)
            backend = "jnp"
            fallback = f"quarantined: {entry.reason}"
            quarantined, quar_info = True, entry.as_dict()
        else:
            try:
                faults.check("compile.stripe_jit", key=key, backend="pallas")
                # per-block hybrid: each fusion group / boundary-piece unit
                # lowers to Pallas or falls back to jnp independently
                with obs_trace.span("lower.pallas", interpret=interpret,
                                    profile=profile):
                    fn = lower_program_hybrid(
                        opt, interpret=interpret,
                        pipeline_depth=hw.pipeline_depth if hw is not None else 2,
                        profile=profile, force_jnp_units=force_jnp_units,
                        vmem_cap=hw.inner_mem().size_bytes if hw is not None else None)
            except UnsupportedPallas as e:
                # legality fallback: deterministic and known, no quarantine
                backend, fallback = "jnp", str(e)
            except Exception as e:  # crash-class failure: quarantine the key
                backend = "jnp"
                fallback = f"compile crashed: {e!r}"
                quarantined = True
                if quarantine is not None:
                    quar_info = quarantine.record_failure(key, repr(e)).as_dict()
            else:
                if quarantine is not None and quarantine.get(key) is not None:
                    # the embargo had lapsed and the retry succeeded
                    quarantine.clear(key)
                if fn.n_pallas > 0:
                    return _Lowered(fn, "pallas", "", fn.n_kernels, groups,
                                    dict(fn.block_backends), dict(fn.block_reasons))
                # every unit fell back: take the whole-program jnp path below
                # (one outer jax.jit beats N independently-jitted dispatches),
                # keeping the per-unit reasons on the record
                backend = "jnp"
                fallback = "; ".join(f"{k}: {v}"
                                     for k, v in fn.block_reasons.items())
                blk_backends = dict(fn.block_backends)
                blk_falls = dict(fn.block_reasons)
    with obs_trace.span("lower.jnp", profile=profile):
        # profiled jnp lowering keeps per-group dispatch boundaries
        # (no outer jit) so each unit can be wall-timed individually
        fn = lower_program_jnp(semantic, groups=groups,
                               jit_scope="group" if profile else None,
                               profile=profile)
        n_kernels = fn.n_kernels
        if jit and not profile:
            import jax

            fn = jax.jit(fn)
    return _Lowered(fn, backend, fallback, n_kernels, groups,
                    blk_backends, blk_falls, quarantined, quar_info)


def _attach_profiling(low: _Lowered, record: CompileRecord,
                      cache: _cache.CompilationCache, interpret: bool,
                      tune_db=None, requested_backend: str = "") -> Callable:
    """Wrap a lowered callable so each dispatch folds the lowering's
    per-unit wall times into ``record.measured_latency_s`` (best
    observation wins; the dict is shared with cache-hit records of the
    same artifact) and the first dispatch appends (predicted, measured)
    rows to the residual JSONL under the cache dir — and, when a tuning
    DB is attached, records the program's measured latency under its
    compile identity, so profiled serving traffic *populates* the DB."""
    inner = low.fn
    unit_times = getattr(inner, "unit_times", None)
    state = {"logged": False}

    def wrapper(arrays):
        t0 = time.perf_counter()
        out = inner(arrays)
        if unit_times is not None:
            record.measured_latency_s.update(unit_times)
        else:
            # whole-program dispatch (reference interpreter): one unit
            try:
                import jax

                jax.block_until_ready(out)
            except Exception:
                pass
            dt = time.perf_counter() - t0
            prev = record.measured_latency_s.get("<program>")
            if prev is None or dt < prev:
                record.measured_latency_s["<program>"] = dt
        if not state["logged"] and record.measured_latency_s:
            state["logged"] = True
            obs_profile.append_residuals(
                obs_profile.residual_rows(record, interpret),
                obs_profile.residual_log_path(cache), db=tune_db)
            if tune_db is not None and record.tilings and record.ir_fingerprint:
                try:
                    tune_db.record(
                        record.ir_fingerprint, record.hw_fingerprint,
                        requested_backend or record.backend, interpret,
                        tilings=record.tilings,
                        measured_s=sum(record.measured_latency_s.values()),
                        predicted_s=(sum(record.predicted_latency_s.values())
                                     or None),
                        block_backends=record.block_backends,
                        source="profile")
                except Exception:
                    pass  # measurement feedback must never fail a dispatch
        return out

    wrapper.takes_stacked = getattr(inner, "takes_stacked", False)
    return wrapper


# --------------------------------------------------------------------------
# Measured-feedback tuning support
# --------------------------------------------------------------------------
def _resolve_tune(tune, cache: _cache.CompilationCache):
    """Normalize the ``tune=`` argument: None/False disables, True opens
    the :class:`~repro.tune.db.TuningDB` next to the cache's disk store
    (or the default cache dir), and a ``TuningDB`` instance is used as
    given."""
    if tune is None or tune is False:
        return None
    from ..tune.db import TuningDB

    if isinstance(tune, TuningDB):
        return tune
    return TuningDB(dir=cache.disk_dir)


def _calibration_fp(hw_fp: str) -> str:
    """The active calibration's cache-key component for this hardware
    fingerprint ("" when the cost model is uncalibrated)."""
    from ..tune import calibrate

    return calibrate.active_fingerprint(hw_fp) if calibrate.any_active() else ""


# --------------------------------------------------------------------------
# Driver entry points
# --------------------------------------------------------------------------
def compile_cached(prog: Program, hw: HardwareConfig,
                   cache: Optional[_cache.CompilationCache] = None,
                   workers: Optional[int] = None,
                   use_disk: bool = True) -> Tuple[Program, CompileRecord]:
    """Run the pass pipeline under the compilation cache; no lowering.

    This is the sweep-friendly compile entry: no backend is built or
    executed, yet the record still carries the fusion groups / kernel
    count and the full pass trace, so the explore subsystem can score a
    point analytically (``cost.score_pass_trace``) straight from it — or,
    on a disk hit, from the persisted payload without recompiling.

    Returns a deep copy on memory hits so callers can mutate freely.
    """
    if cache is None:
        cache = _cache.get_default_cache()
    t0 = time.perf_counter()
    hw_fp = hw.fingerprint()
    ir_fp = ir_fingerprint(prog)
    key = _cache.content_key(
        "compile", DRIVER_VERSION, _cache.CACHE_VERSION,
        ir_fp, hw_fp,
        # tilings chosen under a calibrated cost model can differ, so
        # calibrated compiles never collide with uncalibrated ones
        _calibration_fp(hw_fp),
    )
    hit = cache.get_memory(key)
    if isinstance(hit, tuple) and len(hit) == 2 and isinstance(hit[0], Program):
        # the memory tier holds (optimized program, cold record): hit
        # records keep the cold compile's tilings/trace, so they stay
        # scorable (cost.score_pass_trace) even with the disk tier off
        prog0, rec0 = hit
        rec = dataclasses.replace(copy.deepcopy(rec0), cache_hit=True,
                                  disk_hit=False,
                                  compile_time_s=time.perf_counter() - t0)
        return copy.deepcopy(prog0), rec
    payload = cache.get_disk(key) if use_disk else None
    oracle = TilingOracle(known=(payload or {}).get("tilings"))
    pm = PassManager(hw, oracle=oracle, autotune_workers=workers)
    opt = pm.run(copy.deepcopy(prog))
    groups = _program_groups(opt)
    rec = CompileRecord(key=key, backend="", hw_name=hw.name,
                        disk_hit=payload is not None,
                        compile_time_s=time.perf_counter() - t0,
                        tilings=dict(oracle.chosen), pass_trace=list(pm.trace),
                        n_kernels=len(groups), groups=groups,
                        ir_fingerprint=ir_fp, hw_fingerprint=hw_fp)
    cache.put_memory(key, (opt, rec))
    if use_disk:
        cache.put_disk(key, {"tilings": oracle.chosen, "pass_trace": pm.trace,
                             "hw": hw.name, "compile_time_s": rec.compile_time_s,
                             "n_kernels": rec.n_kernels, "groups": groups})
    return copy.deepcopy(opt), rec


def stripe_jit(fn_or_contraction: Union[Program, TileProgram, str, Callable],
               hw: HardwareConfig, backend: str = "jnp", *,
               tensors: Optional[Mapping[str, Tuple]] = None,
               out: Optional[str] = None,
               ranges: Optional[Mapping[str, int]] = None,
               cache: Optional[_cache.CompilationCache] = None,
               workers: Optional[int] = None,
               interpret: Optional[bool] = None,
               jit: bool = True,
               use_disk: bool = True,
               profile: bool = False,
               tune: Union[None, bool, Any] = None,
               mesh: Union[None, int, Tuple[int, ...], Any] = None) -> CompiledProgram:
    """Compile a tensor op end-to-end through the cached Stripe pipeline.

    ``workers`` enables the parallel autotune search on cold compiles;
    ``interpret`` selects Pallas interpret mode for the pallas backend
    (default: compiled kernels on a TPU, interpret mode elsewhere);
    ``cache`` defaults to the process-wide cache.
    ``profile=True`` wall-times each lowered unit on dispatch: the record
    carries per-unit measured latencies next to the cost model's
    predictions, and the first dispatch appends (predicted, measured)
    rows to ``residuals.jsonl`` under the cache dir (``profile`` is part
    of the cache key — profiled and unprofiled artifacts differ).
    ``tune`` consults the measured-feedback tuning DB before the analytic
    autotile search: ``True`` opens the DB next to the cache's disk store,
    or pass a :class:`repro.tune.TuningDB`.  A fresh-enough measured-best
    entry replays its tilings (and per-unit backend choices) instead of
    searching — ``record.decision_source == "tuned"`` — and the entry's
    candidate id is folded into the cache key, so a better measurement
    automatically re-keys the artifact.  With ``profile=True`` the first
    dispatch also records its measurement back into the DB.
    ``mesh`` routes the compile through the multi-device path: a device
    count, mesh shape tuple, or ``jax.sharding.Mesh`` — the partitioner
    shards the program over the mesh, each shard-local segment compiles
    through this same single-device pipeline, and the segments are
    stitched inside ``shard_map`` with explicit collectives.  A mesh the
    partitioner cannot shard falls back to a single-device compile with
    ``record.mesh["fallback"]`` carrying the reason.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    interpret = resolve_interpret(interpret)
    if cache is None:
        cache = _cache.get_default_cache()
    if mesh is None and getattr(hw, "mesh_devices", lambda: 1)() > 1:
        mesh = hw.mesh  # the config carries a mesh spec: compile for it
    if mesh is not None:
        from . import mesh_lower

        resolved = mesh_lower.resolve_mesh(mesh)
        if resolved is not None:
            return _stripe_jit_mesh(
                fn_or_contraction, hw, backend, resolved,
                tensors=tensors, out=out, ranges=ranges, cache=cache,
                workers=workers, interpret=interpret, jit=jit,
                use_disk=use_disk, profile=profile, tune=tune)
    with obs_trace.span("compile.stripe_jit", backend=backend, hw=hw.name,
                        profile=profile) as csp:
        t0 = time.perf_counter()
        prog = _as_program(fn_or_contraction, tensors=tensors, out=out, ranges=ranges)
        ir_fp = ir_fingerprint(prog)
        hw_fp = hw.fingerprint()
        tune_db = _resolve_tune(tune, cache)
        tuned = None
        if tune_db is not None:
            # consulted *before* the memory probe: the tuned entry's
            # candidate id is part of the key, so a DB update naturally
            # misses the stale artifact and recompiles with the winner
            with obs_trace.span("tune.lookup", backend=backend) as sp:
                tuned = tune_db.lookup(ir_fp, hw_fp, backend, interpret)
                sp.set(hit=tuned is not None)
            if tuned is not None:
                cache.stats.tuned_hits += 1
            else:
                cache.stats.tuned_misses += 1
        key = _cache.content_key(
            "stripe_jit", DRIVER_VERSION, _cache.CACHE_VERSION,
            ir_fp, hw_fp, backend, bool(interpret), bool(jit), bool(profile),
            tuned.fingerprint if tuned is not None else "",
            _calibration_fp(hw_fp),
        )
        with obs_trace.span("cache.probe", level="memory") as sp:
            hit = cache.get_memory(key)
            sp.set(hit=hit is not None)
        if isinstance(hit, CompiledProgram):
            if hit.record.quarantined and not cache.quarantine.active(key):
                # the cached artifact is a quarantine fallback and the backoff
                # embargo has lapsed: drop through and re-attempt the real
                # backend (success clears the entry, failure doubles backoff)
                hit = None
            else:
                # fresh record per call: never mutate the cached one (the cold
                # caller holds it), and report this call's lookup time
                rec = dataclasses.replace(hit.record, cache_hit=True, disk_hit=False,
                                          compile_time_s=time.perf_counter() - t0)
                if rec.quarantined:
                    entry = cache.quarantine.get(key)
                    rec.quarantine = entry.as_dict() if entry is not None else dict(rec.quarantine)
                csp.set(cache="memory", backend_used=rec.backend)
                return CompiledProgram(hit.program, hit._fn, hit.hw, rec)

        with obs_trace.span("cache.probe", level="disk") as sp:
            payload = cache.get_disk(key) if use_disk else None
            sp.set(hit=payload is not None)
        # the tuned entry's tilings take precedence over the disk replay
        # (the disk payload under a tuned key holds the same tilings)
        known = (tuned.tilings if tuned is not None
                 else (payload or {}).get("tilings"))
        oracle = TilingOracle(known=known)
        pm = PassManager(hw, oracle=oracle, autotune_workers=workers)
        opt = pm.run(copy.deepcopy(prog))
        force_jnp = None
        if tuned is not None and backend == "pallas":
            force_jnp = {u for u, b in tuned.block_backends.items() if b == "jnp"}
        low = _lower(opt, backend, interpret, jit, hw,
                     quarantine=cache.quarantine, key=key, profile=profile,
                     force_jnp_units=force_jnp or None)
        record = CompileRecord(
            key=key, backend=low.backend, hw_name=hw.name,
            cache_hit=False, disk_hit=payload is not None,
            compile_time_s=time.perf_counter() - t0,
            tilings=dict(oracle.chosen), pass_trace=list(pm.trace),
            fallback_reason=low.fallback, n_kernels=low.n_kernels,
            groups=low.groups,
            block_backends=low.block_backends, block_fallbacks=low.block_fallbacks,
            quarantined=low.quarantined, quarantine=low.quarantine,
            profiled=bool(profile), ir_fingerprint=ir_fp, hw_fingerprint=hw_fp,
            decision_source="tuned" if tuned is not None else "analytic",
            tuned=({"candidate_id": tuned.candidate_id,
                    "measured_s": tuned.measured_s,
                    "predicted_s": tuned.predicted_s,
                    "source": tuned.source, "rounds": tuned.rounds,
                    "age_s": max(time.time() - tuned.ts, 0.0),
                    "n_candidates": tuned.n_candidates}
                   if tuned is not None else {}),
            stored_reads=stored_reads(prog),
        )
        fn = low.fn
        if profile:
            record.predicted_terms = obs_profile.predicted_unit_terms(
                opt, record.pass_trace)
            record.predicted_latency_s = {
                u: t["latency_s"] for u, t in record.predicted_terms.items()}
            fn = _attach_profiling(low, record, cache, interpret,
                                   tune_db=tune_db, requested_backend=backend)
        compiled = CompiledProgram(opt, fn, hw, record)
        cache.put_memory(key, compiled)
        if use_disk:
            cache.put_disk(key, {
                "tilings": oracle.chosen, "pass_trace": pm.trace,
                "hw": hw.name, "backend": low.backend,
                "compile_time_s": record.compile_time_s,
                "n_kernels": low.n_kernels, "groups": low.groups,
                "block_backends": low.block_backends,
                "block_fallbacks": low.block_fallbacks,
                "decision_source": record.decision_source,
            })
        csp.set(cache="disk" if record.disk_hit else "miss",
                backend_used=low.backend, decision=record.decision_source)
        return compiled


def _single_device_hw(hw: HardwareConfig) -> HardwareConfig:
    """The per-shard view of a meshed config: same machine model, no
    mesh (so segment compiles never re-enter the mesh path) and no
    partition pass (segments are already shard-local)."""
    if not getattr(hw, "mesh", ()) and not any(
            name == "partition" for name, _ in hw.passes):
        return hw
    return dataclasses.replace(
        hw, mesh=(),
        passes=tuple((n, p) for n, p in hw.passes if n != "partition"))


def _stripe_jit_mesh(fn_or_contraction, hw: HardwareConfig, backend: str,
                     resolved, *, tensors=None, out=None, ranges=None,
                     cache: Optional[_cache.CompilationCache] = None,
                     workers: Optional[int] = None, interpret: bool = False,
                     jit: bool = True, use_disk: bool = True,
                     profile: bool = False,
                     tune: Union[None, bool, Any] = None) -> CompiledProgram:
    """The multi-device compile path behind ``stripe_jit(..., mesh=)``.

    The shard planner picks one split per block (output, reduction,
    halo, or ring-overlap — by modelled cost) and cuts the program into
    shard-local *segments*; each segment compiles through the ordinary
    cached single-device ``stripe_jit`` (per-block hybrid Pallas/jnp
    composer, tuning DB, quarantine — everything), and
    :func:`~repro.core.mesh_lower.emit` stitches the compiled segments
    inside ``shard_map`` with the plan's explicit collectives.  A
    program the planner cannot shard falls back to the single-device
    compile, recording the reason in ``record.mesh["fallback"]``.
    """
    from .mesh_lower import emit
    from .shardplan import UnsupportedMesh, plan_program

    jmesh, axis, shape = resolved
    n = int(jmesh.devices.size)
    hw_inner = _single_device_hw(hw)
    with obs_trace.span("compile.stripe_jit_mesh", backend=backend,
                        hw=hw.name, mesh="x".join(map(str, shape))) as csp:
        t0 = time.perf_counter()
        prog = _as_program(fn_or_contraction, tensors=tensors, out=out,
                           ranges=ranges)
        try:
            faults.check("compile.stripe_jit_mesh", backend=backend, n=n)
            plan = plan_program(prog, n, hw, shape)
        except Exception as e:
            if not isinstance(e, UnsupportedMesh):
                # planner crash / injected fault: degrade, don't fail
                e = UnsupportedMesh(f"mesh planning crashed: {e!r}")
            compiled = stripe_jit(prog, hw_inner, backend, cache=cache,
                                  workers=workers, interpret=interpret,
                                  jit=jit, use_disk=use_disk,
                                  profile=profile, tune=tune)
            rec = dataclasses.replace(
                compiled.record,
                mesh={"fallback": str(e), "shape": list(shape),
                      "axis": axis, "n_devices": n})
            csp.set(fallback=str(e)[:200])
            return CompiledProgram(compiled.program, compiled._fn,
                                   compiled.hw, rec)

        ir_fp = ir_fingerprint(prog)
        hw_fp = hw.fingerprint()
        tune_db = _resolve_tune(tune, cache)
        key = _cache.content_key(
            "stripe_jit_mesh", DRIVER_VERSION, _cache.CACHE_VERSION,
            ir_fp, hw_fp, backend, bool(interpret), bool(jit), bool(profile),
            list(shape), axis, n, _calibration_fp(hw_fp),
        )
        # the outer memory cache is bypassed under tuning: segment keys
        # fold in their tuned candidate ids, so a DB update must be able
        # to re-stitch fresh segment artifacts
        if tune_db is None:
            with obs_trace.span("cache.probe", level="memory") as sp:
                hit = cache.get_memory(key)
                sp.set(hit=hit is not None)
            if isinstance(hit, CompiledProgram):
                rec = dataclasses.replace(
                    hit.record, cache_hit=True, disk_hit=False,
                    compile_time_s=time.perf_counter() - t0)
                csp.set(cache="memory", backend_used=rec.backend)
                return CompiledProgram(hit.program, hit._fn, hit.hw, rec)

        segments = plan.build_segments(prog)
        compiled_segs = [
            stripe_jit(seg.program, hw_inner, backend, cache=cache,
                       workers=workers, interpret=interpret, jit=False,
                       use_disk=use_disk, profile=False, tune=tune)
            for seg in segments]
        fn = emit(prog, plan, segments, compiled_segs, jmesh, axis,
                  jit=jit and not profile)

        # merge segment provenance into the whole-program record
        pass_trace: List = []
        block_backends: Dict[str, str] = {}
        block_fallbacks: Dict[str, str] = {}
        tilings: Dict[str, Dict[str, int]] = {}
        groups: List[List[str]] = []
        n_kernels = 0
        backend_used = "reference"
        seg_summaries = []
        for seg, c in zip(segments, compiled_segs):
            r = c.record
            pass_trace.extend(r.pass_trace)
            block_backends.update(r.block_backends)
            block_fallbacks.update(r.block_fallbacks)
            tilings.update(r.tilings)
            groups.extend(r.groups)
            n_kernels += r.n_kernels
            if r.backend == "pallas" or (r.backend == "jnp"
                                         and backend_used != "pallas"):
                backend_used = r.backend
            seg_summaries.append({
                "name": seg.program.entry.name, "key": r.key,
                "backend": r.backend, "n_kernels": r.n_kernels,
                "cache_hit": r.cache_hit, "disk_hit": r.disk_hit,
                "decision_source": r.decision_source,
            })
        pass_trace.append(("partition", {"mesh": list(shape), "axis": axis},
                           plan.report(scale_compute=False)))
        mesh_info = {
            "shape": list(shape), "axis": axis, "n_devices": n,
            "seed": plan.seed, "splits": plan.splits(),
            "collectives": [c.to_json() for c in plan.collectives],
            "collective_bytes": plan.collective_bytes(),
            "comm_s": plan.comm_s, "compute_s": plan.compute_s,
            "overlapped": [c.buffer for c in plan.collectives if c.overlap],
            "segments": seg_summaries,
        }
        record = CompileRecord(
            key=key, backend=backend_used, hw_name=hw.name,
            cache_hit=False, disk_hit=False,
            compile_time_s=time.perf_counter() - t0,
            tilings=tilings, pass_trace=pass_trace,
            n_kernels=n_kernels, groups=groups,
            block_backends=block_backends, block_fallbacks=block_fallbacks,
            profiled=bool(profile), ir_fingerprint=ir_fp,
            hw_fingerprint=hw_fp,
            decision_source=("tuned" if any(
                s["decision_source"] == "tuned" for s in seg_summaries)
                else "analytic"),
            mesh=mesh_info, stored_reads=stored_reads(prog),
        )
        if profile:
            record.predicted_latency_s = {"<program>": plan.cost_s}
            fn = _attach_profiling(
                _Lowered(fn, backend_used), record, cache, interpret,
                tune_db=tune_db, requested_backend=backend)
        compiled = CompiledProgram(prog, fn, hw, record)
        if tune_db is None:
            cache.put_memory(key, compiled)
        if use_disk:
            cache.put_disk(key, {
                "mesh": mesh_info, "tilings": tilings,
                "hw": hw.name, "backend": backend_used,
                "compile_time_s": record.compile_time_s,
                "n_kernels": n_kernels, "groups": groups,
                "segments": seg_summaries,
            })
        csp.set(cache="miss", backend_used=backend_used,
                n_segments=len(segments),
                collective_bytes=mesh_info["collective_bytes"])
        return compiled


def compile_with_tilings(fn_or_contraction: Union[Program, TileProgram, str, Callable],
                         hw: HardwareConfig,
                         tilings: Mapping[str, Mapping[str, int]],
                         backend: str = "jnp", *,
                         tensors: Optional[Mapping[str, Tuple]] = None,
                         out: Optional[str] = None,
                         ranges: Optional[Mapping[str, int]] = None,
                         interpret: Optional[bool] = None,
                         jit: bool = True,
                         profile: bool = False) -> CompiledProgram:
    """Compile with a **fixed tiling assignment** — no cache, no search.

    ``tilings`` uses the tiling-oracle key form (``"<block>#<fp16>"`` ->
    {var: tile}); blocks absent from it fall back to the analytic search.
    This is the explore measure-mode's candidate-replay entry: a sweep
    candidate's tilings are forced through the pass pipeline on the
    *base* config so the only thing that differs between measured
    candidates is the tiling (and the backend), never the model."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    interpret = resolve_interpret(interpret)
    t0 = time.perf_counter()
    prog = _as_program(fn_or_contraction, tensors=tensors, out=out, ranges=ranges)
    ir_fp = ir_fingerprint(prog)
    oracle = TilingOracle(known=tilings)
    pm = PassManager(hw, oracle=oracle)
    opt = pm.run(copy.deepcopy(prog))
    low = _lower(opt, backend, interpret, jit, hw, quarantine=None, key="",
                 profile=profile)
    record = CompileRecord(
        key="", backend=low.backend, hw_name=hw.name,
        compile_time_s=time.perf_counter() - t0,
        tilings=dict(oracle.chosen), pass_trace=list(pm.trace),
        fallback_reason=low.fallback, n_kernels=low.n_kernels,
        groups=low.groups,
        block_backends=low.block_backends, block_fallbacks=low.block_fallbacks,
        profiled=bool(profile), ir_fingerprint=ir_fp,
        hw_fingerprint=hw.fingerprint(), decision_source="replay",
        stored_reads=stored_reads(prog),
    )
    return CompiledProgram(opt, low.fn, hw, record)

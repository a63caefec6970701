"""The stable public facade.

``from repro import api`` is the supported way to consume this repo;
everything in ``__all__`` below is covered by the compatibility promise,
and all ``examples/`` and ``benchmarks/`` import only through here.  The
deep module paths (``repro.core.driver``, ``repro.serving.engine``, …)
remain importable as thin compatibility aliases of the same objects, but
they are internals: they may move between minor versions, this module
may not.

Blessed surface
---------------
Compile:
    ``jit`` (= ``stripe_jit``), ``compile`` (= ``compile_cached``),
    ``TileProgram``, ``single_op_program``, ``CompiledProgram``,
    ``execute_reference``, ``validate_program``, ``lower_program_jnp``,
    ``compile_program``, ``get_pass``, ``split_block``, ``choose_tiling``,
    ``evaluate_tiling``, ``score_pass_trace``
Hardware & model configs:
    ``get_config`` (hardware registry), ``HW_REGISTRY``,
    ``HardwareConfig``, ``configs`` (architecture registry:
    ``configs.get(name)``), ``build_model``, ``make_batch``,
    ``enable_compilation_cache`` (for entry points)
Caching:
    ``CompilationCache``, ``get_default_cache``, ``set_default_cache``
Serving:
    ``ServingEngine``, ``WaveEngine``, ``Request``, ``SamplingParams``,
    ``EngineConfig``
Exploration:
    ``explore`` (subpackage: ``run_sweep``, ``get_space``,
    ``pareto_front``, ``dominating_baseline``, …), ``get_workloads``,
    ``roofline_hillclimb``
Kernels & training (convenience):
    ``matmul``, ``matmul_ref``, ``choose_block_sizes``, ``adamw``,
    ``TrainConfig``, ``Trainer``, ``DataConfig``
Reliability:
    ``faults`` (fault-injection module: ``faults.inject``,
    ``faults.fail_nth``, …), ``FaultPlan``, ``InjectedFault``
Observability:
    ``obs`` (subpackage: ``obs.span``, ``obs.enable_tracing``,
    ``obs.export_chrome_trace``, ``obs.metrics_snapshot``,
    ``obs.read_residuals``, …)
Autotuning:
    ``tune`` (subpackage), ``TuningDB``, ``compile_with_tilings``,
    ``fit_calibration``, ``set_calibration``, ``measure_interleaved``
"""
from __future__ import annotations

from . import configs, explore, obs, tune
from .core import (
    CompilationCache,
    CompiledProgram,
    TileProgram,
    compile_cached,
    execute_reference,
    get_default_cache,
    lower_program_jnp,
    set_default_cache,
    single_op_program,
    stripe_jit,
    validate_program,
)
from .core.driver import compile_with_tilings
from .core.cost import evaluate_tiling, score_pass_trace
from .core.hwconfig import REGISTRY as HW_REGISTRY
from .core.hwconfig import HardwareConfig, get_config
from .core.passes import compile_program, get_pass
from .core.passes.autotile import choose_tiling
from .core.platform import enable_compilation_cache
from .core.tiling import split_block
from .data.pipeline import DataConfig
from .explore import dominating_baseline, get_space, pareto_front, run_sweep
from .explore.hillclimb import roofline_hillclimb
from .explore.workloads import get_workloads
from .kernels.flash_attention.ops import choose_block_sizes
from .kernels.stripe_matmul.ops import matmul, matmul_ref
from .models.build import build_model, make_batch
from .optim import adamw
from .reliability import FaultPlan, InjectedFault, faults
from .serving import EngineConfig, Request, SamplingParams, ServingEngine, WaveEngine
from .train.loop import TrainConfig, Trainer
from .tune import (
    TuningDB,
    fit_calibration,
    measure_interleaved,
    set_calibration,
)
# Multi-device: ``api.jit(..., mesh=)`` accepts a device count, a mesh
# shape tuple, or an ``api.Mesh`` (= ``jax.sharding.Mesh``).
from jax.sharding import Mesh

# The two headline verbs, under their public names.
jit = stripe_jit
compile = compile_cached  # noqa: A001 - deliberate: api.compile, never bare

__all__ = [
    # compile
    "jit", "compile", "stripe_jit", "compile_cached", "TileProgram",
    "single_op_program", "CompiledProgram", "execute_reference",
    "validate_program", "lower_program_jnp", "compile_program", "get_pass",
    "split_block", "choose_tiling", "evaluate_tiling", "score_pass_trace",
    # configs
    "get_config", "HW_REGISTRY", "HardwareConfig", "configs", "Mesh",
    "enable_compilation_cache",
    "build_model", "make_batch",
    # caching
    "CompilationCache", "get_default_cache", "set_default_cache",
    # serving
    "ServingEngine", "WaveEngine", "Request", "SamplingParams", "EngineConfig",
    # exploration
    "explore", "get_workloads", "roofline_hillclimb", "run_sweep",
    "get_space", "pareto_front", "dominating_baseline",
    # kernels & training
    "matmul", "matmul_ref", "choose_block_sizes", "adamw",
    "TrainConfig", "Trainer", "DataConfig",
    # reliability
    "faults", "FaultPlan", "InjectedFault",
    # observability
    "obs",
    # autotuning
    "tune", "TuningDB", "compile_with_tilings", "fit_calibration",
    "set_calibration", "measure_interleaved",
]

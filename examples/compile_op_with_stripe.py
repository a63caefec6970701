"""Deep-dive example: watch each Stripe pass transform the IR, reproduce
the paper's Fig. 5 rewrite, and run the generated Pallas kernel in
interpret mode.

    PYTHONPATH=src python examples/compile_op_with_stripe.py
"""
from pathlib import Path

import numpy as np
import jax.numpy as jnp

from repro import api


def fig5_rewrite():
    print("=" * 70)
    print("Paper Fig. 5: conv tiling rewrite (3x4x16 output tile)")
    prog = api.single_op_program(
        "O[x, y, k] += I[x + i - 1, y + j - 1, c] * F[i, j, c, k]",
        {"I": ((12, 16, 8), "int8"), "F": ((3, 3, 8, 16), "int8"),
         "O": ((12, 16, 16), "int32")},
        out="O",
    )
    blk = prog.entry.stmts[0]
    print("--- before (Fig. 5a) ---")
    print(blk.pretty())
    tiled = api.split_block(blk, {"x": 3, "y": 4})
    print("--- after (Fig. 5b): note I view 5x6x8 at [3x-1, 4y-1, 0] ---")
    print(tiled.pretty())


def pass_by_pass():
    print("=" * 70)
    print("TPU pipeline, pass by pass, on a 512^3 matmul")
    prog = api.single_op_program(
        "O[i, j] += A[i, c] * B[c, j]",
        {"A": ((512, 512), "float32"), "B": ((512, 512), "float32"),
         "O": ((512, 512), "float32")},
        out="O",
    )
    hw = api.get_config("tpu_v5e")
    for name, params in hw.passes:
        prog = api.get_pass(name)(prog, hw, params)
        blocks = [s for s in prog.entry.stmts if hasattr(s, "tags")]
        tags = [sorted(t for t in b.tags if not t.startswith("sched")) for b in blocks]
        print(f"after {name:10s}: {len(blocks)} block(s), tags={tags}")
    print(prog.pretty()[:1200], "...")


def run_generated_kernel():
    print("=" * 70)
    print("Stripe-generated Pallas kernel (interpret mode)")
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(256, 512), jnp.float32)
    w = jnp.asarray(rng.randn(512, 384), jnp.float32)
    b = jnp.asarray(rng.randn(384), jnp.float32)
    got = api.matmul(x, w, b, act="relu")
    want = api.matmul_ref(x, w, b, act="relu")
    print("max |err| vs oracle:", float(jnp.max(jnp.abs(got - want))))


def jit_with_cache():
    """The unified driver: one call runs frontend -> passes -> lowering
    behind the two-level compilation cache; the second compile is a cache
    hit and skips the autotile search entirely."""
    import time

    print("=" * 70)
    print("stripe_jit: compile driver + persistent compilation cache")
    cache = api.CompilationCache()  # disk at $STRIPE_CACHE_DIR or ~/.cache/stripe-repro
    text = "O[x, y, k] += I[x + i - 1, y + j - 1, c] * F[i, j, c, k]"
    tensors = {"I": ((12, 16, 8), "float32"), "F": ((3, 3, 8, 16), "float32"),
               "O": ((12, 16, 16), "float32")}
    t0 = time.perf_counter()
    compiled = api.jit(text, api.get_config("cpu_test"), tensors=tensors, out="O", cache=cache)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    api.jit(text, api.get_config("cpu_test"), tensors=tensors, out="O", cache=cache)
    warm = time.perf_counter() - t0
    rng = np.random.RandomState(0)
    out = compiled({"I": rng.randn(12, 16, 8).astype(np.float32),
                    "F": rng.randn(3, 3, 8, 16).astype(np.float32)})["O"]
    print(f"cold compile {cold*1e3:.1f} ms  (tilings={compiled.record.tilings})")
    print(f"warm compile {warm*1e6:.0f} us  ({cold/warm:.0f}x faster)")
    print(f"output shape {out.shape}; cache stats {cache.stats.as_dict()}")


if __name__ == "__main__":
    api.enable_compilation_cache(Path(__file__).resolve().parents[1])
    fig5_rewrite()
    pass_by_pass()
    run_generated_kernel()
    jit_with_cache()

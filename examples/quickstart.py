"""Quickstart: express a tensor op in the Tile frontend, compile it with
the Stripe pass pipeline for TPU, inspect the optimized IR, and execute
both backends.

    PYTHONPATH=src python examples/quickstart.py
"""
from pathlib import Path

import numpy as np
import jax.numpy as jnp

from repro import api


def main():
    # 1. A fused linear layer in the Tile language (paper §3.4).
    tp = api.TileProgram("fused_linear")
    tp.input("X", (256, 512))
    tp.input("W", (512, 384))
    tp.input("B", (384,))
    tp.temp("T", (256, 384))
    tp.output("O", (256, 384))
    tp.op("T[i, j] += X[i, c] * W[c, j]")
    tp.op("O[i, j] = relu(T[i, j] + B[j])")
    prog = tp.build()
    assert api.validate_program(prog) == []          # Def. 2 holds

    # 2. Compile with the TPU v5e hardware config: fuse -> autotile ->
    #    stencil -> boundary -> localize -> schedule.
    optimized = api.compile_program(prog, api.get_config("tpu_v5e"))
    print("=== optimized Stripe IR ===")
    print(optimized.pretty())

    # 3. Execute: jnp reference backend (and, on TPU, the Pallas backend —
    #    see repro.kernels.stripe_matmul for the generated kernel).
    rng = np.random.RandomState(0)
    arrays = {
        "X": jnp.asarray(rng.randn(256, 512), jnp.float32),
        "W": jnp.asarray(rng.randn(512, 384), jnp.float32),
        "B": jnp.asarray(rng.randn(384), jnp.float32),
    }
    out = api.lower_program_jnp(optimized.source)(arrays)["O"]
    want = np.maximum(np.asarray(arrays["X"]) @ np.asarray(arrays["W"]) + np.asarray(arrays["B"]), 0)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-4)
    print("\njnp backend matches numpy: OK", out.shape)


if __name__ == "__main__":
    api.enable_compilation_cache(Path(__file__).resolve().parents[1])
    main()

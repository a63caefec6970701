"""Jit'd public wrapper for the Stripe-generated matmul kernel."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.platform import resolve_interpret
from .kernel import build_matmul_kernel
from .ref import matmul_ref


@partial(jax.jit, static_argnames=("act", "interpret"))
def _run(x, w, bias, act, interpret):
    m, k = x.shape
    n = w.shape[-1]
    fn = build_matmul_kernel(m, k, n, str(x.dtype), act, bias is not None, interpret)
    return fn(x, w, bias)


def matmul(x: jnp.ndarray, w: jnp.ndarray, bias: Optional[jnp.ndarray] = None,
           act: Optional[str] = None, interpret: Optional[bool] = None) -> jnp.ndarray:
    """act(x @ w + bias) via the Stripe-compiled Pallas kernel.

    ``interpret`` defaults to the platform: the kernel runs compiled on a
    TPU and in Pallas interpret mode (validation) everywhere else.
    """
    return _run(x, w, bias, act, resolve_interpret(interpret))


__all__ = ["matmul", "matmul_ref"]

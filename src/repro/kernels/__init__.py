"""Pallas TPU kernels for the paper's compute hot spots: the CKA Gram terms
behind SimFreeze's drift probe (`cka`), flash attention for the
classifier forwards (`attention`) and the exact RWKV6 recurrence
(`rwkv`). Each package pairs `kernel.py` with a jitted `ops.py` wrapper
and a pure-jnp `ref.py` oracle.

The wrappers choose how a kernel runs from the JAX backend: compiled with
Mosaic on TPU, emulated by the Pallas interpreter on CPU (so the CPU
tests check the kernel bodies against `ref.py`), and refused anywhere
else. An explicit `interpret=` overrides the choice.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode for the current backend: False on TPU, True
    on CPU. An explicit bool wins; any other backend is an error."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels in repro.kernels target TPU (compiled) or CPU "
        f"(interpreted); the {backend!r} backend is neither — pass "
        f"interpret= explicitly")

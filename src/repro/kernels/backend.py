"""Where the Pallas kernels run: the single choice of interpret mode.

A kernel runs compiled (Mosaic) on a TPU and in the Pallas interpreter
on the CPU backend the tests use. Any other platform is an error: a
kernel never drops to the interpreter on an accelerator without saying
so.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """An explicit `interpret` wins; otherwise False on "tpu", True on
    "cpu", and a RuntimeError naming the platform anywhere else."""
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {platform!r}")

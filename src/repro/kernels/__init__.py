"""Pallas TPU kernels, each with a public op (``ops.py``) and a pure-jnp
oracle (``ref.py``).  Kernels compile for the TPU only: tests off the TPU
call a kernel with ``interpret=True`` themselves, and an op asked for
``impl="pallas"`` off the TPU raises instead of running another impl."""
from __future__ import annotations

import jax


def require_tpu(op: str) -> None:
    """Raise unless this process's default backend is a TPU."""
    backend = jax.default_backend()
    if backend == "tpu":
        return
    raise RuntimeError(
        f"{op}: impl='pallas' compiles only for a TPU, and this process's "
        f"JAX backend is {backend!r}; choose a jnp impl, or call the kernel "
        "with interpret=True")

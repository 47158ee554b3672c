"""Public fused-RMSNorm op with MLOS-tunable impl/block_rows."""
from __future__ import annotations

from typing import Optional

import jax

from ...core.configstore import bucket_pow2
from ...core.registry import MetricSpec, tunable_component
from ...core.tunable import Categorical, Int
from .. import require_tpu
from . import ref

__all__ = ["rmsnorm", "rmsnorm_settings", "RmsNormSettings", "workload_signature"]


@tunable_component(
    name="rmsnorm_kernel",
    tunables=(
        Categorical("impl", default="jnp", choices=("jnp", "pallas")),
        Int("block_rows", default=256, low=8, high=4096, log=True,
            description="rows normalized per VMEM tile"),
    ),
    metrics=(MetricSpec("time_us", "d"),),
)
class RmsNormSettings:
    pass


rmsnorm_settings = RmsNormSettings()


def workload_signature(rows: int, d: int) -> str:
    """Bucketed (total rows, feature dim) — the op is row-parallel, so the
    flattened row count is the workload axis that moves the best tile."""
    return f"r{bucket_pow2(rows)}d{d}"


def rmsnorm(x: jax.Array, scale: jax.Array, residual: Optional[jax.Array] = None,
            eps: float = 1e-5, *, impl: Optional[str] = None,
            block_rows: Optional[int] = None, workload: Optional[str] = None) -> jax.Array:
    rows = 1
    for n in x.shape[:-1]:
        rows *= n
    wl = workload or workload_signature(rows, x.shape[-1])
    s = rmsnorm_settings.settings_for(wl)
    impl = impl or s["impl"]
    if impl == "jnp":
        return ref.rmsnorm(x, scale, residual, eps)
    require_tpu("rmsnorm")
    from . import kernel

    return kernel.rmsnorm_pallas(x, scale, residual, eps,
                                 block_rows=block_rows or s["block_rows"])

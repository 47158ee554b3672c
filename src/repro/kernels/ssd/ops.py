"""Public SSD op (Mamba-2) with MLOS-tunable chunk size / implementation."""
from __future__ import annotations

from typing import Optional

from ...core.configstore import bucket_pow2
from ...core.registry import MetricSpec, tunable_component
from ...core.tunable import Categorical, Int
from .. import require_tpu
from . import ref

__all__ = ["ssd", "ssd_decode_step", "ssd_settings", "SsdKernelSettings", "workload_signature"]


@tunable_component(
    name="ssd_kernel",
    tunables=(
        Categorical("impl", default="chunked", choices=("naive", "chunked", "chunked_unrolled", "pallas")),
        Int("chunk", default=128, low=16, high=1024, log=True, description="SSD block-decomposition chunk length"),
    ),
    metrics=(MetricSpec("time_us", "d"), MetricSpec("hlo_flops", "d")),
)
class SsdKernelSettings:
    pass


ssd_settings = SsdKernelSettings()


def _align(chunk: int, seq: int) -> int:
    chunk = min(chunk, seq)
    while seq % chunk:
        chunk //= 2
    return max(chunk, 1)


def workload_signature(b: int, s: int, h: int) -> str:
    """Bucketed (batch, seq, heads) — the chunk decomposition trades per-chunk
    matmul size against the inter-chunk scan length, so the best chunk tracks
    the sequence bucket."""
    return f"b{bucket_pow2(b)}s{bucket_pow2(s)}h{h}"


def ssd(x, dt, A, B, C, D=None, *, impl: Optional[str] = None, chunk: Optional[int] = None,
        init_state=None, return_state: bool = False, workload: Optional[str] = None):
    wl = workload or workload_signature(x.shape[0], x.shape[1], x.shape[2])
    s = ssd_settings.settings_for(wl)
    impl = impl or s["impl"]
    chunk = _align(chunk or s["chunk"], x.shape[1])
    if impl == "naive":
        return ref.ssd_naive_scan(x, dt, A, B, C, D, init_state=init_state, return_state=return_state)
    if impl in ("chunked", "chunked_unrolled"):
        return ref.ssd_chunked(x, dt, A, B, C, D, chunk=chunk, init_state=init_state,
                               return_state=return_state, unroll=impl == "chunked_unrolled")
    if impl == "pallas":
        require_tpu("ssd")
        if init_state is not None:
            # The kernel cannot resume from a carried state yet (ROADMAP
            # queue 2 item 6): chunked decode-resume takes the jnp path.
            return ref.ssd_chunked(x, dt, A, B, C, D, chunk=chunk,
                                   init_state=init_state, return_state=return_state)
        from . import kernel

        return kernel.ssd_pallas(x, dt, A, B, C, D, chunk=chunk, return_state=return_state)
    raise ValueError(f"unknown ssd impl {impl!r}")


ssd_decode_step = ref.ssd_decode_step

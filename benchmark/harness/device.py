"""The device a run is on: the look for a chip, what JAX reports of it."""

from __future__ import annotations

from typing import Dict

class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The TPU devices of this process, or NoChip. Never falls back."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"jax found platform {devices[0].platform!r} "
                     f"({devices[0].device_kind!r}); the benchmark measures "
                     f"only on a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), jax found "
                     f"{len(devices)}")
    return devices


def describe(devices) -> Dict[str, object]:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes the allocator of the fullest chip could not hand out:
    `peak_bytes_in_use` (arguments, results, cached buffers) plus
    `peak_bytes_reserved`, the pool the v5e's runtime sets aside for the
    scratch of the programs it runs and keeps once they end. The allocator
    itself counts both as taken: after a window it reports
    `largest_free_block_bytes` = `bytes_limit` - `bytes_in_use` -
    `bytes_reserved` to within 0.1 MB (PERF.md, section 3). An upper bound
    on what was live at one instant: the two peaks need not coincide, and
    the pool is sized by the compiler's estimate for the largest program
    loaded, here the fit's, which set-up and the window both run. 0 where
    the backend reports nothing, as on the CPU."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak

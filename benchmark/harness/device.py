"""The device a run is on, its published peaks, and what it must not do.

Part of the yardstick: the table of peaks is ``benchmark/peaks.json``,
keyed by ``device_kind``; a device that is not in it is an error, never
a default, and no platform but ``tpu`` is measured.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict

from .loader import BENCH_DIR, read_json


class NoAccelerator(Exception):
    """The machine does not hold the chips the cell asks for."""


class UnknownDevice(Exception):
    """``device_kind`` is not in ``peaks.json``."""


class ShareOverPeak(Exception):
    """A share of a peak read over 100%: the operations or bytes are
    counted too high, or the time leaves out part of the work."""


def require_chips(chips: int) -> Dict[str, Any]:
    """The contract's device block, or NoAccelerator: never a CPU run."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise NoAccelerator("platform is %r, not 'tpu'" % first.platform)
    if len(devices) != chips:
        raise NoAccelerator("the cell asks for %d chip(s), jax finds %d"
                            % (chips, len(devices)))
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}


def peaks_of(kind: str, bench_dir: str = BENCH_DIR) -> Dict[str, float]:
    table = read_json(os.path.join(bench_dir, "peaks.json"))
    if kind not in table:
        raise UnknownDevice(
            "device_kind %r is not in peaks.json (known: %s)"
            % (kind, ", ".join(sorted(table))))
    return table[kind]


def memory_peak_bytes() -> int:
    """Peak bytes held on the fullest chip: the peak of the buffers in
    use (weights, optimizer state, batches, the page pool) plus the peak
    of the region the runtime reserves for compiled programs'
    temporaries, which ``peak_bytes_in_use`` leaves out. The two hold at
    once: a step's arguments and results are live while its temporaries
    are. The reserved region is the step's temporaries as the compiler
    itself counts them — ``memory_analysis().temp_size_in_bytes`` of the
    cells' programs, compiled for a described v5e, against the chip's
    ``peak_bytes_reserved``: 4.25 / 3.94 GB (GPT-2 small, 64 x 1024),
    1.28 / 1.06 GB (16 x 1024), the decode step 2.86 GB (PERF.md,
    section 3; my chip runs, PR 23). Every run prints both terms on its
    ``CELLBENCH memory`` line."""
    import jax

    peak = 0
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def memory_stats_all():
    """Every byte count the backend reports, per chip, for the log."""
    import jax

    return [{k: v for k, v in (dev.memory_stats() or {}).items()
             if "bytes" in k} for dev in jax.devices()]


def share_pct(name: str, achieved: float, peak: float) -> float:
    """``achieved / peak`` in percent; over 100 it raises instead of
    reading as a good number (the driver refuses 105, a clamp would
    hide the fault)."""
    pct = 100.0 * achieved / peak
    if pct > 100.0:
        raise ShareOverPeak("%s reads %.2f%% of its peak" % (name, pct))
    return pct


def matmul_self_check(peaks: Dict[str, float], n: int = 4096,
                      chain: int = 24) -> Dict[str, float]:
    """A timed bf16 matmul chain may not beat its roofline floor: if it
    does, the clock stopped before the device did (a missing sync) or
    the peak is wrong, and every share this run prints would be too."""
    import jax
    import jax.numpy as jnp

    def body(x, w):
        for _ in range(chain):
            x = jnp.dot(x, w, preferred_element_type=jnp.bfloat16)
        return x

    fn = jax.jit(body)
    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)
    w = jnp.eye(n, dtype=jnp.bfloat16)
    fn(x, w).block_until_ready()
    t0 = time.perf_counter()
    fn(x, w).block_until_ready()
    seconds = time.perf_counter() - t0
    flops = 2.0 * n * n * n * chain
    floor = flops / peaks["bf16_flops_per_s"]
    if seconds < floor:
        raise ShareOverPeak(
            "matmul chain took %.6f s, under its floor of %.6f s at the "
            "published peak: the timing does not wait for the device"
            % (seconds, floor))
    return {"seconds": seconds, "floor_seconds": floor,
            "share_pct": 100.0 * floor / seconds}

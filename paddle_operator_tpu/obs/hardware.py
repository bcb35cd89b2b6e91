"""Hardware-efficiency telemetry: analytic MFU, roofline attribution,
and device-memory sampling — the plane that attributes what the chip
DID during the goodput seconds.

The goodput ledger (:mod:`.ledger`) attributes every *second* of wall
clock; this module attributes the *work* inside the good seconds. Three
independent inputs, combined into per-step MFU and a roofline class:

* **step cost** — FLOPs and bytes per optimizer step taken from the
  compiled executable itself (:func:`step_cost_of` walks
  ``Compiled.cost_analysis()`` / ``Lowered.cost_analysis()`` down the
  compile-cache wrapper), with a per-model analytic fallback
  (:class:`StepCost` built by the caller) when XLA's cost model is
  unavailable — the source is always stamped, never guessed.
* **chip capability** — peak bf16 FLOP/s and HBM bandwidth per TPU
  generation (:data:`CHIP_PEAKS`, resolved from ``device_kind``). A TPU
  whose kind is not in the table is an error, never a default; only a
  CPU device gets the stamped fallback ceiling (or the caller's measured
  matmul ceiling). Every :class:`ChipSpec` carries the platform of the
  device it was resolved from, so an MFU can never be divided by a
  ceiling that belongs to another backend.
* **device memory** — live ``device.memory_stats()`` sampling
  (:func:`device_memory_stats`) where the backend provides it; absent
  stats degrade to an empty block, never a crash.

From those three: ``mfu = achieved FLOP/s / peak FLOP/s`` (sanity-
clamped: a computation > 1.0 is a warning and a clamped gauge, never an
exception), ``arithmetic intensity = flops / bytes`` and the
compute-vs-memory-bound roofline classification against the chip's
ridge point (``peak_flops / hbm_bandwidth``).

:class:`HardwarePlane` is the runner-side accumulator: fed executed
steps + the wall seconds of windows that END IN A DEVICE SYNC (enqueue
time says nothing about the chip), it renders the self-conserving
``result["hardware"]`` block (``total_flops == flops_per_step x
steps`` by construction) and mirrors it into the process trace
(``hardware_block`` events), so ``scripts/obs_report.py --hardware``
rebuilds the fleet MFU/roofline picture from trace alone and re-checks
conservation offline. :class:`MfuBaseline` is the detector primitive
the ledger aggregates worker samples through: the eps baseline's
never-normalize rule PLUS an absolute collapse floor — MFU is measured
against the chip's own peak, so a CPU-fallback resume reads ~1e-5 on
the very first sample, no primed baseline needed (the class the eps
detector could only catch after min_samples).

Everything here is stdlib-only at import time; jax is imported lazily
inside the functions that need a live backend, so the operator plane
(which never imports jax) can share the registry and the detector.
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from ..utils.trace import tracer
from .worker import ThroughputBaseline

log = logging.getLogger("tpujob.obs.hardware")

#: peak dense bf16 FLOP/s and HBM bandwidth (bytes/s) per chip, keyed by
#: a lowercase substring of ``device_kind``. Ordered most-specific
#: first: resolution takes the first match.
CHIP_PEAKS: Tuple[Tuple[str, float, float], ...] = (
    ("v6e", 918e12, 1640e9),     # Trillium
    ("v5p", 459e12, 2765e9),
    ("v5litepod", 197e12, 819e9),
    ("v5 lite", 197e12, 819e9),  # device_kind "TPU v5 lite"
    ("v5e", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
)

#: conservative ceiling used when nothing better is known (one modern
#: CPU socket's bf16-ish throughput); MFU against it is explicitly
#: stamped ``source="default"`` so a reader never mistakes it for a
#: measured or registry number
DEFAULT_CPU_PEAK_FLOPS = 1e12
DEFAULT_CPU_BANDWIDTH = 100e9

#: below this absolute MFU a training step is not plausibly running on
#: the chip the peak describes (even badly-shaped models clear ~1%; a
#: CPU fallback reads ~1e-5 against a TPU peak)
MFU_COLLAPSE_FLOOR = 1e-3


@dataclass(frozen=True)
class ChipSpec:
    """One device's capability envelope. ``backend`` is the platform
    the spec describes (``tpu`` | ``cpu`` | ``gpu``) — every MFU derived
    from this spec is only meaningful against steps that ran THERE.
    ``source`` is where the peak came from: ``registry`` (known TPU
    generation), ``calibrated`` (measured matmul ceiling), or
    ``default`` (the conservative fallback)."""

    device_kind: str
    backend: str
    peak_flops: float
    hbm_bandwidth: float
    source: str

    @property
    def ridge(self) -> float:
        """Roofline ridge point (FLOP/byte): arithmetic intensity above
        which the chip is compute-bound."""
        if self.hbm_bandwidth <= 0:
            return 0.0
        return self.peak_flops / self.hbm_bandwidth


@dataclass(frozen=True)
class StepCost:
    """Per-optimizer-step work: FLOPs executed and HBM bytes moved.
    ``source`` stamps provenance: ``cost_analysis`` (XLA's own model on
    the compiled executable), ``analytic`` (per-model closed form), or
    ``unavailable`` (neither — MFU is suppressed, not invented)."""

    flops: float
    bytes_accessed: float
    source: str

    @property
    def arithmetic_intensity(self) -> float:
        if self.bytes_accessed <= 0:
            return 0.0
        return self.flops / self.bytes_accessed


UNAVAILABLE_COST = StepCost(0.0, 0.0, "unavailable")


def lookup_chip(kind: str) -> Optional[Tuple[float, float]]:
    """Registry lookup by device_kind substring."""
    k = kind.lower()
    for pat, flops, bw in CHIP_PEAKS:
        if pat in k:
            return flops, bw
    return None


def resolve_chip(device: Any = None,
                 calibrated_flops: Optional[float] = None,
                 calibrated_bandwidth: Optional[float] = None) -> ChipSpec:
    """Resolve the chip capability envelope for ``device`` (default: the
    first jax device, when jax is importable; else a pure-CPU spec).

    ``backend`` is always the device's own platform. A known
    ``device_kind`` resolves against :data:`CHIP_PEAKS`; an unknown kind
    on the ``tpu`` platform raises — inventing a ceiling for a real chip
    would print an MFU that means nothing. Other platforms (the CPU the
    tests run on) take the caller's calibrated matmul ceiling, then the
    stamped conservative default."""
    kind, backend = "cpu", "cpu"
    if device is None:
        try:
            import jax

            device = jax.devices()[0]
        except Exception:  # jax-free process (operator plane)
            device = None
    if device is not None:
        kind = str(getattr(device, "device_kind", "") or "cpu")
        backend = str(getattr(device, "platform", "") or "cpu")
    hit = lookup_chip(kind)
    if hit is not None:
        return ChipSpec(kind, backend, hit[0], hit[1], "registry")
    if backend == "tpu":
        raise ValueError(
            "TPU device_kind %r is not in obs.hardware.CHIP_PEAKS; add "
            "its published peaks rather than measuring against a guess"
            % kind)
    if calibrated_flops is not None and calibrated_flops > 0:
        return ChipSpec(
            kind, backend, float(calibrated_flops),
            float(calibrated_bandwidth) if calibrated_bandwidth
            else DEFAULT_CPU_BANDWIDTH, "calibrated")
    return ChipSpec(kind, backend, DEFAULT_CPU_PEAK_FLOPS,
                    DEFAULT_CPU_BANDWIDTH, "default")


def _normalize_cost(raw: Any) -> Optional[Dict[str, float]]:
    """``cost_analysis()``'s dict, numeric entries only; None if the
    backend has no cost model."""
    if not isinstance(raw, dict):
        return None
    return {str(k): float(v) for k, v in raw.items()
            if isinstance(v, (int, float))}


def step_cost_of(fn: Any, *args: Any, steps_per_call: int = 1,
                 _depth: int = 0) -> Optional[StepCost]:
    """FLOPs/bytes per optimizer step from the compiled executable.

    Walks the compile-cache ladder the runner actually calls through:
    a ``Compiled``'s own ``cost_analysis()``, a :class:`~..compile_cache.
    CachedStep`'s wrapped fn, or a jit fn's ``lower(*args)`` (tracing
    only — no compile, so probing a memo/AOT-served step stays cheap).
    A fused K-step call's cost is divided by ``steps_per_call`` so the
    figure is always per OPTIMIZER step. Returns None when XLA's cost
    model is unavailable anywhere on the ladder — the caller falls back
    to its analytic figure (or suppresses MFU), it never guesses."""
    if fn is None or _depth > 3:
        return None
    k = max(1, int(steps_per_call))
    # 1) the object itself exposes cost_analysis (jax.stages.Compiled)
    try:
        cost = _normalize_cost(fn.cost_analysis())
    except Exception:
        cost = None
    if cost is None:
        # 2) a compile_cache.CachedStep (or similar wrapper): recurse
        #    into the wrapped callable
        inner = getattr(fn, "_fn", None)
        if inner is not None and inner is not fn:
            return step_cost_of(inner, *args, steps_per_call=k,
                                _depth=_depth + 1)
        # 3) a jit function: trace (no compile) and ask the Lowered
        try:
            cost = _normalize_cost(fn.lower(*args).cost_analysis())
        except Exception:
            return None
    if cost is None:
        return None
    flops = cost.get("flops", 0.0)
    nbytes = cost.get("bytes accessed", 0.0)
    if flops <= 0:
        return None  # backend reports no cost model (e.g. -1 sentinels)
    return StepCost(flops / k, max(0.0, nbytes) / k, "cost_analysis")


def analytic_cost(flops_per_step: float,
                  bytes_per_step: float = 0.0) -> StepCost:
    """Per-model analytic fallback (the caller's closed-form FLOPs —
    e.g. 6 x params x tokens for a transformer)."""
    return StepCost(max(0.0, float(flops_per_step)),
                    max(0.0, float(bytes_per_step)), "analytic")


def device_memory_stats(device: Any = None) -> Dict[str, float]:
    """Live device-memory sample: ``{"in_use", "peak", "limit"}`` bytes,
    from ``device.memory_stats()`` where the backend provides it (TPU
    and GPU do; CPU returns None). Empty dict when unavailable — the
    hbm gauges simply don't render."""
    if device is None:
        try:
            import jax

            device = jax.devices()[0]
        except Exception:
            return {}
    try:
        stats = device.memory_stats()
    except Exception:
        return {}
    if not isinstance(stats, dict):
        return {}
    out: Dict[str, float] = {}
    for key, name in (("bytes_in_use", "in_use"),
                      ("peak_bytes_in_use", "peak"),
                      ("bytes_limit", "limit")):
        v = stats.get(key)
        if isinstance(v, (int, float)) and v >= 0:
            out[name] = float(v)
    return out


def clamped_mfu(achieved_flops_per_s: float,
                peak_flops: float) -> Tuple[float, bool]:
    """``(mfu, clamped)``. An MFU computation above 1.0 means the cost
    model or the peak is wrong — that is a WARNING and a clamped gauge,
    never a crash (acceptance: the sanity clamp)."""
    if peak_flops <= 0 or achieved_flops_per_s <= 0:
        return 0.0, False
    mfu = achieved_flops_per_s / peak_flops
    if mfu > 1.0:
        log.warning(
            "MFU computed as %.3f > 1.0 (achieved %.3g FLOP/s vs peak "
            "%.3g): cost model or peak is inconsistent; clamping",
            mfu, achieved_flops_per_s, peak_flops)
        return 1.0, True
    return mfu, False


def roofline_class(intensity: float, chip: ChipSpec) -> str:
    """``compute_bound`` | ``memory_bound`` | ``unknown`` against the
    chip's ridge point."""
    if intensity <= 0 or chip.ridge <= 0:
        return "unknown"
    return "compute_bound" if intensity >= chip.ridge else "memory_bound"


class MfuBaseline(ThroughputBaseline):
    """The eps baseline's never-normalize rule PLUS an absolute floor.

    MFU is a ratio against the chip's OWN peak, so — unlike examples/s —
    a collapse is detectable on the very first sample: a CPU-fallback
    resume reads ~1e-5 against a TPU peak, orders of magnitude under
    :data:`MFU_COLLAPSE_FLOOR`, before any baseline is primed (the eps
    detector needs ``min_samples`` healthy history first). Degraded
    samples are never folded into the baseline (the never-normalize
    mirror), and recovery requires clearing BOTH the floor and — once a
    baseline exists — ``recovery_ratio`` x the healthy median."""

    def __init__(self, floor: float = MFU_COLLAPSE_FLOOR,
                 degraded_ratio: float = 0.25, recovery_ratio: float = 0.5,
                 window: int = 5, min_samples: int = 3):
        super().__init__(degraded_ratio=degraded_ratio,
                         recovery_ratio=recovery_ratio, window=window,
                         min_samples=min_samples)
        self.floor = float(floor)

    def observe(self, mfu: float) -> Optional[str]:
        v = float(mfu)
        if self.degraded:
            base = self.baseline if len(self._hist) >= self._min else None
            if v >= self.floor and (base is None
                                    or v >= self.recovery_ratio * base):
                self.degraded = False
                self._hist.append(v)
                return "recovered"
            return None
        if v < self.floor:
            # absolute collapse: fires pre-baseline, sample NOT banked
            self.degraded = True
            return "degraded"
        return super().observe(v)


class HardwarePlane:
    """Runner-side accumulator: chip + step cost + executed steps ->
    the self-conserving ``result["hardware"]`` block.

    Thread-safe (``record``/``sample_hbm`` run on the training loop,
    scrape-side readers call :meth:`block`); bounded — three floats of
    state no matter how long the run. ``total_flops == flops_per_step x
    steps`` holds by construction; :meth:`block` carries both sides so
    ``obs_report --hardware`` re-checks it offline from the mirrored
    ``hardware_block`` trace event."""

    def __init__(self, chip: ChipSpec, cost: Optional[StepCost] = None,
                 device: Any = None):
        self.chip = chip
        self.cost = cost if cost is not None else UNAVAILABLE_COST
        #: devices one step spans; the cost is the whole (unpartitioned)
        #: program's, so the ceiling it divides by is peak x devices
        self.devices = 1
        self._device = device
        self._lock = threading.Lock()
        self._steps = 0
        self._step_seconds = 0.0
        self._hbm: Dict[str, float] = {}

    def set_cost(self, cost: Optional[StepCost], devices: int = 1) -> None:
        """Install the step cost once the step is built/compiled (the
        chip is known at plane construction, the cost and the mesh it
        runs over only per cycle)."""
        self.devices = max(1, int(devices))
        if cost is not None:
            self.cost = cost

    def record(self, steps: int, seconds: float) -> None:
        """Bank ``steps`` optimizer steps that took ``seconds`` of wall
        time. The caller's window must end in a device sync
        (``block_until_ready``): the time a dispatch takes to ENQUEUE
        says nothing about how long the chip ran."""
        if steps <= 0 or seconds < 0:
            return
        with self._lock:
            self._steps += int(steps)
            self._step_seconds += float(seconds)

    def sample_hbm(self) -> Dict[str, float]:
        """Sample live device memory; remembered for :meth:`block`."""
        stats = device_memory_stats(self._device)
        with self._lock:
            if stats:
                self._hbm = dict(stats)
            return dict(self._hbm)

    def mfu_of_rate(self, steps_per_second: float) -> Optional[float]:
        """Instantaneous MFU at an observed (readback-synced) step rate
        — the number the worker gauge and the ledger samples carry.
        None when the step cost is unavailable: MFU is suppressed, not
        invented."""
        if self.cost.source == "unavailable" or self.cost.flops <= 0:
            return None
        mfu, _clamped = clamped_mfu(
            steps_per_second * self.cost.flops,
            self.chip.peak_flops * self.devices)
        return mfu

    def block(self) -> Dict[str, Any]:
        """The self-conserving ``result["hardware"]`` block."""
        with self._lock:
            steps = self._steps
            step_seconds = self._step_seconds
            hbm = dict(self._hbm)
        total_flops = self.cost.flops * steps
        mfu: Optional[float] = None
        clamped = False
        if self.cost.source != "unavailable" and step_seconds > 0 \
                and self.cost.flops > 0:
            mfu, clamped = clamped_mfu(total_flops / step_seconds,
                                       self.chip.peak_flops * self.devices)
        intensity = self.cost.arithmetic_intensity
        out: Dict[str, Any] = {
            "device_kind": self.chip.device_kind,
            "backend": self.chip.backend,
            "peak_flops": self.chip.peak_flops,
            "hbm_bandwidth": self.chip.hbm_bandwidth,
            "peak_source": self.chip.source,
            "devices": self.devices,
            "cost_source": self.cost.source,
            "flops_per_step": self.cost.flops,
            "bytes_per_step": self.cost.bytes_accessed,
            "steps": steps,
            "step_seconds": round(step_seconds, 6),
            "total_flops": total_flops,
            "arithmetic_intensity": round(intensity, 6),
            "roofline": roofline_class(intensity, self.chip),
            "mfu": round(mfu, 6) if mfu is not None else None,
        }
        if clamped:
            out["mfu_clamped"] = True
        if hbm:
            out["hbm"] = {k: hbm[k] for k in sorted(hbm)}
        return out

    def emit_trace(self, job: str = "") -> Dict[str, Any]:
        """Mirror the block into the process trace (``hardware_block``)
        so the fleet picture is rebuildable offline. Returns the block."""
        blk = self.block()
        attrs: Dict[str, Any] = {
            k: v for k, v in blk.items()
            if k != "hbm" and v is not None}
        for k, v in (blk.get("hbm") or {}).items():
            attrs["hbm_%s" % k] = v
        if job:
            attrs["job"] = job
        tracer().event("hardware_block", **attrs)
        return blk


def conservation_violations(block: Dict[str, Any],
                            label: str = "hardware block",
                            tol: float = 1e-6) -> List[str]:
    """Self-consistency audit shared by the runner tests and
    ``obs_report --hardware``: ``total_flops == flops_per_step x
    steps`` (relative tolerance), MFU within [0, 1], and an MFU that is
    actually derivable from the block's own totals."""
    errs: List[str] = []
    try:
        fps = float(block.get("flops_per_step") or 0.0)
        steps = float(block.get("steps") or 0)
        total = float(block.get("total_flops") or 0.0)
    except (TypeError, ValueError):
        return ["%s: non-numeric flops/steps fields" % label]
    want = fps * steps
    if abs(total - want) > tol * max(1.0, abs(want)):
        errs.append("%s: total_flops %.6g != flops_per_step %.6g x "
                    "steps %g (hardware block does not conserve)"
                    % (label, total, fps, steps))
    mfu = block.get("mfu")
    if mfu is not None:
        mfu = float(mfu)
        if not (0.0 <= mfu <= 1.0):
            errs.append("%s: mfu %.6g outside [0, 1]" % (label, mfu))
        peak = float(block.get("peak_flops") or 0.0) \
            * float(block.get("devices") or 1)
        secs = float(block.get("step_seconds") or 0.0)
        if peak > 0 and secs > 0 and not block.get("mfu_clamped"):
            derived = min(1.0, total / secs / peak)
            if abs(derived - mfu) > max(1e-4, 0.01 * derived):
                errs.append(
                    "%s: mfu %.6g not derivable from its own totals "
                    "(total_flops/step_seconds/(peak x devices) = %.6g)"
                    % (label, mfu, derived))
    return errs

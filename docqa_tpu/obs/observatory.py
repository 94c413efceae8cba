"""Device observatory: per-stage FLOP/byte cost models, device-time
accounting, and MFU/roofline attribution.

The dispatch spine (``engines/spine.py``) measures WHERE device time
goes; this module says what that time BOUGHT.  Each compiled program is
annotated once with its ``cost_analysis()`` FLOPs / bytes-accessed
(``annotate_lowered`` — jax's lowered-stage estimate, no second
compile), keyed by ``(stage, cost_key)`` where ``cost_key`` is the
shape key the call site already uses (the prefill token budget T, the
decode chunk program, a solo generate's ``(batch, bucket)``).  The
spine then reports every completed item's ``(stage, cost_key,
device_seconds)`` here, so per-stage aggregates carry *issued FLOPs*
next to *measured device time* and

    MFU = flops / device_seconds / peak_flops

is an attribution, not a wall-clock guess.  Peaks come from ONE table
keyed by jax's ``device_kind`` (:data:`DEVICE_PEAKS`); a device that is
not in the table has no peak, so every
``mfu`` / ``roofline_bound`` stays null there — a CPU run never reports
utilization against somebody else's chip.

Stdlib-only like the rest of ``docqa_tpu/obs`` (jax is only touched
lazily inside ``annotate_lowered``/``detect_peak_flops``), so the spine
and telemetry can import it without dragging a backend in.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# The ridge point flops/bytes = flops_bf16 / hbm_bytes_s classifies a
# program compute- vs memory-bound on the roofline.
DEVICE_PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def device_peaks(device_kind: Optional[str]) -> Optional[Dict[str, Any]]:
    """The peaks row for ``device_kind``, or None for a device the table
    does not know (callers report no utilization then, never a default)."""
    row = DEVICE_PEAKS.get(device_kind or "")
    return dict(row, device_kind=device_kind) if row else None


def detect_peak_flops() -> Optional[Dict[str, Any]]:
    """``{peak_flops, peak_bytes_s, peak_flops_source, device_kind}`` of
    the attached device, or None when it is not in :data:`DEVICE_PEAKS`
    (or no backend answers) — never raises (obs must not)."""
    try:
        import jax

        row = device_peaks(jax.devices()[0].device_kind)
    except Exception:
        return None
    if row is None:
        return None
    return {
        "device_kind": row["device_kind"],
        "peak_flops": row["flops_bf16"],
        "peak_bytes_s": row["hbm_bytes_s"],
        "peak_flops_source": row["source"],
    }


def parse_cost_analysis(lowered) -> Optional[Dict[str, float]]:
    """``{"flops", "bytes_accessed"}`` from a jax ``Lowered``/``Compiled``
    object's ``cost_analysis()``, or None when the backend offers no
    usable estimate.  The ONE parser — the compile audit and the
    observatory must never drift on this shape."""
    try:
        ca = lowered.cost_analysis()
        if not ca:
            return None
        flops = float(ca.get("flops", 0.0) or 0.0)
        if flops <= 0.0:
            return None
        return {
            "flops": flops,
            "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
        }
    except Exception:
        return None


class Observatory:
    """Cost-model registry + per-stage device-time/FLOP aggregates."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # (stage, cost_key) -> {"flops": f, "bytes": b}
        self._costs: Dict[Any, Dict[str, float]] = {}
        # stage -> {"calls", "device_s", "flops", "bytes", "uncosted"}
        self._stages: Dict[str, Dict[str, float]] = {}

    # ---- cost registration ---------------------------------------------------

    def annotate(
        self,
        stage: str,
        flops: float,
        bytes_accessed: float = 0.0,
        key: Any = None,
    ) -> None:
        with self._lock:
            self._costs[(stage, key)] = {
                "flops": float(flops),
                "bytes": float(bytes_accessed),
            }

    def annotate_lowered(self, stage: str, lowered, key: Any = None) -> bool:
        """Extract FLOPs/bytes from a jax ``Lowered``/``Compiled``
        object's ``cost_analysis()`` and register them.  Fenced: a
        backend without the estimate returns False, never raises."""
        cost = parse_cost_analysis(lowered)
        if cost is None:
            return False
        self.annotate(stage, cost["flops"], cost["bytes_accessed"], key=key)
        return True

    def cost_of(self, stage: str, key: Any = None) -> Optional[Dict[str, float]]:
        with self._lock:
            c = self._costs.get((stage, key))
            return dict(c) if c else None

    # ---- accounting (called by the spine) ------------------------------------

    def record(self, stage: str, cost_key: Any, device_s: float) -> None:
        """One completed work item.  ``cost_key`` may be a tuple/list of
        keys (a prefill round fetch covering several dispatch groups):
        each key's cost accrues to the stage."""
        keys = (
            list(cost_key)
            if isinstance(cost_key, (list, tuple))
            else [cost_key]
        )
        with self._lock:
            row = self._stages.setdefault(
                stage,
                {"calls": 0, "device_s": 0.0, "flops": 0.0, "bytes": 0.0,
                 "uncosted": 0},
            )
            row["calls"] += 1
            row["device_s"] += max(device_s, 0.0)
            costed = False
            for k in keys:
                c = self._costs.get((stage, k))
                if c is not None:
                    row["flops"] += c["flops"]
                    row["bytes"] += c["bytes"]
                    costed = True
            if not costed:
                row["uncosted"] += 1

    def reset(self) -> None:
        """Zero the aggregates (measurement windows); registered
        cost models survive — they describe programs, not traffic."""
        with self._lock:
            self._stages.clear()

    # ---- attribution ---------------------------------------------------------

    def stats(self, peak: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Per-stage MFU / roofline table.  Stages with no registered
        cost — and every stage on a device with no peaks row (``peak``
        is then null) — report device time only (``mfu: None``): visible
        gaps beat silently-wrong utilization."""
        peak = peak or detect_peak_flops()
        peak_flops = peak["peak_flops"] if peak else None
        ridge = (
            peak_flops / max(peak["peak_bytes_s"], 1.0) if peak else None
        )
        with self._lock:
            rows = {k: dict(v) for k, v in self._stages.items()}
        out: Dict[str, Any] = {"peak": peak, "stages": {}}
        for stage, row in sorted(rows.items()):
            dev = row["device_s"]
            flops = row["flops"]
            entry: Dict[str, Any] = {
                "calls": int(row["calls"]),
                "device_s": round(dev, 6),
                "flops": flops,
                "bytes": row["bytes"],
                "uncosted_calls": int(row["uncosted"]),
                "mfu": None,
                "intensity_flops_per_byte": None,
                "roofline_bound": None,
            }
            if flops > 0.0 and row["bytes"] > 0.0:
                entry["intensity_flops_per_byte"] = round(
                    flops / row["bytes"], 3
                )
            if peak and flops > 0.0 and dev > 0.0:
                mfu = flops / dev / peak_flops
                if mfu > 1.0:
                    # physically impossible: the stage's measured device
                    # time under-covers the program's execution.  Report
                    # the raw ratio for debugging, never claim it as
                    # utilization.
                    entry["mfu_raw_invalid"] = round(mfu, 6)
                else:
                    entry["mfu"] = round(mfu, 6)
                if row["bytes"] > 0.0:
                    entry["roofline_bound"] = (
                        "compute"
                        if flops / row["bytes"] >= ridge
                        else "memory"
                    )
            out["stages"][stage] = entry
        return out


DEFAULT_OBSERVATORY = Observatory()

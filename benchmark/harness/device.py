"""The device: refusal without a TPU, the table of peaks, compile
seconds, peak memory."""

from __future__ import annotations

from typing import Dict

#: published peaks by ``device_kind``; a device not in the table is an
#: error, never a default.  Source: Google Cloud documentation, "TPU v5e"
#: (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(kind: str, what: str) -> float:
    if kind not in PEAKS:
        raise KeyError(
            f"benchmark: no peaks recorded for device kind {kind!r}; add it "
            f"to harness/device.py::PEAKS with its source"
        )
    return PEAKS[kind][what]


def require_tpu(n_chips: int):
    """The devices, or SystemExit: no fallback to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU, JAX found {devices[0].platform!r} "
            f"({len(devices)} device(s)) - not running on it"
        )
    if len(devices) < n_chips:
        raise SystemExit(
            f"benchmark: this cell needs {n_chips} chip(s), JAX found "
            f"{len(devices)}"
        )
    return devices[:n_chips]


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (its own
    ``/jax/core/compile/*`` duration events), and how many backend
    compiles there were."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs
            if event.endswith("backend_compile_duration"):
                self.backend_compiles += 1

    def mark(self):
        return (self.seconds, self.backend_compiles)


def device_record(devices, window_in_use_bytes: int = 0) -> Dict[str, object]:
    """``device`` of the result line.  ``memory_peak_bytes`` is the peak on
    the fullest chip.  On the TPU the allocator books a running program's
    temporaries apart from the arrays: ``peak_bytes_in_use`` read 1.33 GB
    under a train step whose temporaries are 4.55 GB by the compiler's
    count, and ``peak_bytes_reserved`` read 4.45 GB beside it (PERF.md,
    PR 24).  So the peak is the larger of the allocator's array peak and
    the arrays in use inside the window plus the reserved peak.  Read
    before the reference runs; the parts are given beside it."""
    d = devices[0]
    stats = [dev.memory_stats() or {} for dev in devices]
    arrays = max(s.get("peak_bytes_in_use", 0) for s in stats)
    reserved = max(s.get("peak_bytes_reserved", 0) for s in stats)
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(arrays, window_in_use_bytes + reserved)),
        "allocator_peak_bytes": int(arrays),
        "reserved_peak_bytes": int(reserved),
        "window_in_use_bytes": int(window_in_use_bytes),
    }

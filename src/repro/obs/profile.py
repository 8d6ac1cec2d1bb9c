"""Profiling hooks: opt-in ``jax.profiler`` capture + published peaks.

Two pieces:

* :func:`profiler_capture` — a context manager around
  ``jax.profiler.trace``: dumps a TensorBoard/XProf profile directory for
  the enclosed block. Opt-in and failure-tolerant: if the installed jax
  build lacks profiler support (or the capture races another one), the
  block still runs and the context records ``.error`` instead of raising —
  profiling must never take down a serving process. While it records,
  every ``obs.span`` also lands in the profile as a ``repro.<name>``
  annotation (:mod:`repro.obs.trace`).

* :data:`PEAKS` + :func:`peaks` — published per-chip peaks keyed by JAX's
  ``device_kind`` (bf16 FLOP/s, HBM and ICI link bandwidth), read by
  ``repro.launch.roofline`` and ``repro.launch.dryrun_mstg``. A device
  missing from the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict, Optional

__all__ = ["PEAKS", "V5E", "peaks", "profiler_capture"]

V5E = "TPU v5 lite"     # jax's device_kind of a TPU v5e chip

# Published single-chip peaks by device_kind. Source for the v5e row: Google
# Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600
# Gbit/s of chip-to-chip interconnect over 4 ICI links (50 GB/s each).
PEAKS: Dict[str, Dict[str, float]] = {
    V5E: {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The :data:`PEAKS` row of ``device_kind``; an unknown device raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add a sourced row to "
                       f"repro.obs.profile.PEAKS") from None


class profiler_capture:
    """``with obs.profiler_capture("/tmp/prof") as cap:`` — capture a
    ``jax.profiler`` trace of the block into ``log_dir`` (view with
    TensorBoard/XProf). ``cap.ok`` says whether the capture actually ran;
    ``cap.error`` holds the reason when it did not."""

    def __init__(self, log_dir: str, create_perfetto_link: bool = False):
        self.log_dir = log_dir
        self._perfetto = create_perfetto_link
        self._active = False
        self.ok = False
        self.error: Optional[str] = None

    def __enter__(self) -> "profiler_capture":
        try:
            import jax
            jax.profiler.start_trace(
                self.log_dir, create_perfetto_link=self._perfetto)
            self._active = True
        except Exception as e:  # noqa: BLE001 — profiling is best-effort
            self.error = f"{type(e).__name__}: {e}"
        return self

    def __exit__(self, *exc) -> bool:
        if self._active:
            try:
                import jax
                jax.profiler.stop_trace()
                self.ok = True
            except Exception as e:  # noqa: BLE001
                self.error = f"{type(e).__name__}: {e}"
        return False

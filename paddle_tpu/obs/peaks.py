"""Published device peaks — ONE table, keyed by jax's ``device_kind``.

Every roofline gauge (obs/costs.py), the MFU ledger (obs/goodput.py) and
the static cost model (analysis/costmodel.py) divide by these. A device
that is not in the table is an error, not a default: a utilization
computed against a guessed peak is a wrong number under a device metric's
name. Off-chip runs (the CPU test suite, tools/graft_lint.py) say what
they divide by with FLAGS_obs_peak_gbps / FLAGS_obs_peak_tflops.
"""
from __future__ import annotations

import functools

from ..core.flags import flag, set_flags

#: per chip. bf16_tflops / int8_tops: dense matmul peak; hbm_gbps: HBM
#: bandwidth; hbm_gb: HBM capacity.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture, per-chip
    # specifications). jax reports a v5e chip as "TPU v5 lite".
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gbps": 819.0, "hbm_gb": 16.0},
}


#: what the CPU-by-design entry points (tools/graft_lint.py,
#: tools/roofline_report.py, bench.py's _CPU_RUNGS; tests/conftest.py passes
#: the same through the environment) divide by: nominal figures that keep
#: the plumbing's gauges finite, never quotable numbers
NOMINAL_OFF_CHIP = {"FLAGS_obs_peak_gbps": 25.0, "FLAGS_obs_peak_tflops": 0.5}


def set_off_chip_peaks() -> None:
    """Give each peak flag that is still 0 its NOMINAL_OFF_CHIP value."""
    set_flags({k: v for k, v in NOMINAL_OFF_CHIP.items()
               if not float(flag(k))})


@functools.cache
def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def device_peaks(device_kind: str | None = None) -> dict:
    """The table row for `device_kind` (default: this process's first
    device). Raises LookupError for a device the table does not hold."""
    kind = _device_kind() if device_kind is None else device_kind
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device_kind {kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add it to obs/peaks.py with its "
            "source, or set FLAGS_obs_peak_gbps / FLAGS_obs_peak_tflops "
            "to say what to divide by") from None


def peak_gbps() -> float:
    """Peak HBM bandwidth (GB/s): FLAGS_obs_peak_gbps when set, else the
    table's row for this device."""
    v = float(flag("FLAGS_obs_peak_gbps"))
    return v if v > 0 else device_peaks()["hbm_gbps"]


def peak_tflops() -> float:
    """Peak bf16 compute (TFLOP/s): FLAGS_obs_peak_tflops when set, else
    the table's row for this device."""
    v = float(flag("FLAGS_obs_peak_tflops"))
    return v if v > 0 else device_peaks()["bf16_tflops"]

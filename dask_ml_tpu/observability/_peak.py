"""Peak-FLOPs table: the ONE denominator every MFU number divides by.

Published per-chip peaks keyed by the exact ``device_kind`` string jax
reports. A device that is not in the table is an error, never a default
and never a number measured on the spot: a "peak" timed with a matmul on
whatever backend happens to be active (XLA:CPU included) would put a
host figure under a device metric's name. Off-chip callers report
"not measured".
"""

from __future__ import annotations

# device_kind -> (bf16 matmul FLOP/s per chip, source). The MXU runs
# f32-input matmuls at bf16-pass rate under default precision, so the
# bf16 peak is the denominator for BOTH dtypes (using it for f32 yields
# a conservative MFU, never an inflated one).
PEAKS = {
    "TPU v5 lite": (
        197e12,
        "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16 per chip",
    ),
}


class UnknownDeviceError(LookupError):
    """``device_kind`` has no row in :data:`PEAKS`."""


def peak_for(device_kind: str) -> dict:
    """``{"flops", "source", "device_kind"}`` for one exact
    ``device_kind``; raises :class:`UnknownDeviceError` otherwise."""
    try:
        flops, source = PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peak for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add a row with its source to "
            "observability/_peak.py::PEAKS — a peak is never estimated"
        ) from None
    return {"flops": flops, "source": source, "device_kind": device_kind}


def resolve_peak() -> dict:
    """The peak of the device jax runs on (``jax.devices()[0]``)."""
    import jax

    return peak_for(jax.devices()[0].device_kind)


def mfu_fields(model_flops, elapsed, n_chips, peak) -> dict:
    """Achieved model FLOP/s and MFU vs per-chip peak (absolute perf
    measures; model_flops counts the algorithm's useful matmul FLOPs)."""
    fps = model_flops / elapsed
    return {
        "model_flops": round(model_flops),
        "model_flop_per_s": round(fps, 1),
        "mfu": round(fps / (peak["flops"] * n_chips), 5),
        "peak": {"flop_per_s_per_chip": round(peak["flops"], 1),
                 "source": peak["source"],
                 "device_kind": peak["device_kind"]},
    }

"""Production meshes.  Target hardware: TPU v5e pods — 256 chips/pod as a
(16, 16) ("data", "model") mesh; two pods add a leading "pod" axis that the
shardings fold into data parallelism.

Functions (never module-level constants) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before first jax use.
"""
from __future__ import annotations

import jax

__all__ = [
    "make_production_mesh",
    "make_local_mesh",
    "PEAKS",
    "PRODUCTION_DEVICE_KIND",
    "peaks",
]


# Published per-chip peak rates for the roofline analysis, keyed by
# ``jax.Device.device_kind``.  Source: Google Cloud documentation, "TPU
# v5e" (system architecture): 197 TFLOP/s bf16, 819 GB/s of HBM
# bandwidth, 1,600 Gbit/s of interchip interconnect per chip.  ``ici_bw``
# is per link, as the roofline charges a collective to one link: the
# chip's 1,600 Gbit/s over its 4 links of the 2-D torus.
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,
        "hbm_bw": 819e9,  # bytes/s
        "ici_bw": 1600e9 / 8 / 4,  # bytes/s per link
    },
}


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; a device without
    published peaks in :data:`PEAKS` is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak rates for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


# the chips of make_production_mesh, as jax names their device_kind
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(num_devices: int | None = None):
    """Degenerate mesh over whatever devices exist (CPU smoke tests)."""
    n = num_devices or len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))

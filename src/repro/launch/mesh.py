"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set XLA_FLAGS
before any jax initialization."""
from __future__ import annotations

import jax

from repro.utils.jaxcompat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = 1
    for s in shape:
        n *= s
    assert len(jax.devices()) >= n, f"need {n} devices"
    return make_mesh(shape, axes)


# Published per-chip peaks, keyed by jax's ``device_kind``.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s of HBM
# bandwidth, 1,600 Gbit/s of inter-chip interconnect (4 links of 50 GB/s).
PEAKS = {
    "TPU v5 lite": dict(
        peak_flops_bf16=197e12,  # FLOP/s per chip
        hbm_bw=819e9,  # B/s per chip
        ici_bw=50e9,  # B/s per link
    ),
}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device without an entry
    is an error, never a default."""
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {', '.join(PEAKS)})"
        )
    return PEAKS[device_kind]


# the chip the dry-run's production mesh is modelled on
HW = peaks("TPU v5 lite")

"""Per-device-kind peak flops / HBM bandwidth table — the MFU denominator.

Datasheet sources: TPU v5e/v5p/v4/v3 public specs (per-chip dense
matmul peak; f32 at half the bf16 rate on generations without an f32
MXU path).  A device that is not in the table is an error, not a
default: :func:`peaks_for` raises :class:`UnknownDeviceKind`, and the
live MFU gauges (``costmodel.live.publish_dispatch``) then publish
nothing — as on the CPU, where there is no peak to divide by.
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass(frozen=True)
class DevicePeaks:
    """Peak throughput of one device kind (per chip)."""

    device_kind: str
    flops: dict[str, float] = field(default_factory=dict)  # dtype -> flops/s
    hbm_bandwidth: float = 0.0  # bytes/s
    hbm_bytes: int = 0

    def peak_flops(self, dtype: str = "bf16") -> float:
        """Peak for ``dtype``, falling back bf16 -> best known (a missing
        dtype must yield a denominator, not a KeyError mid-trial)."""
        v = self.flops.get(dtype)
        if v is None:
            v = self.flops.get("bf16")
        if v is None and self.flops:
            v = max(self.flops.values())
        return float(v or 0.0)

    @property
    def ridge_intensity(self) -> float:
        """Arithmetic intensity (flops/byte) where the compute and
        bandwidth roofs cross — programs below it are memory-bound."""
        if not self.hbm_bandwidth:
            return 0.0
        return self.peak_flops() / self.hbm_bandwidth


PEAKS: dict[str, DevicePeaks] = {
    "v5e": DevicePeaks(
        "v5e",
        {"bf16": 197e12, "f32": 98.5e12, "int8": 394e12},
        hbm_bandwidth=819e9,
        hbm_bytes=16 * 1024**3,
    ),
    "v5p": DevicePeaks(
        "v5p",
        {"bf16": 459e12, "f32": 229.5e12, "int8": 918e12},
        hbm_bandwidth=2765e9,
        hbm_bytes=95 * 1024**3,
    ),
    "v4": DevicePeaks(
        "v4",
        {"bf16": 275e12, "f32": 137.5e12},
        hbm_bandwidth=1228e9,
        hbm_bytes=32 * 1024**3,
    ),
    "v3": DevicePeaks(
        "v3",
        {"bf16": 123e12, "f32": 61.5e12},
        hbm_bandwidth=900e9,
        hbm_bytes=32 * 1024**3,
    ),
}


class UnknownDeviceKind(LookupError):
    """The device kind has no row in :data:`PEAKS`."""


def normalize_device_kind(kind: str) -> str:
    """Fold a raw ``Device.device_kind`` (or a table key) onto a table
    key: ``"TPU v5 lite"`` -> ``v5e``, ``"TPU v4"`` -> ``v4``.  A kind the
    table does not hold raises :class:`UnknownDeviceKind`."""
    k = str(kind).strip().lower()
    if "v5 lite" in k or "v5lite" in k or "v5e" in k:
        return "v5e"
    if "v5p" in k or k == "tpu v5" or k == "v5":
        return "v5p"
    if "v4" in k:
        return "v4"
    if "v3" in k:
        return "v3"
    raise UnknownDeviceKind(
        f"device kind {kind!r} is not in the peaks table ({', '.join(PEAKS)})"
    )


def detect_device_kind() -> str:
    """The table key of the live backend's first device."""
    import jax

    return normalize_device_kind(jax.devices()[0].device_kind)


def peaks_for(device_kind: str | None = None) -> DevicePeaks:
    """The peaks entry for ``device_kind`` (the live device when None)."""
    kind = (
        normalize_device_kind(device_kind)
        if device_kind is not None
        else detect_device_kind()
    )
    return PEAKS[kind]

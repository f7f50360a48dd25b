"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not here is an error, not a default."""

PEAKS = {
    "TPU v5 lite": dict(
        hbm_bytes_per_s=819e9,
        source="Google Cloud documentation, 'TPU v5e': 16 GB HBM2 at 819 GB/s per chip",
    ),
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

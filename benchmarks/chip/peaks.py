"""Published peaks of each device the benchmark may run on, by the
``device_kind`` JAX reports.  A device that is not here is an error."""

# Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s per chip
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None

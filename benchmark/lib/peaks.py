"""Published peaks per chip, keyed by PJRT `device_kind`.

Source: Google Cloud documentation, "TPU v5e" / "TPU v4" / "TPU v5p" /
"TPU v6e" system-architecture pages (bf16 TFLOP/s and HBM GB/s per chip).
A device that is not in the table is an error, not a default.

The Nature-CNN trunk runs in bf16 (the network's own default) and the f32
heads run as bf16 passes on the MXU at default precision, so the bf16 peak
is the one every share here is taken of.
"""

PEAKS = {
    # device_kind prefix: (bf16 FLOP/s, HBM bytes/s)
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


def _lookup(device_kind: str):
    # Longest prefix first, so "TPU v5 lite" is not read as "TPU v5".
    for name in sorted(PEAKS, key=len, reverse=True):
        if device_kind.startswith(name):
            return PEAKS[name]
    raise KeyError(
        f"no peak on file for device_kind {device_kind!r}: add it to "
        "benchmark/lib/peaks.py with its source")


def peak_flops(device_kind: str) -> float:
    return _lookup(device_kind)[0]


def peak_hbm_bytes_per_s(device_kind: str) -> float:
    return _lookup(device_kind)[1]

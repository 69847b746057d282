"""Published peaks per chip, keyed by `device_kind`. A device that is
not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    # device_kind: (bf16 FLOP/s, HBM bytes/s, HBM bytes, source)
    "TPU v5 lite": (197e12, 819e9, 16 * 1024 ** 3,
                    'Google Cloud documentation, "TPU v5e"'),
}


class UnknownDevice(ValueError):
    pass


def peaks_for(device_kind: str) -> dict:
    """{'flops', 'hbm_bytes_per_s', 'hbm_bytes', 'source'} of one chip."""
    try:
        flops, bw, hbm, source = PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            "no published peaks on record for device kind %r; add it to "
            "benchmark/lib/peaks.py with its source" % (device_kind,))
    return {"flops": flops, "hbm_bytes_per_s": bw, "hbm_bytes": hbm,
            "source": source}

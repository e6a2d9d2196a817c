"""Published peaks of one chip, keyed by ``device_kind`` as jax reports it.

The benchmark's own copy (the program's is ``utils/metrics.PEAK_FLOPS``,
which matches by substring; this one matches the whole kind).  Source of
the one row: Google Cloud documentation, "TPU v5e" system architecture:
197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.  A device
that is not in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    # libtpu has also reported the same part under this kind
    "TPU v5e": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(kind, what="flops"):
    try:
        return PEAKS[kind][what]
    except KeyError:
        raise ValueError(
            f"no published peak known for device kind {kind!r}: add it to "
            "benchmark/lib/peaks.py with its source") from None

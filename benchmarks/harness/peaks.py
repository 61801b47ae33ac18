"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports.  Copied from lightgbm_tpu/obs/perf.py
`DEVICE_PEAKS` (ISSUE 22: sound, copy it).  A device that is not in the
table is an error, not a default: a roofline share against a guessed roof
is not a measurement."""

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flop_per_s": 197e12,
        "int8_op_per_s": 393e12,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                  '393 TOP/s int8, 16 GB HBM at 819 GB/s',
    },
}


def peaks_of(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no published peaks for device kind %r in "
                       "benchmarks/harness/peaks.py; add them with their "
                       "source" % device_kind) from None

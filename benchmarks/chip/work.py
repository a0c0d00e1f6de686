"""The chip's peaks, and the work that any model's step shares.

Every count here and in the model modules (``models/<arch>.py``:
``model_flops``, ``kernel_calls``) is computed from a batch's true node
and edge counts and the configuration's widths, never from the padded
tiles or buckets an implementation builds, so a change of implementation
cannot move the yardstick. A batch is described as a list of layers,
input layer first, each a dict with ``n_src``, ``n_dst`` and ``n_edges``
(true counts).
"""
from __future__ import annotations

# Published per-chip peaks, keyed by the ``device_kind`` JAX reports.
# TPU v5e: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s (Google Cloud
# documentation, "TPU v5e").
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}
F32 = 4      # bytes per feature value (the model is float32)
INDEX = 4    # bytes per node index (int32)


def peaks(device_kind: str) -> dict:
    """Peaks of one chip; a device kind missing from the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for {device_kind!r}") from None


def gather_bytes(rows: int, width: int) -> float:
    """A row gather reads each row once and writes it once, with its index."""
    return float(rows) * (2 * width * F32 + INDEX)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bw"])

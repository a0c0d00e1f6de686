"""Operations and bytes that the algorithm needs, and the chip's peaks.

Everything here is computed from a batch's true node and edge counts and
the model's widths, never from the padded tiles or buckets an
implementation builds, so a change of implementation cannot move the
yardstick.

A batch is described as a list of layers, input layer first, each a dict
with ``n_src``, ``n_dst`` and ``n_edges`` (true counts); the model by its
widths ``dims = [d_in, d_hidden, ..., n_classes]``.
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


def model_flops(layers: list[dict], dims: list[int]) -> float:
    """FLOPs one SAGE training step requires (mean aggregator, 2 weights).

    Per layer with input width ``fi`` and output width ``fo``: aggregation
    ``2·E·fi``, the two projections ``2·(2·n_dst·fi·fo)`` forward and as
    much again for their weight gradients. Every layer but the first also
    needs the gradient of its input: the projections' input gradients and
    the transposed aggregation. Layer 0's input is the feature table, which
    is not trained, so it needs none. Elementwise work is not counted.
    """
    total = 0.0
    for i, lay in enumerate(layers):
        fi, fo = dims[i], dims[i + 1]
        agg = 2.0 * lay["n_edges"] * fi
        proj = 2.0 * 2.0 * lay["n_dst"] * fi * fo
        total += agg + proj + proj            # forward, weight gradients
        if i > 0:
            total += proj + agg                # input gradients
    return total


def spmm_calls(layers: list[dict], dims: list[int]) -> list[dict]:
    """The sparse aggregations one step needs, with FLOPs and bytes each.

    Forward at every layer; transposed at every layer but the first. The
    least bytes read each source row once, write each destination row
    once and read the edge list (source and destination index) once.
    """
    calls = []
    for i, lay in enumerate(layers):
        f = dims[i]
        flops = 2.0 * lay["n_edges"] * f
        nbytes = ((lay["n_src"] + lay["n_dst"]) * f * F32
                  + 2 * lay["n_edges"] * INDEX)
        calls.append({"layer": i, "pass": "forward", "flops": flops,
                      "bytes": nbytes})
        if i > 0:
            calls.append({"layer": i, "pass": "transposed", "flops": flops,
                          "bytes": nbytes})
    return calls


def gather_bytes(rows: int, width: int) -> float:
    """A row gather reads each row once and writes it once, with its index."""
    return float(rows) * (2 * width * F32 + INDEX)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bw"])

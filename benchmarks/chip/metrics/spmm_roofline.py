"""Roofline share of the ``block_spmm`` kernel (forward and transposed):
the least time the algorithm's work needs (``work.spmm_calls``, from true
counts) over the kernel's device time summed from the trace."""
import work
import xtrace

KERNEL = "block_spmm_kernel"


def read(run: dict) -> float | None:
    secs, n = xtrace.kernel_seconds(run["trace"], KERNEL)
    if not n or run["peaks"] is None:
        return None
    least = sum(work.least_time(c["flops"], c["bytes"], run["peaks"])
                for r in run["steps"]
                for c in work.spmm_calls(r["layers"], run["dims"]))
    return 100.0 * least / secs

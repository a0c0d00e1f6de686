"""Roofline share of the fixed-fan-out aggregation kernel (PNA's message
and four aggregators, forward): the least time the algorithm's work
needs (each step's ``kernel_calls`` of this kernel from the model module,
from true counts) over the kernel's device time summed from the trace. A
model that makes no such call, or a run where the kernel never ran, has
no reading."""
import work
import xtrace

KERNEL = "fanout_aggregate_kernel"


def read(run: dict) -> float | None:
    secs, n = xtrace.kernel_seconds(run["trace"], KERNEL)
    calls = [c for r in run["steps"]
             for c in r["kernel_calls"].get(KERNEL, [])]
    if not n or not calls or run["peaks"] is None:
        return None
    least = sum(work.least_time(c["flops"], c["bytes"], run["peaks"])
                for c in calls)
    return 100.0 * least / secs

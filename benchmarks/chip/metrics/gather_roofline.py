"""Roofline share of the device tier's ``embedding_bag`` row gather: the
rows it served, each read and written once at the feature width, over the
gather kernel's device time from the trace."""
import work
import xtrace

KERNEL = "gather_rows_kernel"


def read(run: dict) -> float | None:
    secs, n = xtrace.kernel_seconds(run["trace"], KERNEL)
    rows = sum(r["rows_gathered"] for r in run["steps"])
    if not n or not rows or run["peaks"] is None:
        return None
    nbytes = work.gather_bytes(rows, run["n_feat"])
    return 100.0 * work.least_time(0.0, nbytes, run["peaks"]) / secs

from repro.kernels.fanout_agg.kernel import fanout_aggregate_kernel  # noqa: F401
from repro.kernels.fanout_agg.ops import (  # noqa: F401
    fanout_aggregate,
    neighbour_table,
)

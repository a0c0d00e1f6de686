from repro.kernels.segment_mm.kernel import default_interpret  # noqa: F401
from repro.kernels.segment_mm.ops import (  # noqa: F401
    block_sparse_plan,
    block_spmm,
    block_spmm_xla,
    segment_mm,
    sorted_edge_slots,
    tiles_from_plan,
    to_block_sparse,
)

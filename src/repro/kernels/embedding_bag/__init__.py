from repro.kernels.embedding_bag.ops import (  # noqa: F401
    gather_layout,
    gather_rows,
)

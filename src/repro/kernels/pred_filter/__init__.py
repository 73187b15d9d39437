from .ops import compile_conjunction, scan_mask
from .pred_filter import (
    OPS,
    block_bounds,
    pred_filter,
    pred_filter_batch,
)
from .ref import (
    pred_filter_batch_ref,
    pred_filter_batch_xla,
    pred_filter_ref,
    search_iters,
)

# Distributed substrate: host-level ring collectives and gradient
# compression. Mesh-shape agnostic: runs unchanged on the 1-device CPU test
# mesh and on multi-device meshes.
from .collectives import (hierarchical_all_reduce, reduce_scatter,  # noqa: F401
                          ring_all_gather, ring_all_reduce, ring_gather_stack)
from .compression import (CompressionConfig, compress_with_feedback,  # noqa: F401
                          compression_ratio, init_error_feedback, topk_sparsify)

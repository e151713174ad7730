"""Pallas TPU kernels for the paper's compute hot-spots (native on the
TPU, interpret mode elsewhere; see each module's VMEM/tiling notes)."""
from .ops import (fused_prox_sgd, compact_groups, expand_groups,
                  group_norms_sq)

__all__ = ["fused_prox_sgd", "compact_groups", "expand_groups",
           "group_norms_sq"]

"""Pallas TPU kernels for the framework's compute hot-spots.

heat_scatter      -- FedSubAvg's fused aggregate+correct embedding update
rowsparse_scatter -- generalisation to cohort row-sparse deltas (sparse plane)
union_segsum      -- sorted-run union + segment-sum + heat scaling producing
                     the union-id RowSparse aggregate (sparse server engine)
flash_attention   -- causal GQA flash attention (+ sliding window)
flash_decode      -- single-token decode against long KV caches

Validated in interpret mode on CPU against repro.kernels.ref oracles; on TPU
the real compiled path is selected at runtime.
"""
from repro.kernels.ops import (  # noqa: F401
    flash_attention,
    flash_decode,
    heat_scatter,
    rowsparse_scatter,
    union_segsum,
)

"""Pallas TPU kernels: the paper's partitioned-WS GEMM (+ oracle & wrappers),
routed experts on it, and prefill attention."""

from repro.kernels.attention import (
    attention_table,
    live_blocks,
    packed_positions,
    prefill_attention,
    rope_inv_freq,
)
from repro.kernels.moe import moe_block, route, routed_experts
from repro.kernels.ops import (
    BLOCK_CANDIDATES,
    FusedGemmStats,
    autotune_blocks,
    build_owner_map,
    fused_tenant_gemm,
)
from repro.kernels.partitioned_matmul import (
    GRID_MODES,
    VMEM_BUDGET_BYTES,
    BlockAccounting,
    block_vmem_bytes,
    grid_accounting,
    live_block_tables,
    partitioned_matmul,
)
from repro.kernels.ref import matmul_ref, partitioned_matmul_ref

__all__ = [
    "BLOCK_CANDIDATES",
    "BlockAccounting",
    "FusedGemmStats",
    "GRID_MODES",
    "VMEM_BUDGET_BYTES",
    "attention_table",
    "autotune_blocks",
    "block_vmem_bytes",
    "build_owner_map",
    "fused_tenant_gemm",
    "grid_accounting",
    "live_block_tables",
    "live_blocks",
    "matmul_ref",
    "moe_block",
    "packed_positions",
    "partitioned_matmul",
    "partitioned_matmul_ref",
    "prefill_attention",
    "rope_inv_freq",
    "route",
    "routed_experts",
]

"""Hand-written Pallas TPU kernels (the repo's analog of the reference's
hand-written kernel library, paddle/phi/kernels/primitive/ +
paddle/phi/kernels/gpu/flash_attn_kernel.cu — re-designed for the MXU/VMEM
model rather than translated).

Kernels here are pure jittable functions; dispatch gates live next to the
user-facing functionals (e.g. nn/functional/flash_attention.py for the
attention and cache-write kernels, nn/functional/loss.py for fused CE).
"""
from .cache_write import fused_paged_write, fused_slot_write  # noqa: F401
from .flash_block import (  # noqa: F401
    compute_delta, flash_attention_lse, flash_block_attention,
    flash_block_attention_bwd, merge_lse_blocks)
from .fused_ce import ce_bwd, ce_fwd, online_lse  # noqa: F401
from .gated_delta import gated_delta_pallas, gated_delta_xla  # noqa: F401
from .mega_decode import mega_decode_step  # noqa: F401

__all__ = ["flash_block_attention", "flash_block_attention_bwd",
           "flash_attention_lse", "merge_lse_blocks", "compute_delta",
           "fused_slot_write", "fused_paged_write",
           "ce_fwd", "ce_bwd", "online_lse", "mega_decode_step",
           "gated_delta_pallas", "gated_delta_xla"]

"""Ragged batching infrastructure (reference: inference/v2/ragged/)."""

from deepspeed_tpu.inference.v2.ragged.blocked_allocator import BlockedAllocator
from deepspeed_tpu.inference.v2.ragged.host_tier import (HostKVTier,
                                                         HostTierStats)
from deepspeed_tpu.inference.v2.ragged.kv_cache import (BlockedKVCache,
                                                        CacheLayoutError,
                                                        dequantize_kv,
                                                        quantize_kv)
from deepspeed_tpu.inference.v2.ragged.prefix_cache import (PrefixCacheStats,
                                                            RadixPrefixCache)
from deepspeed_tpu.inference.v2.ragged.ragged_manager import DSStateManager
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import RaggedBatchWrapper
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import (
    DSSequenceDescriptor,
)
from deepspeed_tpu.inference.v2.ragged.state_pool import StateSlotPool

__all__ = ["BlockedAllocator", "BlockedKVCache", "CacheLayoutError",
           "DSStateManager", "HostKVTier", "HostTierStats",
           "PrefixCacheStats", "RadixPrefixCache", "RaggedBatchWrapper",
           "StateSlotPool",
           "DSSequenceDescriptor", "quantize_kv", "dequantize_kv"]

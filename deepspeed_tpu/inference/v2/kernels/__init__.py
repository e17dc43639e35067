"""Ragged inference kernels (reference: inference/v2/kernels/ragged_ops/)."""

from deepspeed_tpu.inference.v2.kernels.blocked_flash import (
    decode_walk_usable,
    paged_attention,
    paged_attention_usable,
    paged_decode_attention,
    paged_prefill_attention,
    paged_verify_attention,
    prefill_key_steps,
)

from deepspeed_tpu.inference.v2.kernels.latent_flash import (
    latent_decode_attention,
    latent_expand,
    latent_prefill_attention,
    latent_prefill_key_steps,
)

__all__ = ["decode_walk_usable", "latent_decode_attention", "latent_expand",
           "latent_prefill_attention", "latent_prefill_key_steps",
           "paged_attention",
           "paged_attention_usable",
           "paged_decode_attention", "paged_prefill_attention",
           "paged_verify_attention", "prefill_key_steps"]

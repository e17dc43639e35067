"""PR 30, chip call 5: does the decode walk move the OLMoE cell's logits gap?

Call 4 read ``correct`` false once (0.0302 of 0.03) on the change where the
parent read 0.0229 on the same seed.  The runner's own check (512 prompt
tokens through ``put``, 8 tokens through ``decode_step``, logits against the
float32 reference) on the cell's configuration, a seed after another, ONE set
of weights a seed and three reads of the one-token rows beside each other:

* ``walk``   — the tree as it is (``_decode_kernel``);
* ``dense``  — ``paged_decode_attention`` replaced by ``_dense_pool_read``:
  the parent's arithmetic on this tree's weights and prefill;
* ``gather`` — replaced by ``_gather_read``: a third order of the same bf16
  operations, to see what two equally precise reads differ by.

A line a seed: the gap of the prefill row (row 511, the same program in all
three) and the largest gap of the 8 decode rows, each over the largest
reference logit.

    python3 tools/chip_calls/pr30_logit_gaps.py <seed> ...
"""

import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _CHECKOUT)

import jax                                              # noqa: E402
import numpy as np                                      # noqa: E402

from benchmark.lib import device, spec                  # noqa: E402
from benchmark.runners import serve_ragged              # noqa: E402

CELL = os.environ.get("CELL", "serve-olmoe-chat-closed32")


def main(seeds) -> int:
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig,
                                            kernels)
    from deepspeed_tpu.inference.v2.model_implementations import ragged_llama

    bench = spec.benchmark_spec()
    cfg = spec.config_for(bench, spec.cell(bench, CELL))
    rehearse = bool(os.environ.get("REHEARSE"))   # the CPU, a tiny size
    if rehearse:
        cfg.update(hidden_size=64, intermediate_size=32,
                   num_attention_heads=4, num_key_value_heads=4,
                   num_hidden_layers=2, vocab_size=256, num_experts=8,
                   num_experts_per_tok=2,
                   serve=dict(cfg["serve"], block_size=16, token_budget=64,
                              max_ragged_sequence_count=4, max_context=256,
                              kv_pool_blocks=80, check_prompt_tokens=40,
                              check_decode_tokens=3))
    device.claim_devices(1, allow_cpu=rehearse)
    device.enable_compile_cache()
    family = spec.module("families", cfg["family"])
    reference = spec.module("reference", family.REFERENCE)
    sv = cfg["serve"]
    n_prompt, n_decode = (int(sv["check_prompt_tokens"]),
                          int(sv["check_decode_tokens"]))
    walk = kernels.paged_decode_attention

    def xla_read(read):
        def run(q, k_pool, v_pool, tables, slot, pos, *, block_size,
                window=None, k_scale=None, v_scale=None):
            batch = {"block_tables": tables, "token_slot": slot,
                     "token_pos": pos}
            return read(q, k_pool, v_pool, k_scale, v_scale, batch,
                        block_size, window)
        return run

    reads = {
        "walk": walk,
        "dense": xla_read(ragged_llama._dense_pool_read),
        "gather": xla_read(lambda *a: ragged_llama._gather_read(*a, True)),
    }
    for seed in seeds:
        params = serve_ragged.make_params(family, cfg, seed)
        ids = np.random.default_rng([seed, 99]).integers(
            0, int(cfg["vocab_size"]), size=(n_prompt + n_decode,))
        want = None
        line = []
        for name, read in reads.items():
            kernels.paged_decode_attention = read
            try:
                engine = InferenceEngineV2(
                    family.serve_model(cfg, int(sv["block_size"])), params,
                    RaggedInferenceEngineConfig.from_dict({
                        "state_manager": {
                            "max_ragged_batch_size": sv["token_budget"],
                            "max_ragged_sequence_count":
                                sv["max_ragged_sequence_count"],
                            "max_context": sv["max_context"]},
                        "kv_cache": {"block_size": sv["block_size"],
                                     "num_blocks": sv["kv_pool_blocks"]}}))
                uid = 1 << 40
                got = [np.asarray(engine.put(
                    [uid], [ids[:n_prompt].tolist()])[uid], np.float32)]
                for t in ids[n_prompt:]:
                    row = engine.decode_step([uid], [int(t)])
                    got.append(np.asarray(jax.device_get(row),
                                          np.float32)[0])
                got = np.stack(got)
                if want is None:
                    want = reference.logits_at(
                        family.reference_params(engine.params), ids, cfg,
                        rows=list(range(n_prompt - 1, n_prompt + n_decode)))
                gaps = np.max(np.abs(got - want), axis=1) / np.max(
                    np.abs(want))
                line.append(f"{name} prefill {gaps[0]:.5f} decode "
                            f"{gaps[1:].max():.5f}")
                del engine
            finally:
                kernels.paged_decode_attention = walk
        print(f"seed {seed}: " + "; ".join(line), flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main([int(s) for s in sys.argv[1:]]))

"""One-off of PR 25 (ran from a checkout's root on the chip): the runner's own
correctness check of the OLMoE cell (512 tokens through ``put``, 8 through
``decode_step``, bf16 engine at the published widths) with every routing the
program makes recorded, against the float32 reference's routings on the same
weights: the logits gap and the share of (token, layer) routings that agree.

    python3 benchmark/tools/calls/pr25_routing_agreement.py <seed> [<seed> ...]
    ROUTER=default python3 benchmark/tools/calls/pr25_routing_agreement.py <seed> ...

With ``ROUTER=default`` the program's router GEMM runs at the TPU's default matmul
precision (operands rounded to bf16) instead of ``Precision.HIGHEST``: the review's
second reading (``ragged_mixtral.moe_router`` sees a ``jnp`` whose ``matmul`` drops the
precision; nothing else does).

The recording wraps ``ops.grouped_gemm.exact_topk_routing`` with a
``jax.debug.callback`` (the program's code is not changed); the rows of the
prompt are those after the ``max_ragged_sequence_count`` single-token rows of
the two-segment batch, a decode step's sequence is row 0.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.getcwd())
import jax                                               # noqa: E402

from benchmark.lib import device, spec                   # noqa: E402
from benchmark.runners import serve_ragged               # noqa: E402
from deepspeed_tpu.inference.v2 import (                 # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.ops import grouped_gemm               # noqa: E402

CELL = "serve-olmoe-chat-closed32"
bench = spec.benchmark_spec()
cfg = spec.config_for(bench, spec.cell(bench, CELL))
family = spec.module("families", cfg["family"])
reference = spec.module("reference", family.REFERENCE)
serve = cfg["serve"]
device.claim_devices(1)
device.enable_compile_cache()

calls = []
real = grouped_gemm.exact_topk_routing


def recording(logits, k, renormalize=True):
    topi, topw = real(logits, k, renormalize)
    jax.debug.callback(lambda a: calls.append(np.asarray(a)), topi,
                       ordered=True)
    return topi, topw


grouped_gemm.exact_topk_routing = recording
if os.environ.get("ROUTER") == "default":
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_implementations import ragged_mixtral

    class DefaultPrecisionJnp:
        matmul = staticmethod(lambda a, b, precision=None: jnp.matmul(a, b))

        def __getattr__(self, name):
            return getattr(jnp, name)

    ragged_mixtral.jnp = DefaultPrecisionJnp()
    print("router GEMM at the default precision", flush=True)
S, L = int(serve["max_ragged_sequence_count"]), int(cfg["num_hidden_layers"])
n_prompt = int(serve["check_prompt_tokens"])
n_decode = int(serve["check_decode_tokens"])
for seed in [int(s) for s in sys.argv[1:]]:
    params = serve_ragged.make_params(family, cfg, seed)
    engine = InferenceEngineV2(
        family.serve_model(cfg, int(serve["block_size"])), params,
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {
                "max_ragged_batch_size": serve["token_budget"],
                "max_ragged_sequence_count": S,
                "max_context": serve["max_context"]},
            "kv_cache": {"block_size": serve["block_size"],
                         "num_blocks": serve["kv_pool_blocks"]}}))
    calls.clear()
    gap = serve_ragged._check_logits(engine, reference, family, cfg, seed,
                                     n_prompt, n_decode)
    jax.effects_barrier()
    fwds = [calls[i:i + L] for i in range(0, len(calls), L)]
    assert len(fwds) == 1 + n_decode, len(calls)
    got = np.concatenate(
        [np.stack([a[S:S + n_prompt] for a in fwds[0]])] +
        [np.stack([a[:1] for a in f]) for f in fwds[1:]], axis=1)
    ids = np.random.default_rng([seed, 99]).integers(
        0, int(cfg["vocab_size"]), size=(n_prompt + n_decode,))
    want = reference.routings(family.reference_params(engine.params), ids,
                              cfg)
    got, want = np.sort(got, -1), np.sort(want, -1)
    same_set = np.all(got == want, axis=-1)
    shared = np.mean([len(set(g) & set(w)) for g, w in
                      zip(got.reshape(-1, got.shape[-1]),
                          want.reshape(-1, want.shape[-1]))])
    print(f"seed {seed}: logits gap {gap:.4f} (tolerance "
          f"{serve_ragged.LOGIT_TOL}); {100 * same_set.mean():.2f}% of "
          f"{same_set.size} (token, layer) routings pick the same "
          f"{got.shape[-1]} experts; by layer "
          f"{[round(100 * float(x), 1) for x in same_set.mean(axis=1)]}; "
          f"mean experts shared {shared:.3f} of {got.shape[-1]}",
          flush=True)
    del engine, params

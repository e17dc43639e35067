"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the published Mistral-7B-v0.1 widths (hidden 4096, 32x128 query heads, 8 KV
heads, MLP 14336, vocab 32000) with depth cut to what one chip holds and
seeded random weights:

* **train** — ``deepspeed_tpu.initialize(model=MistralForCausalLM(cfg),
  config=...)`` then ``engine(batch)`` / ``engine.backward`` /
  ``engine.step``: bf16, AdamW, gradient clipping, sequence 4096.
* **serve** — ``RaggedLlama`` -> ``InferenceEngineV2`` ->
  ``ContinuousBatchScheduler.submit()`` / ``run_until_idle()``: requests of
  mixed length, SplitFuse chunking, greedy decode.
* **moe** (one device) — the routed-expert path at the published
  OLMoE-1B-7B widths (64 experts of width 1024 at top-8, hidden 2048):
  the grouped GEMM at a decode tick's and at a mixed tick's row count
  against ``gmm_reference``, and a depth-2 ``RaggedMixtral`` engine
  (``put`` then ``decode_step``) on the grouped path against the dense
  all-experts composition.
* **gdn** (one device) — linear-attention layers with per-sequence state at
  the published Qwen3-Next-80B-A3B widths, one period deep (3 Gated DeltaNet
  + 1 gated attention layer, a share of the experts): two requests of
  different lengths served TOGETHER through the scheduler (the longer one's
  chunks beside the other's decodes), each one's logits against the plain
  float32 reference's own forward (``benchmark/reference/qwen3_next.py``).
* **mla** (one device) — latent attention at the published Moonlight-16B-A3B
  widths, three layers deep (the dense layer and two routed ones, 16 of the
  64 experts held): two requests of different lengths served TOGETHER, the
  longer one's chunks through the expanded path beside the other's decodes
  through the absorbed one, each against the float32 expanded-form reference
  (``benchmark/reference/moonlight.py``).
* **conv** (one device) — the gated short convolution and 64-wide heads at
  the published LFM2-24B-A2B widths, four layers deep (the two dense layers
  and one period's attention and convolution layer, every expert held): the
  same two requests served TOGETHER, the longer one's chunks carrying their
  convolution tails beside the other's decodes, the one-token rows through
  the decode walk on the flat pool row, each against the float32 reference
  (``benchmark/reference/lfm2_moe.py``).
* **kernels** — ``tools/kernel_selftest.run_selftest()`` as a gate.

With more than one device visible the same phases run across all of them
(ZeRO-3 over ``data`` x tensor parallel over ``model`` for training, tensor
parallel serving) and every device must hold its share of the state.

It refuses to run without a TPU, never catches a phase's failure, and ends
with one JSON line, ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``; the details go to the lines before it and to
``chiprun_out/chip_smoke.json``.  The timings it prints are set-up facts of
a smoke run, not benchmark results.

    python chip_smoke.py          # no arguments; exit code 0 = the chip works
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import json
import os
import re
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))

# Step-0 training loss, flash kernels against the XLA composition on the
# same bf16 weights and batch.  Both routes run the same bf16 matmuls and
# differ only in attention's accumulation order (fp32 online softmax vs one
# fp32 softmax); the loss is a mean over ~4k tokens of fp32 cross-entropies
# near ln(vocab) ~ 10.4, so independent bf16 roundings (2^-8 relative per
# value) average down to ~1e-3.  5e-3 absolute leaves room for that and is
# far below what a mis-masked or mis-scaled kernel moves the loss by.
TRAIN_LOSS_TOL = 5e-3

# Last-token prefill logits, engine (paged Pallas attention, flat [T, H]
# matmuls) against LlamaForCausalLM.apply with implementation="xla".  bf16
# keeps 8 mantissa bits, so each of the ~10 roundings per layer perturbs
# activations by ~0.4% and the two operation orders drift apart layer by
# layer; measured against the largest reference logit, 5% bounds that drift
# over the smoke's depth while a wrong block table, position or mask moves
# logits by their full scale.
SERVE_LOGIT_TOL = 0.05

# Grouped GEMM against ``gmm_reference`` (XLA: one-hot mask and one einsum),
# bf16 in and out, K = 2048: both accumulate in float32 and round the result
# once, so where the order of the sums differs they differ by at most one
# bf16 spacing of a value, 2^-7 of the LARGEST value.  On the v5e the kernel
# reads 0.0 at 256 and at 4352 rows; ``gmm_reference`` with bf16 partial
# sums (16 slices of K) reads 0.0099 and 0.0122 against itself, over the
# limit (PR 25, chip call 4).  A tile visited by the wrong group, a row
# written twice or an unvisited row is off by the value itself.
GMM_TOL = 2.0 ** -7

# Logits of a depth-2 OLMoE-width engine, grouped path against the dense
# all-experts composition on the same bf16 weights, over the largest logit.
# The two sum the same bf16 products in float32 in another order and round
# the expert outputs to bf16 at different points (per routed row against
# per expert and token), so activations differ by bf16 roundings, and where
# a token's 8th and 9th router probabilities lie within such a rounding the
# second layer routes it differently in the two runs.  About twice what the
# v5e reads (0.0062-0.0071 in four calls); top-8 weights renormalised on one
# side, the smallest fault of the router there is, read 0.687 (PR 25, chip
# call 4), and a wrong sort, offset or combine weight is no smaller.
MOE_LOGIT_TOL = 0.015

# Share of rows for which ``moe_router`` on FLOAT32 activations and router
# weights (values that are no bf16 values) picks the same top-k set as
# float64 on the host.  On the v5e 1.00000 of 4352 rows; the same GEMM at
# the TPU's default precision (operands rounded to bf16) 0.98047 (PR 25,
# chip call 4).  The benchmark's ``correct`` cannot see the router's
# precision: a bf16 engine's activations and weights are bf16 values, so
# both precisions give the same logits there, bit for bit.  A float32
# engine on the chip is what this floor guards.
ROUTER_AGREE_FLOOR = 0.999

# Logits of the one-period Qwen3-Next engine (bf16) against the float32
# token-by-token reference on the same weights, over the largest reference
# logit: the benchmark's own limit for a bf16 engine (``LOGIT_TOL`` of
# ``benchmark/runners/serve_ragged.py``).  A state slot handed to the wrong
# sequence, a convolution tail dropped at a chunk boundary or a slot reused
# without its reset moves the logits by their full scale.
GDN_LOGIT_TOL = 0.03

_ATTENTION_KERNELS = ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel",
                      "_kernel", "_prefill_kernel", "_decode_kernel",
                      "_verify_kernel")


class SmokeFailure(RuntimeError):
    """A phase ran to the end and its result is wrong."""


# --------------------------------------------------------------------- #
# Sizes
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class SmokeSizes:
    """Everything that differs between the chip run and the CPU dry run."""

    model_config: Any                 # LlamaConfig (Mistral family)
    train_layers: int
    train_seq: int
    train_steps: int                  # after the warm-up step
    serve_layers: int
    block_size: int
    token_budget: int
    max_seqs: int
    prompt_lens: Sequence[int]
    new_tokens: Sequence[int]
    check_prompt_len: int
    # the moe phase: a MixtralConfig at the published OLMoE widths (depth as
    # given), the grouped GEMM's row counts, the engine's prompt
    moe_config: Any = None
    moe_gmm_rows: Sequence[int] = (256, 4352)
    moe_prompt_len: int = 300
    moe_new_tokens: int = 4
    # the gdn phase: the published Qwen3-Next keys (depth, experts held and
    # vocabulary as given), its two prompts and what each generates
    gdn_hf: Any = None
    gdn_prompt_lens: Sequence[int] = (1500, 300)
    gdn_new_tokens: Sequence[int] = (6, 12)
    # the mla phase: the published Moonlight keys (depth and vocabulary as
    # given); prompts and new tokens as the gdn phase's
    mla_hf: Any = None
    # the conv phase: the published LFM2 keys (depth and vocabulary as
    # given); prompts and new tokens as the gdn phase's
    conv_hf: Any = None


def chip_sizes(n_devices: int) -> SmokeSizes:
    """Published Mistral-7B-v0.1 widths; only depth is cut.

    Training keeps fp32 master weights, two Adam moments, the fp32 gradient
    accumulator and the bf16 compute copy: 18 bytes a parameter.  A layer is
    218M parameters and embedding plus head 262M, so one chip's 15.75 GiB
    holds one layer (8.6 GB of state plus step temporaries; two layers need
    12.6 GB before any activation).  With the state sharded over four chips
    (ZeRO-3 x 2-way tensor parallel) six layers are 7.1 GB a chip.

    Serving holds bf16 weights only: 16 layers are 7.5 GB, and the KV pool
    (64 KB a token at that depth) takes a good part of the rest.
    """
    import jax.numpy as jnp

    from deepspeed_tpu.models.mistral import MistralConfig
    from deepspeed_tpu.models.mixtral import MixtralConfig

    with open(os.path.join(_HERE, "benchmark", "configs",
                           "qwen3-next-80b-a3b-serve-1chip.json")) as f:
        # every width as published; one period, 16 of the 512 experts held,
        # an eighth of the vocabulary slice
        gdn_hf = dict(json.load(f), num_hidden_layers=4, num_experts=16,
                      vocab_size=4748)
    with open(os.path.join(_HERE, "benchmark", "configs",
                           "moonlight-16b-a3b-serve-1chip.json")) as f:
        # every width as published; the dense layer and two routed ones, an
        # eighth of the vocabulary slice
        mla_hf = dict(json.load(f), num_hidden_layers=3, vocab_size=5120)
    with open(os.path.join(_HERE, "benchmark", "configs",
                           "lfm2-24b-a2b-serve-1chip.json")) as f:
        # every width and every expert as published; the two dense layers,
        # an attention layer and a convolution layer behind routers, an
        # eighth of the vocabulary
        conv_hf = json.load(f)
        conv_hf = dict(conv_hf, num_hidden_layers=4, vocab_size=8192,
                       layer_types=conv_hf["layer_types"][:4])
    return SmokeSizes(
        gdn_hf=gdn_hf, mla_hf=mla_hf, conv_hf=conv_hf,
        moe_config=MixtralConfig.olmoe_1b_7b(num_hidden_layers=2,
                                             dtype=jnp.bfloat16),
        model_config=MistralConfig(dtype=jnp.bfloat16),
        train_layers=1 if n_devices == 1 else 6,
        train_seq=4096, train_steps=6,
        serve_layers=16, block_size=128, token_budget=1024, max_seqs=8,
        prompt_lens=(128, 384, 640, 1024, 1536, 2048, 2560, 3000),
        new_tokens=(64, 48, 32, 64, 48, 32, 64, 48),
        check_prompt_len=512)


# --------------------------------------------------------------------- #
# Device, compile clock, memory
# --------------------------------------------------------------------- #
def describe_device(require_chip: bool = True) -> Dict[str, Any]:
    """Print what JAX found; refuse to go on without a TPU."""
    from importlib import metadata

    import jax
    import jaxlib

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']} count={device['count']} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}", flush=True)
    if require_chip:
        from deepspeed_tpu.utils.platform import require_tpu

        require_tpu("chip_smoke")
    return device


class CompileClock:
    """Seconds JAX spent building executables (compiling, or loading them
    from the persistent cache) and how many, since the last ``take``: the
    totals of the program's one ``jax.monitoring`` listener
    (``deepspeed_tpu.observability.tracer.build_totals``), subtracted."""

    def __init__(self):
        self._taken = self._totals()

    @staticmethod
    def _totals():
        from deepspeed_tpu.observability.tracer import build_totals

        totals = build_totals()
        return totals["backend_seconds"], totals["programs"]

    def take(self) -> Dict[str, Any]:
        now = self._totals()
        out = {"compile_s": round(now[0] - self._taken[0], 2),
               "programs_built": now[1] - self._taken[1]}
        self._taken = now
        return out


def memory_report(devices) -> Optional[Dict[str, List[int]]]:
    """``bytes_in_use`` / ``peak_bytes_in_use`` per device (the peak is the
    process's high-water mark so far, not this phase's alone); None where
    the backend keeps no statistics (CPU)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return {"bytes_in_use": [int(s["bytes_in_use"]) for s in stats],
            "peak_bytes_in_use": [int(s["peak_bytes_in_use"])
                                  for s in stats]}


def _release(devices, what: str) -> None:
    """After a phase dropped its arrays: nothing large may stay behind, or
    the next phase inherits a smaller chip."""
    gc.collect()
    mem = memory_report(devices)
    if mem is None:
        return
    limit = devices[0].memory_stats()["bytes_limit"]
    left = max(mem["bytes_in_use"])
    print(f"chip_smoke: after {what}: {left / 2**20:.0f} MiB still in use",
          flush=True)
    if left > 0.05 * limit:
        raise SmokeFailure(
            f"{left} bytes still allocated after the {what} phase dropped "
            f"its arrays — something holds device memory")


def shard_report(tree, devices, what: str) -> Dict[str, Any]:
    """Bytes of ``tree`` each device holds.  Every device must hold some,
    about equally, and the shards must add up to roughly the logical size
    (a tree replicated everywhere, or left on the first device, fails)."""
    import jax

    per_dev = {d.id: 0 for d in devices}
    logical = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        logical += leaf.nbytes
        for sh in leaf.addressable_shards:
            if sh.device.id in per_dev:
                per_dev[sh.device.id] += sh.data.nbytes
    held = list(per_dev.values())
    report = {"logical_bytes": int(logical), "per_device_bytes": held}
    if len(devices) > 1:
        if min(held) == 0:
            raise SmokeFailure(f"{what}: a device holds no shard: {held}")
        if max(held) > 2 * min(held):
            raise SmokeFailure(f"{what}: shards unbalanced: {held}")
        if sum(held) > 1.5 * logical:
            raise SmokeFailure(
                f"{what}: {sum(held)} bytes held for {logical} logical — "
                f"replicated, not sharded")
    return report


def _balanced_bytes_in_use(devices, what: str) -> None:
    mem = memory_report(devices)
    if mem is None or len(devices) == 1:
        return
    used = mem["bytes_in_use"]
    if min(used) == 0 or max(used) > 2 * min(used):
        raise SmokeFailure(f"{what}: bytes_in_use differs by more than 2x "
                           f"across devices: {used}")


def mosaic_kernel_names(lowered_text: str) -> List[str]:
    """The kernel function's name at every Mosaic call site of a lowered
    program, in text order."""
    return re.findall(r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"',
                      lowered_text)


def attention_route(lowered_text: str, require_chip: bool,
                    what: str) -> Dict[str, int]:
    """Which attention kernels a lowered program calls through Mosaic,
    with the number of call sites in the text (a jitted kernel wrapper is
    one function called from every layer, so the count is not the depth).
    Every layer has the same shapes and so takes the same route; on the
    chip that route must be a compiled Pallas kernel — the XLA composition
    and the interpreter leave no ``tpu_custom_call``."""
    route: Dict[str, int] = {}
    for n in mosaic_kernel_names(lowered_text):
        if n in _ATTENTION_KERNELS:
            route[n] = route.get(n, 0) + 1
    if require_chip and not route:
        raise SmokeFailure(
            f"{what}: no Mosaic attention call in the lowered program — "
            f"the route fell through to the XLA composition or the "
            f"interpreter")
    return route


def check_put_routes(routes: Dict[str, Dict[str, int]], max_seqs: int,
                     require_chip: bool) -> None:
    """The ``put`` programs of a serving run whose token budget is whole
    tiles: each is a two-segment program (``prefill_T<rows>_tiled``, rows =
    ``max_seqs`` single-token rows + whole tiles), mixed ticks included,
    and on the chip its tiles go through ``_prefill_kernel``, its
    single-token rows through the decode walk (``_decode_kernel``: every
    model here has a head size the walk can copy) and nothing of it through
    the token-grid ``_kernel`` (PR 21 recorded ``prefill_T1024 -> _kernel``
    for every tick with a decode in it)."""
    puts = {n: r for n, r in routes.items() if n.startswith("prefill_T")}
    tiled = {n: r for n, r in puts.items() if n.endswith("_tiled")}
    if not tiled:
        return                  # a budget that is no whole number of tiles
    if len(tiled) != len(puts):
        raise SmokeFailure(f"serve: a tiled engine ran untiled programs: "
                           f"{sorted(set(puts) - set(tiled))}")
    if not require_chip:
        return
    for name, route in tiled.items():
        rows = int(name[len("prefill_T"):-len("_tiled")])
        if "_kernel" in route or \
                (rows > max_seqs) != ("_prefill_kernel" in route):
            raise SmokeFailure(
                f"serve: {name} took the route {route}; its tiles belong "
                f"to _prefill_kernel and no row to the token-grid _kernel")
        if "_decode_kernel" not in route:
            raise SmokeFailure(
                f"serve: {name} took the route {route}; its single-token "
                f"rows belong to the decode walk, _decode_kernel")


# --------------------------------------------------------------------- #
# Phase: train
# --------------------------------------------------------------------- #
def train_phase(sizes: SmokeSizes, devices, require_chip: bool,
                clock: CompileClock) -> Dict[str, Any]:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.analysis.trace_guard import TraceGuard
    from deepspeed_tpu.models.mistral import MistralForCausalLM
    from deepspeed_tpu.ops.attention import dot_product_attention
    from deepspeed_tpu.parallel import groups

    n = len(devices)
    cfg = dataclasses.replace(sizes.model_config,
                              num_hidden_layers=sizes.train_layers)
    tp = 2 if n > 1 and n % 2 == 0 and cfg.num_key_value_heads % 2 == 0 \
        else 1
    groups.reset()
    topo = groups.initialize_mesh(model_parallel_size=tp,
                                  data_parallel_size=n // tp,
                                  devices=devices)
    ds_config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 3 if n > 1 else 1},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=MistralForCausalLM(cfg), config=ds_config, topology=topo)
    batch = engine.dp_world_size
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(batch, sizes.train_seq)).astype(np.int32)
    engine.initialize_parameters(ids, ids)

    # arithmetic, once, outside the stepping: the same weights and batch
    # through the XLA attention composition
    xla_model = MistralForCausalLM(cfg, attention_fn=functools.partial(
        dot_product_attention, implementation="xla"))
    placed = engine.shard_batch((ids, ids))
    loss_xla = float(jax.jit(
        lambda p, x, y: xla_model.apply({"params": p}, x, y))(
        engine.state["params"], *placed))

    def step():
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        return loss

    t0 = time.perf_counter()
    losses = [float(step())]                      # warm-up: compiles
    warm_s = time.perf_counter() - t0
    compile_warm = clock.take()
    t0 = time.perf_counter()
    with TraceGuard(max_compiles=0, d2h=None, label="train steps"):
        device_losses = [step() for _ in range(sizes.train_steps)]
        jax.block_until_ready(device_losses)
    steps_s = time.perf_counter() - t0
    losses += [float(x) for x in device_losses]

    if not all(np.isfinite(losses)):
        raise SmokeFailure(f"train: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"train: loss did not fall {losses}")
    if abs(losses[0] - loss_xla) > TRAIN_LOSS_TOL:
        raise SmokeFailure(
            f"train: step-0 loss {losses[0]:.5f} (kernel route) vs "
            f"{loss_xla:.5f} (XLA route) differs by more than "
            f"{TRAIN_LOSS_TOL}")
    route = attention_route(engine.lower_train_step().as_text(),
                            require_chip, "train step")

    state = {k: engine.state[k] for k in ("params", "master", "opt")}
    shards = shard_report(state, devices, "train state")
    _balanced_bytes_in_use(devices, "train")
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(engine.state["master"]))
    result = {
        "layers": sizes.train_layers, "seq": sizes.train_seq,
        "global_batch": batch, "mesh": {"data": n // tp, "model": tp},
        "zero_stage": engine.zero_stage, "params_m": round(n_params / 1e6, 1),
        "steps": len(losses), "losses": [round(x, 4) for x in losses],
        "loss_step0_xla_route": round(loss_xla, 4),
        "loss_route_diff": round(abs(losses[0] - loss_xla), 5),
        "attention_route": route,
        "warmup_step_s": round(warm_s, 2),
        "smoke_step_s": round(steps_s / sizes.train_steps, 3),
        **compile_warm,
        "state_shards": shards,
        "memory": memory_report(devices),
    }
    del engine, state, placed, device_losses, xla_model
    groups.reset()
    return result


# --------------------------------------------------------------------- #
# Phase: serve
# --------------------------------------------------------------------- #
def _seeded_bf16_params(cfg, mesh=None, model_cls=None):
    """The parameter tree of ``model_cls`` (LlamaForCausalLM unless given)
    built leaf by leaf in bf16 on the device, each leaf born with its
    tensor-parallel sharding when there is a ``mesh`` (``model.init``
    materialises float32 first, which at these widths overflows the chip
    by itself).  Kernels ~ N(0, 1/fan_in) (stacked expert matrices
    [E, in, out] by their own fan-in), embedding ~ N(0, 0.02^2), norm
    scales 1."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from deepspeed_tpu.inference.v2.model_implementations import (
        ragged_param_specs)
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    shapes = jax.eval_shape(
        (model_cls or LlamaForCausalLM)(cfg).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 8), jnp.int32))["params"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    flat_sh = [None] * len(flat) if mesh is None else [
        NamedSharding(mesh, spec)
        for spec in jax.tree_util.tree_leaves(ragged_param_specs(shapes))]

    @functools.lru_cache(maxsize=None)      # one program per leaf kind
    def maker(shape, std, sh):
        if std is None:
            return jax.jit(lambda k: jnp.ones(shape, jnp.bfloat16),
                           out_shardings=sh)
        return jax.jit(
            lambda k: (jax.random.normal(k, shape, jnp.float32)
                       * std).astype(jnp.bfloat16), out_shardings=sh)

    leaves = []
    for i, ((path, leaf), sh) in enumerate(zip(flat, flat_sh)):
        name = str(getattr(path[-1], "key", path[-1]))
        std = None if name == "scale" else \
            0.02 if name == "embedding" else leaf.shape[-2] ** -0.5
        leaves.append(maker(leaf.shape, std, sh)(
            jax.random.fold_in(jax.random.key(1), i)))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def serve_phase(sizes: SmokeSizes, devices, require_chip: bool,
                clock: CompileClock) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.ops.attention import dot_product_attention
    from deepspeed_tpu.parallel.topology import MeshTopology, ParallelDims
    from deepspeed_tpu.serving import (ContinuousBatchScheduler,
                                       RequestState, SamplingParams)

    cfg = dataclasses.replace(sizes.model_config,
                              num_hidden_layers=sizes.serve_layers)
    # tensor parallel over as many devices as the KV heads divide into
    tp = max(t for t in range(1, len(devices) + 1)
             if len(devices) % t == 0 and cfg.num_key_value_heads % t == 0)
    devices = devices[:tp]
    mesh = MeshTopology(ParallelDims(data=1, model=tp),
                        devices=devices).mesh if tp > 1 else None
    params = _seeded_bf16_params(cfg, mesh)

    bs = sizes.block_size
    max_context = -(-(max(p + g for p, g in zip(sizes.prompt_lens,
                                                 sizes.new_tokens)) + 1)
                    // bs) * bs
    per_seq = max_context // bs
    # room for every sequence at its full length: one-token rows walk the
    # blocks they hold through the manual-DMA kernel at any pool size
    num_blocks = sizes.max_seqs * per_seq + 1
    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": sizes.token_budget,
                          "max_ragged_sequence_count": sizes.max_seqs,
                          "max_context": max_context},
        "kv_cache": {"block_size": bs, "num_blocks": num_blocks},
    })
    engine = InferenceEngineV2(RaggedLlama(cfg, bs, mesh=mesh), params,
                               eng_cfg)
    params = engine.params
    setup_mem = memory_report(devices)

    # arithmetic, once, outside any timing: prefill logits of one prompt
    # through the engine against the dense model on the XLA route
    rng = np.random.default_rng(2)
    check = rng.integers(0, cfg.vocab_size,
                         size=(sizes.check_prompt_len,)).tolist()
    got = np.asarray(engine.put([10_000], [check])[10_000], np.float32)
    engine.flush([10_000])
    ref_model = LlamaForCausalLM(cfg, attention_fn=functools.partial(
        dot_product_attention, implementation="xla"))
    want = np.asarray(jax.jit(
        lambda p, x: ref_model.apply({"params": p}, x)[0, -1])(
        params, jnp.asarray([check], jnp.int32)), np.float32)
    if got.shape != (cfg.vocab_size,) or not np.all(np.isfinite(got)):
        raise SmokeFailure(f"serve: bad prefill logits {got.shape}")
    logit_err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    if logit_err > SERVE_LOGIT_TOL:
        raise SmokeFailure(
            f"serve: prefill logits differ from the XLA reference by "
            f"{logit_err:.4f} of the largest logit (> {SERVE_LOGIT_TOL})")
    del ref_model, want

    def serve_once():
        """Submit every request at once and run the scheduler dry."""
        sched = ContinuousBatchScheduler(engine)
        prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).tolist()
                   for n in sizes.prompt_lens]
        t0 = time.perf_counter()
        reqs = [sched.submit(p, SamplingParams(greedy=True,
                                               max_new_tokens=g))
                for p, g in zip(prompts, sizes.new_tokens)]
        finished = sched.run_until_idle()
        wall_s = time.perf_counter() - t0
        if len(finished) != len(reqs):
            raise SmokeFailure(f"serve: {len(finished)} of {len(reqs)} "
                               f"requests reached a terminal state")
        for req, want_n in zip(reqs, sizes.new_tokens):
            if req.state is not RequestState.FINISHED or \
                    len(req.generated) != want_n:
                raise SmokeFailure(
                    f"serve: request {req.uid} ended {req.state.value} "
                    f"({req.finish_reason}) with {len(req.generated)} of "
                    f"{want_n} tokens")
            if not all(0 <= t < cfg.vocab_size for t in req.generated):
                raise SmokeFailure(f"serve: request {req.uid} emitted a "
                                   f"token outside the vocabulary")
        snap = sched.metrics.snapshot()
        if snap["failed"] or snap["rejected"] or \
                snap["finished"] != len(reqs):
            raise SmokeFailure(f"serve: failed/rejected requests: {snap}")
        return reqs, snap, wall_s

    # the same traffic twice: the first pass meets (and compiles) every
    # program, so only the second pass's TTFT/TPOT are free of compile time
    serve_once()
    compile_cold = clock.take()
    reqs, snap, wall_s = serve_once()
    rebuilt = clock.take()["programs_built"]
    if rebuilt:
        raise SmokeFailure(f"serve: the second pass over the same traffic "
                           f"built {rebuilt} new program(s)")

    # every program the run built: which attention kernel each one calls
    routes = {}
    for key in engine.step_keys:
        name = "decode_step" if key == ("decode_step",) else \
            f"prefill_T{key[0]}" + ("_tiled" if key[1] else "")
        routes[name] = attention_route(engine.lower_step(key).as_text(),
                                       require_chip, name)
    if "decode_step" not in routes:
        raise SmokeFailure("serve: no pure-decode tick ran")
    check_put_routes(routes, sizes.max_seqs, require_chip)

    shards = {
        "params": shard_report(params, devices, "serving weights"),
        "kv_pool": shard_report(engine.state_manager.kv_cache.cache,
                                devices, "KV pool"),
    }
    _balanced_bytes_in_use(devices, "serve")
    ttft = [r.ttft for r in reqs]
    tpot = [r.tpot for r in reqs if r.tpot is not None]
    result = {
        "layers": sizes.serve_layers, "tensor_parallel": tp,
        "block_size": bs, "token_budget": sizes.token_budget,
        "max_context": max_context, "kv_blocks": num_blocks,
        "requests": 2 * len(reqs), "prompt_lens": list(sizes.prompt_lens),
        "new_tokens": list(sizes.new_tokens),
        "preemptions": int(snap["preemptions"]),
        "decode_ticks": int(snap.get("decode_ticks", 0)),
        "check_prompt_len": sizes.check_prompt_len,
        "logit_err_vs_xla": round(logit_err, 5),
        "attention_route": routes,
        "ttft_s": [round(x, 3) for x in ttft],
        "tpot_s": [round(x, 4) for x in tpot],
        "smoke_wall_s": round(wall_s, 2),
        **compile_cold,
        "state_shards": shards,
        "memory_after_setup": setup_mem,
        "memory": memory_report(devices),
    }
    del engine, params, reqs
    return result


# --------------------------------------------------------------------- #
# Phase: routed experts (one device: RaggedMixtral serves TP = 1)
# --------------------------------------------------------------------- #
def moe_phase(sizes: SmokeSizes, devices, require_chip: bool,
              clock: CompileClock) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import (
        RaggedMixtral)
    from deepspeed_tpu.inference.v2.modules.moe import moe_router
    from deepspeed_tpu.models.mixtral import MixtralForCausalLM
    from deepspeed_tpu.ops.grouped_gemm import (_pick_tiles, gmm,
                                                gmm_reference)

    cfg = sizes.moe_config
    if cfg is None or len(devices) > 1:
        # RaggedMixtral serves TP = 1: nothing here exists across chips
        return {"skipped": "no moe_config in these sizes" if cfg is None
                else "one-device phase", **clock.take()}
    e, k = cfg.num_local_experts, cfg.num_experts_per_tok
    h, f = cfg.hidden_size, cfg.intermediate_size
    rng = np.random.default_rng(4)
    cases: Dict[str, Any] = {}
    # the kernel alone: a decode tick's rows (4 an expert, several groups
    # empty, every work unit a boundary unit) and a mixed tick's
    for name, m in zip(("gmm_fwd_e64_decode", "gmm_fwd_e64_prefill"),
                       sizes.moe_gmm_rows):
        group_sizes = rng.multinomial(m, np.full(e, 1.0 / e))
        tm, tn = _pick_tiles(m, h, f, e)
        lhs = jnp.asarray(rng.standard_normal((m, h)), jnp.bfloat16)
        rhs = jnp.asarray(rng.standard_normal((e, h, f)) * h ** -0.5,
                          jnp.bfloat16)
        gs = jnp.asarray(group_sizes, jnp.int32)
        # on the chip the compiled kernel; in the dry run the interpreter
        got = np.asarray(gmm(lhs, rhs, gs, tm, tn,
                             False if require_chip else True), np.float32)
        want = np.asarray(gmm_reference(lhs, rhs, gs), np.float32)
        err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        cases[name] = {"rows": int(m), "tiles": [tm, tn],
                       "group_rows": [int(group_sizes.min()),
                                      int(group_sizes.max())],
                       "empty_groups": int((group_sizes == 0).sum()),
                       "max_err": round(err, 6)}
        if not np.isfinite(got).all() or err > GMM_TOL:
            raise SmokeFailure(f"moe: {name} differs from gmm_reference by "
                               f"{err:.4f} of its largest value "
                               f"(> {GMM_TOL}): {cases[name]}")
        del lhs, rhs, got, want

    # the router alone, float32 in: the top-k set against float64 on the host
    x = rng.standard_normal((sizes.moe_gmm_rows[-1], h)).astype(np.float32)
    wg = (rng.standard_normal((h, e)) * h ** -0.5).astype(np.float32)
    topi = np.sort(np.asarray(jax.jit(
        lambda a, b: moe_router(a, b, k, cfg.norm_topk_prob)[0])(x, wg)), -1)
    want = np.sort(np.argsort(
        -(x.astype(np.float64) @ wg.astype(np.float64)), -1)[:, :k], -1)
    agree = float(np.mean(np.all(topi == want, axis=-1)))
    cases["router_f32_agreement"] = {"rows": int(x.shape[0]),
                                     "same_top_k": round(agree, 5)}
    if agree < ROUTER_AGREE_FLOOR:
        raise SmokeFailure(
            f"moe: the router picks the float64 top-{k} set for only "
            f"{agree:.4f} of {x.shape[0]} float32 rows "
            f"(< {ROUTER_AGREE_FLOOR}): it is not computed in float32")

    # the engine: put then decode_step, grouped against the dense oracle
    class DenseOracle(RaggedMixtral):
        grouped = False

    params = _seeded_bf16_params(cfg, model_cls=MixtralForCausalLM)
    bs = sizes.block_size
    n_prompt, n_new = sizes.moe_prompt_len, sizes.moe_new_tokens
    max_context = -(-(n_prompt + n_new + 1) // bs) * bs
    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": sizes.token_budget,
                          "max_ragged_sequence_count": sizes.max_seqs,
                          "max_context": max_context},
        "kv_cache": {"block_size": bs,
                     "num_blocks": 2 * (max_context // bs) + 2}})
    ids = rng.integers(0, cfg.vocab_size, size=(n_prompt + n_new,))
    logits, routes = {}, {}
    for path, model_cls in (("grouped", RaggedMixtral),
                            ("dense", DenseOracle)):
        engine = InferenceEngineV2(model_cls(cfg, bs), params, eng_cfg)
        rows = [np.asarray(engine.put([1], [ids[:n_prompt].tolist()])[1],
                           np.float32)]
        for t in ids[n_prompt:]:
            rows.append(np.asarray(jax.device_get(
                engine.decode_step([1], [int(t)])), np.float32)[0])
        logits[path] = np.stack(rows)
        for key in engine.step_keys:
            name = "decode_step" if key == ("decode_step",) else \
                f"prefill_T{key[0]}" + ("_tiled" if key[1] else "")
            text = engine.lower_step(key).as_text()
            kernels = mosaic_kernel_names(text)
            routes[f"{path}/{name}"] = {
                "kernels": {n: kernels.count(n) for n in sorted(set(kernels))},
                # the dense composition's [E, T, F] intermediate
                # (the weights are [E, H, F]: the token rows are not H)
                "all_experts_einsum": any(
                    int(t) != h for t in re.findall(
                        rf"tensor<{e}x(\d+)x{f}x", text))}
        del engine
    for name, r in routes.items():
        grouped = name.startswith("grouped/")
        if r["all_experts_einsum"] == grouped or (
                require_chip and grouped != ("_gmm_kernel" in r["kernels"])):
            raise SmokeFailure(f"moe: {name} took the wrong expert path: "
                               f"{r}")
    err = float(np.max(np.abs(logits["grouped"] - logits["dense"]))
                / np.max(np.abs(logits["dense"])))
    if not np.isfinite(logits["grouped"]).all() or err > MOE_LOGIT_TOL:
        raise SmokeFailure(
            f"moe: grouped-path logits differ from the dense all-experts "
            f"composition by {err:.4f} of the largest logit "
            f"(> {MOE_LOGIT_TOL})")
    cases["ragged_moe_serve"] = {
        "layers": cfg.num_hidden_layers, "experts": e, "top_k": k,
        "prompt": n_prompt, "decoded": n_new,
        "logit_err_vs_dense": round(err, 5), "routes": routes}
    return {**cases, **clock.take(), "memory": memory_report(devices)}


# --------------------------------------------------------------------- #
# Phase: linear-attention layers and their state (one device)
# --------------------------------------------------------------------- #
def _interleaved_against_reference(sizes: SmokeSizes, hf, family: str):
    """Two requests of different lengths served TOGETHER through the
    scheduler on a ``benchmark/families/<family>`` engine at ``hf``'s
    widths, each one's logits against its own reference forward.  Returns
    ``(engine, serve_and_compare's result, {program: Mosaic kernels})``."""
    for path in (_HERE, os.path.join(_HERE, "tools")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from benchmark.lib import spec
    from benchmark.runners.serve_ragged import make_params
    from interleaved_logits import serve_and_compare
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    family = spec.module("families", family)
    reference = spec.module("reference", family.REFERENCE)
    bs = sizes.block_size
    max_context = -(-(max(p + g for p, g in zip(
        sizes.gdn_prompt_lens, sizes.gdn_new_tokens)) + 1) // bs) * bs
    engine = InferenceEngineV2(
        family.serve_model(hf, bs), make_params(family, hf, 5),
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": sizes.token_budget,
                              "max_ragged_sequence_count": sizes.max_seqs,
                              "max_context": max_context},
            "kv_cache": {"block_size": bs,
                         "num_blocks": 3 * (max_context // bs) + 2}}))
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, hf["vocab_size"], size=(n,)).tolist()
               for n in sizes.gdn_prompt_lens]
    out = serve_and_compare(engine, reference,
                            family.reference_params(engine.params), hf,
                            prompts, sizes.gdn_new_tokens)
    kernels = {
        "decode_step" if key == ("decode_step",) else f"T{key[0]}":
        sorted(set(mosaic_kernel_names(engine.lower_step(key).as_text())))
        for key in engine.step_keys}
    return engine, out, kernels


def _interleaved_summary(phase: str, sizes, out, kernels, devices, clock,
                         **facts) -> Dict[str, Any]:
    if max(out["gaps"]) > GDN_LOGIT_TOL:
        raise SmokeFailure(
            f"{phase}: logits of interleaved requests differ from their own "
            f"reference forwards by {out['gaps']} of the largest logit "
            f"(> {GDN_LOGIT_TOL})")
    return {**facts, "prompt_lens": list(sizes.gdn_prompt_lens),
            "logit_gaps": [round(g, 5) for g in out["gaps"]],
            "rows_compared": out["rows"], "ticks": out["ticks"],
            "kernels": kernels, **clock.take(),
            "memory": memory_report(devices)}


def gdn_phase(sizes: SmokeSizes, devices, require_chip: bool,
              clock: CompileClock) -> Dict[str, Any]:
    hf = sizes.gdn_hf
    if hf is None or len(devices) > 1:
        return {"skipped": "no gdn_hf in these sizes" if hf is None
                else "one-device phase", **clock.take()}
    engine, out, kernels = _interleaved_against_reference(
        sizes, hf, "qwen3_next")
    for name, names in kernels.items():
        if require_chip and "_gdn_step_kernel" not in names:
            raise SmokeFailure(f"gdn: {name} has no Mosaic delta-rule "
                               f"call: {names}")
    pool = engine.state_manager.state_pool
    if pool.held:
        raise SmokeFailure(f"gdn: {pool.held} state slots still held "
                           f"after every request finished")
    return _interleaved_summary(
        "gdn", sizes, out, kernels, devices, clock,
        layers=hf["num_hidden_layers"], experts_held=hf["num_experts"])


# --------------------------------------------------------------------- #
# Phase: latent attention and its two paths (one device)
# --------------------------------------------------------------------- #
def mla_phase(sizes: SmokeSizes, devices, require_chip: bool,
              clock: CompileClock) -> Dict[str, Any]:
    hf = sizes.mla_hf
    if hf is None or len(devices) > 1:
        return {"skipped": "no mla_hf in these sizes" if hf is None
                else "one-device phase", **clock.take()}
    _engine, out, kernels = _interleaved_against_reference(
        sizes, hf, "moonlight")
    for name, names in kernels.items():
        # a program with a tile segment expands and reads it too
        want = {"_latent_decode_kernel"} | (
            {"_latent_expand_kernel", "_latent_prefill_kernel"}
            if name != "decode_step" and int(name[1:]) > sizes.max_seqs
            else set())
        if require_chip and not want <= set(names):
            raise SmokeFailure(f"mla: {name} lacks Mosaic calls "
                               f"{sorted(want - set(names))}: {names}")
    return _interleaved_summary(
        "mla", sizes, out, kernels, devices, clock,
        layers=hf["num_hidden_layers"],
        experts_held=hf["n_routed_experts"])


# --------------------------------------------------------------------- #
# Phase: the gated short convolution and 64-wide heads (one device)
# --------------------------------------------------------------------- #
def conv_phase(sizes: SmokeSizes, devices, require_chip: bool,
               clock: CompileClock) -> Dict[str, Any]:
    hf = sizes.conv_hf
    if hf is None or len(devices) > 1:
        return {"skipped": "no conv_hf in these sizes" if hf is None
                else "one-device phase", **clock.take()}
    engine, out, kernels = _interleaved_against_reference(
        sizes, hf, "lfm2_moe")
    for name, names in kernels.items():
        # the one-token rows walk the flat pool row; a program with a tile
        # segment reads it through the tiled kernel too
        want = {"_decode_kernel"} | (
            {"_prefill_kernel"} if name != "decode_step"
            and int(name[1:]) > sizes.max_seqs else set())
        if require_chip and not want <= set(names):
            raise SmokeFailure(f"conv: {name} lacks Mosaic calls "
                               f"{sorted(want - set(names))}: {names}")
    pool = engine.state_manager.state_pool
    if pool.held:
        raise SmokeFailure(f"conv: {pool.held} state slots still held "
                           f"after every request finished")
    return _interleaved_summary(
        "conv", sizes, out, kernels, devices, clock,
        layers=hf["num_hidden_layers"], experts_held=hf["num_experts"])


# --------------------------------------------------------------------- #
# Phase: every other kernel
# --------------------------------------------------------------------- #
#: the self-test's cases that time a chunk read (``prefill_us``)
_CHUNK_READS = ("paged_prefill_", "latent_prefill_")


def kernels_phase(_sizes, _devices, _require_chip,
                  clock: CompileClock) -> Dict[str, Any]:
    sys.path.insert(0, os.path.join(_HERE, "tools"))
    from kernel_selftest import run_selftest

    results = run_selftest()
    cases = {k: v for k, v in results.items() if isinstance(v, dict)}
    bad = {k: v for k, v in cases.items() if not v.get("ok")}
    if bad or not results.get("ok") or not cases:
        raise SmokeFailure(f"kernel selftest: {len(bad)} of {len(cases)} "
                           f"cases not ok: {bad or results}")
    return {"cases": len(cases),
            "max_err": {k: v["max_err"] for k, v in cases.items()},
            # share held -> [blocks, decode walk us, dense XLA read us,
            # least us: the held bytes at 819 GB/s]
            "decode_read_us": {k: v["us"] for k, v in cases.items()
                               if "us" in v
                               and not k.startswith(_CHUNK_READS)},
            # cell -> first position of the chunk -> [tiled chunk read us,
            # least us: the visible pairs' dots at 197 TFLOP/s]
            "prefill_us": {k: v["us"] for k, v in cases.items()
                           if "us" in v and k.startswith(_CHUNK_READS)},
            # cell.call -> shape, tiles, live units of units, us, least us
            "gmm_share": cases.get("gmm_share", {}).get("calls"),
            **clock.take()}


PHASES = {"train": train_phase, "serve": serve_phase, "moe": moe_phase,
          "gdn": gdn_phase, "mla": mla_phase, "conv": conv_phase,
          "kernels": kernels_phase}


def run(sizes: Optional[SmokeSizes] = None, require_chip: bool = True,
        phases: Sequence[str] = tuple(PHASES)) -> Dict[str, Any]:
    """All phases in sequence in this one process (one process holds the
    chip), each dropping its arrays before the next.  ``require_chip=False``
    is for the CPU dry run in the tests; the command line cannot waive it."""
    t_start = time.perf_counter()
    device = describe_device(require_chip)

    import jax

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    print(f"chip_smoke: compile cache at {cache_dir}", flush=True)
    devices = jax.devices()
    if sizes is None:
        sizes = chip_sizes(len(devices))
    clock = CompileClock()
    summary: Dict[str, Any] = {"ok": False, "device": device,
                               "compile_cache_dir": cache_dir}
    for name in phases:
        t0 = time.perf_counter()
        out = PHASES[name](sizes, devices, require_chip, clock)
        out["phase_wall_s"] = round(time.perf_counter() - t0, 1)
        print(f"chip_smoke: {name} ok: {json.dumps(out)}", flush=True)
        summary[name] = out
        _release(devices, name)
    summary["wall_s"] = round(time.perf_counter() - t_start, 1)
    summary["ok"] = True
    return summary


def main() -> int:
    summary = run()
    out_dir = os.path.join(_HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print("chip_smoke: compile seconds per phase "
          + json.dumps({p: summary[p]["compile_s"]
                        for p in PHASES})
          + f", wall {summary['wall_s']} s", flush=True)
    print(json.dumps({"ok": True, "device": summary["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

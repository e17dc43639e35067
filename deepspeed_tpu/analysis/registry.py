"""Kernel self-description registry for the Pallas contract checker.

Each kernel module registers one or more *cases* — zero-argument
callables that invoke the module's ``pallas_call`` plumbing at a
representative shape (the same parameter grids
``tools/kernel_selftest.py`` exercises on the real chip). The checker
runs a case under its capture context (``pallas_call`` is intercepted,
no kernel body executes, no Mosaic compile happens) and validates every
captured call against the TPU block/tiling/coverage/VMEM contracts.

The registry is dependency-light on purpose: kernel modules import only
this file, and the checker imports the kernel modules — so registering a
case costs the op module nothing at import time.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, FrozenSet, Iterable

#: name -> case; populated by the ``@pallas_kernel_case`` decorators at
#: kernel-module import time
KERNEL_CASES: "Dict[str, KernelCase]" = {}

#: modules the checker imports to populate the registry — every file
#: with a ``pallas_call`` site must appear here (the checker also
#: AST-scans the package and flags any site no registered case reaches)
KERNEL_MODULES = (
    "deepspeed_tpu.ops.flash_attention",
    "deepspeed_tpu.ops.grouped_gemm",
    "deepspeed_tpu.ops.gated_delta_rule",
    "deepspeed_tpu.ops.selective_scan",
    "deepspeed_tpu.ops.ssd",
    "deepspeed_tpu.ops.quantized_matmul",
    "deepspeed_tpu.ops.quantizer",
    "deepspeed_tpu.ops.block_sparse_attention",
    "deepspeed_tpu.ops.evoformer_attn",
    "deepspeed_tpu.inference.v2.kernels.blocked_flash",
    "deepspeed_tpu.inference.v2.kernels.latent_flash",
    "deepspeed_tpu.inference.v2.kernels.sparse_latent",
    # (no ``pallas_call`` site: the cases of the route over blocked_flash)
    "deepspeed_tpu.inference.v2.modules.attention",
)

#: default per-call VMEM budget estimate ceiling — v5e VMEM is 16 MiB;
#: leave headroom for Mosaic's own temporaries
DEFAULT_VMEM_LIMIT = 16 * 1024 * 1024


@dataclasses.dataclass
class KernelCase:
    name: str
    fn: Callable[[], None]
    vmem_limit: int = DEFAULT_VMEM_LIMIT
    #: rule names waived for this case (e.g. {"pallas-uncovered-tile"}
    #: for kernels whose contract legitimately leaves blocks unwritten)
    allow: FrozenSet[str] = frozenset()
    note: str = ""


def pallas_kernel_case(name: str, *, vmem_limit: int = DEFAULT_VMEM_LIMIT,
                       allow: Iterable[str] = (), note: str = ""):
    """Register a representative kernel invocation with the checker.

    The decorated callable takes no arguments; it builds inputs and
    calls the kernel entry points. It only ever runs inside the
    checker's capture context — never in production code paths.
    """
    def deco(fn: Callable[[], None]) -> Callable[[], None]:
        KERNEL_CASES[name] = KernelCase(
            name=name, fn=fn, vmem_limit=vmem_limit,
            allow=frozenset(allow), note=note)
        return fn
    return deco

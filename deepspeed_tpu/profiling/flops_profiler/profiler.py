"""Flops profiler (reference: profiling/flops_profiler/profiler.py:28).

The reference monkey-patches ``torch.nn.functional`` to count MACs as the
model executes eagerly. Under XLA the compiler already knows the exact FLOP
count of the lowered program — ``Compiled.cost_analysis()`` — so the TPU
profiler asks the compiler instead of shadow-executing Python. This is both
exact (post-fusion, includes the backward when profiling the train step)
and free (no hooks on the hot path).

Two surfaces, mirroring the reference:

* ``FlopsProfiler(ds_engine=engine)`` — attached by the engine when
  ``flops_profiler.enabled``; profiles the engine's own jitted train
  micro-program at ``profile_step``.
* ``get_model_profile(fn, args)`` — standalone: lower+compile any jittable
  callable and report (flops, macs, params).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from deepspeed_tpu.utils.logging import log_dist, logger


def _cost_analysis(compiled) -> Dict[str, float]:
    try:
        return dict(compiled.cost_analysis() or {})
    except Exception as e:  # pragma: no cover
        logger.warning(f"cost_analysis unavailable: {e}")
        return {}


def flops_of(fn: Callable, *args, static_argnums=(), **kwargs) -> float:
    """Exact FLOPs of ``fn`` as XLA will execute it (0.0 if unavailable)."""
    lowered = jax.jit(fn, static_argnums=static_argnums).lower(*args, **kwargs)
    return float(_cost_analysis(lowered.compile()).get("flops", 0.0))


# --------------------------------------------------------------------- #
# Per-module attribution (reference profiler.py's per-module tree — what
# users actually read, and what the autotuner's cost model consumes).
# The reference builds it from nn.Module hooks; here the MODULE NAME
# STACK travels with every jaxpr equation (flax pushes a named scope per
# module), so a pre-lowering jaxpr walk attributes each dot/conv's FLOPs
# to the module that issued it — including through pjit/remat/scan
# sub-jaxprs (scan bodies multiply by trip count).
# --------------------------------------------------------------------- #
def _dot_flops(eqn) -> float:
    lhs_contract = eqn.params["dimension_numbers"][0][0]
    lhs = eqn.invars[0].aval
    out = eqn.outvars[0].aval
    k = 1
    for d in lhs_contract:
        k *= lhs.shape[d]
    return 2.0 * float(np.prod(out.shape, dtype=np.float64)) * k


def _conv_flops(eqn) -> float:
    rhs = eqn.invars[1].aval                 # kernel
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    spatial_and_in = [rhs.shape[d] for d in dn.rhs_spec[1:]]
    k = 1
    for s in spatial_and_in:
        k *= s
    return 2.0 * float(np.prod(out.shape, dtype=np.float64)) * k


def _sub_jaxprs(eqn):
    """(jaxpr, multiplier) pairs nested in an equation (branches handled
    separately by the visitor — only one executes)."""
    p = eqn.params
    if "jaxpr" in p:                         # pjit / closed_call / remat
        j = p["jaxpr"]
        yield (j.jaxpr if hasattr(j, "jaxpr") else j), 1
    if "call_jaxpr" in p:
        j = p["call_jaxpr"]
        yield (j.jaxpr if hasattr(j, "jaxpr") else j), 1
    if "body_jaxpr" in p:
        yield p["body_jaxpr"].jaxpr, 1
    if "cond_jaxpr" in p:
        yield p["cond_jaxpr"].jaxpr, 1


def per_module_flops(fn: Callable, *args, **kwargs) -> Dict[str, float]:
    """Attribute matmul/conv FLOPs of ``fn(*args)`` to the flax module
    path (name stack) that issued them.  Returns {module_path: flops};
    '' collects top-level ops outside any named module.  cond/switch
    count the single most expensive branch (exactly one executes)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)

    def visit(jaxpr, mult: float, acc: Dict[str, float]):
        for eqn in jaxpr.eqns:
            flops = 0.0
            if eqn.primitive.name == "dot_general":
                flops = _dot_flops(eqn)
            elif eqn.primitive.name == "conv_general_dilated":
                flops = _conv_flops(eqn)
            if flops:
                name = str(eqn.source_info.name_stack)
                acc[name] = acc.get(name, 0.0) + flops * mult
            sub_mult = mult
            if eqn.primitive.name == "scan":
                sub_mult = mult * eqn.params.get("length", 1)
            if "branches" in eqn.params:     # exactly one branch runs
                per_branch = []
                for br in eqn.params["branches"]:
                    b_acc: Dict[str, float] = {}
                    visit(br.jaxpr if hasattr(br, "jaxpr") else br,
                          sub_mult, b_acc)
                    per_branch.append(b_acc)
                if per_branch:
                    biggest = max(per_branch,
                                  key=lambda a: sum(a.values()))
                    for k, v in biggest.items():
                        acc[k] = acc.get(k, 0.0) + v
            for sub, m2 in _sub_jaxprs(eqn):
                visit(sub, sub_mult * m2, acc)

    acc: Dict[str, float] = {}
    visit(closed.jaxpr, 1.0, acc)
    return acc


def module_tree(per_module: Dict[str, float], depth: int = -1
                ) -> Dict[str, float]:
    """Roll leaf name-stack paths up to ``depth`` levels (-1 = leaves)."""
    if depth < 0:
        return dict(per_module)
    out: Dict[str, float] = {}
    for name, f in per_module.items():
        key = "/".join(name.split("/")[:depth]) if name else ""
        out[key] = out.get(key, 0.0) + f
    return out


def format_module_profile(per_module: Dict[str, float], depth: int = 2,
                          top: int = 0) -> str:
    """Reference-style per-module table: flops, share of total."""
    rolled = module_tree(per_module, depth)
    total = sum(rolled.values()) or 1.0
    rows = sorted(rolled.items(), key=lambda kv: -kv[1])
    if top:
        rows = rows[:top]
    lines = [f"{'module':<44}{'flops':>14}{'share':>9}"]
    for name, f in rows:
        lines.append(f"{(name or '<top-level>'):<44}"
                     f"{flops_to_string(f):>14}{f / total:>8.1%}")
    return "\n".join(lines)


def params_of(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree)
               if hasattr(l, "shape"))


def number_to_string(num: float, units: Optional[str] = None,
                     precision: int = 2) -> str:
    if units is None:
        for scale, units in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
            if abs(num) >= scale:
                return f"{num / scale:.{precision}f} {units}"
        return f"{num:.{precision}f}"
    scale = {"T": 1e12, "G": 1e9, "M": 1e6, "K": 1e3, "": 1.0}[units]
    return f"{num / scale:.{precision}f} {units}"


def flops_to_string(flops: float, units=None, precision=2) -> str:
    return number_to_string(flops, units, precision) + "FLOPS"


def macs_to_string(macs: float, units=None, precision=2) -> str:
    return number_to_string(macs, units, precision) + "MACs"


def params_to_string(params: float, units=None, precision=2) -> str:
    return number_to_string(params, units, precision)


def duration_to_string(duration: float, units=None, precision=2) -> str:
    if units is None:
        if duration > 1:
            return f"{duration:.{precision}f} s"
        if duration * 1e3 > 1:
            return f"{duration * 1e3:.{precision}f} ms"
        return f"{duration * 1e6:.{precision}f} us"
    scale = {"s": 1.0, "ms": 1e-3, "us": 1e-6}[units]
    return f"{duration / scale:.{precision}f} {units}"


class FlopsProfiler:
    """Compiler-derived flops profile (reference profiler.py:28).

    ``start_profile()`` arms the profiler; the engine (or the user, via
    ``profile_fn``) feeds it compiled programs; ``get_total_flops()`` etc.
    read the totals; ``print_model_profile()`` emits the report.
    """

    def __init__(self, model: Any = None, ds_engine: Any = None,
                 recompute_fwd_factor: float = 0.0):
        self.model = model
        self.ds_engine = ds_engine
        self.recompute_fwd_factor = recompute_fwd_factor
        self.started = False
        self.reset_profile()

    # -- lifecycle ---------------------------------------------------- #
    def reset_profile(self):
        self._flops = 0.0
        self._duration = 0.0
        self._params = 0
        self._per_program: Dict[str, Dict[str, float]] = {}
        self._per_module: Dict[str, float] = {}

    def start_profile(self, ignore_list=None):
        del ignore_list
        self.reset_profile()
        self.started = True
        if self.ds_engine is not None and \
                getattr(self.ds_engine, "state", None) is not None:
            self._params = params_of(self.ds_engine.state["params"])
        elif self.model is not None:
            self._params = params_of(self.model)

    def stop_profile(self):
        self.started = False

    def end_profile(self):
        self.started = False
        self.reset_profile()

    # -- accounting --------------------------------------------------- #
    def profile_compiled(self, name: str, compiled, duration: float = 0.0,
                         calls: int = 1):
        """Record an XLA-compiled program's cost (engine hook)."""
        ca = _cost_analysis(compiled)
        flops = float(ca.get("flops", 0.0)) * calls
        self._per_program[name] = {
            "flops": flops,
            "bytes accessed": float(ca.get("bytes accessed", 0.0)) * calls,
            "duration": duration,
        }
        self._flops = sum(p["flops"] for p in self._per_program.values())
        self._duration += duration

    def profile_fn(self, fn: Callable, *args, name: str = "fn", **kwargs):
        """Lower/compile ``fn``, time one execution, record its cost —
        including the per-module attribution (name-stack jaxpr walk)."""
        compiled = jax.jit(fn).lower(*args, **kwargs).compile()
        # monotonic clock + block on the result before stopping it
        # (dslint timing-no-block: time.time can step backwards)
        t0 = time.perf_counter()
        out = compiled(*args, **kwargs)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        self.profile_compiled(name, compiled, duration=dt)
        try:
            self._per_module = per_module_flops(fn, *args, **kwargs)
        except Exception as e:  # pragma: no cover — attribution is best-
            self._per_module = {}  # never report a stale fn's profile
            logger.warning(f"per-module attribution failed: {e}")  # effort
        return out

    def get_module_profile(self, depth: int = -1) -> Dict[str, float]:
        """Per-module flops of the last ``profile_fn`` call (reference
        per-module tree; {} until a fn has been profiled)."""
        return module_tree(getattr(self, "_per_module", {}), depth)

    # -- reference getters -------------------------------------------- #
    def get_total_flops(self, as_string: bool = False):
        f = self._flops * (1.0 + self.recompute_fwd_factor)
        return flops_to_string(f) if as_string else f

    def get_total_macs(self, as_string: bool = False):
        m = self.get_total_flops() / 2.0
        return macs_to_string(m) if as_string else m

    def get_total_duration(self, as_string: bool = False):
        return duration_to_string(self._duration) if as_string \
            else self._duration

    def get_total_params(self, as_string: bool = False):
        return params_to_string(self._params) if as_string else self._params

    def print_model_profile(self, profile_step: int = 1, module_depth: int = -1,
                            top_modules: int = 1, detailed: bool = True,
                            output_file: Optional[str] = None):
        lines = [
            "-" * 60,
            "DeepSpeed-TPU Flops Profiler (XLA cost analysis)",
            f"profile step:                   {profile_step}",
            f"params:                         {self.get_total_params(True)}",
            f"fwd+bwd flops per step:         {self.get_total_flops(True)}",
            f"fwd+bwd MACs per step:          {self.get_total_macs(True)}",
            f"measured duration:              {self.get_total_duration(True)}",
        ]
        if getattr(self, "_per_module", None):
            lines.append("-" * 60)
            lines.append("per-module flops (name-stack attribution):")
            lines.append(format_module_profile(
                self._per_module,
                depth=(module_depth if module_depth and module_depth > 0
                       else 2),
                # detailed -> full breakdown; summary -> top rows only
                top=0 if detailed else max(top_modules, 1)))
        if self._duration > 0:
            lines.append(
                f"achieved:                       "
                f"{flops_to_string(self.get_total_flops() / self._duration)}")
        if detailed:
            for name, p in self._per_program.items():
                lines.append(
                    f"  {name}: {flops_to_string(p['flops'])}, "
                    f"{number_to_string(p['bytes accessed'])}B accessed, "
                    f"{duration_to_string(p['duration'])}")
        lines.append("-" * 60)
        report = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(report + "\n")
        else:
            log_dist(report, ranks=[0])
        return report


def get_model_profile(model: Callable, args: Tuple = (), kwargs: Dict = None,
                      print_profile: bool = True, detailed: bool = True,
                      warm_up: int = 1, as_string: bool = True,
                      output_file: Optional[str] = None,
                      ignore_modules=None):
    """Standalone profile of a jittable callable (reference
    profiler.py ``get_model_profile``): returns (flops, macs, params)."""
    del ignore_modules
    kwargs = kwargs or {}
    prof = FlopsProfiler()
    prof.start_profile()
    compiled = jax.jit(model).lower(*args, **kwargs).compile()
    for _ in range(max(0, warm_up)):
        jax.block_until_ready(compiled(*args, **kwargs))
    t0 = time.perf_counter()
    out = compiled(*args, **kwargs)
    jax.block_until_ready(out)
    prof.profile_compiled("model", compiled,
                          duration=time.perf_counter() - t0)
    # count params: any array-leaf argument that looks like a weight tree
    prof._params = params_of(args) + params_of(kwargs)
    if print_profile:
        prof.print_model_profile(detailed=detailed, output_file=output_file)
    flops, macs, params = (prof.get_total_flops(), prof.get_total_macs(),
                           prof.get_total_params())
    if as_string:
        return (flops_to_string(flops), macs_to_string(macs),
                params_to_string(params))
    return flops, macs, params

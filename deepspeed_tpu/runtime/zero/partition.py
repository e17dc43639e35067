"""ZeRO as sharding policy (reference: runtime/zero/stage_1_and_2.py:96,
stage3.py:72, partition_parameters.py:734).

The reference implements ZeRO with per-parameter flattening, bucketing,
gradient hooks, and prefetch machinery because torch has no compiler-visible
sharding. On TPU the same *capability* is a set of ``PartitionSpec`` policies
over the ZeRO mesh axes ``('dout','data','seq','expert')``:

=====  ===================  ===================  =====================
stage  optimizer state      gradients            parameters
=====  ===================  ===================  =====================
0      replicated           all-reduced (repl.)  replicated
1      sharded              all-reduced (repl.)  replicated
2      sharded              reduce-scattered     replicated
3      sharded              reduce-scattered     sharded (gathered on use)
=====  ===================  ===================  =====================

Handing the optimizer-state and gradient specs to ``jit`` as in/out
shardings makes XLA emit the reference's pattern for them (reduce-scatter of
grads into sharded state), with the latency-hiding scheduler playing the role
of the reference's prefetch coordinator
(zero/partitioned_param_coordinator.py:58) and bucketer (stage_1_and_2.py:888).

**The stage-3 parameter spec alone does not.**  A spec says where an array is
stored, not that it is gathered before use.  ``param_specs`` adds the ZeRO
axes to a dimension of each kernel (``q_proj`` ``P(None, 'model')`` becomes
``P('data', 'model')``: the contraction dimension over ``data``), and GSPMD
reads that as two-dimensional tensor parallelism: it leaves the weight where
it is and moves the *activations* to it.  The Mistral-7B ZeRO-3 x TP step on
a 2 x 2 mesh compiled to 72 ``all-to-all``s (the float32 residual stream
turned from batch-over-``data`` to hidden-over-``data`` inside every RMSNorm
and back, 54 ms of a 298 ms step on a v5e) and gathers of ``[batch, seq, ...]``
activations; the same happens on a pure ``data`` mesh.  So the engine says
"gathered on use" itself: ``param_use_specs`` is the spec a parameter has
while it is used (its TP base spec), and ``engine._make_micro_grads``
constrains every stage-3 leaf to it inside the ``zero/gather`` scope.  The
compiled step then all-gathers weights only, activations keep the Megatron
placement (batch over ``data``, heads / intermediate over ``model``) through
every layer, and each weight gradient is reduce-scattered to ``grad_specs``.

``param_persistence_threshold`` keeps small params replicated even at stage 3,
mirroring the reference's persistence heuristic
(partition_parameters.py persistence thresholds).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import GROUP_ALIASES, MeshTopology

ZERO_AXES: Tuple[str, ...] = GROUP_ALIASES["zero"]  # ('dout','data','seq','expert')


def _axis_sizes(topology: MeshTopology, axes: Tuple[str, ...]) -> int:
    return math.prod(topology.get_dim(a) for a in axes)


def _spec_entry_axes(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def shard_leaf_spec(shape: Tuple[int, ...], base_spec: Optional[P],
                    topology: MeshTopology,
                    zero_axes: Tuple[str, ...] = ZERO_AXES,
                    min_size: int = 0) -> P:
    """Add ZeRO axes to a (possibly TP-presharded) param's PartitionSpec.

    Picks the largest dim whose per-shard size is divisible by the ZeRO group
    size, preferring dims not already sharded; small params below ``min_size``
    stay at their base spec (persistence threshold).
    """
    zero_size = _axis_sizes(topology, zero_axes)
    if zero_size == 1 or int(np.prod(shape)) < max(1, min_size):
        return base_spec if base_spec is not None else P()

    base = tuple(base_spec) if base_spec is not None else ()
    base = base + (None,) * (len(shape) - len(base))
    used_axes = set(a for e in base for a in _spec_entry_axes(e))
    if any(a in used_axes for a in zero_axes):
        return P(*base)  # already sharded over a zero axis

    # Candidate dims: per-shard size divisible by zero group size.
    def shard_factor(entry) -> int:
        return _axis_sizes(topology, _spec_entry_axes(entry))

    candidates = []
    for d, size in enumerate(shape):
        local = size // shard_factor(base[d])
        if local % zero_size == 0 and local > 0:
            # prefer unsharded dims, then larger dims
            candidates.append((base[d] is None, local, d))
    if not candidates:
        return P(*base)
    _, _, dim = max(candidates)
    new = list(base)
    new[dim] = _spec_entry_axes(base[dim]) + tuple(zero_axes)
    if len(new[dim]) == 1:
        new[dim] = new[dim][0]
    return P(*new)


def _map_specs(tree_shapes, base_specs, fn: Callable) -> Any:
    if base_specs is None:
        base_specs = jax.tree.map(lambda _: None, tree_shapes)
    return jax.tree.map(fn, tree_shapes, base_specs,
                        is_leaf=lambda x: x is None or isinstance(x, P))


class ZeroShardings:
    """Per-stage sharding policy for every component of train state.

    ``param_axes`` / ``master_axes`` / ``grad_axes`` override the zero group
    per component — the ZeRO++ hpZ secondary partition shards *params* over
    the intra-node sub-group only (reference utils/groups.py:505), and MiCS
    confines *all* state to the sub-group (zero/mics.py), replicating over
    the outer ``dout`` axis.
    """

    def __init__(self, stage: int, topology: MeshTopology,
                 param_persistence_threshold: int = 0,
                 zero_axes: Tuple[str, ...] = ZERO_AXES,
                 param_axes: Optional[Tuple[str, ...]] = None,
                 master_axes: Optional[Tuple[str, ...]] = None,
                 grad_axes: Optional[Tuple[str, ...]] = None):
        self.stage = stage
        self.topology = topology
        self.zero_axes = zero_axes
        self.param_axes = param_axes if param_axes is not None else zero_axes
        self.master_axes = master_axes if master_axes is not None else zero_axes
        self.grad_axes = grad_axes if grad_axes is not None else zero_axes
        self.persistence_threshold = param_persistence_threshold

    def _sharded(self, shapes, base_specs, min_size=None, axes=None):
        min_size = self.persistence_threshold if min_size is None else min_size
        axes = self.zero_axes if axes is None else axes

        def fn(shape_leaf, base):
            shape = tuple(shape_leaf.shape) if hasattr(shape_leaf, "shape") \
                else tuple(shape_leaf)
            return shard_leaf_spec(shape, base, self.topology, axes,
                                   min_size=min_size)

        return _map_specs(shapes, base_specs, fn)

    def _base(self, shapes, base_specs):
        def fn(_shape, base):
            return base if base is not None else P()

        return _map_specs(shapes, base_specs, fn)

    # ------------------------------------------------------------------ #
    def param_specs(self, shapes, base_specs=None):
        """Compute-precision parameters (the model's working copy) as they
        are STORED between uses."""
        if self.stage >= 3:
            return self._sharded(shapes, base_specs, axes=self.param_axes)
        return self._base(shapes, base_specs)

    def param_use_specs(self, shapes, base_specs=None):
        """Compute-precision parameters while they are USED: gathered over
        the ZeRO axes, so only the TP base spec is left (what
        ``param_specs`` returns below stage 3).  The engine constrains each
        stage-3 leaf to this inside its ``zero/gather`` scope."""
        return self._base(shapes, base_specs)

    def master_specs(self, shapes, base_specs=None):
        """fp32 master weights + optimizer moments (no persistence floor —
        the reference shards *all* optimizer state from stage 1)."""
        if self.stage >= 1:
            return self._sharded(shapes, base_specs, min_size=0,
                                 axes=self.master_axes)
        return self._base(shapes, base_specs)

    def grad_specs(self, shapes, base_specs=None):
        """Accumulated gradients: sharded (reduce-scatter) from stage 2."""
        if self.stage >= 2:
            return self._sharded(shapes, base_specs, min_size=0,
                                 axes=self.grad_axes)
        return self._base(shapes, base_specs)

    def to_named(self, spec_tree):
        from jax.sharding import NamedSharding

        return jax.tree.map(
            lambda s: NamedSharding(self.topology.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))

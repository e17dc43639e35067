"""One fault at a time in ``RaggedDots3Note`` (``model_type: dots3_note``):
what ``test_ragged_dots3_note.py`` applies at tiny sizes on the CPU and
``benchmark/tools/calls/pr61_faults.py`` at the published widths on the chip.

``window_minus_1`` / ``window_plus_1``: the sliding layers see 512 / 514
positions for 513 (their reads; the pool keeps its band).  ``gate_dropped``:
no gate on either kind.  ``gate_per_value``: the gate's scalars laid along a
head's VALUES (``g[d mod H]`` on value ``d`` of every head) instead of one a
head.  ``q_rescale_dropped`` / ``kv_rescale_dropped``: ``s_q`` / ``s_kv``
left out on both kinds.  ``rescales_swapped``: the query times ``s_kv``, the
latent times ``s_q``.  ``rope_bases_swapped``: each kind rotates by the
other's base.  ``window_reads_global``: a sliding layer reads through the
GLOBAL group's block table (its writes stay in its own pool).
``indexer_skipped``: a full layer reads every cached position
(``glm_dsa_faults.py``'s ``indexer_dropped``: the full layers' read is the
base class's).  ``band_released_early``: the state manager releases the
band's first block one block early, so the band's first table entry names
the trash block.
"""

import contextlib
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from glm_dsa_faults import fault as glm_fault               # noqa: E402

FAULTS = ("window_minus_1", "window_plus_1", "gate_dropped",
          "gate_per_value", "q_rescale_dropped", "kv_rescale_dropped",
          "rescales_swapped", "rope_bases_swapped", "window_reads_global",
          "indexer_skipped", "band_released_early")


@contextlib.contextmanager
def fault(name: str, block: int = 128):
    """The program with one fault in it, for engines built and run inside
    the block."""
    from deepspeed_tpu.inference.v2.model_implementations import (
        ragged_deepseek_v3 as base_mod, ragged_dots3_note as model_mod)
    from deepspeed_tpu.inference.v2.ragged import ragged_manager

    if name == "indexer_skipped":
        with glm_fault("indexer_dropped", block):
            yield
        return
    cls, cfg_cls = model_mod.RaggedDots3Note, model_mod.Dots3NoteConfig
    real_init = cls.__init__
    real_swa = cfg_cls.swa
    patches = []
    if name in ("window_minus_1", "window_plus_1"):
        def init(self, *a, **k):
            real_init(self, *a, **k)
            self._swa.window += -1 if name == "window_minus_1" else 1
        patches.append((cls, "__init__", init))
    elif name == "gate_dropped":
        patches.append((base_mod.RaggedDeepseekV3, "_head_gate",
                        lambda self, att, xa, out: out))
    elif name == "gate_per_value":
        def gate(self, att, xa, out):
            dt = self.config.dtype
            g = jax.nn.sigmoid(base_mod.qmm(
                xa, att["gate_proj"]["kernel"], dt).astype(jnp.float32))
            per_value = g[:, jnp.arange(out.shape[2]) % g.shape[1]]
            return (out.astype(jnp.float32)
                    * per_value[:, None, :]).astype(dt)
        patches.append((base_mod.RaggedDeepseekV3, "_head_gate", gate))
    elif name in ("q_rescale_dropped", "kv_rescale_dropped",
                  "rescales_swapped"):
        def change(q, kv):
            if name == "q_rescale_dropped":
                return 1.0, kv
            if name == "kv_rescale_dropped":
                return q, 1.0
            return kv, q
        full_q, full_kv = cfg_cls.q_scale, cfg_cls.kv_scale
        patches += [
            (cfg_cls, "q_scale", property(lambda self: change(
                full_q.fget(self), full_kv.fget(self))[0])),
            (cfg_cls, "kv_scale", property(lambda self: change(
                full_q.fget(self), full_kv.fget(self))[1]))]

        def swa(self):
            view = real_swa.fget(self)
            view.q_scale, view.kv_scale = change(view.q_scale, view.kv_scale)
            return view
        patches.append((cfg_cls, "swa", property(swa)))
    elif name == "rope_bases_swapped":
        def init(self, config, *a, **k):
            real_init(self, config, *a, **k)
            self.config = dataclasses.replace(
                config, rope_theta=config.swa_rope_theta,
                swa_rope_theta=config.rope_theta)
        patches.append((cls, "__init__", init))
    elif name == "window_reads_global":
        def view(batch):
            return {**batch, "kv_dest": batch["kv_dest_win"]}
        patches.append((cls, "_window_view", staticmethod(view)))
    elif name == "band_released_early":
        real_first = ragged_manager.DSStateManager._window_first

        def first(self, seen_tokens):
            at = real_first(self, seen_tokens)
            return at + 1 if at > 0 else at
        patches.append((ragged_manager.DSStateManager, "_window_first",
                        first))
    elif name != "clean":
        raise ValueError(f"no fault named {name!r}")
    saved = [(obj, attr, obj.__dict__[attr]) for obj, attr, _ in patches]
    for obj, attr, new in patches:
        setattr(obj, attr, new)
    try:
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)

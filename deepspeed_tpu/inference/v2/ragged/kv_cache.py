"""Blocked (paged) KV cache (reference: inference/v2/ragged/kv_cache.py
``BlockedKVCache`` over CUDA block pools + the 2.4k-LoC compression
subsystem's KV quantization, recast TPU-native).

Device layout per layer: ``k/v: [num_blocks * block_size, Hkv*D]`` — a pool
of rows indexed by ``block_id * block_size + offset``, a token's KV heads
side by side in the lanes of its row.  That is the form BOTH Pallas
paged-attention kernels the serving cells run read as it lies (``[blocks,
block_size, Hkv*D]`` is a free split of the leading dimension; the
``[rows, Hkv, D]`` form is not, on the chip the tiled kernel paid a copy of
the whole pool a call for it), so it is the ONE stored form of a float pool
whose row is whole 128-lane tiles and whose heads are whole tiles or divide
one (:func:`flat_row`: decided from the dtype, ``Hkv`` and ``D``, no knob;
the one rule the decode walk asks too).  Any other float row (one KV head
of 64, heads of 96) keeps ``[rows, Hkv, D]`` and the reference reads.  Ragged
token writes are one scatter; per-sequence reads are one gather through the
block table.  XLA turns both into dynamic-slice/scatter fusions.

**Quantized mode** (``dtype="int8"``): the pool stores symmetric int8
payloads ``[num_blocks * block_size, Hkv, D]`` with fp32 scale records
riding ALONGSIDE in the same tree —
``k_scale/v_scale: [num_blocks * block_size, Hkv]``, one scale per pool
row per kv head (quantization group = one head's D-vector, the same
groupwise absmax/127 rule as ``ops/quantizer``'s symmetric int8 path).
Because the scales share the pool's flat row indexing, every block
operation — COW ``copy_block``, the ``gather_blocks``/``scatter_blocks``
host handoff, the host cold tier's spool/restore — moves payload and
scales together with zero special cases, and a restored block is
bit-exact.  Prefill/decode writes quantize on cache insert
(:func:`quantize_kv`); dequant happens in-kernel on the block walk
(``kernels/blocked_flash.py``), never as a separate materialized pass.

**A model-stated row** (``kv_row``, e.g. ``{"ckv": 640}`` for latent
attention, ``{"ckv": 640, "idx_k": 128}`` where a sparse-attention indexer
keeps a second, narrower row a token for its keys): instead of ``k``/``v``
per KV head a layer holds the named leaves ``[num_blocks * block_size,
lanes]``, one or several, behind the same allocator and block tables.  Every
block operation is a ``tree_map`` over pool rows and carries every such leaf
unchanged; what it cannot serve is :data:`LATENT_ROW`.
No model states a ``k`` / ``v`` row: :func:`flat_row` is the one way to the
flat pool.

**Two kinds of KV layer** (``window_layers`` / ``window_blocks``, from a
model's ``kv_groups``): the layers of the window group share a pool of their
own length, ``window_blocks`` blocks, behind the state manager's second
allocator and each sequence's second block table; every other KV layer keeps
``num_blocks``.  ``num_blocks`` stays the global group's.  **A row a group**
(``window_row``, from ``kv_groups["window"]["row"]``): the window layers keep
the leaves their group states (``{"ckv": 1152}``: a window layer whose cache
row is a latent of its own rank) and every other KV layer ``kv_row``
(``{"ckv": 640, "idx_k": 128}``), so one cache holds rows of two widths, each
in its own pool; bytes are counted by group (:attr:`BlockedKVCache.
window_layer_token_bytes`).  What two groups cannot
serve is :data:`WINDOW_GROUP`; behind those, the block operations that move
one block id across every layer (``copy_block``, ``gather_blocks``,
``scatter_blocks``) refuse: an id names different rows in the two groups.

**A cache per (layer, pass)** (``passes``, from a model's ``kv_passes``: a
stack of layers run several times a token, each pass with keys and values of
its own).  The pools keep their names and their form and grow ``passes``
times as long: ``[passes * num_blocks * block_size, ...]``, pass ``t`` in the
rows from ``t * num_blocks * block_size``.  One allocator and one block table
a sequence serve every pass, because a block id names the same offset in
each: pass ``t`` of a step program reads through ``block_tables + t *
num_blocks`` and writes at ``kv_dest + t * num_blocks * block_size``, and
both paged kernels read the pool as it lies.  A block is then ``passes *
block_size`` rows a layer (:attr:`BlockedKVCache.block_rows`), and every
block operation moves them all, pass after pass within a block, so a payload
still splits by block.  What it cannot serve is :data:`PASS_CACHES`; a
window group beside it is refused (no model states both: a pass's offset
would differ between the two pools).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

#: accepted ``kv_cache.dtype`` spellings -> pool storage dtype
KV_DTYPES = {
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "f32": jnp.float32, "fp32": jnp.float32, "float32": jnp.float32,
    "f16": jnp.float16, "float16": jnp.float16,
    "int8": jnp.int8,
}


def resolve_kv_dtype(dtype: Any):
    """Map a config string (``"bf16" | "int8" | ...``) or jnp dtype to
    the pool storage dtype."""
    if isinstance(dtype, str):
        key = dtype.lower()
        if key not in KV_DTYPES:
            raise ValueError(
                f"kv_cache dtype {dtype!r} not understood — one of "
                f"{sorted(KV_DTYPES)} (or a jnp dtype)")
        return KV_DTYPES[key]
    return dtype


def is_int8(dtype: Any) -> bool:
    """Is ``dtype`` (a config string or a jnp dtype) the quantized pool's?"""
    return jnp.dtype(resolve_kv_dtype(dtype)) == jnp.dtype(jnp.int8)


def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantize per (row, kv-head) group over the head
    vector: ``x [..., Hkv, D] -> (q int8 same shape, scale fp32 [..., Hkv])``
    with ``scale = absmax / 127`` (the ops/quantizer symmetric rule —
    deterministic, so identical tokens always produce identical cache
    content and greedy replay/restore parity is bitwise)."""
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x32 / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray,
                  dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`quantize_kv` (the XLA reference path; the hot
    Pallas kernels fuse this into their block walk instead)."""
    return (q.astype(jnp.float32)
            * scale[..., None].astype(jnp.float32)).astype(dtype)


def flat_row(dtype: Any, num_kv_heads: int, head_dim: int) -> bool:
    """Is a ``k`` / ``v`` pool of this dtype and these heads stored in the
    flat row ``[rows, Hkv*D]`` (the module doc)?  A float pool whose row is
    whole 128-lane tiles AND whose heads are whole tiles or divide one: the
    rows the decode walk reads as they lie (``blocked_flash.
    decode_walk_usable`` asks this function), so no pool is stored flat for
    a read that would then copy it back to heads (8 heads of 96: whole
    tiles a row, but a head starts inside a tile).  An int8 pool keeps its
    heads apart, beside the scale a head."""
    return (not is_int8(dtype) and (num_kv_heads * head_dim) % 128 == 0
            and (head_dim % 128 == 0 or 128 % head_dim == 0))


#: The features a cache layout may be unable to serve.  Each extension a
#: model states (``state_spec``: ``state_pool.py``; ``kv_row``, ``kv_groups``:
#: below) says which and why; ``DSStateManager.require`` is the one check.
FEATURES = (
    "prefix_cache",     # attach_prefix / register_prefix / the COW fork
    "host_tier",        # cold blocks spooled to the host, restored on attach
    "kv_handoff",       # flush_to_host(include_kv=True) / resume(kv_state=)
    "verify",           # verify_step, the speculative scheduler
    "decode_loop",      # the scanned greedy decode
    "int8_kv",          # kv_cache.dtype=int8
)


class CacheLayoutError(NotImplementedError):
    """A feature was asked of a cache layout that cannot serve it."""


#: a model-stated row (``kv_row``): (what the model keeps, feature -> why not)
LATENT_ROW = ("keeps a latent row a token in place of per-head keys and "
              "values (kv_row)", {
    "verify": "the K-rows-a-sequence verify read exists for per-head keys "
              "and values only",
    "int8_kv": "quantize_kv keeps one scale per KV head, and a latent row "
               "has no head to scale by",
})

#: two kinds of KV layer (``kv_groups``): each path assumes ONE table a sequence
WINDOW_GROUP = ("keeps window and global KV layers behind two block tables "
                "a sequence (kv_groups)", {
    "prefix_cache": "attach, register and the copy-on-write fork share and "
                    "copy blocks by ONE id a position, and a window block is "
                    "released as its first owner advances",
    "host_tier": "a block is spooled and restored by ONE id, which names "
                 "different rows in the two groups",
    "kv_handoff": "the handoff payload is the rows of one table; without it "
                  "the sequence is recomputed",
    "verify": "the K-rows-a-sequence verify read and commit_verified's "
              "block trim know one table",
    "decode_loop": "the scanned program carries one table and releases "
                   "nothing between its steps; decode_step does",
})


#: one cache per (layer, pass) (``kv_passes``): every feature is served, the
#: block operations move a block's rows in every pass (the module doc)
PASS_CACHES = ("keeps one cache per (layer, pass) behind one block table a "
               "sequence (kv_passes)", {})


class BlockedKVCache:
    def __init__(self, num_layers: int, num_blocks: int, block_size: int,
                 num_kv_heads: int, head_dim: int, dtype: Any = jnp.bfloat16,
                 kv_layers=None, kv_row=None, window_layers=(),
                 window_blocks: int = 0, passes: int = 1, window_row=None):
        #: the layers that hold keys and values (all of them, unless the
        #: model says which: its other layers keep state in slots, see
        #: ``state_pool.py``, and their leaves join ``cache`` beside these)
        self.kv_layers = tuple(range(num_layers) if kv_layers is None
                               else kv_layers)
        self.num_layers = num_layers
        self.num_blocks = num_blocks
        #: the window group (the module doc): its layers and its pool's
        #: blocks; none unless the model states ``kv_groups``
        self.window_layers = tuple(window_layers)
        self.window_blocks = int(window_blocks)
        #: caches a layer keeps, one a pass of the stack (the module doc)
        self.passes = int(passes)
        self.block_size = block_size
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        dtype = resolve_kv_dtype(dtype)
        self.dtype = dtype
        #: int8 pools carry per-row/per-head fp32 scale records in-tree
        self.quantized = is_int8(dtype)
        #: leaf name -> lanes of the row a layer keeps per token, for a
        #: model that states one (see the module doc); None: k and v
        self.kv_row = dict(kv_row) if kv_row else None
        #: the window group's own row (the module doc); None: the row (or
        #: the k / v heads) of every other KV layer
        self.window_row = dict(window_row) if window_row else None

        def layer(i):
            windowed = i in self.window_layers
            rows = block_size * (self.window_blocks if windowed
                                 else self.passes * num_blocks)
            row = (self.window_row if windowed else None) or self.kv_row
            if row:
                return {name: jnp.zeros((rows, lanes), dtype)
                        for name, lanes in row.items()}
            row = (num_kv_heads * head_dim,) \
                if flat_row(dtype, num_kv_heads, head_dim) \
                else (num_kv_heads, head_dim)
            leaves = {"k": jnp.zeros((rows,) + row, dtype),
                      "v": jnp.zeros((rows,) + row, dtype)}
            if self.quantized:
                # scale 1.0 on never-written rows: dequant of the zero
                # payload stays zero, same as the unquantized pool
                leaves["k_scale"] = jnp.ones((rows, num_kv_heads),
                                             jnp.float32)
                leaves["v_scale"] = jnp.ones((rows, num_kv_heads),
                                             jnp.float32)
            return leaves

        self.cache: Dict[str, Dict[str, jax.Array]] = {
            f"layer_{i}": layer(i) for i in self.kv_layers
        }

    # The engine threads self.cache through the jitted forward and stores the
    # updated pytree back here (functional update — no aliasing surprises).
    def update(self, new_cache) -> None:
        self.cache = new_cache

    def copy_block(self, src: int, dst: int) -> None:
        """Copy one block's KV rows ``src -> dst`` across every layer (the
        prefix cache's copy-on-write fork).  One jitted program per cache
        geometry — src/dst are traced scalars, so forking different blocks
        never recompiles; the old cache is donated (in-place on device)."""
        self._update_pools(_copy_block(
            self._pools(), jnp.int32(src), jnp.int32(dst), self.block_size,
            self._pass_rows))

    def _pools(self) -> Dict[str, Dict[str, jax.Array]]:
        """The KV layers of ``cache``: block operations move pool rows and
        leave any state slots beside them alone."""
        if self.window_layers:
            raise CacheLayoutError(
                "a block operation over every KV layer (copy_block, "
                "gather_blocks, scatter_blocks) names ONE block id, and "
                "this cache has two pools behind two block tables "
                "(kv_groups): the id means different rows in each")
        return {f"layer_{i}": self.cache[f"layer_{i}"]
                for i in self.kv_layers}

    def _update_pools(self, pools) -> None:
        self.cache = {**self.cache, **pools}

    @property
    def block_rows(self) -> int:
        """Rows of a layer's pool one block names: ``block_size`` in every
        pass; what a block is in a :meth:`gather_blocks` payload."""
        return self.passes * self.block_size

    @property
    def _pass_rows(self) -> tuple:
        """First pool row of each pass."""
        return tuple(t * self.num_blocks * self.block_size
                     for t in range(self.passes))

    def _block_rows(self, blocks) -> "jax.Array":
        """Flat pool row indices covering ``blocks`` in table order, each
        block's rows in pass 0, then in pass 1, ..."""
        import numpy as np

        base = np.asarray(blocks, np.int32)[:, None, None] * self.block_size \
            + np.asarray(self._pass_rows, np.int32)[None, :, None]
        return jnp.asarray(
            (base + np.arange(self.block_size, dtype=np.int32)).ravel())

    def gather_blocks(self, blocks) -> Dict[str, Dict[str, Any]]:
        """Pull the KV rows of ``blocks`` (one sequence's block table) to
        the host: ``{layer: {"k"/"v": np[len(blocks)*block_rows, Hkv*D]}}``
        (each leaf's rows as the pool stores them).
        One device gather + one transfer for the whole tree — the
        disaggregated prefill→decode handoff payload.  Row order follows
        the block table, so position ``p`` lives at row ``p`` regardless
        of which physical blocks held it (with ``passes`` caches a layer,
        block ``i`` of the table is rows ``[i, i + 1) * block_rows``, pass
        ``t`` of it from ``t * block_size``)."""
        rows = self._block_rows(blocks)
        return jax.device_get(
            jax.tree_util.tree_map(lambda a: a[rows], self._pools()))

    def scatter_blocks(self, blocks, host_tree) -> None:
        """Write a :meth:`gather_blocks` payload into ``blocks`` of THIS
        pool (functional update, stored back like the forward's).  Shapes
        must match this cache's geometry — a handoff between replicas of
        different model geometry is a deployment error, not a cast."""
        rows = self._block_rows(blocks)
        n = int(rows.shape[0])

        def one(a, h):
            h = jnp.asarray(h, a.dtype)
            if h.shape != (n,) + a.shape[1:]:
                raise ValueError(
                    f"scatter_blocks: payload {h.shape} does not match "
                    f"{(n,) + a.shape[1:]} (cache geometry differs)")
            return a.at[rows].set(h)

        self._update_pools(
            jax.tree_util.tree_map(one, self._pools(), host_tree))

    @property
    def per_token_bytes(self) -> int:
        """HBM bytes one cached token occupies across every layer of the
        pool ``num_blocks`` counts (all KV layers; with two groups, the
        global group's) — in int8 mode the payload byte per element PLUS
        the fp32 scale record per (row, head), so occupancy gauges and the
        roofline decode bytes model never over-report bf16 bytes under
        quantization."""
        return self.passes * self.layer_token_bytes * (
            len(self.kv_layers) - len(self.window_layers))

    @property
    def layer_token_bytes(self) -> int:
        """HBM bytes one cached token occupies in ONE cache of one KV
        layer (a layer keeps ``passes`` of them); with two groups, of a
        global layer."""
        itemsize = jnp.dtype(self.dtype).itemsize
        if self.kv_row:
            return sum(self.kv_row.values()) * itemsize
        per_head = self.head_dim * itemsize
        if self.quantized:
            per_head += 4                       # fp32 scale per (row, head)
        return 2 * self.num_kv_heads * per_head

    @property
    def window_layer_token_bytes(self) -> int:
        """HBM bytes one cached token occupies in one WINDOW layer: its
        group's own row where it states one, else a global layer's."""
        if self.window_row:
            return sum(self.window_row.values()) \
                * jnp.dtype(self.dtype).itemsize
        return self.layer_token_bytes

    @property
    def window_token_bytes(self) -> int:
        """HBM bytes one cached token occupies across the window group's
        layers (0 without one): what :attr:`per_token_bytes` is to the
        global group."""
        return len(self.window_layers) * self.window_layer_token_bytes

    @property
    def window_pool_bytes(self) -> int:
        """HBM bytes of the window group's pools (0 without one)."""
        return self.window_blocks * self.block_size * self.window_token_bytes


@partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0,))
def _copy_block(cache, src, dst, block_size: int, pass_rows=(0,)):
    def one(arr):
        for first in pass_rows:     # the block's rows in each pass
            rows = jax.lax.dynamic_slice_in_dim(
                arr, first + src * block_size, block_size, axis=0)
            arr = jax.lax.dynamic_update_slice_in_dim(
                arr, rows, first + dst * block_size, axis=0)
        return arr

    return jax.tree_util.tree_map(one, cache)

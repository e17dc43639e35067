"""PR 41, chip calls 6b: the attention calls of chip_smoke.py's `moe` phase alone (OLMoE heads, 8 slots, a table 3 entries
wide, a pool of 8 blocks): three decode steps of one live row, then the 300-token prefill behind eight pad rows, each
through the kernel route and the XLA reads.  They pass on the chip in every tree; the phase's dense oracle is what
stalls (PERF.md section 7, Opened by PR 41 (6)).  Run from the root of the checkout to measure."""
import sys, numpy as np, jax, jax.numpy as jnp
sys.path.insert(0, ".")
from deepspeed_tpu.inference.v2.model_implementations.ragged_llama import _paged_attention
bs, S, B, nb, h, hkv, d, tile = 128, 8, 3, 8, 16, 16, 128, 128
rng = np.random.default_rng(0)
pool = lambda: jnp.asarray(rng.standard_normal((nb * bs, hkv * d)), jnp.bfloat16)
kp, vp = pool(), pool()
tables = np.zeros((S, B), np.int32); tables[0] = [1, 2, 3]
interp = jax.devices()[0].platform != "tpu"
def run(name, slot, pos, **kw):
    q = jnp.asarray(rng.standard_normal((len(pos), h, d)), jnp.bfloat16)
    batch = {"block_tables": jnp.asarray(tables), "token_slot": jnp.asarray(slot, jnp.int32), "token_pos": jnp.asarray(pos, jnp.int32)}
    got = jax.jit(lambda q, k, v: _paged_attention(q, k, v, batch, bs, use_kernel=True, **kw))(q, kp, vp)
    want = _paged_attention(q, kp, vp, batch, bs, use_kernel=False, **{k: v for k, v in kw.items() if k == "decode_mode"})
    live = np.asarray(pos) >= 0
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))[live])) if live.any() else 0.0
    print(name, "max_err", err, flush=True)
# the moe phase's decode step: one live row of eight at position 300..303
for p in (300, 301, 383):
    run(f"decode pos {p}", np.arange(S), [p] + [-1] * (S - 1), decode_mode=True)
# its prefill: eight pad rows, then 300 tokens in three tiles
T = S + 3 * tile
slot = np.zeros(T, np.int32); pos = np.full(T, -1, np.int32); pos[S:S + 300] = np.arange(300)
run("prefill 300", slot, pos, prefill_tile=tile)

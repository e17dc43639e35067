"""The one table of what a cache layout cannot serve (PR 42): each extension
a model states (``state_spec``, ``kv_row``, ``kv_groups``, ``kv_passes``) lists, where it is
defined, the features it cannot serve and why; ``DSStateManager`` merges
them and ``require(feature, path)`` is the one check.  This walks the table:
every (extension, feature) entry refuses at every path behind the feature,
with one exception class, naming the path, the extension and the entry's
reason.  The model is a stub (no forward runs: every check is made before a
program is built)."""

import re
import types

import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.ragged import CacheLayoutError
from deepspeed_tpu.inference.v2.ragged.kv_cache import (FEATURES, LATENT_ROW,
                                                        PASS_CACHES,
                                                        WINDOW_GROUP)
from deepspeed_tpu.inference.v2.ragged.state_pool import STATE_SLOTS
from deepspeed_tpu.serving import ContinuousBatchScheduler, SpeculativeConfig

#: extension -> (its table, what a model states to ask for it)
EXTENSIONS = {
    "state_spec": (STATE_SLOTS, {"state_spec": {
        "layers": [0], "leaves": {"conv": ((2, 8), jnp.float32)}}}),
    "kv_row": (LATENT_ROW, {"kv_row": {"ckv": 128}}),
    "kv_groups": (WINDOW_GROUP, {"kv_groups": {
        "window": {"layers": [0], "window": 16}}}),
    # (a cache per (layer, pass): every block operation moves a block's rows
    # in every pass, so its table refuses nothing)
    "kv_passes": (PASS_CACHES, {"kv_passes": 3}),
}


class _Model:
    """What the engine reads of a model before it builds a program."""
    config = types.SimpleNamespace(dtype=jnp.float32)
    num_layers, num_kv_heads, head_dim, tp = 2, 2, 16, 1

    def __init__(self, **stated):
        self.__dict__.update(stated)


def _engine(stated, **kv):
    return InferenceEngineV2(
        _Model(**stated), {}, RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 128,
                              "max_ragged_sequence_count": 2,
                              "max_context": 64},
            "kv_cache": {"block_size": 8, "num_blocks": 17, **kv}}))


#: feature -> the paths behind it: (what the message names, the call)
PATHS = {
    "prefix_cache": [("kv_cache.enable_prefix_cache", lambda stated: _engine(
        stated, enable_prefix_cache=True))],
    "host_tier": [("kv_cache.host_tier", lambda stated: _engine(
        stated, enable_prefix_cache=True, host_tier=True,
        host_tier_bytes=1 << 20))],
    "kv_handoff": [
        ("flush_to_host(include_kv=True)", lambda stated: _engine(
            stated).flush_to_host([1], include_kv=True)),
        ("resume(kv_state=...)", lambda stated: _engine(stated).resume(
            9, list(range(8)), kv_state={"seen_tokens": 8, "kv": {}}))],
    "verify": [
        ("verify_step", lambda stated: _engine(stated).verify_step(
            [1], [[3, 4]])),
        ("speculative decoding", lambda stated: ContinuousBatchScheduler(
            _engine(stated), speculative=SpeculativeConfig()))],
    "decode_loop": [("decode_loop", lambda stated: _engine(
        stated).decode_loop([1], [3], 4))],
    "int8_kv": [("kv_cache.dtype=int8", lambda stated: _engine(
        stated, dtype="int8"))],
}

ENTRIES = [(ext, feature) for ext, (table, _) in EXTENSIONS.items()
           for feature in table[1]]


def test_the_tables_name_features_and_every_feature_has_a_path():
    for _keeps, cannot in (STATE_SLOTS, LATENT_ROW, WINDOW_GROUP,
                           PASS_CACHES):
        assert set(cannot) <= set(FEATURES)
    assert set(PATHS) == set(FEATURES)


@pytest.mark.parametrize("extension,feature", ENTRIES,
                         ids=[f"{e}-{f}" for e, f in ENTRIES])
def test_each_entry_refuses_at_its_paths_with_its_reason(extension, feature):
    (keeps, cannot), stated = EXTENSIONS[extension]
    for path, call in PATHS[feature]:
        with pytest.raises(CacheLayoutError,
                           match=re.escape(cannot[feature])) as err:
            call(stated)
        message = str(err.value)
        assert path in message and keeps in message
        assert f"({extension})" in message
        # the refusals the engine makes or passes on name the model
        assert "_Model" in message or path == "speculative decoding"


@pytest.mark.parametrize("extension", EXTENSIONS)
def test_what_an_extension_does_not_list_is_served(extension):
    (_keeps, cannot), stated = EXTENSIONS[extension]
    sm = _engine(stated).state_manager
    assert set(sm.unserved) == set(cannot)
    for feature in set(FEATURES) - set(cannot):
        sm.require(feature, "a path")


def test_plain_pools_serve_every_feature_and_two_extensions_merge():
    sm = _engine({}).state_manager
    assert sm.unserved == {}
    for feature in FEATURES:
        sm.require(feature, "a path")
    with pytest.raises(AssertionError):
        sm.require("no_such_feature", "a path")
    both = {**EXTENSIONS["state_spec"][1], **EXTENSIONS["kv_groups"][1]}
    with pytest.raises(CacheLayoutError) as err:
        _engine(both).verify_step([1], [[3, 4]])
    assert STATE_SLOTS[1]["verify"] in str(err.value)
    assert WINDOW_GROUP[1]["verify"] in str(err.value)


@pytest.mark.parametrize("feature", sorted(STATE_SLOTS[1]))
def test_a_family_with_two_state_leaves_is_refused_what_the_table_says(
        feature):
    """``RaggedJamba``'s own ``state_spec`` (26 layers of a float32 scan
    state beside a bf16 convolution tail at the published sizes; here at a
    small one) walks the state-slot table like the stub's one leaf."""
    from deepspeed_tpu.inference.v2.model_implementations.ragged_jamba \
        import JambaConfig, RaggedJamba

    spec = RaggedJamba(JambaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=2, attn_layer_period=2,
        attn_layer_offset=1, mamba_d_state=4, mamba_dt_rank=4), 8).state_spec
    assert spec["layers"] == [0] and set(spec["leaves"]) == {"ssm", "conv"}
    assert spec["leaves"]["ssm"][1] != spec["leaves"]["conv"][1]
    for path, call in PATHS[feature]:
        with pytest.raises(CacheLayoutError, match=re.escape(
                STATE_SLOTS[1][feature])) as err:
            call({"state_spec": spec})
        assert path in str(err.value) and "(state_spec)" in str(err.value)


# ------------------------------------------------------------------ #
# a model-stated row of TWO leaves (a latent row and its indexer key,
# ``glm_moe_dsa``): the latent row's entry, and every block operation
# carries both
# ------------------------------------------------------------------ #
TWO_LEAVES = {"kv_row": {"ckv": 128, "idx_k": 128}}


@pytest.mark.parametrize("feature", sorted(LATENT_ROW[1]))
def test_a_row_of_two_leaves_refuses_what_a_latent_row_does(feature):
    for path, call in PATHS[feature]:
        with pytest.raises(CacheLayoutError) as err:
            call(TWO_LEAVES)
        assert LATENT_ROW[1][feature] in str(err.value), path


def test_a_row_of_two_leaves_goes_through_every_block_operation():
    import numpy as np

    eng = _engine(TWO_LEAVES, enable_prefix_cache=True, host_tier=True,
                  host_tier_bytes=1 << 20)
    sm = eng.state_manager
    assert set(sm.unserved) == set(LATENT_ROW[1])
    for feature in set(FEATURES) - set(LATENT_ROW[1]):
        sm.require(feature, "a path")
    kv = sm.kv_cache
    assert kv.per_token_bytes == 2 * (128 + 128) * 4
    assert {k: v.shape for k, v in kv.cache["layer_1"].items()} == {
        "ckv": (17 * 8, 128), "idx_k": (17 * 8, 128)}
    # rows of block 3 written by hand in both leaves of both layers
    mark = {name: jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
            + 1000.0 * i for i, name in enumerate(("ckv", "idx_k"))}
    kv.update({layer: {name: pool.at[24:32].set(mark[name])
                       for name, pool in leaves.items()}
               for layer, leaves in kv.cache.items()})
    kv.copy_block(3, 9)
    payload = kv.gather_blocks([9, 3])
    for layer in ("layer_0", "layer_1"):
        for name in ("ckv", "idx_k"):
            got = np.asarray(payload[layer][name])
            assert got.shape == (16, 128)
            assert (got[:8] == np.asarray(mark[name])).all()
            assert (got[8:] == np.asarray(mark[name])).all()
    kv.scatter_blocks([5, 6], payload)
    assert (np.asarray(kv.cache["layer_1"]["idx_k"][40:48])
            == np.asarray(mark["idx_k"])).all()
    with pytest.raises(ValueError):         # a payload of one leaf
        kv.scatter_blocks([5, 6], {layer: {"ckv": leaves["ckv"]}
                                   for layer, leaves in payload.items()})


# ------------------------------------------------------------------ #
# a family whose published layer is TWO attention sub-layers
# (``longcat_flash``): it states the latent row and twice its layers, and
# is refused what a latent row is
# ------------------------------------------------------------------ #
def _double_block_model():
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_longcat_flash import LongcatFlashConfig, RaggedLongcatFlash

    return RaggedLongcatFlash(LongcatFlashConfig(
        vocab_size=64, hidden_size=32, ffn_hidden_size=48,
        expert_ffn_hidden_size=16, num_layers=3, num_attention_heads=2,
        kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=4,
        zero_expert_num=2, moe_topk=2, dtype=jnp.float32), 8)


def test_a_double_block_family_keeps_a_cache_layer_an_attention_sub_layer():
    model = _double_block_model()
    assert model.num_layers == 6 and model.kv_row == {"ckv": 128}
    assert (model.num_kv_heads, model.head_dim) == (1, 128)
    eng = InferenceEngineV2(model, {}, _engine({}).config)
    kv = eng.state_manager.kv_cache
    assert sorted(kv.cache) == [f"layer_{i}" for i in range(6)]
    assert kv.per_token_bytes == 6 * 128 * 4
    assert set(eng.state_manager.unserved) == set(LATENT_ROW[1])
    assert eng.step_counters == ("moe_slots", "moe_zero_slots",
                                 "moe_held_rows")


@pytest.mark.parametrize("feature", sorted(LATENT_ROW[1]))
def test_a_double_block_family_is_refused_what_a_latent_row_is(feature):
    model = _double_block_model()
    config = _engine({}).config
    if feature == "int8_kv":
        config = RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 128,
                              "max_ragged_sequence_count": 2,
                              "max_context": 64},
            "kv_cache": {"block_size": 8, "num_blocks": 17,
                         "dtype": "int8"}})
        call = lambda: InferenceEngineV2(model, {}, config)
    else:
        call = lambda: InferenceEngineV2(model, {}, config).verify_step(
            [1], [[3, 4]])
    with pytest.raises(CacheLayoutError,
                       match=re.escape(LATENT_ROW[1][feature])) as err:
        call()
    assert "RaggedLongcatFlash" in str(err.value)


# ------------------------------------------------------------------ #
# A model that states BOTH a row of its own and two groups, the window
# group with a row of ITS own (``dots3_note``): every entry of both tables,
# from the one place
# ------------------------------------------------------------------ #
BOTH_ROWS = {"kv_row": {"ckv": 128, "idx_k": 128},
             "kv_groups": {"window": {"layers": [0], "window": 16,
                                      "row": {"ckv": 256}}}}
BOTH_ENTRIES = sorted(set(LATENT_ROW[1]) | set(WINDOW_GROUP[1]))


@pytest.mark.parametrize("feature", BOTH_ENTRIES)
def test_a_model_with_a_row_a_group_is_refused_what_both_tables_say(feature):
    reasons = [table[1][feature] for table in (LATENT_ROW, WINDOW_GROUP)
               if feature in table[1]]
    for path, call in PATHS[feature]:
        with pytest.raises(CacheLayoutError) as err:
            call(BOTH_ROWS)
        message = str(err.value)
        assert path in message
        for why in reasons:         # both reasons in the one message
            assert why in message


def test_a_window_groups_own_row_alone_is_a_latent_row_too():
    """A group that states a row makes the cache a latent one even where
    the global layers keep k and v: what a latent row cannot serve is
    refused, and the two kinds of pool have their own leaves."""
    stated = {"kv_groups": {"window": {"layers": [0], "window": 16,
                                       "row": {"ckv": 256}}}}
    sm = _engine(stated).state_manager
    assert set(sm.unserved) == set(LATENT_ROW[1]) | set(WINDOW_GROUP[1])
    assert set(sm.kv_cache.cache["layer_0"]) == {"ckv"}
    assert set(sm.kv_cache.cache["layer_1"]) == {"k", "v"}
    assert sm.kv_cache.cache["layer_0"]["ckv"].shape[1] == 256


def test_a_row_a_group_builds_two_pools_of_two_widths():
    sm = _engine(BOTH_ROWS).state_manager
    kv = sm.kv_cache
    assert {n: a.shape for n, a in kv.cache["layer_0"].items()} \
        == {"ckv": ((sm.window_pool_blocks + 1) * 8, 256)}
    assert {n: a.shape for n, a in kv.cache["layer_1"].items()} \
        == {"ckv": (17 * 8, 128), "idx_k": (17 * 8, 128)}
    assert kv.per_token_bytes == 256 * 4            # the global layer
    assert kv.window_token_bytes == 256 * 4         # the window layer's own
    assert kv.window_layer_token_bytes == 1024
    with pytest.raises(CacheLayoutError, match="two pools"):
        kv.copy_block(1, 2)

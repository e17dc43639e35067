"""Inference v2 model implementations (reference:
inference/v2/model_implementations/ — llama_v2, opt, mistral, mixtral,
falcon families; qwen3_next, deepseek_v3 (and glm_moe_dsa through it), longcat_flash, dots3_note, lfm2_moe, afmoe, ouro, jamba, olmo_hybrid and granite_moe_hybrid have
no reference counterpart).  One file a family (config, parameter shapes,
class) over the shared layers of ``inference/v2/modules/``."""

from deepspeed_tpu.inference.v2.model_implementations.ragged_afmoe import (
    AfmoeConfig,
    RaggedAfmoe,
)

from deepspeed_tpu.inference.v2.model_implementations.ragged_llama import (
    RaggedLlama,
)
from deepspeed_tpu.inference.v2.model_implementations.ragged_falcon import (
    RaggedFalcon,
)
from deepspeed_tpu.inference.v2.model_implementations.ragged_mixtral import (
    RaggedMixtral,
)
from deepspeed_tpu.inference.v2.model_implementations.ragged_opt import (
    RaggedOPT,
)
from deepspeed_tpu.inference.v2.model_implementations.ragged_deepseek_v3 import (
    DeepseekV3Config,
    RaggedDeepseekV3,
)
from deepspeed_tpu.inference.v2.model_implementations.ragged_dots3_note import (
    Dots3NoteConfig,
    RaggedDots3Note,
)
from deepspeed_tpu.inference.v2.model_implementations.ragged_granite_moe_hybrid import (
    GraniteMoeHybridConfig,
    RaggedGraniteMoeHybrid,
)
from deepspeed_tpu.inference.v2.model_implementations.ragged_jamba import (
    JambaConfig,
    RaggedJamba,
)
from deepspeed_tpu.inference.v2.model_implementations.ragged_lfm2 import (
    Lfm2Config,
    RaggedLfm2,
)
from deepspeed_tpu.inference.v2.model_implementations.ragged_longcat_flash import (
    LongcatFlashConfig,
    RaggedLongcatFlash,
)
from deepspeed_tpu.inference.v2.model_implementations.ragged_olmo_hybrid import (
    OlmoHybridConfig,
    RaggedOlmoHybrid,
)
from deepspeed_tpu.inference.v2.model_implementations.ragged_ouro import (
    OuroConfig,
    RaggedOuro,
)
from deepspeed_tpu.inference.v2.model_implementations.ragged_qwen3_next import (
    Qwen3NextConfig,
    RaggedQwen3Next,
)

from deepspeed_tpu.inference.v2.modules.attention import (
    ragged_param_specs,
    shard_ragged_params,
)

# Mistral is the Llama architecture + sliding window: serve it with
# RaggedLlama over a config whose ``sliding_window`` is set (reference
# mistral/ container reuses the llama modules the same way)
RaggedMistral = RaggedLlama

#: what ``InferenceEngineV2.from_hf`` builds: architecture (``hf_loader.
#: config_from_hf``'s name) -> (class, does its constructor take a ``mesh``
#: with a 'model' axis; RaggedDeepseekV3 does, to refuse it in its own words)
HF_MODELS = {
    "llama": (RaggedLlama, True),
    "mistral": (RaggedLlama, True),
    "internlm": (RaggedLlama, True),
    "opt": (RaggedOPT, False),
    "falcon": (RaggedFalcon, False),
    "mixtral": (RaggedMixtral, False),
    "olmoe": (RaggedMixtral, False),
    "qwen3_next": (RaggedQwen3Next, False),
    "deepseek_v3": (RaggedDeepseekV3, True),
    "glm_moe_dsa": (RaggedDeepseekV3, True),
    "longcat_flash": (RaggedLongcatFlash, True),
    "dots3_note": (RaggedDots3Note, True),
    "lfm2_moe": (RaggedLfm2, False),
    "afmoe": (RaggedAfmoe, False),
    "ouro": (RaggedOuro, False),
    "jamba": (RaggedJamba, False),
    "granitemoehybrid": (RaggedGraniteMoeHybrid, True),
}

__all__ = ["AfmoeConfig", "DeepseekV3Config", "Dots3NoteConfig",
           "RaggedDots3Note", "GraniteMoeHybridConfig",
           "HF_MODELS", "RaggedAfmoe", "RaggedGraniteMoeHybrid",
           "RaggedDeepseekV3", "JambaConfig", "RaggedJamba", "Lfm2Config", "LongcatFlashConfig", "RaggedLongcatFlash", "OlmoHybridConfig", "RaggedOlmoHybrid", "OuroConfig", "Qwen3NextConfig", "RaggedLfm2", "RaggedLlama", "RaggedMistral", "RaggedMixtral",
           "RaggedOPT", "RaggedFalcon", "RaggedOuro", "RaggedQwen3Next",
           "ragged_param_specs", "shard_ragged_params"]

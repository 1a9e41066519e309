"""Registry of the assigned architectures (+ the paper's own model).

Every entry cites its source; the exact dimensions come from the assignment
table. ``get_config(name)`` returns the full-size config; ``smoke(name)``
returns the reduced same-family variant used by CPU smoke tests.
"""
from repro.config import ModelConfig, smoke_variant

from repro.configs.qwen1_5_32b import CONFIG as _qwen15_32b
from repro.configs.llama3_2_vision_11b import CONFIG as _llama32v
from repro.configs.jamba1_5_large_398b import CONFIG as _jamba
from repro.configs.llama4_scout_17b_a16e import CONFIG as _scout
from repro.configs.gemma2_9b import CONFIG as _gemma2
from repro.configs.llama4_maverick_400b_a17b import CONFIG as _maverick
from repro.configs.whisper_small import CONFIG as _whisper
from repro.configs.internlm2_1_8b import CONFIG as _internlm2
from repro.configs.mamba2_1_3b import CONFIG as _mamba2
from repro.configs.qwen2_7b import CONFIG as _qwen2
from repro.configs.qwen3_30b_a3b import CONFIG as _qwen3_moe
from repro.configs.qwen3_paper import CONFIG as _qwen3, CONFIG_8B as _qwen3_8b

ARCHS = {c.name: c for c in (
    _qwen15_32b, _llama32v, _jamba, _scout, _gemma2, _maverick,
    _whisper, _internlm2, _mamba2, _qwen2, _qwen3_moe,
)}
# The paper's own training targets (Qwen3-1.7B/8B proxies).
PAPER_ARCHS = {c.name: c for c in (_qwen3, _qwen3_8b)}
ALL = {**ARCHS, **PAPER_ARCHS}

# Architectures with a sub-quadratic (or natively windowed) path that run
# the long_500k decode shape; all others skip it (see DESIGN.md).
LONG_CONTEXT_OK = frozenset({
    "mamba2-1.3b", "jamba-1.5-large-398b", "gemma2-9b",
})


def get_config(name: str) -> ModelConfig:
    try:
        return ALL[name]
    except KeyError as e:
        raise KeyError(
            f"unknown arch {name!r}; have {sorted(ALL)}") from e


def smoke(name: str, **over) -> ModelConfig:
    return smoke_variant(get_config(name), **over)


def config_for(name: str, full_width: bool = False) -> ModelConfig:
    """What the launchers run: the published config with ``full_width``,
    its smoke-sized variant otherwise."""
    return get_config(name) if full_width else smoke(name)


def supports_shape(name: str, shape_name: str) -> bool:
    cfg = get_config(name)
    if shape_name == "long_500k":
        return name in LONG_CONTEXT_OK
    if shape_name in ("decode_32k", "prefill_32k") and cfg.is_encdec:
        # whisper decoder: architecturally fine (decoder-side KV cache);
        # encoder memory stays at its native frame count.
        return True
    return True

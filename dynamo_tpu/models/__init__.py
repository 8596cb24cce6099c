"""Model registry: architecture config → implementing module.

Each model module exposes the same functional surface —
``init_params(cfg, key, dtype)``, ``init_kv_cache(cfg, n, bs, dtype)``,
``forward(params, cfg, ...)`` and ``param_specs(params)`` — so the engine
(engine/model_runner.py) is architecture-agnostic. The reference's
equivalent "model family" axis lived inside its delegated GPU engines
(vLLM/SGLang model zoos, SURVEY.md §2.4); here the zoo is native.
"""

from __future__ import annotations

from ..engine.config import ModelConfig


def resolve(cfg: ModelConfig):
    """Pick the implementing module for an architecture config."""
    if cfg.kv_lora_rank > 0:
        try:
            from . import deepseek
        except ImportError as e:  # pragma: no cover
            raise NotImplementedError(
                "kv_lora_rank > 0 selects MLA attention (DeepSeek-class), "
                "which requires dynamo_tpu/models/deepseek.py"
            ) from e
        return deepseek
    if cfg.hc_mult > 1:
        # mixed residual streams with no family to mix them: every
        # other trunk would add to one stream and serve wrong tokens
        raise NotImplementedError(
            f"hc_mult={cfg.hc_mult} needs a trunk that carries the residual "
            "streams of models/mhc.py; only latent attention (model_type "
            "xing4_0, models/deepseek.py) does"
        )
    if cfg.model_family == "falcon_h1":
        from . import falcon_h1

        return falcon_h1
    if cfg.model_family == "minicpm_sala":
        from . import minicpm_sala

        return minicpm_sala
    if cfg.mixer_types:
        # layers of two kinds with no family to tell them apart: llama
        # would run dense rotary attention in every one
        raise NotImplementedError(
            f"mixer_types ({len(cfg.mixer_types)} entries) needs a family "
            f"that keeps a cache a kind of layer; model_family "
            f"{cfg.model_family!r} has none (models/minicpm_sala.py is "
            "selected by model_type minicpm_sala)"
        )
    if cfg.model_family == "afmoe":
        from . import afmoe

        return afmoe
    if cfg.layer_types:
        # window and full layers with no family to tell them apart:
        # mixtral or llama would run one kind of attention in every one
        raise NotImplementedError(
            f"layer_types ({len(cfg.layer_types)} entries) needs a family "
            f"that keeps pages a kind of layer; model_family "
            f"{cfg.model_family!r} has none (models/afmoe.py is selected "
            "by model_type afmoe)"
        )
    if cfg.mamba_d_ssm > 0:
        # recurrent state with no family to keep it: llama would serve
        # the attention half alone
        raise NotImplementedError(
            f"mamba_d_ssm={cfg.mamba_d_ssm} needs a family that keeps "
            f"recurrent state; model_family {cfg.model_family!r} has none "
            "(models/falcon_h1.py is selected by model_type falcon_h1)"
        )
    if cfg.model_family == "gptoss":
        from . import gptoss

        return gptoss
    if cfg.num_experts > 0:
        from . import mixtral

        return mixtral
    if cfg.model_family == "gemma2":
        from . import gemma2

        return gemma2
    from . import llama

    return llama

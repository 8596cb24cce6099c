"""Load HF checkpoint weights into the engine's stacked-layer param layout.

HF stores one tensor per layer per projection ([out, in] torch layout);
the engine wants [L, in, out] stacks for lax.scan. Streams tensors from
safetensors shards without loading the whole checkpoint at once.
"""

from __future__ import annotations

import glob
import json
import logging
import os
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from ..engine.config import ModelConfig
from . import mhc

logger = logging.getLogger(__name__)


_SCALE_SUFFIXES = ("_scale", "_scale_inv")


def _dequant_fp8(arr: np.ndarray, scale: Optional[np.ndarray],
                 inverse_blocks: bool) -> np.ndarray:
    """FP8 tensor (as float32) × its scale → float32.

    Two schemes cover the FP8 checkpoints in the wild:
    - ``weight_scale`` (compressed-tensors / FP8-dynamic exports, the
      reference's canonical 70B model examples/llm/benchmarks/perf.sh:18):
      scalar or per-output-channel; straight multiply.
    - ``weight_scale_inv`` (DeepSeek-V3/R1 native FP8): per 128×128
      block; expand blockwise over both weight axes.
    """
    if scale is None:
        return arr
    scale = scale.astype(np.float32)
    if inverse_blocks and scale.ndim == 2 and arr.ndim == 2:
        # fixed 128x128 blocks, last block partial (the layout DeepSeek's
        # quantization_config.weight_block_size=[128,128] describes)
        bs_ = 128
        expanded = np.repeat(np.repeat(scale, bs_, axis=0), bs_, axis=1)
        return arr * expanded[: arr.shape[0], : arr.shape[1]]
    if scale.ndim == 1 and arr.ndim >= 2 and scale.size == arr.shape[0]:
        scale = scale.reshape(-1, *([1] * (arr.ndim - 1)))
    return arr * scale


def _bf16_numpy(t) -> np.ndarray:
    """A torch tensor as numpy, bfloat16 kept at 2 bytes an element
    through an ml_dtypes view (numpy itself has no bfloat16)."""
    import ml_dtypes
    import torch

    if t.dtype == torch.bfloat16:
        return t.view(torch.uint16).numpy().view(ml_dtypes.bfloat16)
    return t.numpy()


def _iter_safetensors(model_dir: str):
    """Stream (name, np.ndarray) from all shards. Goes through the torch
    framework because safetensors' numpy framework cannot represent
    bfloat16 (the dtype real Llama-class checkpoints ship in); bf16 stays
    2 bytes/element via an ml_dtypes view so staging a large checkpoint
    doesn't double host RAM.

    FP8 tensors (compressed-tensors ``weight_scale`` exports and
    DeepSeek-native ``weight_scale_inv`` block scales) are upconverted to
    bf16 at load — TPUs have no fp8 compute path in this engine yet, so
    the checkpoint serves at bf16 memory cost (one loud warning)."""
    import ml_dtypes
    import torch
    from safetensors import safe_open

    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no .safetensors under {model_dir}")
    # name → shard, built lazily on the FIRST fp8 tensor (so an fp8
    # weight can find its scale across shard boundaries) — the common
    # bf16/fp16 checkpoint never pays the extra key-listing pass
    index: Dict[str, str] = {}

    def ensure_index() -> Dict[str, str]:
        if not index:
            for p in files:
                with safe_open(p, framework="pt") as f:
                    for n in f.keys():
                        index[n] = p
        return index

    def read(name: str) -> "torch.Tensor":
        with safe_open(index[name], framework="pt") as f:
            return f.get_tensor(name)

    warned = False
    for path in files:
        with safe_open(path, framework="pt") as f:
            for name in f.keys():
                if name.endswith(_SCALE_SUFFIXES) or name.endswith(
                    ("input_scale", "k_scale", "v_scale")
                ):
                    continue  # consumed with (or irrelevant to) a weight
                t = f.get_tensor(name)
                if "float8" in str(t.dtype):
                    if not warned:
                        warned = True
                        logger.warning(
                            "FP8 checkpoint: upconverting to bf16 at load "
                            "(weights occupy 2x the quantized size in HBM; "
                            "TPU-native int8/fp8 compute not yet wired)"
                        )
                    scale = inv = None
                    idx = ensure_index()
                    if f"{name}_scale" in idx:
                        scale = read(f"{name}_scale").to(torch.float32).numpy()
                    elif f"{name}_scale_inv" in idx:
                        inv = read(f"{name}_scale_inv").to(torch.float32).numpy()
                    arr = _dequant_fp8(
                        t.to(torch.float32).numpy(),
                        scale if scale is not None else inv,
                        inverse_blocks=inv is not None,
                    ).astype(ml_dtypes.bfloat16)
                else:
                    arr = _bf16_numpy(t)
                yield name, arr


def _stream_hf_params(model_dir: str, mapping: Dict, n_layers: int,
                      required, label: str):
    """Shared HF-checkpoint streaming for dense trunks: route the
    top-level tensors (embed / final norm / lm_head, transposed) and
    stage per-layer tensors by ``mapping`` (name → (key, transpose)).
    Validates the ``required`` layer keys are complete; keys outside
    ``required`` (e.g. Qwen's optional qkv biases) must be complete only
    if the checkpoint ships any of them. Returns (top, staging)."""
    staging: Dict[str, Dict[int, np.ndarray]] = {}
    top: Dict[str, np.ndarray] = {}
    for name, tensor in _iter_safetensors(model_dir):
        name = name.removeprefix("model.")
        if name == "embed_tokens.weight":
            top["embed"] = tensor
        elif name == "norm.weight":
            top["final_norm"] = tensor
        elif name == "lm_head.weight":
            top["lm_head"] = tensor.T  # [V, D] → [D, V]
        elif name.startswith("layers."):
            _, idx, rest = name.split(".", 2)
            if rest in mapping:
                key, transpose = mapping[rest]
                staging.setdefault(key, {})[int(idx)] = (
                    tensor.T if transpose else tensor
                )
            else:
                logger.debug("skipping unmapped tensor %s", name)
    present = set(staging) | set(required)
    missing = [k for k in present if len(staging.get(k, ())) != n_layers]
    if missing:
        raise ValueError(
            f"incomplete checkpoint: {label} {missing} have "
            f"{[len(staging.get(k, ())) for k in missing]} of {n_layers} layers"
        )
    return top, staging


def load_llama_params(model_dir: str, cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    """HF Llama/Mistral/Qwen-style checkpoint → stacked param pytree."""
    l = cfg.num_layers
    mapping = {
        "input_layernorm.weight": ("ln1", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "post_attention_layernorm.weight": ("ln2", False),
        "mlp.gate_proj.weight": ("w_gate", True),
        "mlp.up_proj.weight": ("w_up", True),
        "mlp.down_proj.weight": ("w_down", True),
        # Qwen2-family qkv biases (models/llama.py adds them pre-rope);
        # optional — present only when the checkpoint ships them
        "self_attn.q_proj.bias": ("bq", False),
        "self_attn.k_proj.bias": ("bk", False),
        "self_attn.v_proj.bias": ("bv", False),
        # Qwen3-family per-head q/k norms (pre-rope, over head_dim)
        "self_attn.q_norm.weight": ("q_norm", False),
        "self_attn.k_norm.weight": ("k_norm", False),
        # Phi-3 fuses qkv and gate|up into single projections; split
        # below after streaming
        "self_attn.qkv_proj.weight": ("_qkv", True),
        "mlp.gate_up_proj.weight": ("_gate_up", True),
    }
    top, staging = _stream_hf_params(
        model_dir, mapping, l, required=("ln1", "ln2", "wo", "w_down"),
        label="llama",
    )
    if "_qkv" in staging:
        # Phi-3 layout: rows [q | k | v] on the out axis (post-transpose
        # the out axis is last): q = heads*hd, k = v = kv_heads*hd
        qd = cfg.num_heads * cfg.head_dim
        kvd = cfg.num_kv_heads * cfg.head_dim
        for i, t in staging.pop("_qkv").items():
            if t.shape[1] != qd + 2 * kvd:
                # a silent short slice would serve plausible garbage
                raise ValueError(
                    f"fused qkv width {t.shape[1]} != heads*hd + 2*kv*hd "
                    f"= {qd + 2 * kvd} (config/checkpoint mismatch)"
                )
            staging.setdefault("wq", {})[i] = t[:, :qd]
            staging.setdefault("wk", {})[i] = t[:, qd:qd + kvd]
            staging.setdefault("wv", {})[i] = t[:, qd + kvd:]
    if "_gate_up" in staging:
        inter = cfg.intermediate_size
        for i, t in staging.pop("_gate_up").items():
            if t.shape[1] != 2 * inter:
                raise ValueError(
                    f"fused gate_up width {t.shape[1]} != "
                    f"2*intermediate_size = {2 * inter}"
                )
            staging.setdefault("w_gate", {})[i] = t[:, :inter]
            staging.setdefault("w_up", {})[i] = t[:, inter:]
    missing = [k for k in ("wq", "wk", "wv", "w_gate", "w_up")
               if len(staging.get(k, ())) != l]
    if missing:
        raise ValueError(
            f"incomplete checkpoint: llama {missing} incomplete over {l} layers"
        )

    def stack(key):
        return jnp.asarray(
            np.stack([staging[key][i] for i in range(l)]), dtype=dtype
        )

    params = {
        "embed": jnp.asarray(top["embed"], dtype=dtype),
        "layers": {k: stack(k) for k in staging},
        "final_norm": jnp.asarray(top["final_norm"], dtype=dtype),
    }
    if "lm_head" in top:
        params["lm_head"] = jnp.asarray(top["lm_head"], dtype=dtype)
    elif not cfg.tie_word_embeddings:
        # tied but config didn't say so — fall back to tied
        logger.info("no lm_head tensor; using tied embeddings")
    return params


def load_gemma2_params(model_dir: str, cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    """HF Gemma2ForCausalLM checkpoint → stacked param pytree.

    Gemma-2 ships four norms per layer and normally ties lm_head to the
    embedding; an untied finetune's lm_head is honored when present
    (models/gemma2.py applies the (1+w) norm semantics and the
    sqrt(hidden) embedding scale at forward time)."""
    l = cfg.num_layers
    mapping = {
        "input_layernorm.weight": ("ln1", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "post_attention_layernorm.weight": ("ln_post_attn", False),
        "pre_feedforward_layernorm.weight": ("ln_pre_mlp", False),
        "mlp.gate_proj.weight": ("w_gate", True),
        "mlp.up_proj.weight": ("w_up", True),
        "mlp.down_proj.weight": ("w_down", True),
        "post_feedforward_layernorm.weight": ("ln_post_mlp", False),
    }
    top, staging = _stream_hf_params(
        model_dir, mapping, l,
        required=tuple(key for key, _ in mapping.values()), label="gemma2",
    )
    params = {
        "embed": jnp.asarray(top["embed"], dtype=dtype),
        "layers": _stack_group(staging, l, 1, dtype, "gemma2"),
        "final_norm": jnp.asarray(top["final_norm"], dtype=dtype),
    }
    if "lm_head" in top:
        params["lm_head"] = jnp.asarray(top["lm_head"], dtype=dtype)
    return params


def _stack_group(
    staging: Dict[str, Dict], n_layers: int, n_experts: int, dtype, label: str,
    keep_f32=(),
) -> Dict:
    """Stack a staged layer group into [L, ...] (or [L, E, ...] for keys
    indexed by (layer, expert) tuples), validating completeness. Keys in
    ``keep_f32`` stay float32 whatever ``dtype`` is."""
    out = {}
    for key, by_idx in staging.items():
        if not by_idx:
            raise ValueError(
                f"incomplete checkpoint: {label}.{key} has 0 tensors"
            )
        per_expert = isinstance(next(iter(by_idx)), tuple)
        want = n_layers * n_experts if per_expert else n_layers
        if len(by_idx) != want:
            raise ValueError(
                f"incomplete checkpoint: {label}.{key} has "
                f"{len(by_idx)}/{want} tensors"
            )
        if per_expert:
            arr = np.stack([
                np.stack([by_idx[(i, j)] for j in range(n_experts)])
                for i in range(n_layers)
            ])
        else:
            arr = np.stack([by_idx[i] for i in range(n_layers)])
        out[key] = jnp.asarray(
            arr, dtype=jnp.float32 if key in keep_f32 else dtype)
    return out


def load_mixtral_params(model_dir: str, cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    """HF GShard-MoE checkpoint → stacked param pytree.

    Speaks both tensor naming schemes that resolve to the mixtral
    module: Mixtral's ``block_sparse_moe.{gate,experts.N.w1/w2/w3}`` and
    Qwen3-MoE's ``mlp.{gate,experts.N.gate/up/down_proj}`` (+ Qwen3's
    per-head q/k norms). HF stores one tensor per (layer, expert)
    projection; the engine wants [L, E, in, out] stacks so the
    routed-experts einsums (models/mixtral.py moe_mlp) see every expert
    as one MXU-shaped batched matmul. Reference analog: the reference
    loads MoE checkpoints through its GPU engines' HF loaders
    (launch/dynamo-run/src/lib.rs:131).
    """
    l, e = cfg.num_layers, cfg.num_experts
    staging: Dict[str, Dict] = {}
    top: Dict[str, np.ndarray] = {}

    attn_map = {
        "input_layernorm.weight": ("ln1", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "self_attn.q_norm.weight": ("q_norm", False),
        "self_attn.k_norm.weight": ("k_norm", False),
        "post_attention_layernorm.weight": ("ln2", False),
        "block_sparse_moe.gate.weight": ("router", True),
        "mlp.gate.weight": ("router", True),
    }
    expert_map = {
        "w1": "w_gate", "w2": "w_down", "w3": "w_up",            # mixtral
        "gate_proj": "w_gate", "down_proj": "w_down", "up_proj": "w_up",
    }

    for name, tensor in _iter_safetensors(model_dir):
        name = name.removeprefix("model.")
        if name == "embed_tokens.weight":
            top["embed"] = tensor
        elif name == "norm.weight":
            top["final_norm"] = tensor
        elif name == "lm_head.weight":
            top["lm_head"] = tensor.T
        elif name.startswith("layers."):
            _, idx, rest = name.split(".", 2)
            idx = int(idx)
            if rest in attn_map:
                key, transpose = attn_map[rest]
                staging.setdefault(key, {})[idx] = (
                    tensor.T if transpose else tensor
                )
            elif rest.startswith(("block_sparse_moe.experts.",
                                  "mlp.experts.")):
                _, _, ei, proj, _ = rest.split(".")
                staging.setdefault(expert_map[proj], {})[(idx, int(ei))] = tensor.T
            elif rest.startswith("mlp.shared_expert"):
                # Qwen2-MoE's gated shared expert — distinct semantics
                # (sigmoid-gated output) this module does not implement
                raise NotImplementedError(
                    "Qwen2-MoE shared-expert checkpoints are not "
                    "supported (gated shared expert); Qwen3-MoE and "
                    "Mixtral load"
                )
            else:
                logger.debug("skipping unmapped tensor %s", name)

    layers = _stack_group(staging, l, e, dtype, "layers")
    params = {
        "embed": jnp.asarray(top["embed"], dtype=dtype),
        "layers": layers,
        "final_norm": jnp.asarray(top["final_norm"], dtype=dtype),
    }
    if "lm_head" in top:
        params["lm_head"] = jnp.asarray(top["lm_head"], dtype=dtype)
    return params


# MXFP4 (the canonical GPT-OSS release format): 4-bit e2m1 values packed
# two-per-byte in 16-byte groups of 32, with one e8m0 exponent (biased
# 127) per group
_FP4_VALUES = np.array(
    [0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0,
     -0.0, -0.5, -1.0, -1.5, -2.0, -3.0, -4.0, -6.0], np.float32,
)


def _dequant_mxfp4(blocks: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """[..., G, 16] uint8 blocks + [..., G] uint8 exponents →
    [..., G*32] float32 (low nibble first, matching transformers'
    integrations/mxfp4.convert_moe_packed_tensors)."""
    vals = np.empty(blocks.shape[:-1] + (32,), np.float32)
    vals[..., 0::2] = _FP4_VALUES[blocks & 0x0F]
    vals[..., 1::2] = _FP4_VALUES[blocks >> 4]
    vals *= np.exp2(scales.astype(np.int32) - 127)[..., None].astype(np.float32)
    return vals.reshape(blocks.shape[:-2] + (blocks.shape[-2] * 32,))


def load_gptoss_params(model_dir: str, cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    """HF GPT-OSS checkpoint → param pytree (models/gptoss.py layout).

    Unlike Mixtral/Qwen-MoE, the expert projections arrive already
    STACKED per layer (``mlp.experts.gate_up_proj`` [E, D, 2I] etc. —
    one tensor per layer, not per expert), so only the layer axis needs
    stacking. Attention projections transpose like every HF linear; the
    per-head ``sinks`` and all biases load as-is. The canonical MXFP4
    releases (expert tensors shipped as ``*_blocks`` + ``*_scales``)
    dequantize at load — values arrive [E, out, in] and transpose into
    the engine's [E, in, out] stacks.
    """
    l = cfg.num_layers
    staging: Dict[str, Dict] = {}
    mx_staging: Dict[str, Dict] = {}  # (key, kind) -> {layer: tensor}
    top: Dict[str, np.ndarray] = {}

    name_map = {
        "input_layernorm.weight": ("ln1", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.q_proj.bias": ("bq", False),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.k_proj.bias": ("bk", False),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.v_proj.bias": ("bv", False),
        "self_attn.o_proj.weight": ("wo", True),
        "self_attn.o_proj.bias": ("bo", False),
        "self_attn.sinks": ("sinks", False),
        "post_attention_layernorm.weight": ("ln2", False),
        "mlp.router.weight": ("router", True),
        "mlp.router.bias": ("router_bias", False),
        "mlp.experts.gate_up_proj": ("w_gate_up", False),
        "mlp.experts.gate_up_proj_bias": ("b_gate_up", False),
        "mlp.experts.down_proj": ("w_down", False),
        "mlp.experts.down_proj_bias": ("b_down", False),
    }

    for name, tensor in _iter_safetensors(model_dir):
        name = name.removeprefix("model.")
        if name == "embed_tokens.weight":
            top["embed"] = tensor
        elif name == "norm.weight":
            top["final_norm"] = tensor
        elif name == "lm_head.weight":
            top["lm_head"] = tensor.T
        elif name.startswith("layers."):
            _, idx, rest = name.split(".", 2)
            if rest in name_map:
                key, transpose = name_map[rest]
                staging.setdefault(key, {})[int(idx)] = (
                    tensor.T if transpose else tensor
                )
            elif rest.startswith("mlp.experts.") and rest.endswith(
                ("_blocks", "_scales")
            ):
                proj, kind = rest.removeprefix("mlp.experts.").rsplit("_", 1)
                key = {"gate_up_proj": "w_gate_up", "down_proj": "w_down"}[proj]
                mx_staging.setdefault((key, kind), {})[int(idx)] = tensor
            else:
                logger.debug("skipping unmapped tensor %s", name)

    for key in ("w_gate_up", "w_down"):
        blocks = mx_staging.get((key, "blocks"), {})
        scales = mx_staging.get((key, "scales"), {})
        for idx, blk in blocks.items():
            if idx not in scales:
                raise ValueError(
                    f"incomplete MXFP4 checkpoint: layers.{key} layer "
                    f"{idx} has blocks but no scales"
                )
            # dequant [E, out, in] → engine stack [E, in, out]
            staging.setdefault(key, {})[idx] = _dequant_mxfp4(
                blk, scales[idx]
            ).transpose(0, 2, 1)

    layers = _stack_group(staging, l, 0, dtype, "layers")
    required = {key for key, _ in name_map.values()} | {"w_gate_up", "w_down"}
    missing = required - set(layers)
    if missing:
        # _stack_group can only validate keys that matched ≥1 tensor; a
        # wholly-absent group (renamed/unknown format) must still fail
        # with the loader's diagnostic, not a KeyError mid-trace
        raise ValueError(
            f"incomplete checkpoint: layers missing {sorted(missing)} "
            f"(unrecognized tensor naming or quantization format?)"
        )
    params = {
        "embed": jnp.asarray(top["embed"], dtype=dtype),
        "layers": layers,
        "final_norm": jnp.asarray(top["final_norm"], dtype=dtype),
    }
    if "lm_head" in top:
        params["lm_head"] = jnp.asarray(top["lm_head"], dtype=dtype)
    return params


def _rope_deinterleave(n: int) -> np.ndarray:
    """Permutation mapping HF DeepSeek's interleaved rope pairs
    (x[2j], x[2j+1]) to this repo's half-rotation layout (x[j], x[j+n/2]).

    Folding it into the projection weights makes models/llama.apply_rope
    numerically exact vs. HF's complex-multiply rope (the permutation is
    applied to BOTH q_rope and k_rope, so their dot product is invariant).
    """
    return np.concatenate([np.arange(0, n, 2), np.arange(1, n, 2)])


# The names of a sublayer's mixing tensors (models/mhc.py) in a
# ``model_type: xing4_0`` checkpoint, under ``model.layers.<i>.``.
# ASSUMED: no checkpoint of the family was at hand when this was
# written (benchmark/configs/xing4-29b-a4b.json, "assumed"); a published
# checkpoint's names are an edit of this table and of nothing else.
# Linear weights are [out, in] as everywhere in HF checkpoints.
XING4_MHC_MODULES = {"attn": "attn_hc", "mlp": "mlp_hc"}
XING4_MHC_TENSORS = {
    # name under the module -> (engine part, position in that part)
    "phi_pre.weight": ("phi", 0), "phi_post.weight": ("phi", 1),
    "phi_res.weight": ("phi", 2),
    "b_pre": ("b", 0), "b_post": ("b", 1), "b_res": ("b", 2),
    "alpha_pre": ("alpha", 0), "alpha_post": ("alpha", 1),
    "alpha_res": ("alpha", 2),
}


def _mhc_tensor(rest: str):
    """``(sublayer, part, position)`` of a mixing tensor's name, or None."""
    module, _, leaf = rest.partition(".")
    for sub, name in XING4_MHC_MODULES.items():
        if module == name and leaf in XING4_MHC_TENSORS:
            return (sub,) + XING4_MHC_TENSORS[leaf]
    return None


def _join_mhc(staging: Dict[str, Dict]) -> None:
    """pre | post | res of every staged mixing tensor -> the engine's one
    ``hc_<sub>_phi`` [n D, 2n + n^2], ``_b`` [2n + n^2], ``_alpha`` [3]."""
    for group in staging.values():
        for key in [k for k in group if isinstance(k, tuple)]:
            sub, part = key
            by_layer = group.pop(key)
            for li, three in by_layer.items():
                if sorted(three) != [0, 1, 2]:
                    raise ValueError(
                        f"incomplete checkpoint: layer {li} hc_{sub}_{part} "
                        f"has parts {sorted(three)} of pre, post, res")
                flat = [np.asarray(three[i], np.float32) for i in range(3)]
                if part == "phi":          # [out, n D] each -> [n D, out]
                    joined = np.concatenate([t.T for t in flat], axis=1)
                else:
                    joined = np.concatenate([t.reshape(-1) for t in flat])
                group.setdefault(f"hc_{sub}_{part}", {})[li] = joined


def load_deepseek_params(model_dir: str, cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    """HF DeepSeek-V2/V3 MLA (+ optional MoE) checkpoint → param pytree.

    Layout transforms, all checked against transformers'
    modeling_deepseek_v2.py semantics:
    - ``kv_a_proj_with_mqa`` [r+rope, D] splits into ``w_dkv`` [D, r] and the
      shared rope key projection ``w_kr`` [D, rope];
    - ``kv_b_proj`` [H*(nope+v), r] splits per head into the absorbed
      up-projections ``w_uk`` [r, H, nope] / ``w_uv`` [r, H, v];
    - rope columns of the q projection and ``w_kr`` are de-interleaved
      (see _rope_deinterleave);
    - MoE layers restack at ``idx - first_k_dense_replace``; V3's
      ``e_score_correction_bias`` loads as ``router_bias``;
    - tensors of ``model.layers.<num_hidden_layers>`` onward are the
      multi-token-prediction modules (``num_nextn_predict_layers``): a
      draft head the main model's logits do not depend on and no path
      here runs; they are recognised, left out and logged once;
    - ``model_type: xing4_0``: each sublayer's mixing tensors
      (XING4_MHC_TENSORS) join into ``hc_<sub>_phi / _b / _alpha``,
      float32 whatever ``dtype`` is.
    """
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r, h, vd = cfg.kv_lora_rank, cfg.num_heads, cfg.v_head_dim
    n_dense = min(cfg.first_k_dense_replace, cfg.num_layers) if cfg.num_experts else cfg.num_layers
    n_moe = cfg.num_layers - n_dense
    e = cfg.num_experts
    perm = _rope_deinterleave(rope)

    # staging[group][key][layer-or-(layer,expert)] where group is
    # "dense_layers" (first k) or "layers" (MoE tail)
    staging: Dict[str, Dict[str, Dict]] = {"dense_layers": {}, "layers": {}}
    top: Dict[str, np.ndarray] = {}

    def put(group: str, key: str, idx, value) -> None:
        staging[group].setdefault(key, {})[idx] = value

    def q_deinterleave(t: np.ndarray) -> np.ndarray:
        # t: [in, H*(nope+rope)] — permute each head's rope columns
        t = t.reshape(t.shape[0], h, nope + rope).copy()
        t[..., nope:] = t[..., nope + perm]
        return t.reshape(t.shape[0], -1)

    skipped_mtp = []
    for name, tensor in _iter_safetensors(model_dir):
        name = name.removeprefix("model.")
        if name == "embed_tokens.weight":
            top["embed"] = tensor
            continue
        if name == "norm.weight":
            top["final_norm"] = tensor
            continue
        if name == "lm_head.weight":
            top["lm_head"] = tensor.T
            continue
        if not name.startswith("layers."):
            continue
        _, idx, rest = name.split(".", 2)
        idx = int(idx)
        if idx >= cfg.num_layers:
            skipped_mtp.append(name)
            continue
        group = "dense_layers" if idx < n_dense else "layers"
        li = idx if idx < n_dense else idx - n_dense

        mhc_part = _mhc_tensor(rest) if cfg.hc_mult > 1 else None
        if mhc_part is not None:
            sub, part, at = mhc_part
            staging[group].setdefault((sub, part), {}).setdefault(li, {})[at] = tensor
        elif rest == "input_layernorm.weight":
            put(group, "ln1", li, tensor)
        elif rest == "post_attention_layernorm.weight":
            put(group, "ln2", li, tensor)
        elif rest == "self_attn.q_proj.weight":
            put(group, "wq", li, q_deinterleave(tensor.T))
        elif rest == "self_attn.q_a_proj.weight":
            put(group, "w_dq", li, tensor.T)
        elif rest == "self_attn.q_a_layernorm.weight":
            put(group, "ln_q", li, tensor)
        elif rest == "self_attn.q_b_proj.weight":
            put(group, "w_uq", li, q_deinterleave(tensor.T))
        elif rest == "self_attn.kv_a_proj_with_mqa.weight":
            t = tensor.T  # [D, r+rope]
            put(group, "w_dkv", li, t[:, :r])
            put(group, "w_kr", li, t[:, r:][:, perm])
        elif rest == "self_attn.kv_a_layernorm.weight":
            put(group, "ln_kv", li, tensor)
        elif rest == "self_attn.kv_b_proj.weight":
            t = tensor.reshape(h, nope + vd, r)  # [H, nope+v, r]
            put(group, "w_uk", li, np.transpose(t[:, :nope, :], (2, 0, 1)))
            put(group, "w_uv", li, np.transpose(t[:, nope:, :], (2, 0, 1)))
        elif rest == "self_attn.o_proj.weight":
            put(group, "wo", li, tensor.T)
        elif rest.startswith("mlp.experts."):
            _, _, ei, proj, _ = rest.split(".")
            key = {"gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down"}[proj]
            put(group, key, (li, int(ei)), tensor.T)
        elif rest.startswith("mlp.shared_experts."):
            _, _, proj, _ = rest.split(".")
            key = {
                "gate_proj": "w_sh_gate", "up_proj": "w_sh_up",
                "down_proj": "w_sh_down",
            }[proj]
            put(group, key, li, tensor.T)
        elif rest == "mlp.gate.weight":
            put(group, "router", li, tensor.T)
        elif rest == "mlp.gate.e_score_correction_bias":
            put(group, "router_bias", li, tensor)
        elif rest in (
            "mlp.gate_proj.weight", "mlp.up_proj.weight", "mlp.down_proj.weight"
        ):
            key = {
                "mlp.gate_proj.weight": "w_gate",
                "mlp.up_proj.weight": "w_up",
                "mlp.down_proj.weight": "w_down",
            }[rest]
            put(group, key, li, tensor.T)
        else:
            logger.debug("skipping unmapped tensor %s", name)

    if skipped_mtp:
        logger.info(
            "left out %d tensors of layers >= %d (multi-token-prediction "
            "modules, e.g. %s): no path here runs them",
            len(skipped_mtp), cfg.num_layers, skipped_mtp[0])
    _join_mhc(staging)
    params: Dict = {
        "embed": jnp.asarray(top["embed"], dtype=dtype),
        "final_norm": jnp.asarray(top["final_norm"], dtype=dtype),
    }
    if "lm_head" in top:
        params["lm_head"] = jnp.asarray(top["lm_head"], dtype=dtype)
    if n_dense > 0:
        params["dense_layers"] = _stack_group(
            staging["dense_layers"], n_dense, 0, dtype, "dense_layers",
            keep_f32=mhc.PARAM_KEYS,
        )
    if n_moe > 0:
        params["layers"] = _stack_group(
            staging["layers"], n_moe, e, dtype, "layers",
            keep_f32=mhc.PARAM_KEYS)
    return params


def _gguf_unpermute(w: np.ndarray, n_head: int) -> np.ndarray:
    """Invert llama.cpp's q/k row permutation on a [out, in] weight.

    The public HF→GGUF converter permutes attn_q/attn_k rows so ggml's
    interleaved rope matches HF's half-rotation rope
    (w.reshape(H, 2, out//H//2, in).swapaxes(1, 2)); this engine uses the
    HF convention (models/llama.apply_rope), so loading a .gguf must undo
    it per head.
    """
    out, inner = w.shape
    hd = out // n_head
    return (
        w.reshape(n_head, hd // 2, 2, inner)
        .swapaxes(1, 2)
        .reshape(out, inner)
    )


def load_gguf_llama_params(path: str, cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    """llama.cpp ``.gguf`` checkpoint → stacked param pytree.

    Tensor data dequantizes through llm/gguf_tensors.py (f16/bf16 and the
    common q* block formats); names follow llama.cpp's export scheme
    (token_embd, blk.N.attn_q, ...). With this the engine serves a .gguf
    end-to-end: tokenizer from metadata (llm/gguf.py), weights from here.
    """
    import ml_dtypes

    from ..llm.gguf import read_gguf
    from ..llm.gguf_tensors import iter_gguf_tensors

    # dequantization yields float32; staging a whole 70B checkpoint at 4
    # bytes per element would need ~4x the serving footprint in host RAM,
    # so narrow to the target dtype per tensor as it streams in
    stage_dtype = (
        ml_dtypes.bfloat16 if dtype == jnp.bfloat16
        else np.float16 if dtype == jnp.float16
        else np.float32
    )

    l = cfg.num_layers
    staging: Dict[str, Dict[int, np.ndarray]] = {
        k: {} for k in ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up", "w_down")
    }
    top: Dict[str, np.ndarray] = {}
    mapping = {
        "attn_norm.weight": ("ln1", False),
        "attn_q.weight": ("wq", True),
        "attn_k.weight": ("wk", True),
        "attn_v.weight": ("wv", True),
        "attn_output.weight": ("wo", True),
        "ffn_norm.weight": ("ln2", False),
        "ffn_gate.weight": ("w_gate", True),
        "ffn_up.weight": ("w_up", True),
        "ffn_down.weight": ("w_down", True),
    }

    g = read_gguf(path)
    for name, tensor in iter_gguf_tensors(path, g):
        tensor = tensor.astype(stage_dtype)
        if name == "token_embd.weight":
            top["embed"] = tensor
        elif name == "output_norm.weight":
            top["final_norm"] = tensor
        elif name == "output.weight":
            top["lm_head"] = tensor.T
        elif name.startswith("blk."):
            _, idx, rest = name.split(".", 2)
            if rest not in mapping:
                logger.debug("skipping unmapped gguf tensor %s", name)
                continue
            key, transpose = mapping[rest]
            if key == "wq":
                tensor = _gguf_unpermute(tensor, cfg.num_heads)
            elif key == "wk":
                tensor = _gguf_unpermute(tensor, cfg.num_kv_heads)
            staging[key][int(idx)] = tensor.T if transpose else tensor

    missing = [k for k, v in staging.items() if len(v) != l]
    if missing:
        raise ValueError(
            f"incomplete gguf checkpoint: {missing} have "
            f"{[len(staging[k]) for k in missing]} of {l} layers"
        )

    params = {
        "embed": jnp.asarray(top["embed"], dtype=dtype),
        "layers": {
            k: jnp.asarray(
                np.stack([staging[k][i] for i in range(l)]), dtype=dtype
            )
            for k in staging
        },
        "final_norm": jnp.asarray(top["final_norm"], dtype=dtype),
    }
    if "lm_head" in top:
        params["lm_head"] = jnp.asarray(top["lm_head"], dtype=dtype)
    elif not cfg.tie_word_embeddings:
        logger.info("no output.weight in gguf; using tied embeddings")
    return params


# a granitemoehybrid checkpoint's tensors under ``model.layers.N.``
# (transformers modeling_granitemoehybrid.py) -> (key, transpose)
_GRANITE_MIXER = {
    "mamba.in_proj.weight": ("ssm_in", True),
    "mamba.conv1d.bias": ("conv_b", False),
    "mamba.dt_bias": ("dt_bias", False),
    "mamba.A_log": ("A_log", False),
    "mamba.D": ("D", False),
    "mamba.norm.weight": ("ssm_norm", False),
    "mamba.out_proj.weight": ("ssm_out", True),
}
_GRANITE_ATTN = {
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
}
_GRANITE_EVERY = {
    "input_layernorm.weight": ("ln1", False),
    "post_attention_layernorm.weight": ("ln2", False),
    "block_sparse_moe.router.layer.weight": ("router", True),
}
_GRANITE_F32 = ("dt_bias", "A_log", "D")


def load_granite_hybrid_params(model_dir: str, cfg: ModelConfig,
                               dtype=jnp.bfloat16) -> Dict:
    """HF ``granitemoehybrid`` checkpoint -> models/granite_hybrid.py's
    pytree (``params["runs"]``: a dict of arrays stacked over each run of
    ``layer_types``).

    The experts are two tensors a layer, ``block_sparse_moe.input_linear
    .weight [E, 2 I, D]`` (``[gate | up]`` on the output axis) and
    ``output_linear.weight [E, D, I]``; where the configuration holds one
    expert-parallel rank's share (``cfg.experts_of``) only the held
    experts' slices are read from the file. The shared expert is
    ``shared_mlp.input_linear.weight [2 S, D]`` and ``output_linear
    .weight [D, S]``; the depthwise conv ``mamba.conv1d.weight [C, 1,
    K]`` becomes ``conv_w [K, C]``; the router is read whole (every
    published expert)."""
    from safetensors import safe_open

    from .llama import layer_runs

    first = cfg.expert_rank * cfg.num_experts
    held = slice(first, first + cfg.num_experts)
    inter = cfg.moe_intermediate_size
    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no .safetensors under {model_dir}")

    array = _bf16_numpy
    plain = {**_GRANITE_MIXER, **_GRANITE_ATTN, **_GRANITE_EVERY}
    top: Dict[str, np.ndarray] = {}
    layers: Dict[int, Dict[str, np.ndarray]] = {}
    for path in files:
        with safe_open(path, framework="pt") as f:
            for name in f.keys():
                short = name.removeprefix("model.")
                if short == "embed_tokens.weight":
                    top["embed"] = array(f.get_tensor(name))
                elif short == "norm.weight":
                    top["final_norm"] = array(f.get_tensor(name))
                elif short == "lm_head.weight":
                    top["lm_head"] = array(f.get_tensor(name)).T
                if not short.startswith("layers."):
                    continue
                _, idx, rest = short.split(".", 2)
                lp = layers.setdefault(int(idx), {})
                if rest in plain:
                    key, transpose = plain[rest]
                    t = array(f.get_tensor(name))
                    lp[key] = t.T if transpose else t
                elif rest == "mamba.conv1d.weight":
                    lp["conv_w"] = array(f.get_tensor(name))[:, 0, :].T
                elif rest == "block_sparse_moe.input_linear.weight":
                    # the held experts' slices alone leave the file
                    t = array(f.get_slice(name)[held]).transpose(0, 2, 1)
                    lp["w_gate"], lp["w_up"] = t[..., :inter], t[..., inter:]
                elif rest == "block_sparse_moe.output_linear.weight":
                    lp["w_down"] = array(f.get_slice(name)[held]).transpose(0, 2, 1)
                elif rest == "shared_mlp.input_linear.weight":
                    t = array(f.get_tensor(name)).T
                    half = t.shape[1] // 2
                    lp["w_sh_gate"], lp["w_sh_up"] = t[:, :half], t[:, half:]
                elif rest == "shared_mlp.output_linear.weight":
                    lp["w_sh_down"] = array(f.get_tensor(name)).T
                else:
                    logger.debug("skipping unmapped tensor %s", name)

    every = [k for k, _ in _GRANITE_EVERY.values()] + [
        "w_gate", "w_up", "w_down", "w_sh_gate", "w_sh_up", "w_sh_down"]
    of_kind = {"mamba": [k for k, _ in _GRANITE_MIXER.values()] + ["conv_w"],
               "attention": [k for k, _ in _GRANITE_ATTN.values()]}
    runs, at = [], 0
    for kind, _, n in layer_runs(cfg.layer_types):
        keys = every + of_kind[kind]
        missing = [(i, k) for i in range(at, at + n) for k in keys
                   if k not in layers.get(i, {})]
        if missing:
            raise ValueError(
                f"incomplete checkpoint: {kind} layers {at}-{at + n - 1} "
                f"lack {missing[:4]}")
        runs.append({k: jnp.asarray(
            np.stack([layers[i][k] for i in range(at, at + n)]),
            dtype=jnp.float32 if k in _GRANITE_F32 else dtype) for k in keys})
        at += n
    if "embed" not in top or "final_norm" not in top:
        raise ValueError("incomplete checkpoint: embed_tokens or norm missing")
    params = {"embed": jnp.asarray(top["embed"], dtype=dtype), "runs": runs,
              "final_norm": jnp.asarray(top["final_norm"], dtype=dtype)}
    if "lm_head" in top and not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(top["lm_head"], dtype=dtype)
    return params


def load_checkpoint_params(model_dir: str, cfg: ModelConfig, arch, dtype=jnp.bfloat16) -> Dict:
    """Dispatch to the loader for the resolved architecture module.

    ``model_dir`` may be an HF snapshot directory or a ``.gguf`` file.
    Raises (rather than silently serving random weights — a user pointing
    the engine at a real checkpoint must never get plausible-looking
    garbage) when no loader exists for the architecture.
    """
    name = arch.__name__.rsplit(".", 1)[-1]
    if model_dir.endswith(".gguf"):
        if name != "llama":
            raise NotImplementedError(
                f"gguf loading is llama-family only (got {name!r})"
            )
        return load_gguf_llama_params(model_dir, cfg, dtype)
    loaders = {
        "llama": load_llama_params,
        "mixtral": load_mixtral_params,
        "deepseek": load_deepseek_params,
        "gemma2": load_gemma2_params,
        "gptoss": load_gptoss_params,
        "granite_hybrid": load_granite_hybrid_params,
    }
    if name not in loaders:
        raise NotImplementedError(
            f"no weight loader for architecture {name!r} (checkpoint at {model_dir})"
        )
    return loaders[name](model_dir, cfg, dtype)


def has_checkpoint(model_dir: str) -> bool:
    if model_dir.endswith(".gguf"):
        return os.path.exists(model_dir)
    return bool(glob.glob(os.path.join(model_dir, "*.safetensors")))

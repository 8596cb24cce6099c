"""Engine configuration: model architecture + serving shapes + mesh layout.

Everything that determines compiled-program shapes lives here, because under
jit every distinct shape is a recompile: decode batch is fixed at
``max_batch_size`` (inactive slots masked), prefill lengths are bucketed,
block tables are fixed-width.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import List, Optional, Tuple


@dataclasses.dataclass
class ModelConfig:
    """Llama-family architecture description (HF config.json compatible)."""

    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 16
    num_heads: int = 16
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    rope_theta: float = 10000.0
    # HF rope_scaling dict (rope_type/type + params): "linear", "llama3"
    # and "yarn" (incl. DeepSeek's mscale variant) are applied exactly
    # (models/llama.rope_frequencies); other types load with a loud
    # warning (unscaled frequencies)
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    # qkv projection biases (Qwen2-family); o_proj stays bias-free
    attention_bias: bool = False
    # MoE (Mixtral-class); num_experts == 0 means dense
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 0    # per-expert width; 0 → intermediate_size
    n_shared_experts: int = 0         # DeepSeek always-on shared expert count
    first_k_dense_replace: int = 0    # DeepSeek: first k layers use dense MLP
    # routing semantics (DeepSeek): gate score fn, top-k weight normalization,
    # and the scaling applied to the routed (non-shared) output
    moe_scoring_func: str = "softmax"  # "softmax" | "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # DeepSeek group-limited routing: experts partition into n_group
    # groups, top-k selection is restricted to the topk_group
    # best-scoring groups (V2 "group_limited_greedy" scores a group by
    # its max expert, V3 "noaux_tc" by its top-2 sum of biased scores).
    # n_group == 1 disables the restriction (Mixtral/Qwen/V2-Lite).
    n_group: int = 1
    topk_group: int = 1
    # DeepSeek's published topk_method; "noaux_tc" (V3) carries a
    # per-expert selection bias, which random-weight init must make
    topk_method: str = ""
    # attention implementation: "auto" (pallas on TPU, xla elsewhere),
    # "xla", or "pallas"
    attention_impl: str = "auto"
    # serving-time weight quantization: None (checkpoint dtype) or "int8"
    # (per-out-channel weight-only; halves the decode weight stream —
    # models/quant.py QUANT_KEYS: llama-family trunks, MoE expert
    # stacks incl. GPT-OSS fused gate/up, DeepSeek shared experts and
    # MLA low-rank projections).
    quantization: Optional[str] = None
    # Gemma-2 family (models/gemma2.py): sandwich norms, GeGLU, logit
    # softcapping, alternating sliding-window attention. model_family
    # "gemma2" routes models.resolve; the numeric fields are 0/off for
    # every other family.
    model_family: str = ""
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    query_pre_attn_scalar: int = 0
    sliding_window: int = 0
    # MLA (DeepSeek-class); kv_lora_rank > 0 enables MLA attention
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    qk_nope_head_dim: int = 0
    v_head_dim: int = 0
    # Falcon-H1 (models/falcon_h1.py, model_family "falcon_h1"): a Mamba-2
    # state-space mixer beside attention in every layer. mamba_d_ssm > 0
    # says the family keeps recurrent state by slot. The multipliers are
    # the published fixed µP scalars; 1.0 everywhere else (key_multiplier
    # is applied by llama.qkv_prologue).
    mamba_d_ssm: int = 0
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5   # z, x, B, C, dt
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)   # gate, down
    # manifold-constrained hyper-connections (models/mhc.py, model_type
    # "xing4_0"): hc_mult > 1 residual streams, mixed around every
    # sublayer by per-token matrices that hc_sinkhorn_iters Sinkhorn
    # iterations make doubly stochastic. hc_mult == 1 is the plain
    # residual path of every other family.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    # MiniCPM-SALA (models/minicpm_sala.py, model_type "minicpm_sala"):
    # mixer_types names each layer's token mixer, "lightning-attn" (gated
    # linear attention, state by slot, no pages) or "minicpm4" (InfLLM-V2
    # block-sparse attention, pages and compressed keys, no state). Empty
    # for every other family. depth_of / first_layer say which layers of
    # the published trunk these are when it is served cut in depth (the
    # residual's scale and the decays are functions of the published
    # depth and index); the sparse_* fields are the published
    # sparse_config, in tokens.
    mixer_types: Tuple[str, ...] = ()
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    dim_model_base: int = 0
    depth_of: int = 0
    first_layer: int = 0
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    # AFMoE (models/afmoe.py, model_type "afmoe"): layer_types names each
    # layer's attention, "sliding_attention" (the last sliding_window
    # keys, rotary embedding, pages given back behind the window) or
    # "full_attention" (every key, no positional term). Empty for every
    # other family (Gemma-2 and GPT-OSS alternate by the layer's index).
    layer_types: Tuple[str, ...] = ()
    # SDAR (models/sdar.py, model_type "sdar_moe"): generation by diffusion
    # over blocks of block_length positions. A block's positions hold
    # mask_token_id until a denoise pass unmasks them, denoising_steps
    # passes a block, which positions by remasking_strategy ("sequential",
    # "low_confidence_static", "low_confidence_dynamic" with
    # confidence_threshold). block_length 0: one token a row a pass, as
    # every other family decodes.
    block_length: int = 0
    mask_token_id: int = -1
    denoising_steps: int = 0
    remasking_strategy: str = ""
    confidence_threshold: float = 0.0
    # Granite 4.0-H (models/granite_hybrid.py, model_type
    # "granitemoehybrid"): layer_types names each layer's token mixer,
    # "mamba" (Falcon-H1's Mamba-2 mixer alone: state by slot, no pages)
    # or "attention" (GQA with no positional term: pages, no state);
    # routed experts and a shared expert of shared_intermediate_size
    # follow every layer. Every sublayer adds residual_multiplier times
    # its output; the softmax scale is attention_multiplier (0: the
    # head's 1/sqrt(head_dim) of every other family).
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    shared_intermediate_size: int = 0
    # one expert-parallel rank's share of every expert layer, on one
    # device and with no exchange (models/mixtral.routed_experts):
    # num_experts counts the experts held, experts_of the published
    # ones the router scores (0: every expert is held) and expert_rank
    # says which share: experts [expert_rank * num_experts, + num_experts)
    experts_of: int = 0
    expert_rank: int = 0
    # Kimi Linear (models/kimi_linear.py, model_type "kimi_linear"):
    # layer_types names each layer's token mixer, "kda" (Kimi Delta
    # Attention: kda_num_heads heads of kda_head_dim, a float32 state
    # [head_dim, head_dim] a head and the causal conv's last
    # kda_conv_kernel - 1 inputs, by slot, no pages) or "mla" (latent
    # attention with no positional term: pages, no state).
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    # dots3_note (models/dots3.py, model_type "dots3_note"): layer_types
    # names each layer's attention, both kinds latent. A
    # "full_attention" layer (the fields every latent family reads:
    # num_heads, kv_lora_rank, ..., rope_theta) scores every key with a
    # learned indexer of index_n_heads heads of index_head_dim over a
    # cache of one such key a token and attends to the index_topk best
    # alone; a "sliding_attention" layer has a head count, ranks, head
    # sizes and a rope base of its own (swa_*) and sees the last
    # sliding_window keys, its pages given back behind the window.
    # mla_lora_rescale: the constants (hidden / rank)^1/2 after the two
    # latent norms; attention_gate "headwise": a sigmoid of one logit a
    # head on the attention output, before W_o.
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    mla_lora_rescale: bool = False
    attention_gate: str = ""
    # MiMo-V2 (models/mimo_v2.py, model_type "mimo_v2"): layer_types names
    # each layer's attention, both kinds grouped-query over pages a kv
    # head. A "sliding_attention" layer has swa_num_kv_heads kv heads
    # (num_kv_heads is a full layer's), its own rope base
    # (swa_rope_theta), sees the last sliding_window keys and, with
    # swa_sink_bias, a learned sink logit a query head; keys are head_dim
    # wide and values v_head_dim; the first int(head_dim *
    # partial_rotary_factor) lanes of a head are rotated; the values are
    # multiplied by attention_value_scale before the product.
    swa_num_kv_heads: int = 0
    partial_rotary_factor: float = 1.0
    attention_value_scale: float = 1.0
    swa_sink_bias: bool = False
    full_sink_bias: bool = False
    # Nemotron-H (models/nemotron_h.py, model_type "nemotron_h"):
    # layer_types names each layer's one sublayer, "mamba" (Falcon-H1's
    # mixer alone: state by slot), "attention" (GQA with no positional
    # term: pages) or "moe" (routed experts and a shared expert); a layer
    # is a norm, that sublayer and one residual add. moe_latent_size > 0:
    # the routed experts work in a latent of that width (the token is
    # projected into it once before the dispatch and the gated sum out
    # of it once; the router and the shared expert read the hidden
    # stream). mlp_hidden_act "relu2": an expert, routed or shared, is
    # two matrices around relu(.)^2 and has no gate matrix ("silu": the
    # SwiGLU of every other family). The shared expert's width is
    # shared_intermediate_size.
    moe_latent_size: int = 0
    mlp_hidden_act: str = "silu"

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        if self.kv_lora_rank > 0:
            missing = [
                name for name in
                ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
                if getattr(self, name) <= 0
            ]
            if missing:
                raise ValueError(
                    f"kv_lora_rank={self.kv_lora_rank} selects MLA attention, "
                    f"which also requires {', '.join(missing)} > 0"
                )
        if self.experts_of and (
                self.num_experts <= 0 or self.experts_of % self.num_experts
                or not 0 <= self.expert_rank < self.experts_of // self.num_experts):
            raise ValueError(
                f"a share of {self.num_experts} experts, rank "
                f"{self.expert_rank}, does not divide the published "
                f"{self.experts_of}")

    def num_moe_layers(self) -> int:
        """Layers that hold routed experts: those ``layer_types`` names
        ``moe`` where it names any (a trunk whose layers are one
        sublayer each: models/nemotron_h.py), else every layer past the
        dense prefix."""
        if "moe" in self.layer_types:
            return self.layer_types.count("moe")
        return self.num_layers - min(self.first_k_dense_replace, self.num_layers)

    @classmethod
    def from_hf_config(cls, config: dict) -> "ModelConfig":
        arch = str(config.get("architectures", "")).lower()
        rope_scaling = config.get("rope_scaling") or None
        if rope_scaling and rope_scaling.get(
                "rope_type", rope_scaling.get("type")) in ("longrope", "su"):
            # longrope's profile choice and attention factor need the
            # original/extended windows, which live OUTSIDE the HF
            # rope_scaling dict — carry them in (models/llama.py)
            rope_scaling = dict(rope_scaling)
            rope_scaling.setdefault(
                "original_max_position_embeddings",
                config.get("original_max_position_embeddings")
                or config.get("max_position_embeddings", 4096),
            )
            rope_scaling.setdefault(
                "max_position_embeddings",
                config.get("max_position_embeddings", 4096),
            )
        if config.get("num_experts") and (
            config.get("mlp_only_layers")
            or config.get("decoder_sparse_step", 1) != 1
        ):
            # Qwen-MoE variants that interleave dense MLP layers; the
            # MoE trunk here is uniformly sparse
            raise NotImplementedError(
                "MoE checkpoints with mlp_only_layers/decoder_sparse_step "
                "(mixed dense+sparse trunks) are not supported"
            )
        if config.get("shared_expert_intermediate_size"):
            # Qwen2-MoE's sigmoid-gated shared expert — reject at config
            # parse, BEFORE any multi-GB checkpoint stream starts (the
            # loader keeps a tensor-level backstop)
            raise NotImplementedError(
                "Qwen2-MoE checkpoints (gated shared expert) are not "
                "supported; Qwen3-MoE and Mixtral load"
            )
        # the family the config names, and its own translation of its
        # published keys, laid over the common ones below (imported here:
        # the family modules import jax, and this module must not)
        from ..models import published

        model_family, fields = published(config)
        n_group = config.get("n_group", 1) or 1
        topk_group = config.get("topk_group", 1) or 1
        if config.get("topk_method") == "greedy":
            # DeepSeek-V2-Lite ships n_group in its config but routes
            # plain greedy — the restriction is off
            n_group = topk_group = 1
        n_experts = (config.get("num_local_experts", 0)
                     or config.get("n_routed_experts", 0)
                     or config.get("num_experts", 0)  # Qwen-MoE config key
                     or 0)
        if n_group > 1:
            # the group-limited restriction only composes when the
            # expert set tiles evenly into groups and the selection can
            # still fill top_k from the permitted groups
            if n_experts % n_group:
                raise ValueError(
                    f"n_group={n_group} does not divide "
                    f"n_routed_experts={n_experts}"
                )
            if not (1 <= topk_group <= n_group):
                raise ValueError(
                    f"topk_group={topk_group} outside [1, n_group={n_group}]"
                )
            if topk_group * (n_experts // n_group) < config.get(
                    "num_experts_per_tok", 2):
                raise ValueError(
                    "permitted groups hold fewer experts than "
                    "num_experts_per_tok"
                )
        made = cls(
            vocab_size=config.get("vocab_size", 32000),
            hidden_size=config.get("hidden_size", 2048),
            intermediate_size=config.get("intermediate_size", 5632),
            num_layers=config.get("num_hidden_layers", 16),
            num_heads=config.get("num_attention_heads", 16),
            num_kv_heads=config.get(
                "num_key_value_heads", config.get("num_attention_heads", 16)
            ),
            head_dim=config.get("head_dim"),
            # float: Falcon-H1 publishes 1e11 as an integer, past int32
            rope_theta=float(config.get("rope_theta", 10000.0)),
            rope_scaling=rope_scaling,
            # Qwen2-family checkpoints carry qkv biases but their HF config
            # has no attention_bias key — infer from the architecture name
            attention_bias=config.get("attention_bias", "qwen2" in arch),
            rms_norm_eps=config.get("rms_norm_eps", 1e-5),
            max_position_embeddings=config.get("max_position_embeddings", 4096),
            tie_word_embeddings=config.get("tie_word_embeddings", False),
            num_experts=n_experts,
            num_experts_per_tok=config.get("num_experts_per_tok", 2),
            moe_intermediate_size=config.get("moe_intermediate_size", 0) or 0,
            n_shared_experts=config.get("n_shared_experts", 0) or 0,
            first_k_dense_replace=config.get("first_k_dense_replace", 0) or 0,
            moe_scoring_func=config.get("scoring_func", "softmax"),
            norm_topk_prob=config.get("norm_topk_prob", True),
            routed_scaling_factor=config.get("routed_scaling_factor", 1.0) or 1.0,
            n_group=n_group,
            topk_group=topk_group,
            topk_method=config.get("topk_method") or "",
            model_family=model_family,
            attn_logit_softcap=config.get("attn_logit_softcapping") or 0.0,
            final_logit_softcap=config.get("final_logit_softcapping") or 0.0,
            query_pre_attn_scalar=config.get("query_pre_attn_scalar", 0) or 0,
            # honored whenever the checkpoint's HF modeling honors it:
            # mistral/phi3-style configs apply it to every layer; qwen2
            # ships the key but disables it via use_sliding_window
            sliding_window=(
                (config.get("sliding_window", 0) or 0)
                if config.get("use_sliding_window", True) else 0
            ),
            # MLA (DeepSeek config.json keys)
            kv_lora_rank=config.get("kv_lora_rank", 0) or 0,
            q_lora_rank=config.get("q_lora_rank", 0) or 0,
            qk_rope_head_dim=config.get("qk_rope_head_dim", 0) or 0,
            qk_nope_head_dim=config.get("qk_nope_head_dim", 0) or 0,
            v_head_dim=config.get("v_head_dim", 0) or 0,
        )
        return dataclasses.replace(made, **fields) if fields else made

    @classmethod
    def from_model_dir(cls, model_dir: str) -> "ModelConfig":
        """HF snapshot dir (config.json) or a .gguf file."""
        if model_dir.endswith(".gguf"):
            from ..llm.gguf import model_config_from_gguf, read_gguf

            return model_config_from_gguf(read_gguf(model_dir))
        with open(os.path.join(model_dir, "config.json")) as f:
            return cls.from_hf_config(json.load(f))


def default_prefill_buckets(max_len: int) -> List[int]:
    """Powers of two up to max_len — each bucket is one compiled program."""
    buckets = []
    b = 64
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return buckets


@dataclasses.dataclass
class EngineConfig:
    model: ModelConfig
    max_batch_size: int = 8          # concurrent decode slots
    max_model_len: int = 4096        # max tokens per sequence (prompt+gen)
    kv_block_size: int = 16
    num_kv_blocks: int = 2048        # HBM budget for the paged cache
    prefill_buckets: Optional[List[int]] = None
    dtype: str = "bfloat16"
    # paged-KV-cache storage dtype: "auto" stores at the engine dtype;
    # "fp8" stores float8_e4m3fn — halves the decode KV stream and
    # doubles cache capacity for ~6% elementwise KV error (the standard
    # serving lever the reference's engines expose as kv_cache_dtype).
    # Unscaled e4m3: post-rope K and V are O(1), well inside its ±448
    # range. GQA families only (the MLA latent is too quantization-
    # sensitive; ModelRunner rejects the combination).
    kv_cache_dtype: str = "auto"
    # mesh axes: pipeline stages x data-parallel replicas x expert-parallel
    # x tensor-parallel. pp > 1 stages the dense trunk over a collective
    # GPipe schedule (parallel/pipeline.py) — reference analog:
    # pipeline_parallel_size = num_nodes (lib/engines/vllm0_7/src/
    # vllm_inc.py:37-38 over Ray); here it is one SPMD program over the
    # mesh's pp axis.
    pp_size: int = 1
    dp_size: int = 1
    ep_size: int = 1
    tp_size: int = 1
    # sequence parallelism for long-context prefill (parallel/sequence.py,
    # docs/long_context.md): sp_size > 1 adds an ``sp`` mesh axis and
    # compiles a sequence-parallel prefill program (``prefill_sp``) that
    # shards ONE oversized prompt's tokens across the axis — ring
    # attention over the chunk + the committed paged prefix — so a 128k
    # prompt prefills across the slice instead of monopolizing one chip.
    # Decode programs ignore the axis (their specs never name it), so an
    # sp engine decodes exactly as before. Llama-family GQA dense trunks
    # only (the ring kernel has no MLA/MoE/sliding-window variant yet).
    sp_size: int = 1
    # the admission class: prompts whose uncached suffix is at least this
    # long route to the sequence-parallel prefill program (local mesh) or,
    # in disagg mode, bias toward the prefill-worker pool whose workers
    # run the same SP chunk ladder. 0 with sp_size > 1 defaults to
    # max_prefill_tokens_per_step (one dense chunk budget); 0 with
    # sp_size == 1 disables the class entirely.
    long_prefill_threshold_tokens: int = 0
    seed: int = 0
    # serve random-init weights when model_dir has no checkpoint (tests,
    # topology dry runs); off by default so a misnamed checkpoint dir
    # fails loudly instead of serving plausible-looking garbage
    allow_random_weights: bool = False
    # scheduler knobs
    max_prefill_tokens_per_step: int = 8192
    # concurrent prompts batched into ONE prefill step (rows padded to a
    # power-of-two ladder; one compiled program per (rows, bucket)).
    # Serial prefill (the round-2 design) queued TTFT linearly under
    # prompt bursts; batching amortizes the weight stream and per-step
    # overhead across rows. 1 restores strictly-serial behavior.
    max_prefill_batch: int = 4
    # decode steps fused into ONE device dispatch (lax.scan inside the
    # compiled program). Each dispatch pays fixed host+launch overhead
    # (scheduler bookkeeping, transfer latency, program launch); at small
    # per-step compute that overhead dominates, and fusing K steps
    # amortizes it K-fold — the TPU-native analog of the multi-step
    # scheduling the reference's engines use. Tokens stream in bursts of
    # K (ITL becomes bursty), so it only engages when no prefill work is
    # waiting, and 1 (default) keeps strict per-token dispatch. Sampling
    # is bit-identical either way (same per-row PRNG fold-in counters).
    multi_step_decode: int = 1
    # the persistent decode loop: at depth 2 the fused decode burst
    # carries a per-row ``done`` mask and evaluates EOS / hidden-stop /
    # max-tokens / model-len checks INSIDE the scan — finished rows
    # freeze (no further sampling or KV writes, padded emission) instead
    # of ending the burst, so the scheduler dispatches bursts
    # back-to-back off the device-resident carry (JAX dispatch is async)
    # and drains completed rows asynchronously: the host's
    # detokenize/stream/finish-check work overlaps the next burst's
    # device compute. Batch membership compacts only at natural barriers
    # (admission, preemption, KV-OOM, drain). The carry also holds
    # speculative state (trailing-token ring), bounded guided grammar
    # state (guided_device_table below), and the stop-string suffix-hash
    # ring (device-approximate: candidate rows freeze on device, the host
    # confirms exactly on drain, and a hash collision resumes
    # byte-identically), so spec / guided / stop-string / n>1 traffic
    # chains too; a pass the chain refuses runs the
    # synchronous path and is counted in
    # dynamo_engine_sync_fallback_total{reason}. 0/1 = strictly
    # synchronous, 2 = the chain (the only other depth).
    decode_pipeline_depth: int = 1
    # guided decoding inside the chain: compile TrieConstraint /
    # in-bound JsonGrammar cursors to a dense device transition table
    # (state x token -> next state) so the per-token mask is computed
    # on device and the grammar cursor advances in the burst carry.
    # Grammars whose reachable state set exceeds the bound keep the
    # host sync path explicitly (fallback reason "guided_table_bound").
    guided_device_table: bool = True
    guided_table_max_states: int = 256
    # n-gram (prompt-lookup) speculative decoding: propose up to K tokens
    # per decode step by matching the context's trailing n-gram against
    # its own history, then VERIFY all K+1 positions in one forward.
    # Decode is bandwidth-bound (weights stream once per step regardless
    # of S), so accepted tokens are nearly free — the reference's engines
    # ship the same technique (vLLM ngram speculative decoding). Greedy,
    # penalty-free requests only; mixed batches fall back per step.
    spec_ngram_tokens: int = 0   # K proposal tokens (0 = off)
    spec_ngram_match: int = 3    # trailing n-gram length to look up
    # draft-MODEL speculative decoding: a small model proposes K tokens
    # per round (its fused K-step burst = ONE extra dispatch) and the
    # target verifies all K+1 positions in one forward — the
    # draft/verify speculation reference-class engines ship. The draft
    # keeps a mirror paged cache on the SAME block ids as the target
    # (same allocator decisions), so prefix-cache hits, resume, and
    # block registration carry valid draft context for free. Greedy,
    # penalty-free requests only (stream is provably identical either
    # way). Mutually exclusive with ngram speculation; incompatible
    # with the host KV tier (restored blocks would hold stale draft KV).
    spec_draft_model: Optional[str] = None  # HF dir of the draft model
    spec_draft_tokens: int = 0              # K proposals per round (2..16)
    # streamed remote prefill (the disagg prefill worker): the worker
    # always chunks its prefill with the shared bucket ladder +
    # max_prefill_tokens_per_step and streams each chunk's completed KV
    # blocks while the next chunk computes, so remote TTFT approaches
    # max(compute, transfer) instead of compute + transfer. This knob is
    # the transfer plane's frame depth: 2 (default) double-buffers — the
    # next frame's gather/host-pack proceeds while the previous frame's
    # bytes are on the wire — and 1 ships frames strictly serially.
    # Streams are byte-identical at every depth; host memory is bounded
    # at <= depth chunk-sized frames either way.
    disagg_stream_depth: int = 2
    enable_prefix_caching: bool = True
    # host-RAM KV offload tier: evicted HBM blocks are copied out and can be
    # restored on later prefix hits instead of recomputed. 0 disables.
    host_kv_blocks: int = 0
    # cluster KV fabric (kv/fabric.py, docs/kv_fabric.md): cross-worker
    # prefix PULL — when the fabric's ownership view says a peer holds a
    # longer prefix of an incoming prompt than every local tier, the
    # scheduler pulls those committed KV blocks over the transfer plane
    # instead of recomputing them (pull failure/timeout falls back to
    # local recompute, byte-identically). The peer view itself (KV event
    # feed + pull-server descriptors) is wired by the CLI/discovery
    # layer; this flag builds the engine-side machinery.
    prefix_pull: bool = False
    # minimum remote/cold extension (in blocks past the local hit) worth
    # a pull — below this the transfer round trip loses to recompute
    prefix_pull_min_blocks: int = 2
    # per-pull deadline: a dead/stalled source must never hold a request
    # past this before the local-recompute fallback takes over
    prefix_pull_timeout_s: float = 30.0
    # content-addressed cold tier (kv/cold_tier.py): host-tier-evicted
    # blocks spill to checksummed files keyed by sequence hash in this
    # directory, so cold-but-hot-again prefixes (system prompts, RAG
    # documents) survive RAM eviction and ANY worker sharing the
    # directory — including a freshly respawned one — can rehydrate
    # them. Requires host_kv_blocks > 0 (the spill source is host-tier
    # eviction). Both knobs must be set together.
    cold_tier_dir: str = ""
    cold_tier_blocks: int = 0
    # stall watchdog (telemetry/watchdog.py): trip when work is pending
    # but the scheduler loop's heartbeat (or its dispatch counter) has
    # been stale for this long — a wedged Mosaic compile or dead host
    # sync then dumps a flight artifact to DYN_FLIGHT_DIR instead of
    # freezing silently. 0 disables the watchdog. The deadline must
    # comfortably exceed one loop PASS (chunked prefill bounds a pass;
    # a cold late compile is the longest legitimate pass).
    watchdog_stall_s: float = 30.0
    watchdog_interval_s: float = 1.0

    def __post_init__(self):
        if self.prefill_buckets is None:
            self.prefill_buckets = default_prefill_buckets(self.max_model_len)
        self.prefill_buckets = sorted(self.prefill_buckets)
        if self.dp_size > 1:
            if self.max_batch_size % self.dp_size:
                raise ValueError(
                    f"max_batch_size {self.max_batch_size} not divisible "
                    f"by dp_size {self.dp_size} (decode rows shard over dp)"
                )
            # batch rows shard over dp in every compiled program (jit
            # in_shardings P("dp")), so the padded prefill row ladder must
            # stay dp-divisible too — scale it; short batches ride as
            # inert pad rows
            self.PREFILL_ROW_BUCKETS = tuple(
                r * self.dp_size for r in type(self).PREFILL_ROW_BUCKETS
            )
        # clamp into the compiled row ladder: values past the top bucket
        # would admit more rows than the step arrays hold (IndexError in
        # the scheduler), and <= 0 would silently admit nothing
        self.max_prefill_batch = max(
            1, min(self.max_prefill_batch, self.PREFILL_ROW_BUCKETS[-1])
        )
        # a burst must fit comfortably inside one sequence's block budget;
        # 64 already amortizes dispatch overhead past the point of returns
        self.multi_step_decode = max(1, min(self.multi_step_decode, 64))
        # depth > 2 names nothing more: the chain bounds its own bursts
        # in flight (Scheduler.CHAIN_MAX_INFLIGHT) — clamp instead of
        # failing
        self.decode_pipeline_depth = max(0, min(self.decode_pipeline_depth, 2))
        self.guided_table_max_states = max(2, self.guided_table_max_states)
        # one frame in flight is the serial floor; beyond two buys nothing
        # (the wire is busy continuously at 2) and unbounds host buffers
        self.disagg_stream_depth = max(1, min(self.disagg_stream_depth, 2))
        # watchdog: negative means off (same as 0); the sampling interval
        # floors at 50 ms so a mistyped value can't busy-spin the loop
        self.watchdog_stall_s = max(0.0, self.watchdog_stall_s)
        self.watchdog_interval_s = max(0.05, self.watchdog_interval_s)
        self.spec_ngram_tokens = max(0, min(self.spec_ngram_tokens, 16))
        self.spec_ngram_match = max(1, self.spec_ngram_match)
        self.sp_size = max(1, self.sp_size)
        self.long_prefill_threshold_tokens = max(
            0, self.long_prefill_threshold_tokens)
        if self.sp_size > 1:
            if self.prefill_buckets[0] % self.sp_size:
                # every SP chunk pads to a bucket sharded over the axis;
                # the smallest bucket bounds the divisibility requirement
                raise ValueError(
                    f"sp_size {self.sp_size} must divide the smallest "
                    f"prefill bucket {self.prefill_buckets[0]}"
                )
            if self.pp_size > 1:
                raise ValueError(
                    "sp_size > 1 does not compose with pipeline "
                    "parallelism (the SP program assumes an unstaged "
                    "cache)"
                )
            if self.long_prefill_threshold_tokens == 0:
                # default: anything past one dense chunk budget is
                # "long" — it would already take multiple ladder passes
                self.long_prefill_threshold_tokens = (
                    self.max_prefill_tokens_per_step
                    or self.prefill_buckets[-1]
                )
        self.prefix_pull_min_blocks = max(1, self.prefix_pull_min_blocks)
        self.prefix_pull_timeout_s = max(0.1, self.prefix_pull_timeout_s)
        if bool(self.cold_tier_dir) != (self.cold_tier_blocks > 0):
            raise ValueError(
                "cold_tier_dir and cold_tier_blocks must be set together "
                f"(got dir={self.cold_tier_dir!r}, "
                f"blocks={self.cold_tier_blocks})"
            )
        if self.cold_tier_blocks > 0 and self.host_kv_blocks <= 0:
            raise ValueError(
                "the cold tier spills from the host tier: "
                "cold_tier_blocks > 0 requires host_kv_blocks > 0"
            )
        if self.spec_draft_tokens and not self.spec_draft_model:
            raise ValueError(
                "spec_draft_tokens set without spec_draft_model — "
                "speculation would silently stay off"
            )
        if self.spec_draft_model:
            if not 2 <= self.spec_draft_tokens <= 16:
                raise ValueError(
                    "spec_draft_model needs spec_draft_tokens in 2..16 "
                    f"(got {self.spec_draft_tokens}; a 1-token draft "
                    "round never amortizes the extra dispatch)"
                )
            if self.spec_ngram_tokens:
                raise ValueError(
                    "spec_draft_model and spec_ngram_tokens are mutually "
                    "exclusive proposal sources"
                )
            if self.host_kv_blocks:
                raise ValueError(
                    "spec_draft_model is incompatible with the host KV "
                    "tier: restored blocks would carry stale draft KV "
                    "(the draft cache mirrors device block ids only)"
                )

    @property
    def blocks_per_seq(self) -> int:
        return math.ceil(self.max_model_len / self.kv_block_size)

    def window_pages_a_row(self, tokens: int = 1) -> int:
        """Pages of the window kind a row can hold: those that overlap
        the keys ``tokens`` consecutive queries see, [first −
        sliding_window + 1, last]."""
        return math.ceil((self.model.sliding_window + tokens - 1)
                         / self.kv_block_size) + 1

    def prefill_chunk_tokens(self) -> int:
        """The most tokens one row advances in a prefill step (the
        largest bucket inside the step's budget; scheduler.
        prefill_bucket_cap at one row)."""
        budget = self.max_prefill_tokens_per_step
        allowed = [b for b in self.prefill_buckets
                   if not budget or b <= budget]
        return allowed[-1] if allowed else self.prefill_buckets[0]

    def window_pool_pages(self) -> int:
        """Pages of the window kind's pool, for a model whose
        ``layer_types`` has window layers; 0 for every
        other. Derived, not set: page 0, which no sequence holds, what
        every slot holds while decoding, and what the rows of one
        prefill step hold more (a chunk's pages beside the window's; the
        scheduler releases before it takes, so the bound holds without
        preemption)."""
        if "sliding_attention" not in self.model.layer_types:
            return 0
        decoding = self.window_pages_a_row()
        prefilling = self.window_pages_a_row(self.prefill_chunk_tokens())
        return (1 + self.max_batch_size * decoding
                + self.max_prefill_batch * (prefilling - decoding))

    @property
    def chain_enabled(self) -> bool:
        """The persistent decode loop (chained bursts, device-resident
        finish detection) is what ``decode_pipeline_depth >= 2`` means."""
        return self.decode_pipeline_depth >= 2

    def bucket_for(self, length: int) -> int:
        for b in self.prefill_buckets:
            if length <= b:
                return b
        raise ValueError(f"prompt length {length} exceeds max bucket {self.prefill_buckets[-1]}")

    PREFILL_ROW_BUCKETS = (1, 2, 4, 8)

    def prefill_row_buckets(self) -> List[int]:
        """Row-count ladder for batched prefill: the prefill batch pads to
        the next power of two (one compiled program per (rows, bucket));
        warmup sweeps this ladder."""
        cap = self.prefill_row_bucket(self.max_prefill_batch)
        return [r for r in self.PREFILL_ROW_BUCKETS if r <= cap]

    def prefill_row_bucket(self, n: int) -> int:
        for r in self.PREFILL_ROW_BUCKETS:
            if n <= r:
                return r
        return self.PREFILL_ROW_BUCKETS[-1]

    def sp_prefill_bucket(self) -> int:
        """The ONE chunk length the sequence-parallel prefill program
        compiles at: the largest prefill bucket whose PER-DEVICE token
        share (bucket / sp) stays within the per-step budget — the same
        ITL bound the dense ladder honors, scaled by the axis. A fixed
        bucket (short/final chunks pad into it) keeps ``prefill_sp`` at
        exactly one compiled shape."""
        budget = self.max_prefill_tokens_per_step
        if not budget:
            return self.prefill_buckets[-1]
        allowed = [
            b for b in self.prefill_buckets
            if b <= self.sp_size * budget and b % self.sp_size == 0
        ]
        return allowed[-1] if allowed else self.prefill_buckets[0]

    def kv_width_buckets(self) -> List[int]:
        """The decode block-table width ladder: powers of two from 8 up to
        the full per-seq width (always included). A decode-shaped program
        whose trace read the table at its width (the XLA gather, block
        selection) is compiled at every rung; one whose kernels walk a
        row's live pages exists at the full width alone
        (``ModelRunner.table_width``, which the scheduler asks and
        ``ModelRunner.warmup`` follows)."""
        widths = []
        w = 8
        while w < self.blocks_per_seq:
            widths.append(w)
            w *= 2
        widths.append(self.blocks_per_seq)
        return widths

    def kv_width_bucket(self, nblocks: int) -> int:
        """The ladder's smallest rung that covers ``nblocks`` live blocks:
        the table width of a decode step whose attention gathers
        ``[B, W]`` pages, where the cost scales with the table's width
        and a short context must not pay max_model_len's."""
        for w in self.kv_width_buckets():
            if nblocks <= w:
                return w
        return self.blocks_per_seq

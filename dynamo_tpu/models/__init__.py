"""Model registry: one module and one row a family.

``FAMILIES`` is the table: a row names a family (``model_family``'s
value and the module's name here), how a published config or a
``ModelConfig`` selects it, the field that is its alone and whether the
pipeline stages it. What else a family decides it declares in its module
under the names below; ``engine/`` and ``parallel/`` read those and name
no family (a ``getattr(arch, ...)`` for a name not listed here fails
``tests/test_model_families.py``).

What families share is written once. ``llama`` is the base every family
imports; ``deepseek`` owns latent attention, ``falcon_h1`` the Mamba-2
mixer and the records by slot, ``mixtral`` the router and the grouped
experts. A family whose layers are of more than one kind takes from
``models/trunk.py`` the walk over them (``walk_runs`` for weights
stacked by run, ``walk_periods`` for weights stacked by kind behind a
dense prefix, ``walk_kinds`` for layers of one sublayer each stacked by
kind), its ``forward`` over its ``forward_counted``
(``forward_over``) and a side of its cache (``SlotCache``,
``KindCache``), and writes no loop over layers of its own; a family
imports a sibling only for what it declares over it (its
``SEQUENCE_STATE``), and no name that crosses a module has a leading
underscore. Its reference test is a tiny config, a reference module,
tolerances and faults over ``tests/served.py``, the one driver of a
family's served path (docs/models.md, Adding a family).

**Required** of every module, called by ``ModelRunner``:
``init_params(cfg, key, dtype)``, ``param_specs(params)``,
``init_kv_cache(cfg, num_blocks, block_size, dtype, num_slots=,
window_blocks=, max_len=)`` (the engine offers every family its decode
slots, the window pool's pages and the longest sequence it admits; a
family takes what it keeps),
``forward(params, cfg, tokens, positions, kv_cache, block_tables,
slot_mapping, context_lens, mesh=, return_hidden=, state_slots=)``
(each row's slot, for records by slot) and
``logits_from_hidden(hidden, params, cfg)``.

**Optional**, and who asks:

- ``config_fields(config)``: ``ModelConfig`` fields from the family's
  own published keys, refusing what the module does not compute;
  required of a row selected by name (``published``, for
  ``ModelConfig.from_hf_config``);
- ``claimed_keys(config)`` over ``CLAIMED_KEYS`` / ``CLAIMED_PREFIXES``,
  and ``CLAIM``: the published keys only this family computes and the
  sentence that refuses them under another ``model_type``; required of
  a row with a ``field`` (``published``);
- ``SEQUENCE_STATE``: what a sequence keeps besides one kind of page and
  the paths refused for it; default ``PAGES_ONLY`` (``ModelRunner``,
  ``Scheduler`` through ``runner.keeps``);
- ``decode_unit(cfg)``: a ``BlockUnit`` where the family's decode unit
  is a block of positions and not one token: a pass over a row unmasks
  some of the block's positions or finds it whole and keeps it (the
  block's length, the mask id, the passes a block and the rule that
  picks what a pass unmasks, and the paths refused for it); default
  ``None``, one token a row a pass (``ModelRunner``, ``Scheduler``
  through ``runner.unit``, ``engine/serving.py`` for what a request may
  not ask of it);
- ``CACHE_SPEC``: the sharding of a cache side that is not a bare page
  stack (``ModelRunner``, ``scripts/layer_loop.py``); such a side
  answers ``.pages`` (those that grow with the context) and ``.rest``
  for the runner's byte counts, ``.dtype`` for ``benchmark/run.py``;
- ``STEP_COUNTERS``, ``step_counts(kv_cache)``: counters the trunk keeps
  in its cache (``ModelRunner._init_family_counters``);
- ``forward_counted``: the trunk with the routed experts' counters
  (``ModelRunner._make_forward`` when ``cfg.num_experts > 0``);
- ``embed_forward``: the cacheless trunk (``ModelRunner.embed_prompts``);
- ``refuse_staged(engine_config)``: what a ``staged`` family refuses
  under ``pp_size > 1`` (``ModelRunner``);
- ``pp_trunk_specs``, ``embed_tokens``, ``make_attn_fn``, ``run_layers``,
  ``mlp_fn``, ``make_moe_mlp_fn``, ``make_mlp_fn``: a staged family's
  pieces, llama's by default (``parallel/pipeline.py``).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Dict, Mapping, Optional, Tuple

from ..engine.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class SequenceState:
    """What a family keeps for a sequence besides one kind of page, and
    the paths that would move, share or roll back a sequence's pages
    without it, each refused by name at start-up
    (``ModelRunner.refuse_without_state``)."""
    slots: bool = False         # records by slot beside the pages
    window_pool: bool = False   # a second pool of pages, a table of its own
    keeps: str = ""             # the sentence a refusal names it with
    refused: Mapping[str, str] = dataclasses.field(default_factory=dict)

    @property
    def private(self) -> bool:
        """No other sequence can take this one's pages: prefix hits are
        blanked, no block is registered, resume is from position 0."""
        return self.slots or self.window_pool


PAGES_ONLY = SequenceState()

REMASKING = ("sequential", "low_confidence_static", "low_confidence_dynamic")


@dataclasses.dataclass(frozen=True)
class BlockUnit:
    """A family whose decode unit is a block (``decode_unit``): a row's
    pending unit is ``length`` token ids at positions ``[n, n + length)``,
    ``n`` a multiple of ``length``, each a token or ``mask_id``. A denoise
    pass samples every masked position and unmasks some, ``length //
    steps`` of them (the remainder to the first passes) by ``strategy``;
    the pass that leaves no mask makes the block whole and its tokens
    leave then; its final keys and values are kept by the pass that
    first denoises the block behind it, which carries both blocks (a
    pass is ``2 · length`` positions a row; there is no commit pass).
    ``refused``: engine settings (start-up) and request
    options (admission) that assume one token a row a pass, path ->
    reason."""
    length: int
    mask_id: int
    steps: int
    strategy: str               # one of REMASKING
    threshold: float            # low_confidence_dynamic's
    refused: Mapping[str, str] = dataclasses.field(default_factory=dict)

    def quotas(self) -> Tuple[int, ...]:
        """Positions pass ``t`` of a block unmasks at least, ``t`` in
        ``range(steps)``."""
        base, rem = divmod(self.length, self.steps)
        return tuple(base + (t < rem) for t in range(self.steps))


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    # a published config names it by model_type or by a substring of its
    # architectures; a family told by shape (a rule over a ModelConfig)
    # leaves model_family blank: tests and GGUF build configs without it
    model_types: Tuple[str, ...] = ()
    architecture: str = ""
    shape: Optional[Callable[[ModelConfig], bool]] = None
    # the field no other family reads and what resolve says when it is
    # set and the row not selected; a row with one also claims published
    # keys (its module's claimed_keys and CLAIM)
    field: str = ""
    unserved: str = ""
    # other rows' fields this family computes too: set, they do not
    # refuse it (two families run the state-space mixer; two read
    # layer_types)
    reads: Tuple[str, ...] = ()
    staged: bool = False    # parallel/pipeline.py stages its trunk

    @property
    def module(self):
        return importlib.import_module(f"{__name__}.{self.name}")


# in the order resolve asks: the first row that serves a config has it
FAMILIES = (
    # before deepseek, whose shape rule (kv_lora_rank > 0) would take it
    Family("kimi_linear", model_types=("kimi_linear",),
           field="kda_num_heads", reads=("layer_types",),
           unserved="kda_num_heads={value} needs a family with Kimi Delta "
                    "Attention layers and their state by slot; model_family "
                    "{family!r} has none (models/kimi_linear.py is selected "
                    "by model_type kimi_linear)"),
    # before deepseek too: both kinds of its layers are latent
    Family("dots3", model_types=("dots3_note",),
           field="index_topk", reads=("layer_types",),
           unserved="index_topk={value} needs a family whose full layers "
                    "pick their keys with a learned indexer over a cache of "
                    "its own; model_family {family!r} attends to every key "
                    "(models/dots3.py is selected by model_type dots3_note)"),
    Family("deepseek", model_types=("xing4_0",),
           shape=lambda cfg: cfg.kv_lora_rank > 0, staged=True,
           field="hc_mult",
           unserved="hc_mult={value} needs a trunk that carries the residual "
                    "streams of models/mhc.py; only latent attention "
                    "(model_type xing4_0, models/deepseek.py) does"),
    Family("falcon_h1", model_types=("falcon_h1",), field="mamba_d_ssm",
           unserved="mamba_d_ssm={value} needs a family that keeps recurrent "
                    "state; model_family {family!r} has none "
                    "(models/falcon_h1.py is selected by model_type "
                    "falcon_h1)"),
    Family("minicpm_sala", model_types=("minicpm_sala",), field="mixer_types",
           unserved="mixer_types ({n} entries) needs a family that keeps a "
                    "cache a kind of layer; model_family {family!r} has none "
                    "(models/minicpm_sala.py is selected by model_type "
                    "minicpm_sala)"),
    Family("afmoe", model_types=("afmoe",), field="layer_types",
           unserved="layer_types ({n} entries) needs a family that keeps "
                    "pages a kind of layer; model_family {family!r} has none "
                    "(models/afmoe.py is selected by model_type afmoe)"),
    Family("mimo_v2", model_types=("mimo_v2",),
           field="swa_num_kv_heads", reads=("layer_types",),
           unserved="swa_num_kv_heads={value} needs a family whose window "
                    "layers keep pages of a kv-head count of their own; "
                    "model_family {family!r} keeps one page shape "
                    "(models/mimo_v2.py is selected by model_type mimo_v2)"),
    Family("granite_hybrid", model_types=("granitemoehybrid",),
           field="residual_multiplier", reads=("mamba_d_ssm", "layer_types"),
           unserved="residual_multiplier={value} needs a family that scales "
                    "what every sublayer adds; model_family {family!r} does "
                    "not (models/granite_hybrid.py is selected by model_type "
                    "granitemoehybrid)"),
    Family("nemotron_h", model_types=("nemotron_h",),
           field="moe_latent_size", reads=("mamba_d_ssm", "layer_types"),
           unserved="moe_latent_size={value} needs a family whose routed "
                    "experts work in a latent the token is projected into "
                    "once; model_family {family!r} dispatches the hidden "
                    "stream (models/nemotron_h.py is selected by model_type "
                    "nemotron_h)"),
    Family("sdar", model_types=("sdar_moe",), field="block_length",
           unserved="block_length={value} needs a family whose decode unit "
                    "is a block of masked positions; model_family {family!r} "
                    "decodes one token a row a pass (models/sdar.py is "
                    "selected by model_type sdar_moe)"),
    Family("gptoss", architecture="gptoss", staged=True),
    Family("mixtral", shape=lambda cfg: cfg.num_experts > 0, staged=True),
    Family("gemma2", architecture="gemma2", staged=True),
    Family("llama", shape=lambda cfg: True, staged=True),
)


def family(cfg: ModelConfig) -> Family:
    """The row that serves ``cfg``; a config whose fields belong to a
    family that was not selected is refused by name (another trunk would
    serve it without them, and wrong tokens)."""
    row = next(r for r in FAMILIES if (
        r.shape(cfg) if r.shape else cfg.model_family == r.name))
    for other in FAMILIES:
        if other is row or not other.field or other.field in row.reads:
            continue
        value = getattr(cfg, other.field)
        if value != ModelConfig.__dataclass_fields__[other.field].default:
            raise NotImplementedError(other.unserved.format(
                value=value, family=cfg.model_family,
                n=len(value) if isinstance(value, tuple) else 0))
    return row


def resolve(cfg: ModelConfig):
    """Pick the implementing module for an architecture config."""
    return family(cfg).module


def published(config: Mapping) -> Tuple[str, Dict]:
    """``(model_family, fields)`` of a published config: the row it
    names, that family's translation of its own keys
    (``config_fields``), and a refusal by name of every key another
    family claims (a trunk this program has no family for under that
    ``model_type`` would fall through to another and serve nonsense). A
    key two families claim (``mamba_*``, a mixed ``layer_types``) is the
    named row's where it claims it, and refused under a third
    ``model_type`` by the first that does."""
    model_type = config.get("model_type")
    arch = str(config.get("architectures", "")).lower()
    row = next((r for r in FAMILIES if model_type in r.model_types
                or (r.architecture and r.architecture in arch)), None)
    own = set(row.module.claimed_keys(config)) if row and row.field else set()
    for other in FAMILIES:
        if other is row or not other.field:
            continue
        keys = [k for k in other.module.claimed_keys(config) if k not in own]
        if keys:
            raise NotImplementedError(
                f"model_type {model_type!r} carries "
                + other.module.CLAIM.format(keys=", ".join(keys[:4])))
    if row is None:
        return "", {}
    return "" if row.shape else row.name, row.module.config_fields(config)

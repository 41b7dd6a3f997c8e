"""Model-family registry: maps HF `architectures` / engine model ids to
native implementations.

Parity note: the reference selects an engine image by `(engine, imageName)`
from config (reference: internal/modelcontroller/model_controller.go:321-355);
here model *code* is selected by architecture, since the engine is in-tree.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

_FAMILIES: dict[str, "ModelFamily"] = {}


class ModelFamily:
    """A family bundle: config parser, param init, prefill/decode fns."""

    def __init__(
        self,
        name: str,
        *,
        config_from_hf: Callable,
        tiny_config: Callable,
        init_params: Callable,
        param_specs: Callable,
        prefill: Callable,
        decode_step: Callable,
        decode_step_paged: Callable | None = None,
        decode_step_paged_pp: Callable | None = None,
        decode_verify_paged: Callable | None = None,
        decode_verify_paged_pp: Callable | None = None,
        prefill_chunk: Callable | None = None,
        hf_architectures: tuple[str, ...] = (),
        feature: str = "TextGeneration",
        hidden_states=None,
        route_dims: Callable | None = None,
        block_forward_paged: Callable | None = None,
        block_commit: Callable | None = None,
        block_generation: Callable | None = None,
        held_experts: Callable | None = None,
        recurrent_state: Callable | None = None,
        kv_layers: Callable | None = None,
        latent_pages: Callable | None = None,
    ):
        self.hidden_states = hidden_states
        # A family whose page layers keep ONE latent row a token, from which
        # keys and values are both read (multi-head latent attention), says
        # so here: latent_pages(cfg) -> {"row": a token's shape a layer,
        # "dtype"}. The engine then holds ONE pool `[page layers, pages,
        # page, *row]` in `cache.k_pages` and none in `cache.v_pages`
        # (None): `prefill` returns (logits, the rows [page layers, A, S,
        # *row], None, ...) and `decode_step_paged` takes and returns the
        # pool and None. Allocator, block tables, reservation and
        # preemption by recompute are every family's; what would need a
        # chunk graph, a verify forward, a quantizer or a wire format for
        # such rows is refused at construction
        # (docs/concepts/latent-cache.md).
        self.latent_pages = latent_pages
        # A family some of whose layers attend a sliding window and the
        # others every earlier position says so here: kv_layers(cfg) ->
        # {"global_layers": layers that own pages by the sequence's length,
        # "window_layers": layers that own a ring of fixed size a slot,
        # "window": positions a window layer sees}. The engine then stacks
        # its page pool over `global_layers` and keeps a window pool
        # `[window_layers, 1 + slots * ring, page, KVH, D]` beside it
        # (engine.cache.state: "k_window", "v_window"; a slot's ring is
        # pages `1 + slot * ring ..`, fixed when the cache is built), and
        # calls `prefill(..., state=True)`, which returns after k and v (the
        # global layers') the window layers' `{"k_window", "v_window"}:
        # [window_layers, A, S, KVH, D]`, of which the admission writes the
        # ring's positions, and `decode_step_paged(..., state=pools)`,
        # which returns the pools after the pages. What needs a rule for a
        # ring that has already forgotten (prefix cache, chunked prefill,
        # speculation, hand-off, spill) is refused at construction
        # (docs/concepts/window-cache.md).
        self.kv_layers = kv_layers
        # A family some of whose layers keep no keys and values but a state
        # of fixed size a slot says so here: recurrent_state(cfg) ->
        # {"state_layers": layers that own state, "page_layers": layers
        # that own pages, "pools": {name: (a slot's shape, dtype)}}. The
        # engine then stacks its page pool over `page_layers`, keeps a pool
        # `[state_layers, slots, *shape]` a name beside it
        # (engine.cache.state), and calls `prefill(..., state=True)`, which
        # returns after k and v the rows `{name: [state_layers, A, *shape]}`
        # an admission writes whole, and `decode_step_paged(...,
        # state=pools)`, which returns the pools after the pages. What needs
        # a snapshot of such state (prefix cache, chunked prefill,
        # speculation, hand-off, spill) is refused at construction.
        self.recurrent_state = recurrent_state
        # A routed family whose layers hold a SHARE of the experts the
        # router scores says which: held_experts(cfg) -> (first, end) global
        # ids, half-open. Its forwards hand over all the ids a row took;
        # the load counters count held experts as touched.
        self.held_experts = held_experts
        # A family that generates by diffusion over blocks says so here:
        # block_generation(cfg) -> {"block_length", "denoising_steps",
        # "confidence_threshold", "mask_token_id"}. Its decode step is
        # block_forward_paged (a block of positions a slot against the
        # page pool, which it does not write) and block_commit (which rows
        # a denoising forward commits); its prefill runs under the block
        # mask and yields no token. It has no decode_step_paged.
        self.block_forward_paged = block_forward_paged
        self.block_commit = block_commit
        self.block_generation = block_generation
        # A family whose FFN routes tokens to experts says so here:
        # route_dims(cfg) -> (experts, experts per token, routed layers).
        # Its prefill / decode_step / decode_step_paged / prefill_chunk
        # then take `routes=True` and append the expert sets they took,
        # [*rows, routed layers, k] of global expert ids
        # (ops.experts.route_dtype).
        # The pp stage forwards and the verify forwards hand none over.
        self.route_dims = route_dims
        self.name = name
        self.config_from_hf = config_from_hf
        self.tiny_config = tiny_config
        self.init_params = init_params
        self.param_specs = param_specs
        self.prefill = prefill
        self.decode_step = decode_step
        # Paged-KV decode (block tables + page pools): what the engine
        # serves with. An Engine refuses a family that has none.
        self.decode_step_paged = decode_step_paged
        # Pipeline-parallel paged decode (stage-local KV over the pp mesh
        # axis). None = family cannot serve on a pp>1 mesh.
        self.decode_step_paged_pp = decode_step_paged_pp
        # Multi-position verify forward for speculative decoding (None =
        # speculation unsupported for this family).
        self.decode_verify_paged = decode_verify_paged
        # Pipeline-staged verify (None = no speculation on a pp>1 mesh).
        self.decode_verify_paged_pp = decode_verify_paged_pp
        # Incremental chunked prefill (None = whole-prompt prefill only;
        # chunked prefill is also the prefix cache's suffix path).
        self.prefill_chunk = prefill_chunk
        self.hf_architectures = hf_architectures
        self.feature = feature

    @property
    def routes(self) -> bool:
        """Whether this family has a router (derived: it said its dims)."""
        return self.route_dims is not None


def register_model_family(family: ModelFamily) -> ModelFamily:
    _FAMILIES[family.name] = family
    for arch in family.hf_architectures:
        _FAMILIES[arch] = family
    return family


def get_model_family(name: str) -> ModelFamily:
    _ensure_builtin()
    if name not in _FAMILIES:
        raise KeyError(
            f"unknown model family {name!r}; known: {sorted(set(f.name for f in _FAMILIES.values()))}"
        )
    return _FAMILIES[name]


_LOADED = False


def _ensure_builtin() -> None:
    global _LOADED
    if _LOADED:
        return
    from kubeai_tpu.models import llama

    register_model_family(
        ModelFamily(
            "llama",
            config_from_hf=llama.LlamaConfig.from_hf_dict,
            tiny_config=llama.LlamaConfig.tiny,
            init_params=llama.init_params,
            param_specs=llama.param_specs,
            prefill=llama.prefill,
            decode_step=llama.decode_step,
            decode_step_paged=llama.decode_step_paged,
            decode_step_paged_pp=llama.decode_step_paged_pp,
            decode_verify_paged=llama.decode_verify_paged,
            decode_verify_paged_pp=llama.decode_verify_paged_pp,
            prefill_chunk=llama.prefill_chunk,
            hf_architectures=("LlamaForCausalLM", "MistralForCausalLM"),
            hidden_states=llama.hidden_states,
        )
    )
    # Qwen2 is the Llama computation plus q/k/v biases — one implementation,
    # config-driven (attention_bias=True via from_hf_dict model_type).
    register_model_family(
        ModelFamily(
            "qwen",
            config_from_hf=llama.LlamaConfig.from_hf_dict,
            tiny_config=lambda: dataclasses.replace(
                llama.LlamaConfig.tiny(), attention_bias=True
            ),
            init_params=llama.init_params,
            param_specs=llama.param_specs,
            prefill=llama.prefill,
            decode_step=llama.decode_step,
            decode_step_paged=llama.decode_step_paged,
            decode_step_paged_pp=llama.decode_step_paged_pp,
            decode_verify_paged=llama.decode_verify_paged,
            decode_verify_paged_pp=llama.decode_verify_paged_pp,
            # Qwen2 is the llama computation with q/k/v biases, which
            # the chunk graph carries (lp.get("bq") projections) — so
            # chunked prefill and the prefix cache work unchanged.
            prefill_chunk=llama.prefill_chunk,
            hf_architectures=("Qwen2ForCausalLM",),
            hidden_states=llama.hidden_states,
        )
    )
    from kubeai_tpu.models import whisper

    register_model_family(
        ModelFamily(
            "whisper",
            config_from_hf=whisper.WhisperConfig.from_hf_dict,
            tiny_config=whisper.WhisperConfig.tiny,
            init_params=whisper.init_params,
            param_specs=lambda cfg: None,  # replicated (encoder-decoder)
            prefill=None,  # served via TranscriptionServer, not the slot engine
            decode_step=None,
            hf_architectures=("WhisperForConditionalGeneration",),
            feature="SpeechToText",
        )
    )
    # Further families self-register on import.
    from kubeai_tpu.models import (  # noqa: F401
        exaone_moe, gemma, kimi_linear, mixtral, qwen3_next,
    )

    _LOADED = True

"""Gemma / Qwen2 / Mixtral parity against the HF reference implementations
and engine integration for each family."""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.weights import load_hf_config, load_params
from kubeai_tpu.models.registry import get_model_family

GREEDY = SamplingParams(temperature=0.0, max_tokens=6)


def _roundtrip(family_name, hf_model, out_dir, prompt=(3, 14, 15, 92, 65)):
    import torch

    cfg = get_model_family(family_name).config_from_hf(
        load_hf_config(str(out_dir))
    )
    params = load_params(family_name, str(out_dir), cfg, dtype=jnp.float32)
    fam = get_model_family(family_name)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(1, 10)).astype(np.int32)
    ours, _, _ = fam.prefill(
        params, cfg, jnp.asarray(tokens), jnp.asarray([10], jnp.int32)
    )
    with torch.no_grad():
        theirs = hf_model(torch.tensor(tokens.astype(np.int64))).logits[0, -1]
    np.testing.assert_allclose(
        np.asarray(ours)[0], theirs.numpy(), rtol=5e-3, atol=5e-3
    )

    # Greedy generation parity through the engine.
    eng = Engine(
        family_name, cfg, params,
        cfg=EngineConfig(num_slots=2, max_seq_len=64),
    )
    ours_gen = eng.generate([list(prompt)], GREEDY)[0]
    with torch.no_grad():
        out = hf_model.generate(
            torch.tensor([list(prompt)]), max_new_tokens=6,
            do_sample=False, pad_token_id=0,
        )
    assert ours_gen == out[0, len(prompt):].tolist()


@pytest.mark.slow
def test_qwen2_parity(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import Qwen2Config, Qwen2ForCausalLM

    hf_cfg = Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, max_position_embeddings=512,
        tie_word_embeddings=False,
    )
    torch.manual_seed(1)
    model = Qwen2ForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    _roundtrip("qwen", model, tmp_path)


@pytest.mark.slow
def test_gemma_parity(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import GemmaConfig as HFGemmaConfig
    from transformers import GemmaForCausalLM

    hf_cfg = HFGemmaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, rope_theta=10000.0, max_position_embeddings=512,
    )
    torch.manual_seed(2)
    model = GemmaForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    _roundtrip("gemma", model, tmp_path)


@pytest.mark.slow
def test_mixtral_parity(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import MixtralConfig as HFMixtralConfig
    from transformers import MixtralForCausalLM

    hf_cfg = HFMixtralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        rope_theta=10000.0, max_position_embeddings=512,
    )
    torch.manual_seed(3)
    model = MixtralForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    _roundtrip("mixtral", model, tmp_path)


@pytest.mark.slow
def test_mixtral_expert_parallel_matches_single(devices8):
    """EP: experts sharded over the tp axis give identical outputs."""
    from kubeai_tpu.models import mixtral
    from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh

    cfg = mixtral.MixtralConfig.tiny()
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(num_slots=2, max_seq_len=64)
    eng1 = Engine("mixtral", cfg, params, cfg=ecfg)
    mesh = build_mesh(MeshConfig(dp=1, sp=1, tp=4), devices=devices8[:4])
    eng4 = Engine("mixtral", cfg, params, mesh=mesh, cfg=ecfg)
    prompts = [[1, 2, 3, 4], [9, 8, 7]]
    assert eng1.generate(prompts, GREEDY) == eng4.generate(prompts, GREEDY)


@pytest.mark.slow
def test_gemma2_parity(tmp_path):
    """Gemma-2: sandwich norms + attention/final logit softcapping."""
    torch = pytest.importorskip("torch")
    from transformers import Gemma2Config as HFG2, Gemma2ForCausalLM

    hf_cfg = HFG2(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, rope_theta=10000.0, max_position_embeddings=512,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        query_pre_attn_scalar=16, sliding_window=512,  # > seq len: behaves as full attention
    )
    torch.manual_seed(4)
    model = Gemma2ForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    _roundtrip("gemma", model, tmp_path)


@pytest.mark.slow
def test_gemma2_sliding_window_parity(tmp_path):
    """Gemma-2 sliding-window attention ENFORCED: HF parity with a window
    smaller than the sequence (alternating local/global layers), plus a
    divergence check against the unwindowed config."""
    torch = pytest.importorskip("torch")
    from transformers import Gemma2Config as HFG2, Gemma2ForCausalLM

    hf_cfg = HFG2(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, rope_theta=10000.0, max_position_embeddings=512,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        query_pre_attn_scalar=16, sliding_window=8,  # << prompt length
        attn_implementation="eager",
    )
    torch.manual_seed(11)
    model = Gemma2ForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    prompt = tuple(int(x) for x in
                   np.random.default_rng(2).integers(1, 256, 24))
    _roundtrip("gemma", model, tmp_path, prompt=prompt)

    # Divergence: ignoring the window (Gemma-1 style full attention) must
    # change the logits once the prompt exceeds the window.
    import dataclasses

    from kubeai_tpu.models import gemma as gm

    cfg = get_model_family("gemma").config_from_hf(
        load_hf_config(str(tmp_path))
    )
    assert cfg.sliding_window == 8
    params = load_params("gemma", str(tmp_path), cfg, dtype=jnp.float32)
    tokens = jnp.asarray([list(prompt)], jnp.int32)
    lengths = jnp.asarray([len(prompt)], jnp.int32)
    with_win, _, _ = gm.prefill(params, cfg, tokens, lengths)
    no_win, _, _ = gm.prefill(
        params, dataclasses.replace(cfg, sliding_window=None), tokens, lengths
    )
    assert float(jnp.max(jnp.abs(with_win - no_win))) > 1e-3

    # Short sequences (<= window) are unaffected by windowing.
    short = tokens[:, :6]
    sl = jnp.asarray([6], jnp.int32)
    a, _, _ = gm.prefill(params, cfg, short, sl)
    b, _, _ = gm.prefill(
        params, dataclasses.replace(cfg, sliding_window=None), short, sl
    )
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("name", ["gemma2", "mixtral"])
def test_gemma_mixtral_paged_equivalence(name):
    """Paged decode against the cache-free forward for the non-llama
    families (gemma2 incl. alternating sliding-window layers; mixtral
    MoE): the long form of test_engine_paged.py's tier-1 cases."""
    from test_engine_paged import check_against_cache_free_forward

    check_against_cache_free_forward(
        name, (5, 19, 33, 27, 11), SamplingParams(temperature=0.0, max_tokens=10))


@pytest.mark.parametrize(
    "rope_scaling",
    [
        {"rope_type": "linear", "factor": 2.0},
        {"rope_type": "yarn", "factor": 4.0,
         "original_max_position_embeddings": 32},
        {"rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
         "high_freq_factor": 4.0, "original_max_position_embeddings": 32},
    ],
    ids=["linear", "yarn", "llama3"],
)
@pytest.mark.slow
def test_rope_scaling_variant_parity(tmp_path, rope_scaling):
    """Context-extension rope variants match HF exactly (logits + greedy),
    with prompts LONGER than original_max_position_embeddings (32) so
    the scaled bands actually engage (engine context caps at 64)."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFLlama, LlamaForCausalLM

    hf_cfg = HFLlama(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=512, rope_theta=10000.0,
        rope_scaling=dict(rope_scaling),
        attn_implementation="eager",
    )
    torch.manual_seed(7)
    model = LlamaForCausalLM(hf_cfg)
    model.eval()
    out_dir = tmp_path / rope_scaling["rope_type"]
    model.save_pretrained(out_dir, safe_serialization=True)
    prompt = tuple(int(x) for x in
                   np.random.default_rng(9).integers(1, 256, 56))
    _roundtrip("llama", model, out_dir, prompt=prompt)


def test_dynamic_ntk_frequencies_rescale():
    """Dynamic NTK: frequencies rescale at the serving context and reduce
    to the base frequencies when no extension is configured."""
    from kubeai_tpu.ops.rope import rope_frequencies

    base = rope_frequencies(32, 10000.0, None)
    dyn = rope_frequencies(
        32, 10000.0,
        {"rope_type": "dynamic", "factor": 4.0,
         "original_max_position_embeddings": 2048,
         "max_position_embeddings": 8192},
    )
    # Extended context lowers every non-constant frequency.
    assert (dyn[1:] < base[1:]).all()
    # Without original_max_position_embeddings, HF reads the model's
    # context length — the top-level fallback must engage, not no-op.
    fallback = rope_frequencies(
        32, 10000.0, {"rope_type": "dynamic", "factor": 4.0},
        max_position_embeddings=2048,
    )
    assert (fallback[1:] < base[1:]).all()
    import pytest as _pytest

    with _pytest.raises(ValueError):
        rope_frequencies(32, 10000.0, {"rope_type": "dynamic", "factor": 4.0})
    # "default" is HF's explicit no-scaling marker.
    np.testing.assert_allclose(
        rope_frequencies(32, 10000.0, {"rope_type": "default"}), base
    )


# ---- gemma chunked prefill (round 5: enables chunked admission + the
# prefix cache for the family) ------------------------------------------------


def _gemma_chunk_vs_whole(cfg, seed=3):
    from kubeai_tpu.models import gemma as G

    rng = np.random.default_rng(seed)
    params = G.init_params(cfg, jax.random.PRNGKey(seed))
    S, L = 50, 64
    tokens = rng.integers(1, cfg.vocab_size, S)
    want_logits, k_want, v_want = G.prefill(
        params, cfg, jnp.asarray(tokens[None]), jnp.asarray([S])
    )
    C = 16
    k_slot = jnp.zeros((cfg.num_layers, L, cfg.num_kv_heads, cfg.head_dim),
                       jnp.float32)
    v_slot = jnp.zeros_like(k_slot)
    logits = None
    n_chunks = -(-S // C)
    for i in range(n_chunks):
        start = i * C if i < n_chunks - 1 else S - C
        chunk = tokens[start:start + C]
        logits, k_slot, v_slot = G.prefill_chunk(
            params, cfg, jnp.asarray(chunk[None]), jnp.asarray(start),
            jnp.asarray(S), k_slot, v_slot,
            want_logits=(i == n_chunks - 1),
        )
    np.testing.assert_allclose(
        np.asarray(k_slot[:, :S]),
        np.asarray(k_want[:, 0], np.float32),
        atol=2e-2, rtol=2e-2,
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(want_logits), atol=2e-2, rtol=2e-2
    )
    assert int(jnp.argmax(logits)) == int(jnp.argmax(want_logits))


@pytest.mark.slow
def test_gemma_prefill_chunk_matches_whole_prompt():
    from kubeai_tpu.models import gemma as G

    cfg = dc.replace(G.GemmaConfig.tiny(), dtype=jnp.float32)
    _gemma_chunk_vs_whole(cfg)


@pytest.mark.slow
def test_gemma2_prefill_chunk_matches_whole_prompt():
    """Gemma-2 specifics through the chunk graph: sandwich norms, logit
    softcaps, query scale, and the per-layer sliding-window alternation
    with a window SMALLER than the prompt."""
    from kubeai_tpu.models import gemma as G

    cfg = dc.replace(
        G.GemmaConfig.tiny(), dtype=jnp.float32, sandwich_norms=True,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        query_pre_attn_scalar=16.0, sliding_window=8,
    )
    _gemma_chunk_vs_whole(cfg, seed=5)


@pytest.mark.slow
def test_gemma2_engine_chunked_and_prefix_cache():
    """The engine's chunked admission AND prefix cache serve gemma2
    exactly like whole-prompt admission."""
    from kubeai_tpu.engine import Engine, EngineConfig
    from kubeai_tpu.engine.sampling import SamplingParams
    from kubeai_tpu.models import gemma as G

    cfg = dc.replace(
        G.GemmaConfig.tiny(), sandwich_norms=True,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        query_pre_attn_scalar=16.0, sliding_window=8,
    )
    params = G.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(7)
    system = rng.integers(1, cfg.vocab_size, 48).tolist()
    prompts = [system + rng.integers(1, cfg.vocab_size, 12).tolist()
               for _ in range(2)]
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    base = dict(num_slots=2, max_seq_len=256, page_size=16)
    want = Engine("gemma", cfg, params, cfg=EngineConfig(**base)).generate(
        prompts, sp
    )
    chunked = Engine(
        "gemma", cfg, params, cfg=EngineConfig(prefill_chunk=32, **base)
    )
    assert chunked.generate(prompts, sp) == want
    apc = Engine(
        "gemma", cfg, params,
        cfg=EngineConfig(prefill_chunk=32, prefix_cache=True, **base),
    )
    assert apc.generate(prompts, sp) == want
    assert apc.prefix_stats["hit_tokens"] > 0


@pytest.mark.slow
def test_mixtral_engine_chunked_and_prefix_cache():
    """Mixtral (dense top-k MoE) through the engine's chunked admission
    and prefix cache — streams exact vs whole-prompt admission."""
    from kubeai_tpu.models import mixtral as MX

    cfg = MX.MixtralConfig.tiny()
    params = MX.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(8)
    system = rng.integers(1, cfg.vocab_size, 48).tolist()
    prompts = [system + rng.integers(1, cfg.vocab_size, 12).tolist()
               for _ in range(2)]
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    base = dict(num_slots=2, max_seq_len=256, page_size=16)
    want = Engine("mixtral", cfg, params, cfg=EngineConfig(**base)).generate(
        prompts, sp
    )
    chunked = Engine(
        "mixtral", cfg, params, cfg=EngineConfig(prefill_chunk=32, **base)
    )
    assert chunked.generate(prompts, sp) == want
    apc = Engine(
        "mixtral", cfg, params,
        cfg=EngineConfig(prefill_chunk=32, prefix_cache=True, **base),
    )
    assert apc.generate(prompts, sp) == want
    assert apc.prefix_stats["hit_tokens"] > 0


def test_no_family_file_imports_from_another_family():
    """What families share lives under `kubeai_tpu/ops/` under public names
    (the routed expert layer in `ops/experts.py`, the choice of the prefill
    kernel in `ops/attention.py`): a file under `models/` imports no other
    file there but the registry, and the registry imports the families only
    where it loads them."""
    import ast
    import pathlib

    import kubeai_tpu.models as models

    root = pathlib.Path(models.__file__).parent
    reached = {}
    for path in sorted(root.glob("*.py")):
        if path.stem in ("registry", "__init__"):
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom) and node.module:
                names = [node.module] + [
                    f"{node.module}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            else:
                continue
            into = {
                n.split(".")[2] for n in names
                if n.startswith("kubeai_tpu.models.")
            } - {"registry", path.stem}
            if into:
                reached.setdefault(path.stem, set()).update(into)
    assert reached == {}
    assert len(list(root.glob("*.py"))) >= 8  # the walk saw the families

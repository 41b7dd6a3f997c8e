"""`engine/sampling.py:sample` takes the argmax and nothing else where no
row samples (PR 44), and gives every row what it gave before: all-greedy
batches against `argmax`, mixed and all-sampling batches row for row
against the function as it stood before the conditional (kept here
verbatim as the plain reference), and an engine on the CPU in which a
sampled request leaves its slot to greedy ones."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.sampling import (
    MAX_TOP_K,
    SamplingParams,
    any_samples,
    sample,
)
from kubeai_tpu.models import llama


def reference_sample(logits, seeds, positions, temperature, top_k, top_p):
    """`sample` of the parent commit (2d8541a), line for line: every row's
    candidate pool is computed and a greedy row takes its argmax at the end."""
    B, V = logits.shape
    K = min(MAX_TOP_K, V)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp

    vals, idxs = jax.lax.top_k(scaled, K)  # [B, K] descending
    # top-k filter within the candidate pool.
    k_eff = jnp.where(top_k > 0, jnp.minimum(top_k, K), K)  # [B]
    keep_k = jnp.arange(K)[None, :] < k_eff[:, None]

    # top-p (nucleus) over the RENORMALIZED post-top-k distribution.
    kvals = jnp.where(keep_k, vals, -jnp.inf)
    probs = jax.nn.softmax(kvals, axis=-1)
    cumsum = jnp.cumsum(probs, axis=-1)
    keep_p = cumsum - probs < top_p[:, None]
    keep = keep_k & keep_p
    keep = keep.at[:, 0].set(True)  # top-1 always survives
    masked = jnp.where(keep, kvals, -jnp.inf)

    def _row(seed, pos, row_logits):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
        return jax.random.categorical(key, row_logits)

    choice = jax.vmap(_row)(seeds, positions, masked)  # [B] in [0, K)
    sampled = jnp.take_along_axis(idxs, choice[:, None], axis=-1)[:, 0]
    return jnp.where(
        temperature <= 0.0, greedy_tok, sampled.astype(jnp.int32)
    )


SAMPLE = jax.jit(sample)
REFERENCE = jax.jit(reference_sample)
B = 8


def _batch(vocab, temperature, top_k, top_p, seed=0):
    rng = np.random.default_rng(vocab * 31 + seed)
    return (
        jnp.asarray(rng.normal(0.0, 3.0, (B, vocab)), jnp.float32),
        jnp.asarray(rng.integers(0, 2**32, B, dtype=np.uint32)),
        jnp.asarray(rng.integers(1, 4096, B), jnp.int32),
        jnp.asarray(temperature, jnp.float32),
        jnp.full((B,), top_k, jnp.int32),
        jnp.full((B,), top_p, jnp.float32),
    )


@pytest.mark.parametrize("top_k, top_p", [(0, 1.0), (5, 0.3), (200, 0.0)])
@pytest.mark.parametrize("vocab", [64, 32768, 151936])
def test_all_rows_greedy_take_the_argmax(vocab, top_k, top_p):
    args = _batch(vocab, np.zeros(B), top_k, top_p)
    assert not bool(any_samples(args[3]))
    got = SAMPLE(*args)
    assert got.dtype == jnp.int32
    assert np.array_equal(got, np.argmax(np.asarray(args[0]), axis=-1))


# A row's temperature by the kind of batch: some rows greedy (0.0, and a
# negative one, which the parent also served greedily), or every row drawing.
TEMPERATURES = {
    "mixed": [0.0, 0.7, 0.0, 1.3, -1.0, 0.2, 0.0, 1.0],
    "sampling": [0.7, 0.7, 1.0, 1.3, 0.05, 0.2, 2.0, 1.0],
}


@pytest.mark.parametrize("top_p", [0.0, 0.3, 1.0])
@pytest.mark.parametrize("top_k", [0, 1, 5, 64, 200])
@pytest.mark.parametrize("vocab", [48, 1000])  # 48 < MAX_TOP_K
@pytest.mark.parametrize("kind", sorted(TEMPERATURES))
def test_rows_equal_the_parents_sample(kind, vocab, top_k, top_p):
    temperature = np.asarray(TEMPERATURES[kind])
    drew = False
    for seed in range(3):  # other logits, request seeds and positions
        args = _batch(vocab, temperature, top_k, top_p, seed)
        assert bool(any_samples(args[3]))
        got, want = np.asarray(SAMPLE(*args)), np.asarray(REFERENCE(*args))
        assert np.array_equal(got, want), (seed, got, want)
        greedy = np.argmax(np.asarray(args[0]), axis=-1)
        assert np.array_equal(got[temperature <= 0], greedy[temperature <= 0])
        drew = drew or not np.array_equal(got, greedy)
    # The comparison is of draws, not of argmaxes, where the whole pool is
    # left; a pool of one candidate is the argmax.
    if top_k == 1 or top_p == 0.0:
        assert not drew
    elif top_p == 1.0:
        assert drew


def test_a_sampling_row_does_not_depend_on_its_batch_mates():
    """Rows 1, 3, 5 and 7 ask for the same temperature among greedy rows
    and among sampling rows."""
    mixed = _batch(1000, TEMPERATURES["mixed"], 0, 1.0)
    sampling = _batch(1000, TEMPERATURES["sampling"], 0, 1.0)
    rows = [1, 3, 5, 7]
    got = np.asarray(SAMPLE(*mixed))[rows]
    assert np.array_equal(got, np.asarray(SAMPLE(*sampling))[rows])
    assert not np.array_equal(got, np.argmax(np.asarray(mixed[0]), -1)[rows])


# ---- the engine: a sampled request leaves its slot to greedy ones ----------------

PROMPTS = ([1, 2, 3, 4, 5, 6, 7], [9, 8, 7], [11, 12, 13, 14, 15])


def _served(first: SamplingParams):
    """Two slots. A long greedy stream and `first` start together; `first`
    ends early and, a few chunks later, a greedy request takes its slot.
    Returns (tokens by request, what the run saw of the sampler's counter)."""
    cfg = llama.LlamaConfig.tiny()
    eng = Engine(
        "llama", cfg, llama.init_params(cfg, jax.random.PRNGKey(0)),
        cfg=EngineConfig(num_slots=2, max_seq_len=128, page_size=16,
                         decode_chunk=4),
    )
    greedy = SamplingParams(temperature=0.0, max_tokens=40)
    out: dict[int, list[int]] = {}

    def step():
        for ev in eng.step():
            out.setdefault(ev.rid, []).append(ev.token)

    long = eng.add_request(list(PROMPTS[0]), greedy)
    early = eng.add_request(list(PROMPTS[1]), first)
    slot = None
    while early not in out or len(out[early]) < first.max_tokens:
        step()
        slot = next((s for s, r in eng._active.items() if r.rid == early), slot)
    seen = {"while_it_lived": dict(eng.sampler_chunks)}
    # Its slot stands empty while the other stream decodes on; the device's
    # row still holds the temperature it asked for.
    for _ in range(3):
        step()
    assert slot not in eng._active and len(eng._active) == 1
    seen["stale_temp"] = float(np.asarray(eng._state["temp"])[slot])
    seen["slot_empty"] = dict(eng.sampler_chunks)
    late = eng.add_request(
        list(PROMPTS[2]), SamplingParams(temperature=0.0, max_tokens=12))
    step()  # the admission that refills the slot (LIFO: the same one)
    assert eng._active[slot].rid == late
    seen["refilled"] = dict(eng.sampler_chunks)
    while eng.has_work():
        step()
    seen["end"] = dict(eng.sampler_chunks)
    return {"long": out[long], "early": out[early], "late": out[late]}, seen


@pytest.fixture(scope="module")
def runs():
    sampled = _served(SamplingParams(
        temperature=0.9, top_k=8, seed=13, max_tokens=6))
    never = _served(SamplingParams(temperature=0.0, max_tokens=6))
    return sampled, never


def test_greedy_streams_beside_and_after_a_sampled_one_are_the_greedy_runs(runs):
    (sampled, _), (never, _) = runs
    assert len(sampled["early"]) == len(never["early"]) == 6
    assert sampled["early"] != never["early"]  # it did draw
    assert sampled["long"] == never["long"] and len(never["long"]) == 40
    assert sampled["late"] == never["late"] and len(never["late"]) == 12


def test_the_pool_runs_while_the_sampled_request_lives_and_no_longer(runs):
    (_, seen), (_, never) = runs
    lived = seen["while_it_lived"]
    assert lived["pool"] >= 1 and lived["argmax"] == 0
    # The slot it left keeps its temperature on the device until the next
    # admission overwrites it, and holds no page: at most the one chunk
    # that was in flight when its last token was read ran the pool for it.
    assert seen["stale_temp"] == pytest.approx(0.9)
    assert seen["slot_empty"]["pool"] <= lived["pool"] + 1
    assert seen["slot_empty"]["argmax"] >= 2
    # From the admission that refills the slot on, the argmax alone.
    assert (seen["end"]["pool"] == seen["refilled"]["pool"]
            == seen["slot_empty"]["pool"])
    assert seen["end"]["argmax"] > seen["refilled"]["argmax"]
    # A run that never sampled never entered the pool.
    assert never["end"]["pool"] == 0 and never["stale_temp"] == 0.0
    assert never["end"]["argmax"] == sum(seen["end"].values())

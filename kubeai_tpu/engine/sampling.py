"""Token sampling: greedy, temperature, top-k, top-p — jit-safe.

Rows are told apart by masking (no Python control flow on traced values);
the one branch on data is `sample`'s, over the whole batch: all greedy, or
not. Semantics match the conventional engine behavior users calibrate
against: top-k filters first, then top-p operates on the *renormalized*
post-top-k distribution; the most-likely token always survives (so
top_p=0.0 degrades to greedy, not to token 0).

Per-request reproducibility: `sample` takes per-row uint32 seeds and the
current position; the row key is fold_in(PRNGKey(seed), position), so a
request with a fixed seed replays identically regardless of batch-mates.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling config (host-side; arrays built per batch).

    `stop` holds stop *strings*; they operate on detokenized text and are
    enforced by the server layer (kubeai_tpu.engine.server), not here —
    the engine core works purely in token space (EOS token ids).
    """

    temperature: float = 1.0
    top_k: int = 0  # 0 = disabled
    top_p: float = 1.0
    max_tokens: int = 16
    stop: tuple[str, ...] = ()
    seed: int | None = None

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


# Sampling candidate pool: top-k and the nucleus are computed within the
# MAX_TOP_K most likely tokens. Bounds the per-step cost to one
# lax.top_k(64) instead of two full-vocab sorts (a ~10x decode-step win on
# 128k vocabs); the same cap is standard in serving engines.
MAX_TOP_K = 64


def any_samples(temperature: jnp.ndarray) -> jnp.ndarray:
    """Whether any row of `temperature` [B] asks for a draw: the scalar
    `sample` branches on, and the one a decode chunk hands back beside its
    tokens so the host can count what the device ran."""
    return jnp.any(temperature > 0)


@jax.named_scope("sample")
def sample(
    logits: jnp.ndarray,  # [B, V] float32
    seeds: jnp.ndarray,  # [B] uint32 per-request seeds
    positions: jnp.ndarray,  # [B] int32 current position (per-step entropy)
    temperature: jnp.ndarray,  # [B] (0 = greedy)
    top_k: jnp.ndarray,  # [B] int32 (0 = off; capped at MAX_TOP_K)
    top_p: jnp.ndarray,  # [B] float32 (1 = off)
) -> jnp.ndarray:
    """Vectorized per-request sampling. Returns [B] int32 token ids.

    Where no row samples, the argmax and nothing else: the candidate pool
    (`sample_rows`: a top-k over the vocabulary, a softmax, a draw) sits in
    the branch of a conditional that a batch of greedy rows never enters.
    A greedy row gets `argmax(logits)` from either branch, a sampling row
    what `sample_rows` gives it, so no token depends on its batch-mates."""
    return jax.lax.cond(
        any_samples(temperature),
        sample_rows,
        lambda logits, *_: jnp.argmax(logits, axis=-1).astype(jnp.int32),
        logits, seeds, positions, temperature, top_k, top_p,
    )


def sample_rows(logits, seeds, positions, temperature, top_k, top_p):
    """`sample`'s branch for a batch in which some row samples (arguments
    and result as there): every row's candidate pool is computed, and a
    greedy row takes its argmax at the end."""
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

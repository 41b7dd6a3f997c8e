"""Plain reference for the SDAR-MoE architecture (SDARMoeForCausalLM, e.g.
SDAR-30B-A3B-Chat): the Qwen3-MoE decoder, generated by diffusion over blocks.

**A layer**, on rows `x` of width E: `h = rms_norm(x)`; `q = h Wq` (H heads of
D, where `head_dim` is a key of its own: H * D need not be E), `k = h Wk`,
`v = h Wv` (KVH heads of D), no biases; q and k are RMS-normed over D, a head,
with one weight of size D each that all heads share (QK-norm, in the family's
modeling file, not a key of `config.json`), THEN rotated (rotate-half, theta
from the config); attention with scale D^-1/2 under the **block mask**: row i
sees column j iff `j // B <= i // B`, causal between blocks of B positions and
full inside one; `x = x + attn Wo`. Then `h = rms_norm(x)`, router logits
`h Wr` over `num_experts`, the `num_experts_per_tok` largest selected, weights
the softmax over the SELECTED logits (`norm_topk_prob` true: a softmax over
all experts renormalised over the selected gives the same numbers), and
`x = x + sum_e w_e (silu(h Wg_e) * (h Wu_e)) Wd_e` over the selected experts of
width `moe_intermediate_size`. No shared expert; every layer is routed
(`decoder_sparse_step` 1, `mlp_only_layers` []). Final norm, untied head.
Logits at a position predict the token AT that position (no shift).

**Generation** (the family's published `block_diffusion_generate`; B, T, the
threshold and the mask id are no keys of `config.json` and are read from the
configuration file's `assumed` group: `block_length`, `denoising_steps`,
`confidence_threshold`, `mask_token_id`; strategy `low_confidence_dynamic`).
The prompt's whole blocks (`P // B * B` tokens) are one forward under the
block mask, which writes their K and V; the `P % B` tokens left over open the
first block. A block is its known tokens and `mask_token_id` elsewhere. A
denoising forward runs the block's B rows against the cache and its own B
rows and commits, among the rows still masked, the most confident one (the
first of equals) and every one whose confidence is over the threshold, each
to its top token: 1 to T forwards a block. Once no mask is left, one more
forward over the finished block writes its K and V. Tokens are served in
position order; the last block is filled whole and cut at `max_tokens`.
**The corner the published code leaves open**: a top token that is itself
`mask_token_id` would leave the row masked for ever. Here, as in the program,
the mask id is never a candidate: its logit is taken out before the argmax
and before the softmax the confidence is read from. Every other reserved id
is a token like any other.

**The hand-over** (`HANDOVER`: the request flag `kubeai_forwards`). A chunk
carries a list of packed blocks `{"forwards", "shape", "dtype", "data"}`: the
forwards of the model over the request's rows since its last chunk, in the
order they ran. `shape` is `[routed layers, k]`; `data` (base64) holds, a
forward, little-endian uint32 `start` (the first row's position), `rows`
(their number), `n`, the `n` offsets of the rows this forward committed and
the `n` tokens it committed them to (`n` = 0 for the prompt's forward and for
a block's last), then the rows' expert sets, `rows x layers x k` ids of
`dtype`, row-major, as on the `kubeai_routes` wire. **The rule**
(`_rule`): the prompt's forward first, if `P // B` > 0 (start 0, `P // B * B`
rows); then, a block from `P // B * B` on, 1 to T denoising forwards of B rows
whose commits are non-empty, name rows that were still masked, and together
name each masked row once, then one forward with no commit; blocks up to the
one that holds the last served token, nothing missing, nothing twice, nothing
left over; every committed token an id of the vocabulary other than the mask;
every set k distinct ids below the number of experts. Replay is teacher-forced
on the SERVED tokens (a committed row holds the token that was served there;
`tokens` fills the last block's rows past them), so a served token that is
not what the forward chose shows as a gap. A hand-over that breaks
the rule is not replayed (None; a request re-admitted after a preemption
hands its rows over twice and breaks it: the cells reserve every page).

**Decisions** (`DECISIONS`). `routes`: one a (forward, row, layer); the
selection score is the router logit, and the trail is the reference's own
k-th logit minus the lowest logit of the given set. `order`: one a denoising
forward; the selection score is the confidence (the top token's probability,
the mask id left out). The given commit differs where it is not the set the
rule above would commit on that state, and trails by the larger of: how far a
committed row that the rule would leave masked lies under the nearer of the
rule's two bars (the best masked row's confidence, the threshold); how far a
row left masked lies over the threshold.

**How it is computed, and the one departure.** Float32, matmul precision
"highest", no kernel, one request at a time through attention and one expert
at a time through the mixture. Replay is teacher-forced, so the state of every
forward is known from the hand-over before anything runs; and because the
mask is causal between blocks, the K and V a block leaves in the cache are
those of one forward over the whole sequence under the block mask. So a
request's finished blocks are computed once (that forward; its rows are the
prompt's forward and each block's last), and its denoising forwards, each B
rows against those K and V below its block and its own B rows, beside them.
That is the mathematics, not the program's cache. With `follow="own"` the
denoising forwards of a block depend on one another and are run in rounds.

Imports nothing of the program. Weights come from the seed as Mixtral's do
(perf/reference/mixtral.py), with the two QK-norm weights beside them.
"""

from __future__ import annotations

import base64

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference import mistral as base
from perf.reference import mixtral as routed
from perf.reference.mistral import _Frozen, _f32, matmul, rms_norm

HANDOVER = ("kubeai_forwards",)
DECISIONS = ("routes", "order")
# Leaves of `served_params` whose last axis is the experts: what the planted
# fault `--break-path route` permutes (perf/check.py:break_router).
ROUTER_LEAVES = ("router",)
DISPATCH_BLOCK = 1024  # rows of one expert a call: one shape, whatever the load
HEAD_ROWS = 512  # rows a call of the head takes
WIRE_DTYPES = {"uint8": "<u1", "uint16": "<u2", "uint32": "<u4"}
BF16 = 2


def generation(hf: dict) -> dict:
    """B, T, the threshold and the mask id: the configuration's `assumed`
    group, else the family's published defaults."""
    how = {**hf, **(hf.get("assumed") or {})}
    return {"B": int(how.get("block_length", 4)),
            "T": int(how.get("denoising_steps", 4)),
            "threshold": float(how.get("confidence_threshold", 0.9)),
            "mask": int(how.get("mask_token_id", 151669))}


def _mx(hf: dict) -> dict:
    """The keys Mixtral's reference reads, from this family's."""
    return _Frozen({k: v for k, v in {
        **hf, "num_local_experts": hf["num_experts"],
        "intermediate_size": hf["moe_intermediate_size"]}.items()
        if not isinstance(v, (dict, list))})


# ---- the seeded weights -----------------------------------------------------


def shared_weights(hf: dict, seed_key, layer) -> dict:
    """Attention, norms, QK-norm and router of one block."""
    w = routed.shared_weights(_mx(hf), seed_key, layer)
    d = base.sizes(_mx(hf))["D"]
    w["q_norm"] = jnp.ones((d,), jnp.bfloat16)
    w["k_norm"] = jnp.ones((d,), jnp.bfloat16)
    return w


def layer_weights(hf: dict, seed_key, layer) -> dict:
    lkey = base.layer_key(seed_key, layer)
    experts = jax.vmap(lambda x: routed.expert_weights(_mx(hf), lkey, x))(
        jnp.arange(hf["num_experts"], dtype=jnp.int32))
    return {**shared_weights(hf, seed_key, layer), **experts}


def served_params(hf: dict, seed_key) -> dict:
    return base.served_params(_mx(hf), seed_key, layer_fn=lambda _, k, l: (
        layer_weights(hf, k, l)))


# ---- a layer ----------------------------------------------------------------


def rope(x, positions, theta):
    """x [T, heads, D] at `positions` [T]; rotate-half convention."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def qkv(hf, x, w, positions, quant=None):
    """x [T, E] -> q [T, H, D], k and v [T, KVH, D]: projected, q and k
    RMS-normed a head, then rotated."""
    s, eps = base.sizes(hf), hf["rms_norm_eps"]
    t = x.shape[0]
    h = rms_norm(x, w["input_norm"], eps)
    q = matmul(h, w["wq"], quant).reshape(t, s["H"], s["D"])
    k = matmul(h, w["wk"], quant).reshape(t, s["KVH"], s["D"])
    v = matmul(h, w["wv"], quant).reshape(t, s["KVH"], s["D"])
    q = rms_norm(q, w["q_norm"], eps)
    k = rms_norm(k, w["k_norm"], eps)
    return (rope(q, positions, hf["rope_theta"]),
            rope(k, positions, hf["rope_theta"]), v)


def _scores(hf, q, k):
    """q [Tq, H, D] against k [Tk, KVH, D] -> [H, Tq, Tk], scaled."""
    s = base.sizes(hf)
    k = jnp.repeat(k, s["H"] // s["KVH"], axis=1)
    return jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(s["D"]))


def _mix(hf, probs, v):
    """probs [H, Tq, Tk] over v [Tk, KVH, D] -> [Tq, H * D]."""
    s = base.sizes(hf)
    v = jnp.repeat(v, s["H"] // s["KVH"], axis=1)
    return jnp.einsum("hts,shd->thd", probs, v).reshape(probs.shape[1], -1)


def finished_attention(hf, block, x, w, quant=None):
    """The finished blocks of one request, x [T, E] at positions 0..T-1,
    under the block mask. Returns x + attention and the rows' K and V."""
    t = x.shape[0]
    at = jnp.arange(t)
    q, k, v = qkv(hf, x, w, at, quant)
    seen = at[None, :] // block <= at[:, None] // block
    scores = jnp.where(seen[None], _scores(hf, q, k), -jnp.inf)
    attn = _mix(hf, jax.nn.softmax(scores, axis=-1), v)
    return x + matmul(attn, w["wo"], quant), k, v


def forward_attention(hf, x, starts, k_done, v_done, w, quant=None):
    """Forwards of B rows each, x [F, B, E], forward f at positions
    `starts[f] ..`: each attends the finished K and V `[T, KVH, D]` below
    its start and its own B rows, all of them."""
    f, b, e = x.shape
    at = (starts[:, None] + jnp.arange(b)[None, :]).reshape(-1)
    q, k, v = qkv(hf, x.reshape(f * b, e), w, at, quant)
    below = jnp.arange(k_done.shape[0])[None, :] < jnp.repeat(starts, b)[:, None]
    old = jnp.where(below[None], _scores(hf, q, k_done), -jnp.inf)  # [H, FB, T]
    heads = q.shape[1]
    own = jax.vmap(lambda qf, kf: _scores(hf, qf, kf), out_axes=1)(
        q.reshape(f, b, heads, -1), k.reshape(f, b, *k.shape[1:]))  # [H, F, B, B]
    probs = jax.nn.softmax(
        jnp.concatenate([old, own.reshape(heads, f * b, b)], -1), axis=-1)
    mixed_own = jax.vmap(lambda pf, vf: _mix(hf, pf, vf), in_axes=(1, 0))(
        probs[..., -b:].reshape(heads, f, b, b), v.reshape(f, b, *v.shape[1:]))
    attn = _mix(hf, probs[..., :-b], v_done) + mixed_own.reshape(f * b, -1)
    return x + matmul(attn, w["wo"], quant).reshape(f, b, e)


def route(hf, x, w, given, follow, quant=None):
    """Rows x [R, E] after attention. `given` [R, k] are the sets to take
    where `follow` [R]; every other row takes its own. Returns the normed
    rows, the sets taken, their weights (softmax over the taken logits), the
    reference's own sets (best first) and the trail [R]: the own k-th logit
    minus the lowest logit of the taken set."""
    h = rms_norm(x, w["post_attn_norm"], hf["rms_norm_eps"])
    logits = matmul(h, w["router"], quant)
    ownv, own = jax.lax.top_k(logits, hf["num_experts_per_tok"])
    sets = jnp.where(follow[:, None], given, own)
    taken = jnp.take_along_axis(logits, sets, axis=-1)
    return (h, sets, jax.nn.softmax(taken, axis=-1), own,
            ownv[:, -1] - taken.min(axis=-1))


def dispatch(hf, seed_key, layer, x, h, sets, probs, expert, idx, live, quant=None):
    """x + one expert's weighted output on the rows `idx` (padded: a padded
    entry is not `live` and adds nothing). The expert's weights are made
    from the seed here, upcast, used on these rows and dropped."""
    w = _f32(routed.expert_weights(hf, base.layer_key(seed_key, layer), expert))
    weight = jnp.sum(jnp.where(sets[idx] == expert, probs[idx], 0.0), -1) * live
    return x.at[idx].add(routed.expert_apply(hf, h[idx], w, quant) * weight[:, None])


def head_logits(hf, mask_id, x, final_norm, lm_head, quant=None):
    """Logits [rows, V] of rows x [rows, E]; the mask id is no candidate."""
    logits = matmul(rms_norm(x, final_norm, hf["rms_norm_eps"]), lm_head.T, quant)
    return logits.at[:, mask_id].set(-jnp.inf)


def head_choice(hf, mask_id, x, final_norm, lm_head, quant=None):
    """The top token of each row and its probability (the confidence)."""
    logits = head_logits(hf, mask_id, x, final_norm, lm_head, quant)
    return (jnp.argmax(logits, axis=-1),
            jnp.exp(logits.max(axis=-1) - jax.nn.logsumexp(logits, axis=-1)))


def layer_attention(hf, block, n_done, xs, starts, k_done, v_done, w, quant=None):
    """One layer's attention, a request at a time (`lax.map`: a loop, no
    batch). xs [requests, rows, E]: a request's first `n_done` rows are its
    finished blocks at positions 0.., whose K and V are returned; the rest
    are forwards of `block` rows at `starts` [requests, F], against the K
    and V of the finished blocks: these where n_done > 0, else the given
    `k_done`, `v_done` [requests, T, KVH, D] of an earlier pass."""
    e = xs.shape[-1]

    def one(args):
        x, start, kd, vd = args
        parts = []
        if n_done:
            done, kd, vd = finished_attention(hf, block, x[:n_done], w, quant)
            parts.append(done)
        if x.shape[0] > n_done:
            parts.append(forward_attention(
                hf, x[n_done:].reshape(-1, block, e), start, kd, vd, w, quant
            ).reshape(-1, e))
        return jnp.concatenate(parts), kd, vd

    return jax.lax.map(one, (xs, starts, k_done, v_done))


def head_rows(hf, mask_id, xs, request, idx, final_norm, lm_head, quant=None):
    """Logits [len(idx), V] of the rows `idx` of one request, out of xs
    [passes, requests, rows, E] (a row of pass p is p * rows + its number)."""
    x = xs[:, request].reshape(-1, xs.shape[-1])[idx]
    return head_logits(hf, mask_id, x, final_norm, lm_head, quant)


def head_choices(hf, mask_id, chunk, xs, final_norm, lm_head, quant=None):
    """The top token and the confidence of every row of xs [..., E], `chunk`
    rows of logits at a time."""
    flat = xs.reshape(-1, xs.shape[-1])
    n = flat.shape[0]
    flat = jnp.pad(flat, ((0, -n % chunk), (0, 0))).reshape(-1, chunk, flat.shape[-1])
    top, conf = jax.lax.map(
        lambda x: head_choice(hf, mask_id, x, final_norm, lm_head, quant), flat)
    return top.reshape(-1)[:n], conf.reshape(-1)[:n]


_make_shared = jax.jit(lambda hf, key, layer: _f32(shared_weights(hf, key, layer)),
                       static_argnums=0)
_make_top = jax.jit(lambda hf, key: _f32(base.top_weights(hf, key)), static_argnums=0)
_attention = jax.jit(layer_attention, static_argnums=(0, 1, 2, 8))
_route = jax.jit(route, static_argnums=(0, 5))
_dispatch = jax.jit(dispatch, static_argnums=(0, 10))
_head_rows = jax.jit(head_rows, static_argnums=(0, 1, 7))
_head_choices = jax.jit(head_choices, static_argnums=(0, 1, 2, 6))


def experts_apply(hf, seed_key, layer, x, w, given, follow, live, quant):
    """The mixture of one layer on rows x [R, E] (`live` [R]: padding rows
    take no expert), one expert at a time, each on the rows routed to it and
    on no other. Returns the new rows, the own sets [R, k], the trail [R]."""
    f, k = _mx(hf), hf["num_experts_per_tok"]
    h, sets, probs, own, trail = _route(f, x, w, given, follow, quant)
    taken = np.asarray(sets)
    rows = np.flatnonzero(live)
    flat = taken[rows].reshape(-1)
    order = np.argsort(flat, kind="stable")
    ends = np.cumsum(np.bincount(flat, minlength=hf["num_experts"]))
    for expert in range(hf["num_experts"]):
        idx = rows[order[ends[expert - 1] if expert else 0:ends[expert]] // k]
        # DISPATCH_BLOCK rows a call, whatever an expert's load: one shape.
        for at in range(0, len(idx), DISPATCH_BLOCK):
            part = idx[at:at + DISPATCH_BLOCK]
            padded = np.zeros(DISPATCH_BLOCK, np.int32)
            padded[:len(part)] = part
            x = _dispatch(f, seed_key, layer, x, h, sets, probs, expert, padded,
                          (np.arange(DISPATCH_BLOCK) < len(part)).astype(np.float32),
                          quant)
    return x, np.asarray(own), np.asarray(trail)


# ---- the hand-over and its rule ---------------------------------------------


def _parse(blocks):
    """The hand-over's packed blocks as the forwards `follow` takes:
    `{"start", "commit", "tokens", "routes"}` each (`routes` [rows, routed
    layers, k]); None where a block's bytes are not what its header says."""
    forwards = []
    for b in blocks or ():
        try:
            dtype = np.dtype(WIRE_DTYPES[b["dtype"]])
            layers, k = (int(n) for n in b["shape"])
            raw, at = base64.b64decode(b["data"]), 0
            for _ in range(int(b["forwards"])):
                start, rows, n = (int(x) for x in np.frombuffer(raw, "<u4", 3, at))
                marks = np.frombuffer(raw, "<u4", 2 * n, at + 12).astype(np.int64)
                at += 12 + 8 * n
                size = rows * layers * k * dtype.itemsize
                if rows < 1 or at + size > len(raw):
                    return None
                forwards.append({
                    "start": start, "commit": marks[:n].tolist(),
                    "tokens": marks[n:].tolist(),
                    "routes": np.frombuffer(raw, dtype, rows * layers * k, at)
                    .reshape(rows, layers, k).astype(np.int64)})
                at += size
            if at != len(raw):
                return None
        except (KeyError, TypeError, ValueError):
            return None
    return forwards


def _layout(hf, n_prompt, n_served):
    """(tokens of the prompt's whole blocks, the starts of the blocks that
    generation fills)."""
    b = generation(hf)["B"]
    whole = n_prompt // b * b
    return whole, list(range(whole, n_prompt + n_served, b))


def _rule(hf, prompt, served, forwards, experts):
    """Whether `forwards` are a whole generation of `served` after `prompt`,
    forward for forward (the module's docstring has the rule)."""
    g = generation(hf)
    b, k = g["B"], hf["num_experts_per_tok"]
    if not forwards or not served:
        return False
    for f in forwards:
        routes = f["routes"]
        ordered = np.sort(routes, -1)
        if routes.shape[1:] != (hf["num_hidden_layers"], k) or (
                (routes < 0).any() or (routes >= experts).any()
                or (ordered[..., 1:] == ordered[..., :-1]).any()):
            return False
        if len(f["commit"]) != len(f["tokens"]):
            return False
    shape = lambda f: (f["start"], len(f["routes"]))  # noqa: E731
    whole, starts = _layout(hf, len(prompt), len(served))
    at = 0
    if whole:
        if shape(forwards[0]) != (0, whole) or forwards[0]["commit"]:
            return False
        at = 1
    for start in starts:
        masked = {r for r in range(b) if start + r >= len(prompt)}
        denoising = 0
        while masked:
            if at >= len(forwards) or shape(forwards[at]) != (start, b):
                return False
            commit = forwards[at]["commit"]
            if not commit or len(set(commit)) != len(commit) or (
                    not set(commit) <= masked):
                return False
            for token in forwards[at]["tokens"]:
                if token == g["mask"] or not 0 <= token < hf["vocab_size"]:
                    return False
            masked -= set(commit)
            denoising, at = denoising + 1, at + 1
        if denoising > g["T"] or at >= len(forwards) or (
                shape(forwards[at]) != (start, b) or forwards[at]["commit"]):
            return False
        at += 1
    return at == len(forwards)


# ---- replay -----------------------------------------------------------------


def _forwards_pad(hf, rows_pad):
    """The most forwards of B rows a request of `rows_pad` served tokens
    brings to a pass: T a block (one more block where the prompt ends inside
    one, and one where the served tokens do), and the last block's closing
    forward."""
    g = generation(hf)
    return g["T"] * (rows_pad // g["B"] + 2) + 1


def replay(hf, key, requests, *, state, quant=None, follow=None, pad_to=0,
           rows_pad=0):
    """See perf/README.md, "A generator of its own". A request that cannot
    be replayed comes back None."""
    experts = int(((state or {}).get("moe") or {}).get("experts", hf["num_experts"]))
    g = generation(hf)
    pad_to = pad_to + g["B"]  # the last block may pass the served tokens
    plans = []
    for i, request in enumerate(requests):
        prompt = [int(t) for t in request["prompt"]]
        served = [int(t) for t in request["served"]]
        forwards = None
        if follow != "own":
            forwards = (_parse((request.get("handover") or {}).get(HANDOVER[0]))
                        if follow is None else follow[i])
            if forwards is None or not _rule(hf, prompt, served, forwards, experts):
                plans.append(None)
                continue
        plans.append((prompt, served, forwards))
    with jax.default_matmul_precision("highest"):
        done = iter(_Replay(hf, key, [p for p in plans if p is not None],
                            quant, pad_to, rows_pad).run())
    return [None if p is None else next(done) for p in plans]


class _Replay:
    """All live requests of one `replay` call, layer by layer: attention a
    request, the mixture over every request's rows at once (an expert's
    weights exist once a layer). Every request has the same padded shape, so
    each stage compiles once a run."""

    def __init__(self, hf, key, plans, quant, pad_to, rows_pad):
        self.hf, self.key, self.quant = hf, key, quant
        self.f = _mx(hf)
        self.g = generation(hf)
        self.layers, self.k = hf["num_hidden_layers"], hf["num_experts_per_tok"]
        self.pad_to = pad_to
        self.forwards_pad = _forwards_pad(hf, rows_pad)
        self.rows_pad = rows_pad + self.g["B"]
        self.plans = plans
        self.top = _make_top(self.f, key) if plans else None
        self.kv = None  # a layer: the finished blocks' (K, V), every request

    # -- one pass of the model over some rows of every request ---------------

    def _pass(self, finished, forwards):
        """`finished`: None, or a request (tokens [pad_to], live rows n,
        given [n, layers, k] or None). `forwards`: None, or a request
        (starts [F], tokens [F, B], given [F, B, layers, k] or None), F at
        most `forwards_pad`. Returns the finished rows' (own, trail) a
        request, the forwards' (own, trail) a request, and the forwards'
        final rows [requests, forwards_pad * B, E]; the finished blocks' K
        and V stay in `self.kv` for a later pass without them."""
        f, b, n_req = self.f, self.g["B"], len(self.plans)
        n_done = self.pad_to if finished else 0
        n_fwd = self.forwards_pad * b if forwards else 0
        rows = n_done + n_fwd
        tokens = np.zeros((n_req, rows), np.int32)
        starts = np.zeros((n_req, self.forwards_pad if forwards else 0), np.int32)
        given = np.zeros((n_req, rows, self.layers, self.k), np.int32)
        follow = np.zeros((n_req, rows), bool)
        live = np.zeros((n_req, rows), bool)
        for i in range(n_req):
            for part, at in ((finished, 0), (forwards, n_done)):
                if not part or part[i] is None:
                    continue
                if part is finished:
                    toks, n, sets = part[i]
                    tokens[i, :n_done] = toks
                else:
                    start, toks, sets = part[i]
                    n = len(start) * b
                    starts[i, :len(start)] = start
                    tokens[i, at:at + n] = np.asarray(toks).reshape(-1)
                live[i, at:at + n] = True
                if sets is not None:
                    given[i, at:at + n] = np.asarray(sets).reshape(
                        n, self.layers, self.k)
                    follow[i, at:at + n] = True
        xs = base._embed(f, self.top["embed"], jnp.asarray(tokens))
        starts = jnp.asarray(starts)
        follow_d, flat_live = jnp.asarray(follow.reshape(-1)), live.reshape(-1)
        if finished:
            s = base.sizes(f)
            empty = jnp.zeros((n_req, 1, s["KVH"], s["D"]), jnp.float32)
            self.kv = [(empty, empty)] * self.layers
        own, trail = [], []
        for layer in range(self.layers):
            w = _make_shared(f, self.key, layer)
            xs, k_done, v_done = _attention(
                f, b, n_done, xs, starts, *self.kv[layer], w, self.quant)
            self.kv[layer] = (k_done, v_done)
            flat, o, t = experts_apply(
                self.hf, self.key, layer, xs.reshape(n_req * rows, -1), w,
                jnp.asarray(given[:, :, layer].reshape(n_req * rows, self.k)),
                follow_d, flat_live, self.quant)
            xs = flat.reshape(n_req, rows, -1)
            own.append(o.reshape(n_req, rows, self.k))
            trail.append(t.reshape(n_req, rows))
        own, trail = np.stack(own, 2), np.stack(trail, 2)  # [req, rows, layers(, k)]

        def part_of(part, at, width):
            """(own, trail) of each request's live rows of that part."""
            if not part:
                return [None] * n_req
            lives = live[:, at:at + width].sum(1)
            return [None if part[i] is None else
                    (own[i, at:at + lives[i]], trail[i, at:at + lives[i]])
                    for i in range(n_req)]

        return (part_of(finished, 0, n_done), part_of(forwards, n_done, n_fwd),
                xs[:, n_done:])

    def _choices(self, xs):
        """Top token and confidence of every row of xs [requests, rows, E]."""
        top, conf = _head_choices(
            self.f, self.g["mask"], HEAD_ROWS, xs, self.top["final_norm"],
            self.top["lm_head"], self.quant)
        shape = xs.shape[:2]
        return (np.asarray(top).reshape(shape),
                np.asarray(conf, np.float64).reshape(shape))

    def _result(self, xs, i, rows, own, differs, trail):
        """`rows`: of xs [passes, requests, rows a pass, E], the row of
        request i whose logits chose each served token."""
        idx = np.zeros(self.rows_pad, np.int32)
        idx[:len(rows)] = rows
        logits = np.asarray(_head_rows(
            self.f, self.g["mask"], xs, i, jnp.asarray(idx),
            self.top["final_norm"], self.top["lm_head"], self.quant))[:len(rows)]
        return {"logits": logits.astype(np.float32), "own": own,
                **{kind: {"differs": np.concatenate(
                              [np.ravel(d) for d in differs[kind]] or [np.zeros(0, bool)]),
                          "trail": np.concatenate(
                              [np.ravel(t) for t in trail[kind]] or [np.zeros(0)])}
                   for kind in DECISIONS}}

    # -- the two ways to run ---------------------------------------------------

    def run(self):
        if not self.plans:
            return []
        if all(p[2] is not None for p in self.plans):
            return self._run_followed()
        return self._run_own()

    def _commit_rule(self, conf, masked):
        """Of the masked rows, the most confident (the first of equals) and
        every one over the threshold."""
        rows = np.flatnonzero(masked)
        best = rows[np.argmax(conf[rows])]
        return sorted({int(best), *(int(r) for r in rows
                                    if conf[r] > self.g["threshold"])})

    def _order_trail(self, conf, masked, given, mine):
        bar = min(conf[np.flatnonzero(masked)].max(), self.g["threshold"])
        behind = [bar - conf[r] for r in given if r not in mine]
        left = [conf[r] - self.g["threshold"] for r in mine if r not in given]
        return float(max([0.0, *behind, *left]))

    def _run_followed(self):
        """Every state is known from the hand-over: one pass."""
        b, mask = self.g["B"], self.g["mask"]
        finished, forwards, books = [], [], []
        for prompt, served, fwds in self.plans:
            whole, starts = _layout(self.hf, len(prompt), len(served))
            last = starts[-1]
            tokens = np.zeros(self.pad_to, np.int64)
            known = prompt + served
            tokens[:len(known)] = known
            given = np.zeros((last, self.layers, self.k), np.int64)
            at = 0
            if whole:
                given[:whole] = fwds[0]["routes"]
                at = 1
            rows_start, rows_state, rows_given, book = [], [], [], []
            for start in starts:
                state = [known[start + r] if start + r < len(prompt) else mask
                         for r in range(b)]
                while mask in state:
                    fwd = fwds[at]
                    book.append(("denoise", at, len(rows_start), list(state)))
                    rows_start.append(start)
                    rows_state.append(list(state))
                    rows_given.append(fwd["routes"])
                    for r, token in zip(fwd["commit"], fwd["tokens"]):
                        # Teacher-forced: the served token where one was
                        # served, the handed-over one past them.
                        state[r] = known[start + r] if start + r < len(known) else token
                    at += 1
                if start < last:
                    given[start:start + b] = fwds[at]["routes"]
                    book.append(("finished", at, start, None))
                else:
                    book.append(("close", at, len(rows_start), list(state)))
                    rows_start.append(start)
                    rows_state.append(list(state))
                    rows_given.append(fwds[at]["routes"])
                at += 1
            if len(rows_start) > self.forwards_pad:
                raise ValueError(f"{len(rows_start)} forwards do not fit the "
                                 f"padding {self.forwards_pad}")
            finished.append((tokens, last, given))
            forwards.append((rows_start, rows_state, np.stack(rows_given)))
            books.append(book)
        done, fwd, xs = self._pass(finished, forwards)
        top, conf = self._choices(xs)
        return [self._read(i, books[i], done[i], fwd[i], xs,
                           top[i].reshape(-1, b), conf[i].reshape(-1, b))
                for i in range(len(self.plans))]

    def _read(self, i, book, done, fwd, xs, top, conf):
        """One followed request's result from what the pass gave."""
        prompt, served, fwds = self.plans[i]
        b = self.g["B"]
        whole = len(prompt) // b * b
        own_routes = fwd[0].reshape(-1, b, self.layers, self.k)
        fwd_trail = fwd[1].reshape(-1, b, self.layers)
        own = [None] * len(fwds)
        differs = {"routes": [None] * len(fwds), "order": []}
        trail = {"routes": [None] * len(fwds), "order": []}
        chosen = {}  # position -> the row of the pass that chose its token

        def routes_of(at, start, mine, behind):
            given = fwds[at]["routes"]
            differs["routes"][at] = (np.sort(given, -1) != np.sort(mine, -1)).any(-1)
            trail["routes"][at] = behind
            own[at] = {"start": start, "commit": [], "tokens": [], "routes": mine}

        if whole:
            routes_of(0, 0, done[0][:whole], done[1][:whole])
        for kind, at, where, state in book:
            start = fwds[at]["start"]
            if kind == "finished":
                routes_of(at, start, done[0][where:where + b], done[1][where:where + b])
                continue
            routes_of(at, start, own_routes[where], fwd_trail[where])
            if kind == "close":
                continue
            masked = np.asarray([t == self.g["mask"] for t in state])
            mine = self._commit_rule(conf[where], masked)
            own[at].update(commit=mine, tokens=[int(top[where][r]) for r in mine])
            given = fwds[at]["commit"]
            differs["order"].append(set(given) != set(mine))
            trail["order"].append(self._order_trail(conf[where], masked, given, mine))
            for r in given:
                chosen[start + r] = where * b + r
        rows = [chosen[len(prompt) + n] for n in range(len(served))]
        return self._result(xs[None], i, rows, own, differs, trail)

    def _run_own(self):
        """Every decision is the reference's own, on the served tokens: the
        finished blocks below the last first, then the denoising forwards of
        every block in rounds (a round is each block's next forward), then
        the last block's closing forward."""
        b, mask, n_req = self.g["B"], self.g["mask"], len(self.plans)
        layouts, finished = [], []
        for prompt, served, _ in self.plans:
            whole, starts = _layout(self.hf, len(prompt), len(served))
            tokens = np.zeros(self.pad_to, np.int64)
            tokens[:len(prompt) + len(served)] = prompt + served
            layouts.append((whole, starts))
            finished.append((tokens, starts[-1], None))
        done, _, _ = self._pass(finished, None)
        # A block: its state, and the forwards it has run.
        blocks = []
        for (prompt, served, _), (whole, starts) in zip(self.plans, layouts):
            known = prompt + served
            blocks.append([{
                "start": s, "ran": [],
                "state": [known[s + r] if s + r < len(prompt) else mask
                          for r in range(b)]} for s in starts])
        # position -> (round, row of the round) of the forward that chose
        # its token; every round's final rows.
        chosen = [{} for _ in self.plans]
        kept = []
        for _ in range(self.g["T"] + 1):
            open_ = [[blk for blk in blks if mask in blk["state"]] for blks in blocks]
            closing = not any(open_)
            if closing:  # the last block's closing forward
                open_ = [[blks[-1]] for blks in blocks]
            _, fwd, xs = self._pass(None, [
                ([blk["start"] for blk in blks], [blk["state"] for blk in blks], None)
                if blks else None for blks in open_])
            kept.append(xs)
            top, conf = self._choices(xs)
            for i in range(n_req):
                known = self.plans[i][0] + self.plans[i][1]
                for j, blk in enumerate(open_[i]):
                    ran = {"start": blk["start"], "commit": [], "tokens": [],
                           "routes": fwd[i][0][j * b:(j + 1) * b]}
                    blk["ran"].append(ran)
                    if closing:
                        continue
                    masked = np.asarray([t == mask for t in blk["state"]])
                    for r in self._commit_rule(conf[i, j * b:(j + 1) * b], masked):
                        at = blk["start"] + r
                        # Teacher-forced where a token was served; the
                        # reference's own top token past them.
                        token = known[at] if at < len(known) else int(top[i, j * b + r])
                        blk["state"][r] = token
                        ran["commit"].append(r)
                        ran["tokens"].append(token)
                        chosen[i][at] = (len(kept) - 1, j * b + r)
            if closing:
                break
        xs = jnp.stack(kept)
        per_round = self.forwards_pad * b
        out = []
        for i, (prompt, served, _) in enumerate(self.plans):
            whole, starts = layouts[i]
            own = []
            if whole:
                own.append({"start": 0, "commit": [], "tokens": [],
                            "routes": done[i][0][:whole]})
            for blk in blocks[i]:
                own += blk["ran"]
                if blk["start"] < starts[-1]:
                    s = blk["start"]
                    own.append({"start": s, "commit": [], "tokens": [],
                                "routes": done[i][0][s:s + b]})
            rows = [chosen[i][len(prompt) + n][0] * per_round
                    + chosen[i][len(prompt) + n][1] for n in range(len(served))]
            # It follows nothing, so nothing differs and nothing trails.
            sizes = {"routes": sum(f["routes"].shape[0] * self.layers for f in own),
                     "order": sum(bool(f["commit"]) for f in own)}
            out.append(self._result(
                xs, i, rows, own,
                {kind: [np.zeros(n, bool)] for kind, n in sizes.items()},
                {kind: [np.zeros(n)] for kind, n in sizes.items()}))
        return out


# ---- what the algorithm needs: bytes and FLOPs ------------------------------


def _shapes(hf):
    s = base.sizes(hf)
    attn = s["E"] * s["H"] * s["D"] * 2 + 2 * s["E"] * s["KVH"] * s["D"]
    expert = 3 * s["E"] * hf["moe_intermediate_size"]
    return s, attn, expert


def layer_params(hf: dict, experts: float) -> float:
    """Parameters of one block that holds `experts` experts (norms: 2E and
    the two QK-norm weights)."""
    s, attn, expert = _shapes(hf)
    return (attn + s["E"] * hf["num_experts"] + 2 * s["E"] + 2 * s["D"]
            + experts * expert)


def weight_bytes(hf: dict) -> int:
    """Every parameter a forward can read: all blocks with all experts, the
    final norm and the head (an embedding row a token is left out)."""
    s = base.sizes(hf)
    return int(BF16 * (s["NL"] * layer_params(hf, hf["num_experts"])
                       + s["E"] + s["V"] * s["E"]))


def kv_bytes_per_token(hf: dict) -> int:
    s = base.sizes(hf)
    return 2 * s["NL"] * s["KVH"] * s["D"] * BF16


def experts_touched(hf: dict, rows: float) -> float:
    """Experts of a layer that at least one of `rows` rows is routed to, at
    even load: X (1 - (1 - k / X)^rows). A seeded router is far from even
    (`moe_imbalance` 3.9 on the chip, PR 36), so the readers take the
    measured number (`kubeai_engine_moe_experts_touched_total`)."""
    x, k = hf["num_experts"], hf["num_experts_per_tok"]
    return x * (1.0 - (1.0 - k / x) ** rows)


def block_forward_bytes(hf: dict, resident_tokens: float, experts: float) -> float:
    """Least HBM traffic of one forward over every slot's block that routes
    rows to `experts` experts a layer (measured, or `experts_touched` at even
    load): those experts, the rest of the weights, the resident keys and
    values, once each."""
    s = base.sizes(hf)
    weights = BF16 * (s["NL"] * layer_params(hf, experts) + s["E"] + s["V"] * s["E"])
    return weights + resident_tokens * kv_bytes_per_token(hf)


def moe_experts_bytes(hf: dict, experts: float) -> float:
    """Weight bytes of one layer's three grouped products over `experts`
    experts that hold rows."""
    return BF16 * experts * _shapes(hf)[2]


def moe_experts_flops(hf: dict, rows: float) -> float:
    """Routed FLOPs of one layer's three grouped products over `rows`."""
    return 2.0 * rows * hf["num_experts_per_tok"] * _shapes(hf)[2]


def prefill_flops_per_token(hf: dict, context: float = 0.0) -> float:
    """Useful FLOPs to prefill one prompt token: 2 a matrix parameter of
    the experts it is routed to, attention against `context` tokens. The
    head runs on no prompt row (a prefill serves no token)."""
    s, attn, expert = _shapes(hf)
    per_layer = 2 * (attn + s["E"] * hf["num_experts"]
                     + hf["num_experts_per_tok"] * expert) + (
        4 * s["H"] * s["D"] * context)
    return s["NL"] * per_layer


def decode_step_bytes_per_chip(hf: dict, resident_tokens: float, chips: int) -> float:
    """One forward over every slot's block with every expert touched."""
    return (weight_bytes(hf) + resident_tokens * kv_bytes_per_token(hf)) / chips


sizes = base.sizes

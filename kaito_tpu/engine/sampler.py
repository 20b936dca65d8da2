"""Token sampling: greedy / temperature / top-k / top-p, fully batched
and jittable (no data-dependent shapes).

Per-slot sampling parameters live in arrays so one compiled decode step
serves heterogeneous requests — the continuous-batching analogue of
vLLM's SamplingParams handling inside the reference's engine.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclass
class SamplingState:
    """Per-slot sampling knobs, shape [B]."""

    temperature: jax.Array   # 0 => greedy
    top_k: jax.Array         # 0 => disabled
    top_p: jax.Array         # 1.0 => disabled
    key: jax.Array           # [B, 2] per-slot PRNG keys
    presence: jax.Array      # 0 => disabled (OpenAI presence_penalty)
    frequency: jax.Array     # 0 => disabled (OpenAI frequency_penalty)
    repetition: jax.Array    # 1 => disabled (HF/vLLM repetition_penalty)
    min_p: jax.Array         # 0 => disabled (vLLM min_p)

    @staticmethod
    def create(batch: int, seed: int = 0) -> "SamplingState":
        keys = jax.random.split(jax.random.PRNGKey(seed), batch)
        # idle rows are greedy/no-mask/no-penalty so the sampler's
        # cond gates (which read every row) stay enabled on a fresh
        # engine; admission overwrites the row via set_slot
        return SamplingState(
            temperature=jnp.zeros((batch,), jnp.float32),
            top_k=jnp.zeros((batch,), jnp.int32),
            top_p=jnp.ones((batch,), jnp.float32),
            key=jnp.asarray(keys, jnp.uint32),
            presence=jnp.zeros((batch,), jnp.float32),
            frequency=jnp.zeros((batch,), jnp.float32),
            repetition=jnp.ones((batch,), jnp.float32),
            min_p=jnp.zeros((batch,), jnp.float32),
        )

    def reset_slot(self, i: int) -> "SamplingState":
        """Greedy/no-mask/no-penalty row without touching the PRNG key
        (admission reseeds it)."""
        return _set_row(self, np.int32(i), None, *_ROW_DEFAULTS)

    def set_slot(self, i: int, *, temperature: float, top_k: int, top_p: float,
                 seed: int, presence: float = 0.0, frequency: float = 0.0,
                 repetition: float = 1.0, min_p: float = 0.0
                 ) -> "SamplingState":
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        return _set_row(
            self, np.int32(i), jnp.asarray(key, jnp.uint32),
            np.int32(top_k),
            np.asarray([temperature, top_p, presence, frequency, repetition,
                        min_p], np.float32))

    @property
    def any_penalty(self) -> jax.Array:
        return jnp.any((self.presence != 0.0) | (self.frequency != 0.0)
                       | (self.repetition != 1.0))


# top_k and [temperature, top_p, presence, frequency, repetition, min_p]
# of a row that samples nothing: greedy, no mask, no penalty
_ROW_DEFAULTS = (np.int32(0),
                 np.asarray([0.0, 1.0, 0.0, 0.0, 1.0, 0.0], np.float32))


@jax.jit
def _set_row(state: SamplingState, i, key, top_k, floats) -> SamplingState:
    """Write one slot's row in ONE program (``key`` None keeps the
    row's key).  Field by field it was some ninety small programs an
    admission, and on a TPU the engine thread waited in them for the
    decode window in flight (PERF.md section 6, PR 29)."""
    return SamplingState(
        temperature=state.temperature.at[i].set(floats[0]),
        top_k=state.top_k.at[i].set(top_k),
        top_p=state.top_p.at[i].set(floats[1]),
        key=state.key if key is None else state.key.at[i].set(key),
        presence=state.presence.at[i].set(floats[2]),
        frequency=state.frequency.at[i].set(floats[3]),
        repetition=state.repetition.at[i].set(floats[4]),
        min_p=state.min_p.at[i].set(floats[5]),
    )


def chosen_logprob(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """log p(token) per row under the UNMODIFIED model distribution
    (OpenAI logprobs semantics — the sampling mask/temperature do not
    change the reported values).  logits [B, V] fp32, tokens [B]."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    chosen = jnp.take_along_axis(
        logits, tokens[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return chosen - lse


def apply_penalties(logits: jax.Array, state: SamplingState,
                    counts: jax.Array, prompt_seen=None) -> jax.Array:
    """Sampling penalties, gated behind a cond like the sort path — a
    [B, V] read-modify-write per step must cost nothing for
    penalty-free batches.

    vLLM semantics: presence/frequency consider OUTPUT tokens only
    (``counts``, [B, V] int32 histogram); repetition_penalty considers
    prompt AND output (``prompt_seen``, [B, V] bool)."""

    def apply(l):
        c = counts.astype(jnp.float32)
        out_seen = c > 0
        rep_seen = out_seen if prompt_seen is None \
            else (out_seen | prompt_seen)
        rep = state.repetition[:, None]
        l = jnp.where(rep_seen & (l > 0), l / rep,
                      jnp.where(rep_seen, l * rep, l))
        return l - state.frequency[:, None] * c \
            - state.presence[:, None] * out_seen.astype(jnp.float32)

    return jax.lax.cond(state.any_penalty, apply, lambda l: l, logits)


def spec_verify_sample(target_logits: jax.Array, draft_logits: jax.Array,
                       proposal: jax.Array, prop_len: jax.Array,
                       temperature: jax.Array, onehot_q: jax.Array,
                       keys: jax.Array, grammar_rows=None):
    """Leviathan-style speculative verification: accept a prefix of the
    proposal, then draw one token from the residual distribution — the
    emitted stream is distribution-identical to sampling the target
    autoregressively (and bit-identical to greedy prefix-accept + bonus
    when temperature == 0).

    target_logits [B, W, V] fp32 (W = K+1 window positions);
    draft_logits  [B, K, V] fp32 (draft dist at each proposed position;
                  ignored where ``onehot_q`` or temperature == 0 — a
                  deterministic proposer's q is one-hot at the proposal);
    proposal      [B, K] int32; prop_len [B] valid proposal tokens;
    temperature   [B]; onehot_q [B] bool (n-gram / deterministic rows);
    keys          [B, 2] uint32 PRNG keys (speculation-private — the
                  engine's SamplingState keys are never consumed here);
    grammar_rows  optional [B, W, V] fp32 of 0 / -inf grammar masks per
                  window position (a shape-mismatched placeholder
                  statically disables the path).  The verify
                  distribution renormalizes under the mask — softmax of
                  masked logits IS the renormalized conditional — so
                  constrained rows keep speculating instead of falling
                  back to plain decode.

    Returns (out [B, W] int32, n_emit [B] int32, lps [B, W] f32,
    new_keys [B, 2]).  out[:, :n_emit] are the emitted tokens (accepted
    prefix + one residual/bonus draw); positions >= n_emit are garbage.
    lps are log p(token) under the UNMODIFIED target distribution
    (OpenAI logprobs semantics, matching ``chosen_logprob``).
    """
    B, W, V = target_logits.shape
    K = W - 1
    masked_logits = target_logits
    if grammar_rows is not None and grammar_rows.shape == target_logits.shape:
        masked_logits = target_logits + grammar_rows
    greedy_row = temperature <= 0.0
    temp = jnp.maximum(temperature, 1e-6)[:, None, None]
    p_soft = jax.nn.softmax(masked_logits / temp, axis=-1)
    p_hot = jax.nn.one_hot(jnp.argmax(masked_logits, axis=-1), V,
                           dtype=p_soft.dtype)
    p = jnp.where(greedy_row[:, None, None], p_hot, p_soft)     # [B, W, V]
    q_soft = jax.nn.softmax(draft_logits / temp, axis=-1)
    q_hot = jax.nn.one_hot(proposal, V, dtype=q_soft.dtype)
    det = (onehot_q | greedy_row)[:, None, None]
    q = jnp.where(det, q_hot, q_soft)                           # [B, K, V]

    j = jnp.arange(K)[None, :]
    valid = j < prop_len[:, None]                               # [B, K]
    p_prop = jnp.take_along_axis(
        p[:, :K], proposal[..., None], axis=-1)[..., 0]
    q_prop = jnp.take_along_axis(q, proposal[..., None], axis=-1)[..., 0]
    ratio = p_prop / jnp.maximum(q_prop, 1e-20)

    def row_draws(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        new_key, k_u, k_cat = jax.random.split(key, 3)
        u = jax.random.uniform(k_u, (K,))
        return jax.random.key_data(new_key), u, jax.random.key_data(k_cat)

    new_keys, u, cat_keys = jax.vmap(row_draws)(keys)
    accept = (u < ratio) & valid
    # longest accepted PREFIX (a single rejection stops the row)
    n = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=-1), axis=-1)

    # residual at the first rejected position: max(p - q, 0) normalized;
    # past the proposal (full accept / empty proposal) the "residual"
    # is the target distribution itself (the bonus token)
    p_n = jnp.take_along_axis(p, n[:, None, None], axis=1)[:, 0]  # [B, V]
    q_pad = jnp.concatenate([q, jnp.zeros((B, 1, V), q.dtype)], axis=1)
    q_n = jnp.take_along_axis(q_pad, n[:, None, None], axis=1)[:, 0]
    q_n = jnp.where((n < prop_len)[:, None], q_n, 0.0)
    resid = jnp.maximum(p_n - q_n, 0.0)
    rs = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(rs > 1e-12, resid / jnp.maximum(rs, 1e-12), p_n)

    def row_cat(key_data, probs):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        tok = jax.random.categorical(
            key, jnp.log(jnp.maximum(probs, 1e-38)))
        return tok.astype(jnp.int32)

    extra_cat = jax.vmap(row_cat)(cat_keys, resid)
    # greedy rows stay draw-free: one-hot residual -> exact argmax
    extra = jnp.where(greedy_row,
                      jnp.argmax(resid, axis=-1).astype(jnp.int32),
                      extra_cat)

    jj = jnp.arange(W)[None, :]
    prop_pad = jnp.concatenate(
        [proposal, jnp.zeros((B, 1), proposal.dtype)], axis=1)
    out = jnp.where(jj < n[:, None], prop_pad, 0)
    out = jnp.where(jj == n[:, None], extra[:, None], out)
    out = out.astype(jnp.int32)
    logp = jax.nn.log_softmax(target_logits, axis=-1)
    lps = jnp.take_along_axis(logp, out[..., None], axis=-1)[..., 0]
    return out, (n + 1).astype(jnp.int32), lps, new_keys


def sample(logits: jax.Array, state: SamplingState,
           counts=None, prompt_seen=None,
           grammar_rows=None) -> tuple[jax.Array, SamplingState]:
    """Sample one token per row. logits: [B, V] fp32; counts: optional
    [B, V] output-token histogram for penalties (a shape-mismatched
    placeholder statically disables the penalty path, so penalty-free
    engines never allocate or touch [B, V] state); grammar_rows:
    optional [B, V] fp32 of 0 / -inf constrained-decoding masks,
    pre-gathered per slot (same placeholder discipline — grammar-free
    engines compile this path away entirely).  The mask lands before
    temperature/top-k/top-p so greedy, categorical and nucleus paths
    all honor it; unconstrained rows carry an all-zero row (no-op).

    The sort-based top-k/top-p masking and the categorical draw are
    gated behind ``lax.cond`` on what the batch actually requests: a
    full [B, V] sort every decode step tripled the fused decode step's
    device time at a 200k vocab when every slot was greedy.  The masked
    path is bit-identical to the always-sort implementation whenever any
    slot enables top-k/top-p."""
    B, V = logits.shape
    if counts is not None and counts.shape == logits.shape:
        logits = apply_penalties(logits, state, counts, prompt_seen)
    if grammar_rows is not None and grammar_rows.shape == logits.shape:
        logits = logits + grammar_rows
    temp = jnp.maximum(state.temperature, 1e-6)[:, None]
    scaled = logits / temp

    def mask_topk_topp(scaled):
        # top-k: mask logits below the k-th largest (k==0 disables)
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        k = jnp.clip(state.top_k, 0, V)
        kth = jnp.take_along_axis(
            sorted_desc, jnp.maximum(k - 1, 0)[:, None], axis=-1)
        out = jnp.where((k[:, None] > 0) & (scaled < kth), -jnp.inf, scaled)

        # top-p (nucleus): keep the smallest prefix of the sorted
        # distribution with cumulative prob >= p
        probs_sorted = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs_sorted, axis=-1)
        cutoff_idx = jnp.sum(cum < state.top_p[:, None], axis=-1)  # [B]
        cutoff_val = jnp.take_along_axis(sorted_desc, cutoff_idx[:, None],
                                         axis=-1)
        return jnp.where(out < cutoff_val, -jnp.inf, out)

    def mask_min_p(scaled):
        # vLLM min_p: drop tokens whose prob is below min_p * max_prob
        # (scale-invariant in logit space: logit < max_logit + log(min_p))
        mx = jnp.max(scaled, axis=-1, keepdims=True)
        thresh = mx + jnp.log(jnp.maximum(state.min_p, 1e-10))[:, None]
        keep_all = (state.min_p <= 0.0)[:, None]
        return jnp.where(keep_all | (scaled >= thresh), scaled, -jnp.inf)

    random_row = state.temperature > 0.0
    need_mask = jnp.any(random_row & ((state.top_k > 0)
                                      | (state.top_p < 1.0)))
    scaled = jax.lax.cond(need_mask, mask_topk_topp, lambda s: s, scaled)
    need_min_p = jnp.any(random_row & (state.min_p > 0.0))
    scaled = jax.lax.cond(need_min_p, mask_min_p, lambda s: s, scaled)

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(operands):
        keys, rows = operands

        def one(key_data, row):
            key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
            new_key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, row)
            return jax.random.key_data(new_key), tok.astype(jnp.int32)

        return jax.vmap(one)(keys, rows)

    new_keys, sampled = jax.lax.cond(
        jnp.any(random_row), draw,
        lambda operands: (operands[0], greedy), (state.key, scaled))
    tokens = jnp.where(random_row, sampled, greedy)
    new_state = SamplingState(
        temperature=state.temperature, top_k=state.top_k, top_p=state.top_p,
        key=new_keys, presence=state.presence, frequency=state.frequency,
        repetition=state.repetition, min_p=state.min_p)
    return tokens.astype(jnp.int32), new_state

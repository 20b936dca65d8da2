"""Draft-model speculative decoding machinery (docs/speculative.md).

Three pieces live here, composed by the engine's speculative step:

* :class:`NgramIndex` — per-request cached prompt-lookup index (the
  n-gram proposer's lookup structure, append-updated as tokens are
  emitted instead of rescanning the trailing context every step).
* :class:`DepthController` — per-slot adaptive speculation depth: an
  accept-rate EWMA drives AIMD on K (additive raise on high acceptance,
  multiplicative decay on low), and sustained-poor acceptance falls the
  slot back to the n-gram proposer (then plain decode) with a probation
  window before the draft model is retried.
* :class:`DraftRunner` — the co-resident draft model: its own (small)
  paged KV pool and allocator, per-slot draft positions, chunked
  catch-up prefill, and a jitted K-step autoregressive proposal scan.

The draft pool is entirely private: draft pages are never taken from
the target's allocator, so speculation can never trigger a preemption
(the speculative-page invariant the n-gram path already enforces via
``_lookahead_fits``).  Acceptance itself — Leviathan-style rejection
sampling fused into the target's verification forward — lives in
``sampler.spec_verify_sample``.
"""

from __future__ import annotations

import logging
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from kaito_tpu.engine.devprof import phase_scope
from kaito_tpu.engine.kv_cache import create_kv_cache
from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.models.registry import (
    draft_compatibility_errors,
    get_model_by_name,
)

logger = logging.getLogger(__name__)


class NgramIndex:
    """Last-occurrence index over one request's token stream.

    Replaces the old per-step rescan of the trailing ``window``-token
    context (4096, the scan's bound): a dict maps each ``k``-gram (that
    has at least one following token) to its NEWEST start offset.
    ``append`` is O(1) amortized per emitted token; ``propose`` is one
    dict probe.  Matching the scan's semantics, the gram ending at the
    current tail is indexed only once a token follows it — a lookup
    never matches the tail itself — and a match older than the trailing
    window is a miss, exactly as it fell off the scanned context
    before.  Memory stays O(window): the retained token buffer is
    trimmed and stale dict entries are swept as the stream grows.
    """

    def __init__(self, k: int, tokens, window: int = 4096):
        self.k = k
        self.window = max(int(window), k + 1)
        toks = [int(t) for t in tokens]
        self.n = len(toks)                 # absolute stream length
        self.off = max(0, self.n - self.window)   # abs index of buf[0]
        self.tokens = toks[self.off:]      # trailing retained buffer
        self.last: dict[tuple, int] = {}   # gram -> newest ABS start
        for end in range(k - 1, len(self.tokens) - 1):
            self.last[tuple(self.tokens[end - k + 1:end + 1])] = \
                self.off + end - k + 1
        self._sweep_at = self.n + self.window

    def append(self, tok: int) -> None:
        self.tokens.append(int(tok))
        self.n += 1
        m = self.n - 2                # previous tail ABS index: it now
        if m - self.k + 1 >= self.off:  # has a follower, gram is usable
            rel = m - self.off
            self.last[tuple(self.tokens[rel - self.k + 1:rel + 1])] = \
                m - self.k + 1
        if len(self.tokens) > 2 * self.window:   # amortized front trim
            cut = len(self.tokens) - self.window
            del self.tokens[:cut]
            self.off += cut
        if self.n >= self._sweep_at:  # periodic stale-entry sweep
            lo = self.n - self.window
            self.last = {g: s for g, s in self.last.items() if s >= lo}
            self._sweep_at = self.n + self.window

    def propose(self, max_tokens: int) -> list[int]:
        if self.n < self.k + 1 or max_tokens <= 0:
            return []
        start = self.last.get(tuple(self.tokens[-self.k:]))
        if start is None or start < self.n - self.window:
            return []   # no occurrence inside the trailing window
        lo = start + self.k - self.off
        return self.tokens[lo:lo + max_tokens]


class DepthController:
    """Per-slot adaptive speculation depth (AIMD on an accept-rate EWMA).

    Modes per slot: ``"draft"`` (propose with the draft model at depth
    ``k``) and ``"ngram"`` (fall back to the prompt-lookup proposer; a
    probation countdown retries the draft at depth 1).  When the n-gram
    proposer also finds nothing the engine's speculative step returns 0
    and the slot decodes plainly — the full fallback ladder is
    draft → n-gram → plain decode.
    """

    def __init__(self, slots: int, k_max: int, *, k_init: int = 2,
                 alpha: float = 0.25, raise_at: float = 0.8,
                 lower_at: float = 0.4, fallback_below: float = 0.2,
                 fallback_patience: int = 4, probation_rounds: int = 16):
        self.k_max = max(1, int(k_max))
        self.k_init = min(max(1, k_init), self.k_max)
        self.alpha = alpha
        self.raise_at = raise_at
        self.lower_at = lower_at
        self.fallback_below = fallback_below
        self.fallback_patience = fallback_patience
        self.probation_rounds = probation_rounds
        self._k = [self.k_init] * slots
        self._ewma: list = [None] * slots
        self._bad = [0] * slots
        self._mode = ["draft"] * slots
        self._probation = [0] * slots

    def depth(self, i: int) -> int:
        return self._k[i] if self._mode[i] == "draft" else 0

    def mode(self, i: int) -> str:
        return self._mode[i]

    def accept_ewma(self, i: int) -> float:
        return float(self._ewma[i]) if self._ewma[i] is not None else 0.0

    def observe(self, i: int, proposed: int, accepted: int) -> None:
        """Record one draft verification round for slot ``i``."""
        if proposed <= 0:
            return
        rate = accepted / proposed
        e = self._ewma[i]
        self._ewma[i] = rate if e is None else \
            self.alpha * rate + (1.0 - self.alpha) * e
        if rate >= self.raise_at:                       # additive increase
            self._k[i] = min(self._k[i] + 1, self.k_max)
        elif rate < self.lower_at:                      # multiplicative decrease
            self._k[i] = max(1, self._k[i] // 2)
        if self._ewma[i] < self.fallback_below:
            self._bad[i] += 1
            if self._bad[i] >= self.fallback_patience:
                self._mode[i] = "ngram"
                self._probation[i] = self.probation_rounds
                self._bad[i] = 0
                self._ewma[i] = None
                self._k[i] = 1
        else:
            self._bad[i] = 0

    def note_fallback_round(self, i: int) -> None:
        """Tick the probation countdown while slot ``i`` rides the
        n-gram fallback; at zero the draft model is retried at depth 1."""
        if self._mode[i] != "ngram":
            return
        self._probation[i] -= 1
        if self._probation[i] <= 0:
            self._mode[i] = "draft"
            self._k[i] = 1
            self._ewma[i] = None
            self._bad[i] = 0

    def reset(self, i: int) -> None:
        self._k[i] = self.k_init
        self._ewma[i] = None
        self._bad[i] = 0
        self._mode[i] = "draft"
        self._probation[i] = 0

    def mean_depth(self, idxs) -> float:
        ks = [self.depth(i) for i in idxs]
        return sum(ks) / len(ks) if ks else 0.0


class DraftRunner:
    """The co-resident draft model and its private paged KV state.

    Owns: draft params (synthetic or from
    ``cfg.speculative_draft_weights_dir``), a draft KV pool sized so
    every slot can hold a full context (the draft's KV is a small
    fraction of the target's), per-slot page tables / positions, a
    speculation-private PRNG key per slot (the engine's SamplingState
    streams are never consumed by speculation), chunked catch-up
    prefill, and the jitted K-step proposal scan.

    Invariant mirrored from the engine: a round's proposal scan writes
    draft KV at positions ``p .. p + k_exec - 1`` (last committed token
    plus the first ``k_exec - 1`` proposals), so after a verification
    round that accepted ``a`` of ``k_exec`` proposals the engine
    commits ``min(p + a + 1, p + k_exec)`` — the new target position,
    except after a full-accept round, where the last accepted token's
    KV was never written and ``sync`` backfills the one-token gap at
    the start of the next round.  Steady-state partial-accept rounds
    need zero catch-up.  Rejected-position entries past the valid
    prefix are overwritten before any later step can attend to them
    (attention lengths track the valid prefix).
    """

    def __init__(self, engine):
        cfg = engine.cfg
        self.cfg = cfg
        self.md = get_model_by_name(cfg.speculative_draft)
        errs = draft_compatibility_errors(engine.md, self.md)
        if errs:
            raise ValueError("speculative draft pairing rejected: "
                             + "; ".join(errs))
        if engine.pp_exec is not None:
            raise ValueError("speculative_draft is not supported on "
                             "pipeline-parallel engines")
        self.dtype = engine.dtype
        self.mesh = engine.mesh
        self.model = TransformerLM(
            self.md.arch, dtype=self.dtype,
            attn_impl=getattr(engine.model, "attn_impl", "jax"))
        if engine.model.head_shard is not None:
            # same mesh as the target; the draft pool below is
            # replicated, so every device runs every draft head
            self.model.head_shard = (self.mesh, None)
        self.params = self._init_params(cfg, engine)
        self.page_size = cfg.page_size
        self.pages_per_seq = engine.pages_per_seq
        self.buckets = engine.buckets
        S = cfg.max_num_seqs
        # the draft pool is sized for every slot at full context: the
        # draft's bytes/token are a fraction of the target's, and a
        # pool that can never run dry keeps speculation allocation-free
        # on the hot path (and trivially preserves the never-preempt
        # invariant — no draft page is ever taken from the target pool)
        num_pages = S * self.pages_per_seq + 1
        # the draft pool stays floating point (int8 KV is a target-side
        # capacity lever; the draft pool is already small) but matches
        # the target's fp KV dtype so a self-consistent draft sees the
        # same rounding the verifier does
        kv_dt = jnp.dtype(cfg.kv_dtype)
        if kv_dt == jnp.int8:
            kv_dt = jnp.dtype(jnp.bfloat16)
        self.cache = create_kv_cache(self.md.arch, num_pages,
                                     cfg.page_size, dtype=kv_dt)
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            self.cache = jax.device_put(
                self.cache, NamedSharding(self.mesh, P()))
        from kaito_tpu.engine.engine import PageAllocator

        self.alloc = PageAllocator(num_pages)
        self.tables = np.zeros((S, self.pages_per_seq), np.int32)
        self.pages: list[list[int]] = [[] for _ in range(S)]
        self.pos = np.zeros((S,), np.int64)   # draft KV valid prefix
        self.keys = jnp.asarray(
            jax.random.split(jax.random.PRNGKey(cfg.seed + 7919), S),
            jnp.uint32)
        self._fns: dict = {}
        logger.info(
            "speculative draft: %s (%d layers, vocab %d), %d KV pages x "
            "%d tokens (%.2f GiB), k_max=%d",
            self.md.name, self.md.arch.num_layers, self.md.arch.vocab_size,
            num_pages, cfg.page_size,
            2 * self.cache.k.nbytes / 2**30, cfg.speculative_draft_k)

    def _init_params(self, cfg, engine):
        if cfg.speculative_draft_weights_dir:
            from kaito_tpu.engine.weights import load_safetensors_params

            logger.info("loading draft checkpoint from %s",
                        cfg.speculative_draft_weights_dir)
            params = load_safetensors_params(
                self.model, cfg.speculative_draft_weights_dir)
        else:
            logger.info("initializing synthetic draft weights for %s",
                        self.md.name)
            t0 = time.monotonic()
            with jax.default_device(jax.local_devices()[0]):
                params = jax.jit(self.model.init_params)(
                    jax.random.PRNGKey(cfg.seed))
            jax.block_until_ready(params)
            logger.info("draft weights ready in %.1fs",
                        time.monotonic() - t0)
        if engine.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            # draft weights are small; replicate across the mesh so
            # the proposal scan needs no resharding
            params = jax.device_put(
                params, NamedSharding(engine.mesh, P()))
        return params

    # -- per-slot paged state ------------------------------------------

    def release_slot(self, i: int) -> None:
        if self.pages[i]:
            self.alloc.release(self.pages[i])
            self.pages[i] = []
            self.tables[i, :] = 0
        self.pos[i] = 0

    def ensure_pages(self, i: int, tokens_total: int) -> bool:
        """Grow slot ``i``'s draft page list to cover ``tokens_total``
        tokens; False when the slot would exceed its per-seq cap (the
        pool itself cannot run dry — see ``__init__``)."""
        need = -(-tokens_total // self.page_size)
        if need > self.pages_per_seq:
            return False
        have = len(self.pages[i])
        if need <= have:
            return True
        try:
            new = self.alloc.alloc(need - have)
        except MemoryError:
            return False
        for j, p in enumerate(new):
            self.tables[i, have + j] = p
        self.pages[i].extend(new)
        return True

    # -- catch-up prefill ----------------------------------------------

    def _prefill_fn(self, bucket: int):
        fn = self._fns.get(("prefill", bucket))
        if fn is None:
            model = self.model

            @partial(jax.jit, donate_argnums=(1,))
            @phase_scope("draft")
            def prefill_ctx(params, cache, tokens, true_lens, page_tables,
                            start_pos):
                cache, _, _ = model.prefill(params, cache, tokens,
                                            true_lens, page_tables,
                                            start_pos=start_pos)
                return cache

            fn = prefill_ctx
            self._fns[("prefill", bucket)] = fn
        return fn

    def sync(self, i: int, position: int, tokens_fn) -> bool:
        """Bring slot ``i``'s draft KV up to the target position (KV
        written for ``tokens[0:position]``).  Steady-state rounds are
        already synced and return immediately; first speculation after
        admission / preemption / a fallback stint prefills the gap.
        ``tokens_fn`` lazily materializes the slot's full token list.
        """
        cur = int(self.pos[i])
        if cur == position:
            return True
        if cur > position:   # defensive: target rewound under us
            self.release_slot(i)
            cur = 0
        if not self.ensure_pages(i, position):
            return False
        toks = tokens_fn()
        gap = [int(t) for t in toks[cur:position]]
        if not gap:
            self.pos[i] = position
            return True
        bucket = next((b for b in self.buckets if b >= len(gap)),
                      self.buckets[-1])
        if len(gap) > bucket:     # longer than the largest bucket:
            gap = gap[:bucket]    # chunk; the next round continues
        arr = np.zeros((1, bucket), np.int32)
        arr[0, :len(gap)] = gap
        self.cache = self._prefill_fn(bucket)(
            self.params, self.cache, jnp.asarray(arr),
            jnp.asarray([len(gap)], jnp.int32),
            jnp.asarray(self.tables[i:i + 1]),
            jnp.asarray([cur], jnp.int32))
        self.pos[i] = cur + len(gap)
        return int(self.pos[i]) == position

    # -- K-step proposal scan ------------------------------------------

    def _propose_fn(self, k_exec: int):
        fn = self._fns.get(("propose", k_exec))
        if fn is None:
            model = self.model

            @partial(jax.jit, donate_argnums=(1,))
            @phase_scope("draft")
            def propose(params, cache, tokens, positions, page_tables,
                        active, temperature, keys, gmask, gtrans, grows):
                temp = jnp.maximum(temperature, 1e-6)[:, None]
                rnd = temperature > 0.0

                def step(carry, _):
                    cache, toks, pos, keys, gr = carry
                    cache, logits = model.decode(params, cache, toks, pos,
                                                 page_tables, active=active)
                    logits = logits.astype(jnp.float32)
                    if gmask.shape[0] > 1:
                        # grammar-constrained rows propose under the
                        # mask; the returned logits are then the MASKED
                        # q — exactly the distribution the tokens were
                        # drawn from, which is what Leviathan rejection
                        # sampling needs (unconstrained rows gather the
                        # reserved all-zero row: a no-op)
                        logits = logits + gmask[gr]
                    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

                    def draw(operands):
                        ks, rows = operands

                        def one(kd, row):
                            key = jax.random.wrap_key_data(
                                kd, impl="threefry2x32")
                            nk, sub = jax.random.split(key)
                            t = jax.random.categorical(sub, row)
                            return (jax.random.key_data(nk),
                                    t.astype(jnp.int32))

                        return jax.vmap(one)(ks, rows)

                    keys, sampled = jax.lax.cond(
                        jnp.any(rnd), draw,
                        lambda o: (o[0], greedy), (keys, logits / temp))
                    nxt = jnp.where(rnd, sampled, greedy)
                    if gmask.shape[0] > 1:
                        gr = gtrans[gr, nxt]
                    return (cache, nxt, pos + 1, keys, gr), (nxt, logits)

                (cache, _, _, keys, _), (toks, logits) = jax.lax.scan(
                    step, (cache, tokens, positions, keys, grows), None,
                    length=k_exec)
                # scan stacks [K, B] / [K, B, V]; row-major for the host
                return (cache, toks.T,
                        jnp.transpose(logits, (1, 0, 2)), keys)

            fn = propose
            self._fns[("propose", k_exec)] = fn
        return fn

    def propose(self, slot_map, last_tokens, positions, temps, active,
                k_exec: int, grammar=None):
        """Run the K-step draft scan over the compact verify batch.

        slot_map: [B] engine-slot index per row (-1 = padding);
        active: [B] bool — rows that actually draft-propose this round
        (others ride along masked to the null page).  ``grammar`` is
        None or an engine-provided (gmask, gtrans, grows) triple —
        packed mask/transition tables plus each row's starting table
        row — that keeps constrained rows proposing only
        grammar-valid tokens (returned logits become the masked q).
        Returns (proposals np [B, k_exec] int32, draft_logits device
        [B, k_exec, V] f32).  The per-slot speculation keys for active
        rows advance in place.
        """
        idx = np.maximum(slot_map, 0)
        keys = jnp.asarray(self.keys)[jnp.asarray(idx)]
        if grammar is None:
            gmask = jnp.zeros((1, 1), jnp.float32)
            gtrans = jnp.zeros((1, 1), jnp.int32)
            grows = jnp.zeros((len(slot_map),), jnp.int32)
        else:
            gmask, gtrans, grows = grammar
        cache, toks, dlogits, new_keys = self._propose_fn(k_exec)(
            self.params, self.cache,
            jnp.asarray(last_tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(self.tables[idx]),
            jnp.asarray(active, bool),
            jnp.asarray(temps, jnp.float32),
            keys, gmask, gtrans, grows)
        self.cache = cache
        # enqueue the proposal readback before the key scatter so the
        # D2H copy rides the device stream alongside the scatter
        # dispatch instead of serializing after it (the blocking
        # np.asarray below then usually finds the bytes already landed)
        try:
            toks.copy_to_host_async()
        except Exception:          # backend without async copies
            pass
        self.scatter_keys(slot_map, new_keys,
                          only=np.asarray(active, bool))
        return np.asarray(toks), dlogits

    # -- speculation PRNG keys (shared with the verify/accept draw) ----

    def gather_keys(self, slot_map):
        idx = np.maximum(slot_map, 0)
        return jnp.asarray(self.keys)[jnp.asarray(idx)]

    def scatter_keys(self, slot_map, new_keys, only=None) -> None:
        rows = [r for r, s in enumerate(slot_map) if s >= 0
                and (only is None or only[r])]
        if not rows:
            return
        idx = jnp.asarray([slot_map[r] for r in rows])
        self.keys = self.keys.at[idx].set(new_keys[jnp.asarray(rows)])

    def commit(self, i: int, new_position: int) -> None:
        """After a verify round: advance the draft KV valid prefix.
        The engine passes min(new target position, p + k_exec) — never
        past what the proposal scan actually wrote (class docstring);
        any remaining gap is prefilled by ``sync`` next round."""
        self.pos[i] = new_position

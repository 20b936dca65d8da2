"""The inference engine: continuous batching over a paged KV cache.

This is the tokens/s hot loop — the TPU counterpart of the vLLM engine
step loop the reference leans on (SURVEY.md §3.1 "HOT LOOP").  Design:

- Fixed decode *slots* (``max_num_seqs``).  One compiled decode step
  advances every slot each iteration; inactive slots write to the null
  page and their samples are discarded.  Static shapes, one program.
- Prefill runs in bounded chunks that interleave with decode at a
  configurable ratio (decode-priority: running batches keep their
  cadence while new prompts stream in), writing straight into the
  request's pages (no copy into the decode state — the page table IS
  the hand-off).  Admission is bookkeeping-only and fills every free
  slot per step.
- Pages come from a free-list allocator on demand: admission reserves
  only the prompt's pages; decode grows a sequence page-by-page and,
  when the pool is exhausted, preempts the newest sequence back to the
  queue (its generated tokens become part of the prompt on resume, so
  clients never see a discontinuity).
- jit with donated cache/state keeps HBM traffic at the theoretical
  minimum; per-bucket programs are compiled on first use and cached.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import logging
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from kaito_tpu.engine import nn
from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.devprof import phase_scope
from kaito_tpu.engine.grammar import GrammarCache, GrammarSlot, GrammarTable
from kaito_tpu.engine.kv_cache import (KVCache, create_kv_cache,
                                       create_conv_state_pool,
                                       create_delta_state_pool,
                                      create_state_pool,
                                       kv_cache_is_quantized,
                                       scale_bytes_per_page)
from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.engine.sampler import (SamplingState, chosen_logprob,
                                      sample, spec_verify_sample)
from kaito_tpu.engine.spec import NgramIndex
from kaito_tpu.engine.tokenizer import load_tokenizer
from kaito_tpu.estimator.estimator import PER_CHIP_OVERHEAD_BYTES, HBM_UTILIZATION
from kaito_tpu.models.metadata import ModelMetadata
from kaito_tpu.models.registry import get_model_by_name
from kaito_tpu.utils.failpoints import FAILPOINTS
from kaito_tpu.utils.tracing import (PhaseClock, RingTracer, StepTimeline,
                                     format_span_tree)

logger = logging.getLogger(__name__)

# The collector's full passes (docs/observability.md, "The heap").  A
# full collection walks every tracked Python object under the
# interpreter lock, and tracing the step programs leaves about 10^5 of
# them a program for the process's life: 0.4 s for 600,000 objects on an
# idle core.  One landed inside 2 of 28 windows of the benchmark's widest
# cell and stopped every stream for 1.7 s and 3.5 s (PERF.md section 6).
# So the loop freezes the heap when it goes idle after new programs
# were compiled (``InferenceEngine._settle_heap``), and from then on a
# pass that still holds the lock for long is named in the log.
# Compiles are counted where they happen, with their seconds
# (kaito:engine_compiles_total, kaito:engine_compile_seconds_total; a
# step that compiled carries both on its timeline record).
_COMPILES = [0]
_COMPILE_SECONDS = [0.0]
_GC_STARTED = [0.0]


def _count_compile(name: str, secs: float, **_kw) -> None:
    if name == "/jax/core/compile/backend_compile_duration":
        _COMPILES[0] += 1
        _COMPILE_SECONDS[0] += secs


def _watch_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _GC_STARTED[0] = time.monotonic()
        return
    held = time.monotonic() - _GC_STARTED[0]
    if held > 0.1:
        logger.warning("gc: a generation-%d pass held the interpreter lock "
                       "%.2fs", info["generation"], held)


jax.monitoring.register_event_duration_secs_listener(_count_compile)


class RequestScopedError(RuntimeError):
    """An exception the scheduler loop can attribute to ONE request.

    Raising this (instead of a bare exception) from inside ``step``
    tells ``_loop`` that the failure domain is a single request — the
    loop fails that request with a structured error and keeps serving
    everyone else, instead of taking the ``_fail_all`` engine-fatal
    path.  The request must already be detached from its slot (pages
    released) by the raiser."""

    def __init__(self, req: "Request", message: str = ""):
        super().__init__(message or f"request {req.req_id} failed")
        self.req = req

# columns in the fused-decode on-device stop matrix; requests with more
# stop ids than this fall back to the single-step path
_STOP_WIDTH = 8


@dataclass
class SamplingParams:
    max_tokens: int = 128
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    stop_token_ids: tuple[int, ...] = ()
    seed: int = 0
    ignore_eos: bool = False
    logprobs: bool = False     # per-generated-token log p (model dist)
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    repetition_penalty: float = 1.0
    min_p: float = 0.0
    # grammar-constrained decoding (docs/structured-output.md): a
    # grammar.CompiledGrammar the server resolved from response_format
    # or a forced tool_choice BEFORE admission (compilation never runs
    # in the step thread).  None = unconstrained.
    grammar: Optional[object] = field(default=None, compare=False,
                                      repr=False)

    @property
    def has_penalties(self) -> bool:
        return (self.presence_penalty != 0.0
                or self.frequency_penalty != 0.0
                or self.repetition_penalty != 1.0)


@dataclass
class Request:
    req_id: str
    prompt_tokens: list[int]
    params: SamplingParams
    out: "queue.SimpleQueue[Optional[int]]" = field(default_factory=queue.SimpleQueue)
    output_tokens: list[int] = field(default_factory=list)
    output_logprobs: list = field(default_factory=list)  # floats (None for
    # tokens whose logits never existed locally, e.g. PD-imported firsts)
    # P/D disaggregation (kaito_tpu.engine.pd)
    export_kv: bool = False                # prefill role: stage KV on finish
    kv_import: Optional[tuple] = None      # decode role: (meta, payload, first_token)
    kv_chunked: Optional[object] = None    # decode role: pd.ChunkedImport
    kv_device: Optional[tuple] = None      # colocated decode role:
    # (meta, (k_dev, v_dev), first_token) — device-to-device scatter
    submit_time: float = field(default_factory=time.monotonic)
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    finish_reason: str = ""
    aborted: bool = False
    preemptions: int = 0
    prompt_counted: bool = False   # metrics: prompt tokens counted once
    adapter: str = ""              # per-request LoRA adapter name
    # failure-domain isolation: absolute monotonic deadline (None = no
    # deadline), structured error surfaced to the HTTP layer when
    # finish_reason lands on "error"/"deadline", and the remaining
    # retry budget for TRANSIENT KV-transfer failures (retrying falls
    # back to local recompute — the request still succeeds, just slower)
    deadline: Optional[float] = None
    error: Optional[dict] = None
    kv_retries: int = 0
    # end-to-end trace identity (X-Request-Id): distinct from req_id so
    # a client-supplied id can never collide with engine-internal keys
    # (kv_exports, host_kv); defaults to req_id at submit
    trace_id: str = ""
    # multi-tenant QoS (docs/qos.md): tenant identity + resolved class
    # priority.  Both stay at their zero values when QoS is off, so
    # the scheduler's legacy single-FIFO behavior is untouched.
    tenant: str = ""
    priority: int = 0
    # cluster-wide KV pool (docs/kv-pool.md): the request's chained
    # prefix block hashes (one per whole KV page of prompt), computed
    # at intake from the same bytes the EPP hashes; the finished
    # prefill publishes its prefix pages under these.  kv_prefix_tokens
    # marks an in-flight POOL fetch: kv_chunked holds only the first
    # kv_prefix_tokens of prompt KV and prefill finishes the rest —
    # any fetch failure silently falls back to a full local prefill.
    pool_blocks: list = field(default_factory=list)
    kv_prefix_tokens: int = 0
    # conversation identity (X-Kaito-Session): opaque client-chosen id
    # that keys session→holder routing in the EPP; "" for one-shot
    # requests keeps every pre-session code path byte-identical.
    session: str = ""
    # per-token ITL (--itl): wall time of the last emitted token.  The
    # stamp lives on the request, not the slot, so a gap that spans a
    # preemption/re-admission still counts as one client-visible stall.
    last_emit_time: Optional[float] = None

    @property
    def expired(self) -> bool:
        return self.deadline is not None and time.monotonic() > self.deadline

    def resume_tokens(self) -> list[int]:
        """Prompt plus everything generated so far — what a preempted
        request prefills from on re-admission."""
        return list(self.prompt_tokens) + list(self.output_tokens)

    def stream(self):
        """Yield token ids until completion."""
        while True:
            tok = self.out.get()
            if tok is None:
                return
            yield tok


class PageAllocator:
    """Free-list page allocator (page 0 reserved as the null page).

    A C++ twin lives in kaito_tpu/native for the radix-tree prefix cache;
    the free list itself is not the bottleneck.
    """

    def __init__(self, num_pages: int):
        self.free = list(range(num_pages - 1, 0, -1))
        self.num_pages = num_pages

    @property
    def available(self) -> int:
        return len(self.free)

    def alloc(self, n: int) -> list[int]:
        if n <= 0:
            return []
        if n > len(self.free):
            raise MemoryError(f"need {n} pages, have {len(self.free)}")
        taken = self.free[-n:][::-1]
        del self.free[len(self.free) - n:]
        return taken

    def release(self, pages: list[int]) -> None:
        self.free.extend(reversed(pages))


@dataclass
class _Slot:
    request: Optional[Request] = None
    pages: list[int] = field(default_factory=list)
    # the window kind's table (docs/kv-cache.md, "Two kinds of page"):
    # page index -> page of the window pool; entries behind the window
    # have gone back to the pool.  Empty for a one-kind cache.
    wpages: dict = field(default_factory=dict)
    position: int = 0          # next token position (== current length)
    remaining: int = 0
    prefilling: bool = False
    importing: bool = False    # PD decode role: KV chunks still landing
    prefill_pos: int = 0       # prompt tokens written so far (incl. cached)
    prefill_tokens: list[int] = field(default_factory=list)
    prefill_t0: float = 0.0    # first-chunk dispatch time (cost model)
    prefill_base: int = 0      # prefill_pos at first dispatch (cached skip)
    staged_t0: float = 0.0     # admission time: queue-wait-since-staging
                               # vs compute in TTFT attribution
    seq: int = 0               # admission order (newest preempts first)

    @property
    def written(self) -> int:
        """Tokens whose KV has actually landed in the cache."""
        return self.prefill_pos if self.prefilling else self.position


def _zero_moe_stats(cache: KVCache) -> KVCache:
    """A decode program counts its own steps: the counters start at
    zero (a no-op for a cache that has none)."""
    if cache.moe_stats is None:
        return cache
    return dataclasses.replace(cache,
                               moe_stats=jnp.zeros_like(cache.moe_stats))


def _moe_stats_out(cache: KVCache):
    """The counters as an output of their own: the cache is donated to
    the next program before the host reads this one's tokens."""
    return None if cache.moe_stats is None else cache.moe_stats + 0


@jax.jit
def _patch_carry_row(tokens, positions, active, left, gstate, row):
    """Write one slot's row of the decode carry (docs/decode-loop.md):
    ``row`` is int32 [slot, last token, position, budget left, grammar
    row]; the slot becomes active, every other row keeps what the
    device has advanced it to."""
    i = row[0]
    return (tokens.at[i].set(row[1]), positions.at[i].set(row[2]),
            active.at[i].set(True), left.at[i].set(row[3]),
            gstate.at[i].set(row[4]))


@jax.jit
def _first_token_step(logits, sampling, carry, rows, counts, prompt_seen,
                      grows):
    """What a completed prefill hands to decode, in one program
    (docs/decode-loop.md): sample the first token of each row of
    ``logits`` [n, V] from its slot's sampling state, and, given the
    decode ``carry`` (_CARRY_FIELDS), join the rows to it on the device.
    ``rows`` is int32 [n, 4 + _STOP_WIDTH]: slot, position, budget,
    grammar row, then the slot's stop ids (-1-padded).  A row joins
    ``active`` unless its budget is spent by this token or the token is
    one of its stop ids: what _emit decides on the host.  ``carry``,
    ``counts``/``prompt_seen`` ([S, V] penalty state) and ``grows``
    ([n, V] grammar masks) may be None: each then compiles away.
    Returns (carry, key, tokens, logprobs): key is the whole [S, 2]
    key array with the rows' draws taken."""
    sel = rows[:, 0]
    sub = jax.tree.map(lambda a: a[sel], sampling)
    if counts is not None:
        counts, prompt_seen = counts[sel], prompt_seen[sel]
    tok, sub = sample(logits, sub, counts, prompt_seen, grows)
    lp = chosen_logprob(logits, tok)
    key = sampling.key.at[sel].set(sub.key)
    if carry is not None:
        tokens, positions, active, left, gstate = carry
        spent = rows[:, 2] - 1
        hit = jnp.any(tok[:, None] == rows[:, 4:], axis=1)
        carry = (tokens.at[sel].set(tok),
                 positions.at[sel].set(rows[:, 1]),
                 active.at[sel].set(~hit & (spent > 0)),
                 left.at[sel].set(spent),
                 gstate.at[sel].set(rows[:, 3]))
    return carry, key, tok, lp


class InferenceEngine:
    """Synchronous engine core; the HTTP server drives it via a thread."""

    # loop-state fields the async decode path keeps device-resident
    # (docs/decode-loop.md).  "left" is the fused-scan budget countdown
    # (host mirror: _remaining); the rest mirror the same-named numpy
    # arrays.  DEVICE_ADVANCED fields are the ones the scan itself
    # advances — their host mirrors lag any in-flight window, so a
    # dirty mark on them forces a pipeline drain before re-upload
    # (uploading a stale mirror would roll the device state back).
    # page_tables / slot_adapters are host-only-written and safe to
    # re-upload while a window is in flight.
    # "gstate" is the per-slot grammar-automaton row (host mirror:
    # _gram_state, advanced by _emit along the replay path) — the scan
    # advances it in-device, so constrained decoding rides the async
    # pipeline drain-free like the rest of the loop state.
    _STATE_FIELDS = ("last_tokens", "positions", "active", "page_tables",
                     "slot_adapters", "left", "gstate")
    _CARRY_FIELDS = ("last_tokens", "positions", "active", "left", "gstate")
    _DEVICE_ADVANCED = frozenset(_CARRY_FIELDS)

    def __init__(
        self,
        cfg: EngineConfig,
        metadata: Optional[ModelMetadata] = None,
        params=None,
        mesh=None,
    ):
        self.cfg = cfg
        self.md = metadata or get_model_by_name(cfg.model)
        arch = self.md.arch
        self.dtype = jnp.dtype(cfg.dtype)
        # None = auto: Pallas kernels on TPU, pure-JAX elsewhere
        use_pallas = (jax.default_backend() == "tpu"
                      if cfg.use_pallas is None else bool(cfg.use_pallas))
        self.model = TransformerLM(
            arch, dtype=self.dtype,
            attn_impl="pallas" if use_pallas else "jax")
        if arch.num_experts > 0:
            # EP shards the expert reduction via GSPMD over the dense
            # path (exact, psum-combined); single-group serving keeps
            # the grouped-matmul (ragged) path whose FLOPs scale with
            # top_k instead of the expert count
            self.model.moe_impl = ("dense" if cfg.expert_parallel > 1
                                   else "ragged")
        self.tokenizer = load_tokenizer(self.md.hf_id, arch.vocab_size)
        if self.model.has_state:
            self._refuse_for_state_pool(mesh)
        # an expert layer's grouped matmuls: the Pallas kernel where the
        # attention kernels are, on one chip (engine/nn.py); on a mesh
        # the partitioner splits XLA's ragged dot, as it did
        self.model.moe_kernel = use_pallas and mesh is None
        self.two_kinds = arch.two_kind_cache
        if self.two_kinds:
            self._refuse_for_two_kinds(mesh)
        if self.model.is_mla and arch.expert_shards > 1:
            self._refuse_for_latent_share(mesh)
        if jnp.dtype(cfg.kv_dtype) == jnp.int8 and (
                cfg.pipeline_parallel > 1 or cfg.sequence_parallel > 1):
            # the staged 6-dim PP pools and the CP ring prefill don't
            # carry the page-scale tensors yet
            raise ValueError(
                "kv_dtype='int8' is not supported with pipeline_parallel>1 "
                "or sequence_parallel>1")
        self.pp_exec = None
        if cfg.pipeline_parallel > 1:
            if cfg.pd_enabled and jax.process_count() > 1:
                # exporting a pipeline-sharded pool needs every stage's
                # shard on this host; multi-process PP can't gather it
                raise ValueError(
                    "P/D disaggregation is not supported on MULTI-PROCESS "
                    "pipeline engines (the staged KV pool spans hosts); "
                    "single-process PP composes with PD")
            if mesh is not None:
                raise ValueError("pipeline-parallel serving builds its own "
                                 "(pipeline, tensor) mesh; an explicit mesh "
                                 "cannot be honored")
            if cfg.sequence_parallel > 1:
                logger.warning("sequence_parallel=%d ignored on a pipeline-"
                               "parallel engine (the stage executor has no "
                               "sequence axis); long prompts use chunked "
                               "prefill", cfg.sequence_parallel)
            self.mesh = None       # the PP executor owns the full mesh
            self.pp_exec = self._build_pp_executor()
        else:
            self.mesh = mesh if mesh is not None else self._build_mesh()
            if use_pallas and self.mesh is not None:
                # a Mosaic call is never auto-partitioned: on a mesh the
                # attention kernels run per head shard (model.head_shard)
                self.model.head_shard = (self.mesh, self._kv_head_axis())
            sp = (dict(self.mesh.shape).get("sequence", 1)
                  if self.mesh is not None else 1)
            if sp > 1:
                if self.model.is_mla:
                    # MLA's latent stream has no standard q/k/v for the
                    # ring; long MLA prompts keep the chunked path
                    logger.warning("sequence_parallel>1 ignored for MLA "
                                   "models; using chunked prefill")
                else:
                    tp_sz = dict(self.mesh.shape).get("tensor", 1)
                    head_axis = ("tensor" if tp_sz > 1
                                 and arch.num_heads % tp_sz == 0
                                 and arch.num_kv_heads % tp_sz == 0
                                 else None)
                    self.model.cp = (self.mesh, "sequence", head_axis,
                                     cfg.cp_q_tile)
                    logger.info("context-parallel prefill: sequence=%d "
                                "(head_axis=%s)", sp, head_axis)

        # collective-compute overlap (docs/multichip.md): pipelined
        # ring decomposition of the TP decode all-reduces + layer-ahead
        # slab prefetch.  Off by default — the gate-off path keeps
        # dispatch, numerics and the exposition byte-identical; None
        # follows KAITO_COMM_OVERLAP (which doubles as the trace-time
        # ring/jax implementation override, overlap_collectives.py).
        # Only a flat TP>=2 mesh qualifies: PP drives decode through
        # its own executor, CP only reshapes prefill, single-chip has
        # no collective to hide.
        co = cfg.comm_overlap if cfg.comm_overlap is not None else (
            os.environ.get("KAITO_COMM_OVERLAP", "").strip().lower()
            not in ("", "0", "false", "off"))
        self.comm_overlap = False
        if co and self.mesh is not None and self.pp_exec is None:
            from kaito_tpu.parallel.sharding import SERVE_RULES, ring_axis

            ax = ring_axis(SERVE_RULES)
            tp_sz = dict(self.mesh.shape).get(ax, 1) if ax else 1
            emb = arch.hidden_size
            if (tp_sz >= 2 and emb % tp_sz == 0
                    and arch.num_heads % tp_sz == 0
                    and arch.intermediate_size % tp_sz == 0):
                self.comm_overlap = True
                self.model.overlap = (self.mesh, ax)
                logger.info("collective-compute overlap: ring TP decode "
                            "(%s=%d, %d ppermute hops per projection)",
                            ax, tp_sz, tp_sz - 1)
            else:
                logger.warning(
                    "comm-overlap requested but not applicable "
                    "(ring axis=%s size=%d, embed=%d heads=%d "
                    "intermediate=%d must all divide); keeping the "
                    "unoverlapped path", ax, tp_sz, emb,
                    arch.num_heads, arch.intermediate_size)

        # a latent-attention model's pool is read by the Pallas decode
        # kernel where that can run: the attention kernels' platform,
        # one device, a bf16 pool, and nothing that moves pages in and
        # out of the pool by their five-dimensional shape (the XLA
        # paths stand everywhere else: docs/kv-cache.md, "Latent pages")
        self.latent_kernel = bool(
            self.model.is_mla and use_pallas and self.mesh is None
            and self.pp_exec is None
            and jnp.dtype(cfg.kv_dtype) == jnp.bfloat16
            and not (cfg.pd_enabled or cfg.kv_pool_enabled
                     or cfg.host_kv_offload_bytes or cfg.speculative_draft
                     or cfg.speculative_ngram))

        if not cfg.max_model_len:
            cfg.max_model_len = min(self.md.max_model_len, 8192)
        self.pages_per_seq = cfg.pages_per_seq
        # buckets must cover any admissible prompt (< max_model_len)
        self.buckets = tuple(sorted(
            {b for b in cfg.prefill_buckets if b < cfg.max_model_len}
            | {cfg.max_model_len}))
        self.moe_tiles = self._expert_tiles()
        if self.moe_tiles:
            logger.info("expert layer: grouped-matmul tiles (m, k, n) by "
                        "[K, N]: decode %s, prefill %s",
                        self.moe_tiles["decode"], self.moe_tiles["prefill"])
        if cfg.quantization:
            from kaito_tpu.engine.quant import (QUANT_SCHEMES,
                                                supports_quantization)

            # fail fast BEFORE any allocation or weight loading
            if cfg.quantization not in QUANT_SCHEMES:
                raise ValueError(
                    f"unknown quantization {cfg.quantization!r} "
                    f"(known: {', '.join(QUANT_SCHEMES)})")
            if not supports_quantization(arch, cfg.quantization):
                raise ValueError(
                    f"quantization {cfg.quantization!r} does not support "
                    f"this architecture (hidden_size={arch.hidden_size})")

        # params BEFORE the KV pool: sizing reads the ACTUAL resident
        # weight bytes (post-quantization), and quantizing with a
        # donated tree frees the bf16 weights before the pool claims
        # the rest of HBM
        if cfg.quantization and params is None and not cfg.weights_dir:
            # synthetic weights: FUSE init+quantize in one jit so XLA's
            # memory planner frees each bf16 leaf right after its
            # quantize — an 8B-class bf16 tree (16 GiB) never has to be
            # resident at once on a 16 GiB chip
            self.params = self._init_quantized_params()
        elif cfg.quantization and params is None:
            # real checkpoint: the loader quantizes per tensor as it
            # streams (_make_leaf_transform) — nothing left to do here
            self.params = self._init_params()
        else:
            self.params = params if params is not None else self._init_params()
            if cfg.quantization:
                from kaito_tpu.engine.quant import quantize_params

                t0 = time.monotonic()
                # under a TP mesh the QTensor tree gets explicit
                # shardings derived from SERVE_RULES (q8/q4 keep the
                # weight's spec, the scale keeps the out dim's — plus
                # the group dim's under int4); otherwise XLA would be
                # free to re-lay-out the donated tree
                qkw = ({"out_shardings": self._quantized_param_shardings()}
                       if self.mesh is not None else {})
                self.params = jax.jit(
                    partial(quantize_params, scheme=cfg.quantization),
                    donate_argnums=0, **qkw)(self.params)
                jax.block_until_ready(self.params)
                logger.info(
                    "%s weights ready in %.1fs (%.2f GiB)",
                    cfg.quantization, time.monotonic() - t0,
                    sum(x.nbytes for x in jax.tree.leaves(self.params))
                    / 2**30)

        # a latent-attention model's ``kv_b_k`` / ``kv_b_v`` as decode
        # reads them, named on /health: the Pallas kernel's absorb and
        # expand products batch over heads, so beside a kernel-read
        # pool the two stacks are held head-major too, made here once
        # and BEFORE pool sizing, which counts every resident leaf
        self.latent_weights = None
        if self.latent_kernel:
            self.params = jax.block_until_ready(
                self.model.latent_head_major(self.params))
            self.latent_weights = "head_major"
        elif self.model.is_mla:
            self.latent_weights = "as_drawn"

        # draft-model speculation (docs/speculative.md): the draft and
        # its private KV pool come up BEFORE target-pool sizing so the
        # derived page count reads the HBM actually left over
        self.spec_draft = None
        self.spec_ctl = None
        self._ngram_idx: dict[int, NgramIndex] = {}
        if cfg.speculative_draft:
            from kaito_tpu.engine.spec import DepthController, DraftRunner

            self.spec_draft = DraftRunner(self)
            self.spec_ctl = DepthController(cfg.max_num_seqs,
                                            cfg.speculative_draft_k)

        # the per-slot recurrent-state pool of a model with a
        # state-space mixer (docs/kv-cache.md) is allocated BEFORE HBM
        # is measured: the pages get what it leaves
        self._state_pool = self._make_state_pool()
        self.sizing_report: dict = {}
        self._num_window_pages = 0
        num_pages = cfg.max_pages or self._derive_max_pages()
        num_pages = max(num_pages, cfg.max_num_seqs * self.pages_per_seq // 4 + 2)
        self._num_pages = num_pages
        if self.two_kinds and not self._num_window_pages:
            # max_pages sizes the full kind's pool by hand; the window
            # kind's follows the slots
            self._num_window_pages = self._window_pool_cap()
        if cfg.max_pages:
            self.sizing_report = {"source": "configured"}
        # report the FINAL pool size (post-floor), not the derived value
        self.sizing_report["pages"] = num_pages
        self.cache = self._fresh_cache()
        logger.info("KV cache: %d pages x %d tokens (%.2f GiB)",
                    num_pages, cfg.page_size,
                    (self.cache.k.nbytes + self.cache.v.nbytes) / 2**30)
        if self.two_kinds:
            self.sizing_report["window_pages"] = self._num_window_pages
            self.sizing_report["full_pool_bytes"] = \
                int(self.cache.k.nbytes + self.cache.v.nbytes)
            self.sizing_report["window_pool_bytes"] = \
                self.cache.window_pool_bytes
            logger.info("window pool: %d pages x %d tokens, %d layers "
                        "(%.2f GiB); a sequence holds at most %d of them "
                        "while it decodes", self._num_window_pages,
                        cfg.page_size, arch.attention_layers(1),
                        self.cache.window_pool_bytes / 2**30,
                        self._window_pages_per_seq)
        if self.model.is_mla:
            self.sizing_report["latent_pool_bytes"] = self.latent_pool_bytes
            self.sizing_report["latent_bytes_per_token"] = \
                self.latent_bytes_per_token
            logger.info("latent pool: %d B a token stored (%d logical), "
                        "read by the %s path", self.latent_bytes_per_token,
                        self.md.kv_bytes_per_token(
                            jnp.dtype(cfg.kv_dtype).itemsize),
                        self.attention_path)
        if self.model.has_state:
            self.sizing_report["state_pool_bytes"] = \
                self.cache.state_pool_bytes
            logger.info("state pool: %d slots x %d layers, %s "
                        "(%.2f GiB)", cfg.max_num_seqs,
                        arch.conv_layers or arch.gdn_layers
                        or arch.num_layers,
                        self.dtype.name, self.cache.state_pool_bytes / 2**30)
        if self.model.has_conv or self.model.has_gdn:
            # pages for the attention layers alone, a row of state for
            # the rest (docs/kv-cache.md, "A row of conv state", "A row
            # of matrix state")
            self.sizing_report["kv_bytes_per_token"] = \
                self.md.kv_bytes_per_token(jnp.dtype(cfg.kv_dtype).itemsize)
            self.sizing_report["state_bytes_per_row"] = \
                arch.state_bytes_per_seq(self.dtype.itemsize)
        self.adapter_index: dict[str, int] = {}
        self.adapters_merged = False
        self.adapter_cache = None
        # load-refusal reasons -> counts (the
        # kaito:adapter_load_failures_total{reason} family; shared with
        # the cache's own counter dict when the cache is on)
        self.adapter_load_failures: dict[str, int] = {}
        if (cfg.adapter_slots > 0 and self.pp_exec is None
                and not self.model.is_mla):
            # dynamic multi-LoRA (docs/multi-lora.md): fixed-capacity
            # slot table sized NOW so /v1/adapters hot-loads are pure
            # in-place buffer writes — no recompiles, no restarts
            from kaito_tpu.engine.adapter_cache import (AdapterCache,
                                                        AdapterLoadError)
            from kaito_tpu.engine.adapters import discover_adapters

            self.adapter_cache = AdapterCache(
                self.model, slots=cfg.adapter_slots,
                rmax=cfg.adapter_rmax,
                base_model=self.md.name,
                host_bytes=cfg.adapter_host_bytes,
                allow_base_mismatch=getattr(
                    cfg, "adapter_allow_base_mismatch", False),
                mesh=self.mesh)
            self.adapter_cache.busy_fn = self._adapter_busy
            self.adapter_load_failures = self.adapter_cache.load_failures
            # adapter_index IS the cache's residency map (same dict,
            # mutated in place by hot-load/evict)
            self.adapter_index = self.adapter_cache.name_to_slot
            for name, path in discover_adapters(cfg.adapters_dir).items():
                try:
                    self.adapter_cache.load_from_path(name, path)
                except AdapterLoadError:
                    pass        # counted + logged by the cache
            self.params = {**self.params,
                           "serve_lora": self.adapter_cache.serve_lora}
        elif cfg.adapters_dir or cfg.adapter_slots > 0:
            if cfg.adapter_slots > 0:
                logger.warning(
                    "adapter cache requested but unsupported on this "
                    "engine (PP or MLA); falling back to boot-time "
                    "adapter discovery")
            from kaito_tpu.engine.adapters import (
                apply_adapters_to_params,
                discover_adapters,
                load_adapter_stacks,
            )

            serve_lora, self.adapter_index = load_adapter_stacks(
                self.model, cfg.adapters_dir, self.md.name,
                allow_base_mismatch=getattr(
                    cfg, "adapter_allow_base_mismatch", False),
                refusals=self.adapter_load_failures)
            if serve_lora:
                if self.mesh is not None:
                    # adapter factors are tiny; replicate across the
                    # TP mesh so the scan body sees local buffers
                    from jax.sharding import NamedSharding
                    from jax.sharding import PartitionSpec as P

                    serve_lora = jax.device_put(
                        serve_lora, NamedSharding(self.mesh, P()))
                # under PP the stacks stage-split alongside the layer
                # stacks in stage_params below — per-request multi-LoRA
                # keeps working at every parallelism tier
                self.params = {**self.params, "serve_lora": serve_lora}
            elif discover_adapters(cfg.adapters_dir):
                # MLA or no routable targets: keep the round-1
                # merge-into-base behavior so advertised adapters
                # still take effect (selection routes to base)
                self.params = apply_adapters_to_params(
                    self.model, self.params, cfg.adapters_dir)
                self.adapters_merged = True
        if self.pp_exec is not None:
            self.params = self.pp_exec.stage_params(self.params)
        self.prefix_cache = None
        if cfg.enable_prefix_caching and self.model.has_state:
            # a page of a shared prefix carries keys and values but no
            # recurrent state (a radix-tree node no conv state): reuse
            # waits for state snapshots
            logger.warning("prefix caching requested but this model keeps "
                           "a recurrent state no page carries; serving "
                           "WITHOUT prefix reuse")
        elif cfg.enable_prefix_caching and self.two_kinds:
            # a window page goes back to its pool once the sequence is a
            # window past it: a shared prefix would need pages that are
            # no longer there
            logger.warning("prefix caching requested but this model's "
                           "window layers free their pages behind the "
                           "window; serving WITHOUT prefix reuse")
        elif cfg.enable_prefix_caching and self.model.is_mla:
            logger.warning("prefix caching requested but latent-attention "
                           "layers have no prefix reuse yet (ROADMAP R5); "
                           "serving WITHOUT prefix reuse")
        elif cfg.enable_prefix_caching:
            # the radix tree tracks host-side PAGE IDS only — the same
            # ids index the sharded (TP) or stage-split (PP) pools, so
            # prefix reuse is layout-independent and works under any
            # mesh (lifting the round-2 single-chip gate)
            try:
                from kaito_tpu.native import NativePrefixCache

                self.prefix_cache = NativePrefixCache(num_pages, cfg.page_size)
                logger.info("prefix caching enabled (native radix tree)")
            except RuntimeError:
                # there is no Python radix tree behind the native one:
                # the request was for prefix reuse and it is NOT served
                # (visible as prefix_cache "off" on /health)
                logger.warning(
                    "prefix caching requested but the native library is "
                    "unavailable (make -C kaito_tpu/native); serving "
                    "WITHOUT prefix reuse", exc_info=True)
        # the prefix cache subsumes the free-list (same available/num_pages
        # surface for metrics)
        self.allocator = self.prefix_cache or PageAllocator(num_pages)
        # the window kind's pool has a free list of its own
        self.window_allocator = (PageAllocator(self._num_window_pages)
                                 if self.two_kinds else None)
        # a single sequence can never outgrow the whole pool (generation
        # is length-capped so the preempt-self path always terminates)
        self._capacity_tokens = (num_pages - 1) * cfg.page_size
        self.host_kv = None
        if cfg.host_kv_offload_bytes > 0:
            from kaito_tpu.engine.host_offload import HostKVPool

            # multi-process pipeline engines spill PER-HOST SHARDS
            # (host_offload._HostShards): each lockstep process keeps
            # its own slice of the gathered pages and restore
            # reassembles the global array — preemption costs a page
            # restore at every parallelism tier, never a recompute
            self.host_kv = HostKVPool(cfg.host_kv_offload_bytes)
            logger.info("host KV offload tier: %.2f GiB",
                        cfg.host_kv_offload_bytes / 2**30)
        # cluster-wide KV pool (docs/kv-pool.md): replica-local store of
        # published prompt prefixes, served over the chunked PD wire.
        # None when the feature is off — every pool code path gates on
        # it, keeping scheduling and /metrics byte-identical to before.
        self.kv_pool = None
        if cfg.kv_pool_enabled:
            from kaito_tpu.engine.kv_pool import PrefixPageStore

            self.kv_pool = PrefixPageStore(cfg.kv_pool_bytes)
            logger.info("cluster KV pool store: %.2f GiB",
                        cfg.kv_pool_bytes / 2**30)
        # tier-3 SSD spill (docs/kv-pool.md "Tier 3: SSD"): host-LRU
        # victims demote to a bounded slab directory via an async spill
        # worker (serialization may block on a D2H drain — never on the
        # step loop), and pool misses probe it before remote peers.
        # None when off — every tier code path AND the kv_tier metric
        # families gate on it, keeping disk-off byte-identical.
        self.kv_tier = None
        self._spill_q: Optional[queue.Queue] = None
        self._spill_thread: Optional[threading.Thread] = None
        if (self.kv_pool is not None
                and cfg.kv_pool_disk_bytes > 0):
            import tempfile

            from kaito_tpu.engine.kv_pool import DiskPageStore

            root = cfg.kv_pool_disk_dir or os.path.join(
                tempfile.gettempdir(), "kaito-kv-tier")
            self.kv_tier = DiskPageStore(root, cfg.kv_pool_disk_bytes)
            self._spill_q = queue.Queue(maxsize=256)
            self.kv_pool.on_evict = self._enqueue_spill
            self._spill_thread = threading.Thread(
                target=self._spill_worker, daemon=True,
                name="kv-tier-spill")
            self._spill_thread.start()
            logger.info("KV pool disk tier: %.2f GiB at %s",
                        cfg.kv_pool_disk_bytes / 2**30, root)
        S = cfg.max_num_seqs
        self.slots = [_Slot() for _ in range(S)]
        # a table a sequence, and for two kinds of page a table a kind:
        # [S, 2, pages], the full kind's first (model._run_layers_kinds)
        self.page_tables = np.zeros(
            (S, 2, self.pages_per_seq) if self.two_kinds
            else (S, self.pages_per_seq), np.int32)
        self.positions = np.zeros((S,), np.int32)
        self.active = np.zeros((S,), bool)
        self.sampling = SamplingState.create(S, cfg.seed)
        # penalty state is LAZY: [S, V] output-token histogram + [S, V]
        # prompt-seen mask allocate on the first penalized admission
        # (the decode programs retrace once on the shape change); a
        # penalty-free engine passes [1, 1] placeholders, which the
        # sampler's static shape gate compiles to a no-op — zero HBM
        # and zero per-step cost until someone actually sends a penalty
        self.token_counts = None
        self.prompt_seen = None
        self.last_tokens = np.zeros((S,), np.int32)
        self.slot_adapters = np.zeros((S,), np.int32)  # 0 = base model

        self._score_lock = threading.Lock()
        self.waiting: "collections.deque[Request]" = collections.deque()
        self._waiting_count = 0
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._tick = 0
        self._decode_since_prefill = 0
        self._prefill_rr = 0
        self._admit_seq = 0
        # multi-tenant QoS (docs/qos.md): None keeps the legacy single
        # FIFO + newest-preempts-first behavior bit-for-bit.  With a
        # config, admission becomes strict-priority across classes and
        # deficit-round-robin across tenants within a class, and
        # preemption evicts the lowest-priority newest sequence.
        from kaito_tpu.engine.qos import parse_qos_config

        self.qos = parse_qos_config(cfg.qos_config)
        self._tenant_queues: dict[str, "collections.deque[Request]"] = {}
        self._drr_order: dict[int, "collections.deque[str]"] = {}
        self._drr_deficit: dict[str, float] = {}

        # metrics (scraped by the server's /metrics)
        self.counters = {
            "prompt_tokens_total": 0,
            "generation_tokens_total": 0,
            "requests_total": 0,
            "requests_finished_total": 0,
            "prefill_steps_total": 0,
            # prefill turns of the serial scheduler that ran two or
            # more prompts / exactly one (docs/prefill.md)
            "prefill_turns_multi_total": 0,
            "prefill_turns_single_total": 0,
            "decode_steps_total": 0,
            # slot-steps the decode programs ran, and those of them
            # whose slot was not decoding: such a row attends to nothing
            # and copies no KV page (docs/kv-cache.md)
            "decode_rows_total": 0,
            "decode_rows_idle_total": 0,
            "prefix_cached_tokens_total": 0,
            # per-request prefix-cache outcome (routing layer scrapes
            # these to judge affinity quality, docs/routing.md)
            "prefix_cache_hits_total": 0,
            "prefix_cache_misses_total": 0,
            "preemptions_total": 0,
            # the per-slot recurrent-state pool (docs/kv-cache.md): rows
            # reset at an admission (its first prefill chunk starts from
            # zeros), and resumes that had to rebuild a state by
            # recompute; both stay 0 for a model with no mixer
            "state_resets_total": 0,
            "state_recomputes_total": 0,
            # two kinds of page: window pages returned behind the window
            "window_pages_freed_total": 0,
            # an expert layer's counters over the decode steps
            # (kv_cache.KVCache.moe_stats, read back with each window)
            "moe_expert_calls_total": 0,
            "moe_experts_touched_total": 0,
            "moe_pairs_held_total": 0,
            "moe_pairs_routed_total": 0,
            "host_kv_spilled_pages_total": 0,
            "host_kv_restored_pages_total": 0,
            "spec_steps_total": 0,
            "spec_proposed_tokens_total": 0,
            "spec_accepted_tokens_total": 0,
            # draft-model speculation (the three above stay the n-gram
            # proposer's; /metrics labels them mode="ngram"|"draft")
            "spec_draft_steps_total": 0,
            "spec_draft_rows_total": 0,
            "spec_draft_proposed_tokens_total": 0,
            "spec_draft_accepted_tokens_total": 0,
            "pd_device_handoffs_total": 0,
            # failure-domain isolation
            "requests_failed_total": 0,       # request-scoped failures
            "requests_expired_total": 0,      # deadline-aborted (408)
            "kv_import_retries_total": 0,     # transient -> local recompute
            "engine_fatal_total": 0,          # _fail_all escalations
            # observability (docs/observability.md)
            "prefill_tokens_total": 0,        # prefill tokens dispatched
            "prefill_rows_total": 0,          # rows of their programs
            "requests_shed_total": 0,         # 429s (bumped by the server)
            # cluster-wide KV pool (docs/kv-pool.md) — exposed on
            # /metrics only when the pool is enabled
            "kv_pool_fetches_total": 0,        # cross-replica prefix imports
            "kv_pool_fetched_tokens_total": 0,  # prompt tokens skipped
            "kv_pool_fetch_failures_total": 0,  # fell back to recompute
            "kv_pool_published_total": 0,       # prefixes published locally
            # tier-3 SSD spill (docs/kv-pool.md "Tier 3: SSD") —
            # exposed on /metrics only when the disk tier is enabled
            "kv_tier_host_hits_total": 0,     # local probe hit host RAM
            "kv_tier_disk_hits_total": 0,     # local probe hit SSD
            "kv_tier_import_tokens_total": 0,  # prompt tokens skipped
            "kv_tier_spill_drops_total": 0,   # spill queue full, entry lost
        }
        self._last_deadline_sweep = 0.0
        self._last_export_tick = 0.0

        # tracing + flight recorder (docs/observability.md): bounded,
        # always on — recording is a deque append, scrapes snapshot
        from kaito_tpu.engine.metrics import Histogram

        self.tracer = RingTracer(cfg.trace_capacity)
        self.timeline = StepTimeline(cfg.timeline_capacity)
        # registry=None: the server's EngineMetrics registry adopts
        # these at construction so /metrics exposes them
        self.step_hist = Histogram(
            "kaito:engine_step_seconds", "Scheduler step wall time", None,
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5))
        self.queue_wait_hist = Histogram(
            "kaito:queue_wait_seconds",
            "Submit-to-admission queue wait", None,
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0, 10.0, 30.0))
        # the step split into its phases (docs/observability.md): spans
        # on the profiler's clock, and per non-idle iteration one
        # observation per family, zero included, so that the means are
        # per iteration and add up to engine_step_seconds
        self.phases = PhaseClock(jax.profiler.TraceAnnotation)
        self.phase_hists = {
            phase: Histogram(f"kaito:engine_{family}_seconds", help_, None,
                             buckets=self.step_hist.buckets)
            for phase, family, help_ in (
                ("engine.schedule", "schedule",
                 "Per step: deadline sweep, page reservation, admission"),
                ("engine.decode", "decode_step",
                 "Per step: the decode dispatch, its readback and replay"),
                ("engine.decode.dispatch", "decode_dispatch",
                 "Per step: decode argument build up to the jitted "
                 "call's return"),
                ("engine.decode.wait", "decode_wait",
                 "Per step: blocked on the decode readback"),
                ("engine.decode.replay", "decode_replay",
                 "Per step: replay of the decoded tokens through _emit"),
                ("engine.prefill", "prefill_step",
                 "Per step: prefill dispatch, first-token sampling and "
                 "admission to decode"),
                ("loop_stall", "loop_stall",
                 "Per step: wall less thread CPU time over schedule, "
                 "both dispatches and the replay"),
                # parts of a phase (PhaseClock.part), decode and
                # prefill summed
                ("host.args", "dispatch_args",
                 "Per step: inside the dispatches, everything before "
                 "the jitted call: host arrays, uploads"),
                ("host.launch", "launch",
                 "Per step: the jitted calls themselves, call to return"),
                ("launch_stall", "launch_stall",
                 "Per step: wall less thread CPU time inside the jitted "
                 "calls: held by the runtime or waiting for the "
                 "interpreter lock"),
                ("host.plan", "decode_plan",
                 "Per step: the planning loops over the slots before a "
                 "decode launch"),
                ("replay_stall", "replay_stall",
                 "Per step: wall less thread CPU time inside the replay "
                 "alone: the interpreter lock or the OS"))}
        # prefill scheduling (docs/prefill.md): prompts a prefill turn
        # and staged-to-first-dispatch wait — the two numbers that say
        # whether concurrent arrivals are admitted together or still
        # one an iteration
        self.prefill_pack_hist = Histogram(
            "kaito:engine_prefill_pack_size",
            "Prompts a prefill turn", None,
            buckets=(1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 24.0, 32.0))
        self.prefill_wait_hist = Histogram(
            "kaito:prefill_queue_wait_seconds",
            "Staged-to-first-prefill-dispatch wait", None,
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0))
        self._prefill_pack_note = 0

        # true per-token inter-token latency (--itl / KAITO_ITL): every
        # _emit() stamps wall time and observes the gap since the
        # request's previous token — the single funnel covers the plain,
        # speculative-replay and async-dispatch-replay retire paths.
        # Off (default): itl_hist is None, _emit takes no extra work,
        # and the /metrics exposition is byte-identical.
        self.itl_enabled = cfg.itl_enabled or (
            os.environ.get("KAITO_ITL", "") in ("1", "true"))
        self.itl_hist = None
        # server wires this to SLOWatchdog.observe_itl(gap, tenant)
        self.itl_observer = None
        self._itl_time = time.monotonic
        if self.itl_enabled:
            self._itl_stall_s = max(
                1e-6, float(cfg.slo_itl_p99_ms) * 1e-3)
            self.counters["itl_stalls_total"] = 0
            self.itl_hist = Histogram(
                "kaito:inter_token_latency_seconds",
                "True per-token inter-token latency (gap between "
                "consecutive emitted tokens of one request)", None,
                buckets=(0.002, 0.005, 0.01, 0.02, 0.04, 0.06, 0.08,
                         0.1, 0.25, 0.5, 1.0, 2.5))

        self._decode_fn = self._build_decode_fn()
        self._prefill_fns: dict[int, object] = {}
        self._heap_settled_at = -1      # _COMPILES at the last gc.freeze
        ra = cfg.decode_run_ahead
        if ra is None:
            # fused steps amortize per-dispatch overhead (jit-cache
            # walk, arg staging, runtime RPC on remote plugins); beyond
            # some depth emission burstiness grows faster than the
            # amortization gain.  16 is the depth every chip run of the
            # benchmark has served at (PERF.md); no record holds a
            # sweep of depths, so it is a default and not a knee
            ra = 16 if jax.default_backend() == "tpu" else 1
        self.run_ahead = max(1, int(ra))
        self._decode_multi_fns: dict[int, object] = {}

        # two-deep decode dispatch (docs/decode-loop.md): device-resident
        # loop state, window N+1 launched before window N is read back.
        # None resolves from what the engine can observe, like
        # decode_run_ahead above: on where the backend is an accelerator
        # (the host's turnaround is device idle there), off on the CPU
        # backend; KAITO_ASYNC_DISPATCH=1/0 and an explicit True/False
        # pin it.  PP drives decode through its own executor and
        # multi-process engines run lockstep off the step broadcast, so
        # both keep the synchronous loop whatever was asked.
        ad = cfg.async_dispatch
        if ad is None:
            env = os.environ.get("KAITO_ASYNC_DISPATCH", "").strip().lower()
            ad = (True if env in ("1", "true")
                  else False if env in ("0", "false")
                  else jax.default_backend() != "cpu")
        self.async_dispatch = (bool(ad) and self.pp_exec is None
                               and jax.process_count() == 1)
        # drains of a window in flight, by what forced them; the
        # step's own go on its timeline record
        self.drain_counts: dict[str, int] = {}
        self._step_drains: list[str] = []
        # first tokens sampled and joined to the carry on the device,
        # not yet read back: [(slot, slot.seq, prompt length), ...],
        # the tokens and logprobs on the device, dispatch time.  Those
        # the host had to read at once are counted by what made it
        self._first_pending: "collections.deque[tuple]" = collections.deque()
        self.first_token_blocking: dict[str, int] = {}
        self.first_token_resolve_hist = None
        if self.async_dispatch:
            self.drain_counts = dict.fromkeys(self._DRAIN_REASONS, 0)
            self.counters["first_tokens_deferred_total"] = 0
            self.first_token_blocking = dict.fromkeys(
                self._FIRST_TOKEN_BLOCKS, 0)
            self.first_token_resolve_hist = Histogram(
                "kaito:engine_first_token_resolve_seconds",
                "Dispatch of a completed prefill's first-token program "
                "to the token's emission", None,
                buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                         0.25, 0.5, 1.0, 2.5))
            self.counters["h2d_uploads_total"] = 0
            # a window is primed when another was in flight at its
            # launch: the host's work for that one overlaps this one
            self.counters["decode_windows_primed_total"] = 0
            self.counters["decode_windows_unprimed_total"] = 0
            logger.info("async decode dispatch enabled (two-deep "
                        "pipeline, device-resident loop state)")
        # device-resident state mirrors: host numpy stays authoritative
        # at admission/eviction/preempt boundaries; the async loop
        # uploads only fields marked dirty since the last dispatch
        self._dev_state: dict[str, object] = {}
        self._state_dirty: set[str] = set(self._STATE_FIELDS)
        self._decode_multi_state_fns: dict[int, object] = {}
        # [K, toks, acts, lps, owners]: the trace still on the device
        # and each slot's owner (slot.seq) when the window was launched
        self._inflight: Optional[list] = None
        self._dirty_reason = ""
        # fused-dispatch argument caches (built for both loops): the
        # stop matrix is epoch-keyed (stop sets are per-request
        # immutable, so batch membership is the only invalidation) and
        # the remaining-budget array is an incrementally maintained
        # mirror of slot.remaining — no per-dispatch Python loop
        self._remaining = np.zeros((S,), np.int32)
        self._batch_epoch = 0
        self._stop_cache: tuple = (-1, None)

        # grammar-constrained decoding (docs/structured-output.md).
        # The compiled-schema LRU always exists (the server compiles
        # against it pre-admission), but the packed device table — like
        # the penalty state above — is allocated lazily on the first
        # constrained admission, so grammar-free engines keep the [1,1]
        # placeholder path compiled away and never retrace.
        self.grammar_cache = GrammarCache(
            entries=cfg.grammar_cache_entries,
            max_states=cfg.grammar_max_states)
        self._gram_table: Optional[GrammarTable] = None
        self._gram_slots: list[Optional[GrammarSlot]] = [None] * S
        self._gram_state = np.zeros((S,), np.int32)
        self._dev_gmask = None
        self._dev_gtrans = None
        self._gram_version = 0

        from kaito_tpu.engine.pd import KVExportRegistry, TransferCostModel

        self.kv_exports = KVExportRegistry()
        # live-calibrated transfer-vs-recompute constants: observed
        # prefill throughput + observed import bandwidth feed the
        # break-even decision (static knobs are cold-start priors only)
        self.pd_costs = TransferCostModel()

        # sampled device-time attribution (docs/observability.md).  Off
        # by default: no sampler thread, no kaito:device_* families,
        # /debug/device 403 — the exposition stays byte-identical.
        self.devprof = None
        if cfg.devprof_interval_s > 0:
            from kaito_tpu.engine.devprof import DeviceProfiler

            self.devprof = DeviceProfiler(
                interval_s=cfg.devprof_interval_s,
                window_s=cfg.devprof_window_s,
                ring=cfg.devprof_ring,
                roofline=self._devprof_roofline(),
                tokens_fn=lambda: self.counters["generation_tokens_total"])
            logger.info("device profiler enabled: %.3gs window every "
                        "%.3gs", self.devprof.window_s,
                        self.devprof.interval_s)

    # ------------------------------------------------------------------
    # Construction helpers
    # ------------------------------------------------------------------

    def _devprof_roofline(self) -> dict:
        """Chip peaks + model constants for devprof's achieved-vs-peak
        window rates: the weight stream only, without the per-sequence
        KV term (batch composition changes mid-window, so the weight
        stream is the stable lower bound)."""
        from kaito_tpu.sku.catalog import CHIP_CATALOG

        chip = CHIP_CATALOG.get("v5e")
        quant = self.cfg.quantization or ""
        n_params = self.md.arch.param_count()
        peak_flops = (chip.int8_tops if quant == "int8"
                      else chip.bf16_tflops) * 1e12
        param_bytes = n_params * {"": 2.0, "int8": 1.0,
                                  "int4": 0.53125}.get(quant, 2.0)
        return {"params": float(n_params),
                "bytes_per_tok": float(param_bytes),
                "peak_flops": peak_flops,
                "peak_bytes_s": chip.hbm_gbps * 1e9}

    def _build_mesh(self):
        """SP×EP×TP mesh from config (the planner's sequence/expert/
        tensor axes): weights and KV heads shard across chips, expert
        stacks place over the expert axis, long-prompt prefills shard
        their activations over the sequence axis; XLA inserts the
        collectives."""
        tp = self.cfg.tensor_parallel
        ep = self.cfg.expert_parallel
        sp = self.cfg.sequence_parallel
        self._validate_ep(ep)
        if tp * ep * sp <= 1:
            return None
        from kaito_tpu.parallel.mesh import build_mesh
        from kaito_tpu.parallel.plan import make_mesh_spec

        devices = jax.devices()
        if len(devices) < tp * ep * sp:
            raise ValueError(f"sequence_parallel={sp} x expert_parallel={ep}"
                             f" x tensor_parallel={tp} but only "
                             f"{len(devices)} devices visible")
        return build_mesh(make_mesh_spec(sequence=sp, expert=ep, tensor=tp),
                          devices[:tp * ep * sp])

    def _validate_ep(self, ep: int) -> None:
        if ep > 1 and (self.md.arch.num_experts < ep
                       or self.md.arch.num_experts % ep):
            raise ValueError(f"expert_parallel={ep} must divide the "
                             f"{self.md.arch.num_experts} experts")

    def _build_pp_executor(self):
        """Stage-sharded serving executor over the planner's pipeline
        axis, with TP composing inside each stage — the reference's
        tier 3 (TP-within-node x PP-across-nodes,
        interface.go:514-560)."""
        from jax.sharding import Mesh

        from kaito_tpu.parallel.pp_serve import PipelineServeExecutor

        pp = self.cfg.pipeline_parallel
        tp = max(1, self.cfg.tensor_parallel)
        ep = max(1, self.cfg.expert_parallel)
        self._validate_ep(ep)
        devices = jax.devices()
        if len(devices) < pp * ep * tp:
            raise ValueError(f"pipeline_parallel={pp} x expert_parallel={ep}"
                             f" x tensor_parallel={tp} but only "
                             f"{len(devices)} devices visible")
        if ep * tp > 1:
            # pipeline outermost (the DCN/process axis); EP and TP ride
            # ICI inside each stage, mirroring the flat engine's mesh
            mesh = Mesh(np.array(devices[:pp * ep * tp]).reshape(pp, ep, tp),
                        ("pipeline", "expert", "tensor"))
        else:
            mesh = Mesh(np.array(devices[:pp]), ("pipeline",))
        if self.cfg.pp_microbatches < 1:
            raise ValueError(f"pp_microbatches must be >= 1, got "
                             f"{self.cfg.pp_microbatches}")
        M = min(self.cfg.pp_microbatches, self.cfg.max_num_seqs)
        while self.cfg.max_num_seqs % M:
            M -= 1
        if M != self.cfg.pp_microbatches:
            logger.info("pp_microbatches adjusted %d -> %d to divide "
                        "max_num_seqs=%d (pipeline overlap is M/(M+S-1))",
                        self.cfg.pp_microbatches, M, self.cfg.max_num_seqs)
        return PipelineServeExecutor(self.model, mesh, num_microbatches=M)

    # what cannot serve a model whose cache holds a recurrent state
    # beside its pages (docs/kv-cache.md), and what each waits for
    _STATE_POOL_REFUSALS = (
        ("tensor_parallel", 1, "tensor parallelism (the mixer's heads "
         "are not sharded)"),
        ("pipeline_parallel", 1, "pipeline parallelism (the stage "
         "executor threads no state pool)"),
        ("sequence_parallel", 1, "context-parallel prefill (the ring has "
         "no scan of the state)"),
        ("expert_parallel", 1, "expert parallelism"),
        ("host_kv_offload_bytes", 0, "host KV offload (a spilled "
         "sequence's state is not spilled; resume recomputes it)"),
        ("pd_enabled", False, "prefill/decode disaggregation (the wire "
         "carries pages, not the recurrent state)"),
        ("kv_pool_enabled", False, "the cluster KV pool (a published "
         "prefix carries no recurrent state)"),
        ("speculative_ngram", 0, "n-gram speculation (a rejected token's "
         "state update cannot be rolled back)"),
        ("speculative_draft", "", "draft-model speculation (a rejected "
         "token's state update cannot be rolled back)"),
    )

    def _refuse_for_state_pool(self, mesh) -> None:
        """Refuse by name, at start, every setting a model with a
        state pool (a state-space mixer's, or rows of conv state)
        cannot be served under yet."""
        if mesh is not None:
            raise ValueError(
                f"{self.md.name} keeps a per-slot recurrent state beside "
                f"its KV pages and is served on one device: no mesh")
        if self.model.has_conv or self.model.has_gdn:
            if jnp.dtype(self.cfg.kv_dtype) == jnp.int8:
                raise ValueError(
                    f"{self.md.name} keeps a per-slot recurrent state "
                    f"beside its KV pages and cannot be served with an "
                    f"int8 KV cache (its token-flat pools have no scale "
                    f"tensors): unset kv_dtype")
        for field_name, off, what in self._STATE_POOL_REFUSALS:
            if getattr(self.cfg, field_name) != off:
                raise ValueError(
                    f"{self.md.name} keeps a per-slot recurrent state "
                    f"beside its KV pages and cannot be served with "
                    f"{what}: unset {field_name}")

    # what cannot serve a model whose window layers keep their own page
    # pool and table and free pages behind the window (docs/kv-cache.md),
    # and what each waits for
    _TWO_KIND_REFUSALS = (
        ("tensor_parallel", 1, "tensor parallelism (the two kinds' KV "
         "heads, 4 and 8, would need a sharding each)"),
        ("pipeline_parallel", 1, "pipeline parallelism (the stage "
         "executor threads one pool and one table)"),
        ("sequence_parallel", 1, "context-parallel prefill (the ring "
         "writes one pool)"),
        ("expert_parallel", 1, "expert parallelism inside the engine "
         "(the chip's share of the experts is the model's "
         "configuration: expert_shards)"),
        ("host_kv_offload_bytes", 0, "host KV offload (a spilled "
         "sequence's window pages are not spilled)"),
        ("pd_enabled", False, "prefill/decode disaggregation (the wire "
         "carries one pool's pages)"),
        ("kv_pool_enabled", False, "the cluster KV pool (a published "
         "prefix would need window pages that were freed)"),
        ("speculative_ngram", 0, "n-gram speculation (the verify window "
         "has no two-table path)"),
        ("speculative_draft", "", "draft-model speculation (the verify "
         "window has no two-table path)"),
    )

    def _refuse_settings(self, what: str, mesh, refusals) -> None:
        """Raise, naming the setting, for a mesh or the first field of
        ``refusals`` ((field, its off value, why) each) that is on."""
        if mesh is not None:
            raise ValueError(f"{what} and is served on one device: no mesh")
        for field_name, off, why in refusals:
            if getattr(self.cfg, field_name) != off:
                raise ValueError(
                    f"{what} and cannot be served with {why}: "
                    f"set {field_name} to {off!r}")

    def _refuse_for_two_kinds(self, mesh) -> None:
        """Refuse by name, at start, every setting a model with two
        kinds of page cannot be served under yet."""
        what = (f"{self.md.name} keeps a page pool and a page table for "
                f"its window layers beside the full layers'")
        if mesh is None and jnp.dtype(self.cfg.kv_dtype) == jnp.int8:
            raise ValueError(f"{what} and cannot be served with an int8 KV "
                             f"cache (the window pool has no scale "
                             f"tensors): unset kv_dtype")
        self._refuse_settings(what, mesh, self._TWO_KIND_REFUSALS)

    # what cannot serve a latent-attention model that holds a share of
    # its expert layers (docs/kv-cache.md, "Latent pages"), and what
    # each waits for
    _LATENT_SHARE_REFUSALS = (
        ("tensor_parallel", 1, "tensor parallelism (the one latent "
         "stream all heads share is not sharded)"),
        ("pipeline_parallel", 1, "pipeline parallelism (the stage "
         "executor splits whole expert layers)"),
        ("sequence_parallel", 1, "context-parallel prefill (the ring has "
         "no latent stream)"),
        ("expert_parallel", 1, "expert parallelism inside the engine "
         "(the chip's share of the experts is the model's "
         "configuration: expert_shards)"),
        ("host_kv_offload_bytes", 0, "host KV offload (a spilled page's "
         "shape is not the kernel-read pool's)"),
        ("pd_enabled", False, "prefill/decode disaggregation (the wire "
         "carries pages of the five-dimensional pool)"),
        ("kv_pool_enabled", False, "the cluster KV pool (latent layers "
         "have no prefix reuse)"),
        ("speculative_ngram", 0, "n-gram speculation (the verify window "
         "has no kernel path over a latent pool)"),
        ("speculative_draft", "", "draft-model speculation (the verify "
         "window has no kernel path over a latent pool)"),
        ("adapter_slots", 0, "the adapter cache (latent projections "
         "carry no adapter slots)"),
    )

    def _refuse_for_latent_share(self, mesh) -> None:
        """Refuse by name, at start, every setting a latent-attention
        model that holds a share of its expert layers cannot be served
        under yet."""
        self._refuse_settings(
            f"{self.md.name} caches a latent stream and holds "
            f"{self.md.arch.experts_held} of {self.md.arch.num_experts} "
            f"experts a layer", mesh, self._LATENT_SHARE_REFUSALS)

    def _expert_tiles(self) -> Optional[dict]:
        """The grouped-matmul kernel's (m, k, n) tiles by the experts'
        [K, N], as ``/health`` lists them under ``moe_tiles``: for a
        decode step of every slot and for the longest prefill chunk
        (``nn.expert_tiles``: chosen from the shapes when a program is
        traced).  None where no such kernel runs: no grouped expert
        layer, a CPU or a mesh, quantized stacks (XLA's ragged dot)."""
        if not (self.model.moe_combine and self.model.moe_kernel) \
                or self.cfg.quantization:
            return None
        cfg, arch = self.cfg, self.md.arch
        chunk = self._bucket(min(max(cfg.max_prefill_tokens, cfg.page_size),
                                 cfg.max_model_len))
        itemsize = self.dtype.itemsize
        return {"decode": nn.expert_tiles(arch, cfg.max_num_seqs, itemsize),
                "prefill": nn.expert_tiles(arch, chunk, itemsize)}

    @property
    def attention_path(self) -> str:
        """What reads the cache in a decode step, as ``/health`` names
        it: ``pallas`` where a kernel does (a latent pool: the kernel-
        read layout), ``jax`` where XLA gathers the pages; ``+conv``
        behind either where the model's other layers are short
        convolutions, ``+delta`` where they are gated delta rules."""
        if self.model.is_mla:
            return "pallas" if self.latent_kernel else "jax"
        if self.model.has_conv:
            # most layers mix their tokens by a short convolution (XLA)
            # and read a row of conv state, not pages
            return self.model.attn_impl + "+conv"
        if self.model.has_gdn:
            # most layers keep a matrix a head (the Pallas state update
            # where attention's kernel is) and read no page
            return self.model.attn_impl + "+delta"
        return self.model.attn_impl

    @property
    def latent_bytes_per_token(self) -> int:
        """Bytes a cached token holds in the latent pool across all
        layers, stored lanes included (0: no latent attention)."""
        if not self.model.is_mla:
            return 0
        return self.md.kv_bytes_per_token(
            jnp.dtype(self.cfg.kv_dtype).itemsize, stored=self.latent_kernel)

    @property
    def latent_pool_bytes(self) -> int:
        return int(self.cache.k.nbytes) if self.model.is_mla else 0

    def _refuse_kv_import(self) -> None:
        if self.two_kinds:
            raise ValueError(
                f"{self.md.name} keeps a second page pool for its window "
                f"layers: imported KV pages carry none of it, so a "
                f"request with KV cannot be admitted")
        if self.model.has_state:
            raise ValueError(
                f"{self.md.name} keeps a per-slot recurrent state beside "
                f"its KV pages: imported KV pages carry none of it, so a "
                f"request with KV cannot be admitted")

    def _state_rows(self, idxs) -> Optional[jax.Array]:
        """The slots' rows of the state pool, for a prefill program (None
        for a model with no pool: the argument compiles away)."""
        if not self.model.has_state:
            return None
        return jnp.asarray(np.asarray(idxs, np.int32))

    def _make_state_pool(self) -> dict:
        """The zeroed state pool as the cache's fields: a state-space
        mixer's state and convolution tail, the short-convolution
        layers' rows of conv state, or the delta-rule layers' rows of
        matrix state and their convolutions' tails; {} for a model with
        none of them."""
        arch, slots = self.md.arch, self.cfg.max_num_seqs
        if self.model.has_gdn:
            state, tail = create_delta_state_pool(arch, slots, self.dtype)
            return {"delta_state": state, "conv_state": tail}
        if self.model.has_conv:
            return {"conv_state": create_conv_state_pool(arch, slots,
                                                         self.dtype)}
        state, conv = create_state_pool(arch, slots, self.dtype)
        return {} if state is None else {"ssm_state": state,
                                         "ssm_conv": conv}

    def _fresh_cache(self) -> KVCache:
        """Zeroed page pool, laid out for the active parallelism mode.
        Under a mesh the pool is CREATED under its sharding: no device —
        in particular not device 0, which under in-engine DP already
        holds its own group's weights and pool — ever allocates more
        than its own shard."""
        make = partial(create_kv_cache, self.md.arch, self._num_pages,
                       self.cfg.page_size, jnp.dtype(self.cfg.kv_dtype),
                       window_pages=self._num_window_pages,
                       latent_kernel=self.latent_kernel)
        if self.model.has_state:
            # one device (_refuse_for_state_pool); the state pool that
            # was there when HBM was measured, or a new one after a
            # failed step took it
            pool, self._state_pool = self._state_pool, None
            return dataclasses.replace(make(),
                                       **(pool or self._make_state_pool()))
        if self.pp_exec is not None:
            return self.pp_exec.stage_cache(make())
        if self.mesh is None:
            return make()
        sh = self._cache_sharding()
        ssh = (self._scale_sharding()
               if kv_cache_is_quantized(self.cfg.kv_dtype) else None)
        return jax.jit(make, out_shardings=KVCache(
            k=sh, v=sh, k_scale=ssh, v_scale=ssh))()

    def _param_shardings(self):
        from jax.sharding import NamedSharding

        from kaito_tpu.parallel.sharding import SERVE_RULES

        axes = self.model.param_logical_axes()
        return jax.tree.map(
            lambda ax: NamedSharding(self.mesh, SERVE_RULES.spec(ax)),
            axes, is_leaf=lambda x: isinstance(x, tuple))

    def _quantized_param_shardings(self):
        """Shardings for the post-quantization tree: q8/q4 keep their
        weight's SERVE_RULES spec (int4's packed dim is still the in
        axis, at half length, and adjacent-pair packing keeps shard
        boundaries aligned with original rows); the scale drops the
        contracted (in) dim, except int4's group dim which inherits the
        in axis's assignment so scale rows follow their groups'
        shards."""
        from jax.sharding import NamedSharding

        from kaito_tpu.engine.quant import is_quantized_leaf, \
            qtensor_logical_axes
        from kaito_tpu.parallel.sharding import SERVE_RULES

        scheme = self.cfg.quantization or "int8"

        def sh(ax):
            return NamedSharding(self.mesh, SERVE_RULES.spec(ax))

        out: dict = {}
        for k, v in self.model.param_logical_axes().items():
            if isinstance(v, dict):
                out[k] = {
                    n: (jax.tree.map(sh, qtensor_logical_axes(ax, scheme),
                                     is_leaf=lambda x: isinstance(x, tuple))
                        if is_quantized_leaf(k, n) else sh(ax))
                    for n, ax in v.items()}
            else:
                out[k] = sh(v)
        return out

    def _kv_head_axis(self) -> Optional[str]:
        """Mesh axis the KV pools' head dimension is sharded over, or
        None when they are replicated (MLA's single latent stream, or
        a head count the tensor axis does not divide)."""
        heads = self.md.arch.kv_cache_heads
        tp = dict(self.mesh.shape).get("tensor", 1)
        return "tensor" if tp > 1 and heads > 1 and heads % tp == 0 else None

    def _cache_sharding(self):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        # [L, pages, page_size, kv_heads, D]
        return NamedSharding(self.mesh,
                             P(None, None, None, self._kv_head_axis()))

    def _scale_sharding(self):
        """[L, pages, kv_heads] page-scale pools follow the KV pools:
        head-sharded iff the pools are."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return NamedSharding(self.mesh, P(None, None, self._kv_head_axis()))

    def _make_leaf_transform(self):
        """Per-tensor checkpoint-load hook (weights.assemble_params):
        each stacked tensor lands straight on its mesh sharding and —
        under --quantization — quantizes immediately with donation, so
        peak HBM during a 70B int8 load is the int8 tree plus ONE bf16
        stacked tensor (never the whole bf16 tree, and never a full
        tensor on a single chip of the mesh)."""
        from jax.sharding import NamedSharding

        from kaito_tpu.engine.quant import is_quantized_leaf, quantize_weight
        from kaito_tpu.parallel.sharding import SERVE_RULES

        np_dtype = np.dtype(self.dtype)
        quant = bool(self.cfg.quantization)
        mesh = self.mesh
        # ONE derivation of the target layouts (the same trees the
        # synthetic/post-load paths use) — indexed per leaf below
        weight_sh = self._param_shardings() if mesh is not None else None
        qtensor_sh = (self._quantized_param_shardings()
                      if quant and mesh is not None else None)
        qfns: dict = {}   # out_shardings (or None) -> jitted quantizer

        def transform(group: str, key: str, np_arr):
            host = (np_arr if np_arr.dtype == np_dtype
                    else np_arr.astype(np_dtype))
            if mesh is not None:
                sh = weight_sh[group][key] if group else weight_sh[key]
                arr = jax.device_put(host, sh)
            else:
                arr = jnp.asarray(host)
            if quant and group and is_quantized_leaf(group, key):
                out_sh = (tuple(sorted(qtensor_sh[group][key].items()))
                          if qtensor_sh is not None else None)
                fn = qfns.get(out_sh)
                if fn is None:
                    kw = ({"out_shardings": dict(out_sh)}
                          if out_sh is not None else {})
                    fn = qfns[out_sh] = jax.jit(
                        partial(quantize_weight,
                                scheme=self.cfg.quantization),
                        donate_argnums=0, **kw)
                arr = fn(arr)
            return arr

        return transform

    def _init_params(self):
        if self.cfg.weights_dir:
            wd = self.cfg.weights_dir
            logger.info("loading checkpoint from %s%s", wd,
                        f" ({self.cfg.quantization} per-tensor "
                        "quantize-on-load)"
                        if self.cfg.quantization else "")
            transform = self._make_leaf_transform()
            if wd.startswith(("gs://", "http://", "https://")):
                # streaming load: per-tensor ranged reads, no local copy
                from kaito_tpu.engine.streaming import (
                    stream_safetensors_params)

                return stream_safetensors_params(self.model, wd,
                                                 leaf_transform=transform)
            from kaito_tpu.engine.weights import load_safetensors_params

            return load_safetensors_params(self.model, wd,
                                           leaf_transform=transform)
        logger.info("initializing synthetic weights for %s (mesh=%s)",
                    self.md.name, self.mesh)
        t0 = time.monotonic()
        if self.mesh is not None:
            params = jax.jit(
                self.model.init_params,
                out_shardings=self._param_shardings())(
                    jax.random.PRNGKey(self.cfg.seed))
        else:
            # local_devices, not devices: in a multi-process cluster
            # (PP over DCN) global device 0 is unaddressable on workers,
            # and the staging reshape needs a fully-addressable array
            with jax.default_device(jax.local_devices()[0]):
                params = jax.jit(self.model.init_params)(
                    jax.random.PRNGKey(self.cfg.seed))
        jax.block_until_ready(params)
        logger.info("weights ready in %.1fs (%.2f GiB)",
                    time.monotonic() - t0,
                    sum(x.nbytes for x in jax.tree.leaves(params)) / 2**30)
        return params

    def _init_quantized_params(self):
        """Synthetic weights, quantized inside the init jit (see
        __init__: keeps peak HBM at quantized-tree + one bf16 leaf)."""
        from kaito_tpu.engine.quant import quantize_params

        logger.info("initializing synthetic %s weights for %s (mesh=%s)",
                    self.cfg.quantization, self.md.name, self.mesh)
        t0 = time.monotonic()

        def init_q(key):
            return quantize_params(self.model.init_params(key),
                                   scheme=self.cfg.quantization)

        if self.mesh is not None:
            params = jax.jit(
                init_q, out_shardings=self._quantized_param_shardings())(
                    jax.random.PRNGKey(self.cfg.seed))
        else:
            # local_devices, not devices: see _init_params
            with jax.default_device(jax.local_devices()[0]):
                params = jax.jit(init_q)(jax.random.PRNGKey(self.cfg.seed))
        jax.block_until_ready(params)
        logger.info("%s weights ready in %.1fs (%.2f GiB)",
                    self.cfg.quantization, time.monotonic() - t0,
                    sum(x.nbytes for x in jax.tree.leaves(params)) / 2**30)
        return params

    def _derive_max_pages(self) -> int:
        """Size the page pool from free HBM (the engine-side analogue of
        the reference's gpu-memory-utilization default computed from
        torch.cuda.mem_get_info, inference_api.py).  Sizing reads THIS
        engine's own device: under in-engine DP, group N's pool must
        budget against its own chips, not device 0's already-occupied
        HBM."""
        meshes = (self.mesh, self.pp_exec.mesh if self.pp_exec else None)
        mesh = next((m for m in meshes if m is not None), None)
        if mesh is not None:
            # first ADDRESSABLE mesh device: on a multi-process mesh,
            # flat[0] belongs to process 0 and workers can't stat it
            dev = next((d for d in mesh.devices.flat
                        if d.process_index == jax.process_index()),
                       jax.local_devices()[0])
        else:
            dev = jax.local_devices()[0]
        itemsize = jnp.dtype(self.cfg.kv_dtype).itemsize
        two_kinds = self.md.arch.two_kind_cache
        if two_kinds:
            # what one slot's worth of pages costs: a whole context of
            # the full kind's and the few the window kind ever holds
            arch = self.md.arch
            full_pb, win_pb = (arch.kv_bytes_per_token_kind(
                k, itemsize, stored=True) * self.cfg.page_size
                for k in (0, 1))
            page_bytes = full_pb + win_pb * self._window_pages_per_seq \
                / self.pages_per_seq
        else:
            # (a kernel-read latent pool holds a token at its stored
            # lanes; every other pool at its logical shape)
            bpt = self.md.kv_bytes_per_token(
                itemsize, stored=getattr(self, "latent_kernel", False))
            page_bytes = bpt * self.cfg.page_size
        if jnp.dtype(self.cfg.kv_dtype) == jnp.int8:
            # each page also carries two fp32 scale rows (k + v), one
            # entry per (layer, kv head) — ~0.4% of the int8 page bytes
            # at typical shapes, but counted so sizing stays exact
            page_bytes += scale_bytes_per_page(self.md.arch)
        # sizing runs AFTER params are resident (and quantized), so the
        # ACTUAL weight bytes are known — no dtype/quant estimation
        weights = sum(x.nbytes for x in jax.tree.leaves(self.params))
        # static estimator's view of this chip, for the disagreement log
        est_overhead = PER_CHIP_OVERHEAD_BYTES
        if dev.platform == "cpu":
            # host RAM: enough for max_num_seqs full contexts
            pages = self.cfg.max_num_seqs * self.pages_per_seq + 1
            self.sizing_report = {"source": "seq-cap", "pages": pages}
            if two_kinds:
                self._num_window_pages = self._window_pool_cap()
            return pages
        # an accelerator that cannot report its memory is an error, not
        # a reason to budget against an assumed HBM size
        stats = dev.memory_stats()
        if not stats or "bytes_limit" not in stats:
            raise RuntimeError(
                f"{dev} reports no memory_stats(); cannot size the KV "
                f"page pool (set max_pages to size it by hand)")
        limit = stats["bytes_limit"] * HBM_UTILIZATION
        in_use = stats["bytes_in_use"]
        # SELF-MEASURED program temps (SURVEY §7 hard-part (d), the
        # profile_run analogue): run the widest sampler program — the
        # fused-decode step's biggest scratch, the [B, V] top-p sort —
        # and take the observed peak delta when it exceeds the static
        # overhead constant
        temps = self._measure_sampler_temps(dev)
        overhead = max(est_overhead, temps)
        # bytes_in_use already includes the resident weights
        free = limit - in_use - overhead
        self.sizing_report = {
            "hbm_limit_bytes": int(stats["bytes_limit"]),
            "weights_bytes": int(weights),
            "measured_in_use_bytes": int(in_use),
            "measured_temps_bytes": int(temps),
            "estimator_overhead_bytes": int(est_overhead),
            "source": "measured",
        }
        # disagreement between the static estimator model and the
        # device's own accounting (fed to status.performance via the
        # benchmark probe / health surface)
        drift = in_use - weights
        if abs(drift) > est_overhead:
            logger.warning(
                "HBM estimator drift: device reports %.2f GiB in use "
                "vs %.2f GiB weights (drift %.2f GiB > static "
                "overhead %.2f GiB); sizing from measurement",
                in_use / 2**30, weights / 2**30, drift / 2**30,
                est_overhead / 2**30)
        if two_kinds:
            # both pools from what HBM leaves, in the ratio a slot needs
            # them (docs/kv-cache.md): the window kind's transient pages
            # first, then whole slots' worth of both
            free -= (self._window_transient_pages + 1) * win_pb
            seqs = int(max(free, 0) // (page_bytes * self.pages_per_seq))
            seqs = max(1, min(seqs, self.cfg.max_num_seqs))
            self._num_window_pages = self._window_pool_cap(seqs)
            return seqs * self.pages_per_seq + 1
        pages = int(max(free, 0) // page_bytes)
        cap = self.cfg.max_num_seqs * self.pages_per_seq
        return max(2, min(pages, cap) + 1)

    @property
    def _window_pages_per_seq(self) -> int:
        """Most window pages a decoding sequence holds: the window's
        positions touch ``window/page_size + 1`` pages, and a decode
        window writes up to a page ahead."""
        return -(-self.md.arch.sliding_window // self.cfg.page_size) + 2

    @property
    def _window_transient_pages(self) -> int:
        """Window pages one context-prefill chunk holds for the one step
        that reads them: the chunk's own and the window before it."""
        chunk = min(max(self.cfg.max_prefill_tokens, self.cfg.page_size),
                    self.cfg.max_model_len)
        return -(-(chunk + self.md.arch.sliding_window)
                 // self.cfg.page_size) + 1

    def _window_pool_cap(self, seqs: Optional[int] = None) -> int:
        """Pages of the window pool that ``seqs`` slots can ever hold
        (null page included): each its few, and one chunk's transient."""
        seqs = self.cfg.max_num_seqs if seqs is None else seqs
        return seqs * self._window_pages_per_seq \
            + self._window_transient_pages + 1

    def _measure_sampler_temps(self, dev) -> int:
        """Compile + run the [max_num_seqs, vocab] sampler with the
        sort path live (one top-p row) and return the peak-memory delta
        it caused — the dominant decode-program scratch at 100k+
        vocabs.  Returns 0 when the backend can't report peaks."""
        try:
            base_peak = dev.memory_stats().get("peak_bytes_in_use", 0)
            if not base_peak:
                return 0
            from kaito_tpu.engine.sampler import SamplingState, sample

            B, V = self.cfg.max_num_seqs, self.md.arch.vocab_size
            # pin to THIS engine's device: under in-engine DP the
            # default device is another group's chip, which would both
            # measure nothing and transiently tax a foreign HBM budget
            with jax.default_device(dev):
                state = SamplingState.create(B, self.cfg.seed)
                state = state.set_slot(0, temperature=1.0, top_k=0,
                                       top_p=0.9, seed=1)
                logits = jnp.zeros((B, V), jnp.float32)
                toks, _ = jax.jit(sample)(logits, state)
                jax.block_until_ready(toks)
            peak = dev.memory_stats().get("peak_bytes_in_use", 0)
            # peak is a lifetime high-water mark: if weight loading
            # already peaked higher, the delta reads 0 and sizing falls
            # back to the static overhead constant (safe direction)
            return int(max(0, peak - base_peak))
        except Exception:
            logger.debug("sampler temp probe failed", exc_info=True)
            return 0

    # ------------------------------------------------------------------
    # Compiled steps
    # ------------------------------------------------------------------

    def _build_decode_fn(self):
        model = self.model
        pp_decode = (self.pp_exec.build_decode_fn()
                     if self.pp_exec is not None else None)

        @partial(jax.jit, donate_argnums=(1, 2, 3))
        @phase_scope("decode")
        def decode_step(params, cache, sampling, counts, prompt_seen,
                        tokens, positions, page_tables, active, adapter_ids,
                        gmask, gtrans, gstate):
            cache = _zero_moe_stats(cache)
            if pp_decode is not None:
                cache, logits = pp_decode(params, cache, tokens, positions,
                                          page_tables, active,
                                          adapter_ids=adapter_ids)
            else:
                cache, logits = model.decode(params, cache, tokens, positions,
                                             page_tables, active,
                                             adapter_ids=adapter_ids)
            # grammar masks: one gather of 0/-inf rows per constrained
            # batch ([1,1] placeholders compile the path away; row 0 is
            # the all-zero unconstrained row, so mixed batches cost the
            # same single gather)
            grows = gmask[gstate] if gmask.shape[0] > 1 else None
            next_tokens, new_sampling = sample(logits, sampling, counts,
                                               prompt_seen, grows)
            # inactive rows keep their PRNG keys: a sampled stream must
            # be seed-deterministic regardless of co-tenant scheduling
            # (prefilling/idle rows never burn draws)
            sampling = SamplingState(
                temperature=new_sampling.temperature,
                top_k=new_sampling.top_k, top_p=new_sampling.top_p,
                key=jnp.where(active[:, None], new_sampling.key,
                              sampling.key),
                presence=new_sampling.presence,
                frequency=new_sampling.frequency,
                repetition=new_sampling.repetition,
                min_p=new_sampling.min_p)
            B = next_tokens.shape[0]
            if counts.shape == logits.shape:   # penalty state live
                counts = counts.at[jnp.arange(B), next_tokens].add(
                    active.astype(jnp.int32))
            # logprobs report the MODEL distribution (pre-penalty)
            return cache, sampling, counts, next_tokens, \
                chosen_logprob(logits, next_tokens), _moe_stats_out(cache)

        return decode_step

    def _build_decode_multi_fn(self, K: int, with_state: bool = False):
        """K fused decode steps in ONE dispatch (lax.scan) with
        on-device sampling, stop-token detection and per-slot budget
        tracking.  A slot that emits a stop token (or exhausts its
        budget) goes inactive inside the scan, so no KV is ever written
        past its last real token — the host replays the returned
        (tokens, active) trace through the exact same _emit path as the
        single-step loop.

        with_state=True additionally returns the final scan carry
        (next_tokens, positions, active, steps_left) so the async loop
        can feed window N+1 straight from device-resident state without
        ever materializing the host mirrors (docs/decode-loop.md)."""
        model = self.model

        @partial(jax.jit, donate_argnums=(1, 2, 3))
        @phase_scope("decode")
        def decode_multi(params, cache, sampling, counts, prompt_seen,
                         tokens, positions, page_tables, active, adapter_ids,
                         stop_ids, steps_left, gmask, gtrans, gstate):
            cache = _zero_moe_stats(cache)

            def body(carry, _):
                cache, sampling, counts, toks, pos, act, left, gst = carry
                cache, logits = model.decode(params, cache, toks, pos,
                                             page_tables, act,
                                             adapter_ids=adapter_ids)
                grows = gmask[gst] if gmask.shape[0] > 1 else None
                nxt, new_sampling = sample(logits, sampling, counts,
                                           prompt_seen, grows)
                sampling = SamplingState(
                    temperature=new_sampling.temperature,
                    top_k=new_sampling.top_k, top_p=new_sampling.top_p,
                    key=jnp.where(act[:, None], new_sampling.key,
                                  sampling.key),
                    presence=new_sampling.presence,
                    frequency=new_sampling.frequency,
                    repetition=new_sampling.repetition,
                    min_p=new_sampling.min_p)
                lp = chosen_logprob(logits, nxt)
                nxt = jnp.where(act, nxt, toks)
                B = nxt.shape[0]
                if counts.shape == logits.shape:   # penalty state live
                    counts = counts.at[jnp.arange(B), nxt].add(
                        act.astype(jnp.int32))
                left = left - act.astype(jnp.int32)
                # advance the grammar automaton in-scan on the emitted
                # token (transition rows hold absolute table rows; the
                # unconstrained row 0 self-loops on every token)
                if gmask.shape[0] > 1:
                    gst = jnp.where(act, gtrans[gst, nxt], gst)
                # stop_ids is -1-padded, token ids are >= 0
                hit = jnp.any(nxt[:, None] == stop_ids, axis=1)
                act_next = act & ~hit & (left > 0)
                pos = pos + act.astype(jnp.int32)
                return (cache, sampling, counts, nxt, pos, act_next, left,
                        gst), (nxt, act, lp)

            carry = (cache, sampling, counts, tokens, positions, active,
                     steps_left, gstate)
            (cache, sampling, counts, nxt, pos, act, left, gst), \
                (toks, acts, lps) = jax.lax.scan(body, carry, None, length=K)
            # an expert layer's counters over the window's steps ride
            # back with its tokens (None: no such layer)
            stats = _moe_stats_out(cache)
            if with_state:
                return (cache, sampling, counts, toks, acts, lps, stats,
                        (nxt, pos, act, left, gst))
            return cache, sampling, counts, toks, acts, lps, stats

        return decode_multi

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            model = self.model
            pp_prefill = (self.pp_exec.build_prefill_fn(with_context=False)
                          if self.pp_exec is not None else None)

            @partial(jax.jit, donate_argnums=(1,))
            @phase_scope("prefill")
            def prefill_step(params, cache, tokens, true_lens, page_tables,
                             adapter_ids, state_rows=None):
                if pp_prefill is not None:
                    return pp_prefill(params, cache, tokens, true_lens,
                                      page_tables, adapter_ids=adapter_ids)
                cache, logits, _ = model.prefill(params, cache, tokens,
                                                 true_lens, page_tables,
                                                 adapter_ids=adapter_ids,
                                                 state_rows=state_rows)
                return cache, logits

            fn = prefill_step
            self._prefill_fns[bucket] = fn
        return fn

    def _prefill_cp_fn(self, bucket: int):
        """Context-parallel single-shot prefill (sequence-axis ring);
        selected by _advance_prefills for long fresh prompts."""
        key = ("cp", bucket)
        fn = self._prefill_fns.get(key)
        if fn is None:
            model = self.model

            @partial(jax.jit, donate_argnums=(1,))
            @phase_scope("prefill")
            def prefill_cp(params, cache, tokens, true_lens, page_tables,
                           adapter_ids):
                cache, logits, _ = model.prefill_cp(
                    params, cache, tokens, true_lens, page_tables,
                    adapter_ids=adapter_ids)
                return cache, logits

            fn = prefill_cp
            self._prefill_fns[key] = fn
        return fn

    def _prefill_ctx_fn(self, bucket: int):
        key = ("ctx", bucket)
        fn = self._prefill_fns.get(key)
        if fn is None:
            model = self.model
            pp_prefill = (self.pp_exec.build_prefill_fn(with_context=True)
                          if self.pp_exec is not None else None)

            @partial(jax.jit, donate_argnums=(1,))
            @phase_scope("prefill")
            def prefill_ctx(params, cache, tokens, true_lens, page_tables,
                            start_pos, adapter_ids, state_rows=None):
                if pp_prefill is not None:
                    return pp_prefill(params, cache, tokens, true_lens,
                                      page_tables, start_pos,
                                      adapter_ids=adapter_ids)
                cache, logits, _ = model.prefill(params, cache, tokens,
                                                 true_lens, page_tables,
                                                 start_pos=start_pos,
                                                 adapter_ids=adapter_ids,
                                                 state_rows=state_rows)
                return cache, logits

            fn = prefill_ctx
            self._prefill_fns[key] = fn
        return fn

    def _score_fn(self, bucket: int):
        """Jitted prompt scorer: [1, bucket] tokens -> [bucket-1] log
        p(token[t+1] | tokens[:t+1]) under the model (the lm-eval
        loglikelihood contract: completions echo+logprobs+max_tokens=0).

        One causal forward; the vocab projection runs in 128-position
        chunks so a 200k-vocab [T, V] logits tensor never materializes.
        """
        key = ("score", bucket)
        fn = self._prefill_fns.get(key)
        if fn is None:
            model = self.model
            CH = 128

            @jax.jit
            def score(params, tokens, true_len):
                B, T = tokens.shape
                positions = jnp.broadcast_to(
                    jnp.arange(T, dtype=jnp.int32), (B, T))
                x = model._embed(params, tokens)
                x, _ = model._run_layers(
                    params, None, x, "train", positions=positions,
                    page_tables=None, lengths=None,
                    true_lens=jnp.broadcast_to(true_len, (B,)),
                    active=None, remat=False)
                h = model._norm(x, params, "final_norm")      # [1, T, E]
                targets = jnp.concatenate(
                    [tokens[:, 1:], jnp.zeros((B, 1), jnp.int32)], axis=1)
                nc = T // CH
                h_c = h.reshape(nc, CH, h.shape[-1])
                t_c = targets.reshape(nc, CH)

                def one(args):
                    hc, tc = args
                    logits = model._logits(params, hc).astype(jnp.float32)
                    return chosen_logprob(logits, tc)

                lp = jax.lax.map(one, (h_c, t_c))             # [nc, CH]
                return lp.reshape(T)[: T - 1]

            fn = score
            self._prefill_fns[key] = fn
        return fn

    def score_prompt(self, tokens: list[int]) -> list[float]:
        """log p of each prompt token given its prefix (None for the
        first token, which has no conditioning prefix) — runs outside
        the scheduler; device execution serializes with the loop."""
        if self.pp_exec is not None:
            raise ValueError("prompt scoring is not supported on "
                             "pipeline-parallel engines")
        n = len(tokens)
        if n < 1:
            return []
        if n >= self.cfg.max_model_len:
            raise ValueError(f"prompt length {n} exceeds max_model_len "
                             f"{self.cfg.max_model_len}")
        # sized directly (NOT via the prefill buckets, whose ceiling is
        # the chunk budget): any prompt under max_model_len scores
        bucket = max(128, -(-n // 128) * 128)
        buf = np.zeros((1, bucket), np.int32)
        buf[0, :n] = tokens
        # one scorer at a time: serializes the jit-compile of a new
        # bucket and keeps burst device pressure bounded
        with self._score_lock:
            lp = np.asarray(self._score_fn(bucket)(
                self.params, jnp.asarray(buf), jnp.asarray(n, jnp.int32)))
        return [None] + [float(x) for x in lp[: n - 1]]

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds largest bucket {self.buckets[-1]}")

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------

    @property
    def state_rows_in_use(self) -> int:
        """Rows of the recurrent-state pool that hold a sequence's state
        (a slot with a request; 0 for a model with no such pool)."""
        if not self.model.has_state:
            return 0
        return sum(1 for s in self.slots if s.request is not None)

    @property
    def window_pages_in_use(self) -> int:
        """Pages of the window kind's pool that sequences hold (0 for a
        one-kind cache)."""
        if self.window_allocator is None:
            return 0
        return self.window_allocator.num_pages - 1 \
            - self.window_allocator.available

    @property
    def num_waiting(self) -> int:
        return self._waiting_count

    @property
    def num_running(self) -> int:
        return int(self.active.sum())

    def _validate_submit(self, prompt_tokens: list[int],
                         params: SamplingParams) -> None:
        if len(prompt_tokens) >= self.cfg.max_model_len:
            raise ValueError(
                f"prompt length {len(prompt_tokens)} exceeds max_model_len "
                f"{self.cfg.max_model_len}")
        if len(prompt_tokens) + 1 > self._capacity_tokens:
            raise ValueError(
                f"prompt length {len(prompt_tokens)} exceeds KV pool "
                f"capacity {self._capacity_tokens} tokens")
        if params.max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {params.max_tokens}")

    def _validate_kv_meta(self, meta: dict, n_prompt: int,
                          strict_shape: bool = False) -> None:
        """Reject an incompatible KV handoff in the REQUEST thread (a
        clean 4xx) instead of letting the scatter explode inside the
        scheduler loop: model identity and token count always; with
        ``strict_shape`` (the colocated device path, where the slabs
        land in the pool as-is) the wire shape's layer count, page
        count, page_size and head layout must match this engine's pool
        too.  Chunked imports stay lenient: their assemble step
        re-checks per-chunk shapes against the host buffers anyway."""
        self._refuse_kv_import()
        if meta.get("model") not in ("", None, self.md.name):
            raise ValueError(f"KV transfer model mismatch: {meta.get('model')} "
                             f"!= {self.md.name}")
        if meta.get("n_tokens") not in (None, n_prompt):
            raise ValueError(
                f"KV transfer token mismatch: client sent {n_prompt} prompt "
                f"tokens, staged slab holds {meta.get('n_tokens')}")
        wire_dt = meta.get("dtype")
        if wire_dt is not None and np.dtype(wire_dt) != np.dtype(self.cache.k.dtype):
            raise ValueError(
                f"KV transfer dtype mismatch: wire {wire_dt} vs pool "
                f"{np.dtype(self.cache.k.dtype).name} — prefill and decode "
                f"roles must run the same --kv-cache-dtype")
        shape = meta.get("shape")
        if not strict_shape or not shape:
            return
        shape = tuple(int(s) for s in shape)
        staged = self.cache.k.ndim == len(shape) + 1
        if not staged and self.cache.k.ndim != len(shape):
            raise ValueError(f"KV slab rank mismatch: wire shape {shape} vs "
                             f"pool rank {self.cache.k.ndim}")
        L = (self.cache.k.shape[0] * self.cache.k.shape[1]) if staged \
            else self.cache.k.shape[0]
        tail = tuple(self.cache.k.shape[3 if staged else 2:])
        n_pages = -(-n_prompt // self.cfg.page_size)
        # page count is a floor, not an equality: exporters may ship a
        # rounded-up slab; layer count and the per-page layout must
        # match this pool exactly
        if shape[0] != L or shape[2:] != tail or shape[1] < n_pages:
            raise ValueError(
                f"KV slab incompatible with this engine: wire shape {shape}, "
                f"pool expects ({L}, >={n_pages}) + {tail} (layers, prompt "
                f"pages, page_size, kv heads, head dim)")

    def _deadline_for(self, timeout_s: Optional[float]) -> Optional[float]:
        """Absolute monotonic deadline from a per-request timeout,
        falling back to the server default (0 = no deadline)."""
        t = timeout_s if timeout_s else self.cfg.request_timeout_s
        return (time.monotonic() + float(t)) if t else None

    def _resolve_qos(self, tenant: str, priority: str) -> tuple[str, int]:
        """(tenant id, numeric class priority) for a submission.  With
        QoS off, the tenant rides along for tracing only and priority
        stays 0 (the scheduler never reads either)."""
        if self.qos is None:
            return tenant or "", 0
        from kaito_tpu.engine.qos import DEFAULT_TENANT

        t = tenant or DEFAULT_TENANT
        return t, self.qos.class_of(t, priority).priority

    def _enqueue(self, req: Request) -> None:
        """Queue a validated request for admission (all submit paths)."""
        with self._lock:
            self.counters["requests_total"] += 1
            self._waiting_count += 1
            if self.qos is None:
                self.waiting.append(req)
            else:
                self._qos_push_locked(req)
        self._wake.set()

    def submit(self, prompt_tokens: list[int], params: SamplingParams,
               req_id: Optional[str] = None,
               export_kv: bool = False, adapter: str = "",
               timeout_s: Optional[float] = None,
               trace_id: Optional[str] = None,
               tenant: str = "", priority: str = "",
               pool_blocks: Optional[list] = None) -> Request:
        self._validate_submit(prompt_tokens, params)
        self._resolve_adapter(adapter)
        rid = req_id or f"req-{self.counters['requests_total']}"
        t, prio = self._resolve_qos(tenant, priority)
        req = Request(rid,
                      list(prompt_tokens), params, export_kv=export_kv,
                      adapter=adapter,
                      deadline=self._deadline_for(timeout_s),
                      trace_id=trace_id or rid,
                      tenant=t, priority=prio,
                      pool_blocks=list(pool_blocks or []))
        self._enqueue(req)
        return req

    def submit_with_kv(self, prompt_tokens: list[int], first_token: int,
                       meta: dict, payload: bytes,
                       params: SamplingParams,
                       req_id: Optional[str] = None,
                       timeout_s: Optional[float] = None,
                       trace_id: Optional[str] = None,
                       tenant: str = "", priority: str = "") -> Request:
        """Decode-role entry: continue a prefilled request from
        transferred KV pages."""
        self._validate_submit(prompt_tokens, params)
        self._validate_kv_meta(meta, len(prompt_tokens))
        rid = req_id or f"pd-{self.counters['requests_total']}"
        t, prio = self._resolve_qos(tenant, priority)
        req = Request(rid,
                      list(prompt_tokens), params,
                      kv_import=(meta, payload, first_token),
                      deadline=self._deadline_for(timeout_s),
                      trace_id=trace_id or meta.get("trace_id") or rid,
                      tenant=t, priority=prio)
        self._enqueue(req)
        return req

    def submit_with_kv_device(self, prompt_tokens: list[int],
                              first_token: int, meta: dict, slabs,
                              params: SamplingParams,
                              req_id: Optional[str] = None,
                              timeout_s: Optional[float] = None,
                              trace_id: Optional[str] = None,
                              tenant: str = "",
                              priority: str = "",
                              adapter: str = "") -> Request:
        """Colocated decode entry: the prefill engine lives in THIS
        process, so its staged canonical KV slab hands off as a single
        device-to-device scatter — no host bounce, no wire (the
        reference's NIXL device path,
        preset_inferences.go:909-938, re-imagined for a shared slice).
        ``slabs`` is ``StagedExport.device_slabs()``.  ``adapter``
        continues decode under the prefill's adapter (the server
        enforces the staged-meta match before calling)."""
        self._validate_submit(prompt_tokens, params)
        self._resolve_adapter(adapter)
        # fail in the REQUEST thread, not the scheduler: a token count,
        # page_size or head layout that disagrees with the staged slab
        # would otherwise raise in _start_device_import on the engine
        # loop (or, worse, decode silently against misaligned KV when
        # the page counts happen to match)
        self._validate_kv_meta(meta, len(prompt_tokens), strict_shape=True)
        rid = req_id or f"pd-{self.counters['requests_total']}"
        t, prio = self._resolve_qos(tenant, priority)
        req = Request(rid,
                      list(prompt_tokens), params, adapter=adapter,
                      kv_device=(meta, slabs, first_token),
                      deadline=self._deadline_for(timeout_s),
                      trace_id=trace_id or meta.get("trace_id") or rid,
                      tenant=t, priority=prio)
        self._enqueue(req)
        return req

    def submit_with_kv_chunked(self, prompt_tokens: list[int],
                               first_token: int, meta: dict, plans,
                               params: SamplingParams,
                               req_id: Optional[str] = None,
                               deadline_s: float = 120.0,
                               timeout_s: Optional[float] = None,
                               trace_id: Optional[str] = None,
                               tenant: str = "", priority: str = "",
                               adapter: str = ""):
        """Decode-role entry for the CHUNKED transfer path: the request
        is admitted immediately and its KV chunks are scattered by the
        scheduler loop as the caller ``feed``s them into the returned
        request's ``kv_chunked`` (overlapping the transfer with decode
        of other requests).  Returns the Request; the caller feeds
        ``req.kv_chunked.feed(i, payload)`` for every chunk."""
        from kaito_tpu.engine.pd import ChunkedImport

        self._validate_submit(prompt_tokens, params)
        self._resolve_adapter(adapter)
        self._validate_kv_meta(meta, len(prompt_tokens))
        rid = req_id or f"pd-{self.counters['requests_total']}"
        t, prio = self._resolve_qos(tenant, priority)
        req = Request(rid,
                      list(prompt_tokens), params, adapter=adapter,
                      kv_chunked=ChunkedImport(meta, list(plans), first_token,
                                               deadline_s=deadline_s),
                      deadline=self._deadline_for(timeout_s),
                      kv_retries=max(0, self.cfg.kv_import_retries),
                      trace_id=trace_id or meta.get("trace_id") or rid,
                      tenant=t, priority=prio)
        self._enqueue(req)
        return req

    def submit_with_kv_prefix(self, prompt_tokens: list[int], meta: dict,
                              plans, n_prefix_tokens: int,
                              params: SamplingParams,
                              req_id: Optional[str] = None,
                              deadline_s: float = 30.0,
                              timeout_s: Optional[float] = None,
                              trace_id: Optional[str] = None,
                              tenant: str = "", priority: str = "",
                              adapter: str = "",
                              pool_blocks: Optional[list] = None):
        """Cluster-KV-pool entry (docs/kv-pool.md): a PARTIAL prefix of
        the prompt's KV is being fetched from a holder replica over the
        chunked wire; the local prefill finishes the remainder once the
        pages land.  Unlike the PD paths this never carries the first
        generated token (the prefill produces it), and unlike
        ``_validate_kv_meta`` the slab's n_tokens is expected to be
        SMALLER than the prompt.  Any transfer failure — transient or
        permanent — falls back to a full local prefill; the pool is an
        optimization, never a correctness dependency."""
        from kaito_tpu.engine.pd import ChunkedImport

        self._refuse_kv_import()
        self._validate_submit(prompt_tokens, params)
        self._resolve_adapter(adapter)
        if meta.get("model") not in ("", None, self.md.name):
            raise ValueError(f"KV pool model mismatch: {meta.get('model')} "
                             f"!= {self.md.name}")
        # pool keys fold the adapter into the hash chain, so a fetch
        # can only name a same-adapter entry — but the meta check stays
        # the authority (hash collisions, hand-rolled clients): KV
        # computed under another adapter's deltas must never import
        if str(meta.get("adapter") or "") != (adapter or ""):
            raise ValueError(
                f"KV pool adapter mismatch: entry "
                f"{meta.get('adapter') or 'base'!r} vs request "
                f"{adapter or 'base'!r}")
        wire_dt = meta.get("dtype")
        if wire_dt is not None \
                and np.dtype(wire_dt) != np.dtype(self.cache.k.dtype):
            raise ValueError(f"KV pool dtype mismatch: wire {wire_dt} vs "
                             f"pool {np.dtype(self.cache.k.dtype).name}")
        ps = self.cfg.page_size
        if not (0 < n_prefix_tokens < len(prompt_tokens)
                and n_prefix_tokens % ps == 0):
            raise ValueError(
                f"prefix token count {n_prefix_tokens} must be a positive "
                f"whole-page multiple below the prompt length "
                f"{len(prompt_tokens)}")
        rid = req_id or f"kvp-{self.counters['requests_total']}"
        t, prio = self._resolve_qos(tenant, priority)
        req = Request(rid,
                      list(prompt_tokens), params, adapter=adapter,
                      kv_chunked=ChunkedImport(meta, list(plans), -1,
                                               deadline_s=deadline_s),
                      kv_prefix_tokens=n_prefix_tokens,
                      deadline=self._deadline_for(timeout_s),
                      trace_id=trace_id or rid,
                      tenant=t, priority=prio,
                      pool_blocks=list(pool_blocks or []))
        self._enqueue(req)
        return req

    # -- dynamic multi-LoRA admin (docs/multi-lora.md) ---------------------

    def _resolve_adapter(self, adapter: str) -> None:
        """Validate (and, with the cache, fault-in) an adapter for a
        submission.  A host-tier adapter is re-installed into an HBM
        slot HERE — in the request thread, before admission — so the
        scheduler never sees a name without a slot index."""
        if not adapter:
            return
        if self.adapter_cache is not None:
            try:
                self.adapter_cache.ensure(adapter)
            except KeyError:
                raise ValueError(f"unknown adapter {adapter!r}") from None
        elif adapter not in self.adapter_index:
            raise ValueError(f"unknown adapter {adapter!r}")

    def _adapter_busy(self, name: str) -> bool:
        """In-flight work references this adapter: an active decode
        slot selects its lane, or a queued request names it.  Busy
        adapters are pinned — the cache refuses to evict or overwrite
        them (swapping factors under a live sequence would change its
        weights mid-generation)."""
        # boot-time preloads run before the batch state and queues
        # exist: nothing can be in flight yet, so nothing is pinned
        if getattr(self, "active", None) is None:
            return False
        idx = self.adapter_index.get(name)
        if idx:
            act, sa = self.active, self.slot_adapters
            if any(bool(act[i]) and int(sa[i]) == idx
                   for i in range(len(act))):
                return True
        with self._lock:
            if any(r.adapter == name for r in self.waiting):
                return True
            for q in self._tenant_queues.values():
                if any(r.adapter == name for r in q):
                    return True
        return False

    def adapter_snapshot(self) -> Optional[dict]:
        """The ``GET /v1/adapters`` payload; None when the cache is off
        (the server answers 403 — same gating as the KV pool)."""
        if self.adapter_cache is None:
            return None
        return self.adapter_cache.snapshot()

    def load_adapter_dynamic(self, name: str, path: str) -> int:
        """Hot-load an adapter artifact directory into an HBM slot (the
        POST /v1/adapters entry).  Raises AdapterLoadError (a
        ValueError) on refusal, AdapterBusyError when every slot is
        pinned by in-flight work."""
        if self.adapter_cache is None:
            raise RuntimeError("adapter cache is not enabled")
        return self.adapter_cache.load_from_path(name, path)

    def delete_adapter(self, name: str) -> bool:
        """Drop an adapter from both cache tiers (DELETE /v1/adapters).
        Raises AdapterBusyError while in-flight requests pin it."""
        if self.adapter_cache is None:
            raise RuntimeError("adapter cache is not enabled")
        return self.adapter_cache.remove(name)

    def abort(self, req: Request) -> None:
        """Request cancellation; the scheduler retires the slot at its
        next touch.  (MultiHostEngine overrides: aborts must reach every
        process via the step broadcast before the scheduler acts.)"""
        req.aborted = True
        self._wake.set()

    def generate(self, prompt: str, params: Optional[SamplingParams] = None) -> str:
        """Blocking single-request helper (tests, benchmark probe)."""
        params = params or SamplingParams()
        toks = self.tokenizer.encode(prompt)
        req = self.submit(toks, params)
        out = list(req.stream())
        return self.tokenizer.decode(out)

    def start(self):
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="engine-loop")
        self._thread.start()
        if self.devprof is not None:
            self.devprof.start()

    def _enqueue_spill(self, entry) -> None:
        """``PrefixPageStore.on_evict`` hook, called on whatever thread
        triggered the eviction (usually the step loop finishing a
        request).  Only a non-blocking queue put happens here; a full
        queue drops the victim — always safe, the tier can only ever
        remove work."""
        try:
            self._spill_q.put_nowait(entry)
        except queue.Full:
            self.counters["kv_tier_spill_drops_total"] += 1

    def _spill_worker(self) -> None:
        """Async demotion loop: serialize evicted entries' chunks
        (which may block on the export's D2H drain) and persist them
        to the SSD tier, off the step loop."""
        while True:
            entry = self._spill_q.get()
            if entry is None:
                return
            try:
                self.kv_tier.spill(entry)
            except Exception:
                logger.exception("kv_tier spill of %s failed", entry.key)

    def stop(self):
        if self.devprof is not None:
            self.devprof.stop()
        self._stop.set()
        self._wake.set()
        if self._thread:
            self._thread.join(timeout=30)
        if self._heap_settled_at >= 0:
            # what _settle_heap froze is the collector's again: an
            # engine that stops may be garbage the process outlives
            self._heap_settled_at = -1
            gc.unfreeze()
            if _watch_gc in gc.callbacks:
                gc.callbacks.remove(_watch_gc)
        if self.async_dispatch:
            # the labelled family is easy to lose in a scrape's
            # reduction: the log keeps the reasons beside the windows
            logger.info("decode windows: %d primed, %d unprimed; drains %s; "
                        "first tokens: %d deferred, blocking %s",
                        self.counters["decode_windows_primed_total"],
                        self.counters["decode_windows_unprimed_total"],
                        {k: v for k, v in self.drain_counts.items() if v},
                        self.counters["first_tokens_deferred_total"],
                        {k: v for k, v
                         in self.first_token_blocking.items() if v})
        if self._spill_thread is not None:
            self._spill_q.put(None)
            self._spill_thread.join(timeout=10)
        # fail whatever is still in flight so no client blocks forever
        # in Request.stream() after shutdown (the loop thread is gone;
        # nothing else would ever deliver their end-of-stream sentinel)
        self._fail_all()

    # ------------------------------------------------------------------
    # Scheduler loop
    # ------------------------------------------------------------------

    def _loop(self):
        while not self._stop.is_set():
            try:
                did_work = self.step()
            except RequestScopedError as e:
                # failure domain: ONE request.  The raiser already
                # detached it from its slot; fail it and keep serving —
                # UNLESS the step donated the cache into the failure,
                # in which case nothing in flight can survive anyway.
                logger.warning("request-scoped failure: %s", e)
                self._fail_request(e.req, message=str(e))
                if self._cache_poisoned():
                    logger.error("cache donated into a scoped failure; "
                                 "escalating to fail-all")
                    self.counters["engine_fatal_total"] += 1
                    self._fail_all()
                continue
            except Exception:
                # A scheduler-loop failure must not strand waiting clients.
                logger.exception("engine loop failure; failing in-flight requests")
                self.counters["engine_fatal_total"] += 1
                self._fail_all()
                continue
            if not did_work:
                self._settle_heap()
                with self.phases.annotate("engine.idle"):
                    self._wake.wait(timeout=0.05)
                self._wake.clear()

    @staticmethod
    def compile_totals() -> tuple[int, float]:
        """Programs this process has compiled (or fetched from the
        compile cache) and the seconds that took, since its start."""
        return _COMPILES[0], _COMPILE_SECONDS[0]

    def _settle_heap(self) -> None:
        """The loop is idle.  If programs were compiled since it last
        was, move every live object to the permanent generation: what
        tracing left behind lives as long as the process, and the
        collector's full passes then walk only what came after.  No
        collection here, so a request that arrives now waits for
        nothing; an object frozen while still in use is freed by its
        reference count as before."""
        if _COMPILES[0] == self._heap_settled_at:
            return
        self._heap_settled_at = _COMPILES[0]
        gc.freeze()
        if _watch_gc not in gc.callbacks:
            gc.callbacks.append(_watch_gc)
        logger.info("heap settled after %d compiles: %d objects in the "
                    "permanent generation", _COMPILES[0],
                    gc.get_freeze_count())

    def _pop_waiting(self) -> Optional[Request]:
        with self._lock:
            if self.qos is not None:
                return self._qos_pop_locked()
            if not self.waiting:
                return None
            self._waiting_count -= 1
            return self.waiting.popleft()

    def _requeue_front(self, req: Request):
        with self._lock:
            self._waiting_count += 1
            if self.qos is None:
                self.waiting.appendleft(req)
            else:
                self._qos_push_locked(req, front=True)

    # -- QoS admission queues (docs/qos.md) ----------------------------
    #
    # Per-tenant deques behind the same num_waiting/_pop/_requeue
    # surface: admission pops strict-priority across classes and
    # deficit-round-robin across tenants within a class, so one noisy
    # tenant can neither starve a guaranteed class nor crowd out its
    # own-priority peers beyond its weight.  All helpers assume
    # self._lock is held.

    def _qos_push_locked(self, req: Request, front: bool = False) -> None:
        q = self._tenant_queues.get(req.tenant)
        if q is None:
            q = self._tenant_queues[req.tenant] = collections.deque()
        order = self._drr_order.setdefault(req.priority,
                                           collections.deque())
        if front:
            q.appendleft(req)
            if req.tenant in order:
                order.remove(req.tenant)
            order.appendleft(req.tenant)
            # a preempted resume must not wait out a DRR rotation: top
            # the tenant's deficit up to one service
            self._drr_deficit[req.tenant] = max(
                self._drr_deficit.get(req.tenant, 0.0), 1.0)
        else:
            q.append(req)
            if req.tenant not in order:
                order.append(req.tenant)

    def _qos_pop_locked(self) -> Optional[Request]:
        for prio in sorted(self._drr_order, reverse=True):
            order = self._drr_order[prio]
            # every full rotation grants each tenant its weight of
            # deficit (weight >= 1), so two passes guarantee a service
            for _ in range(2 * len(order) + 1):
                if not order:
                    break
                t = order[0]
                q = self._tenant_queues.get(t)
                if not q:
                    # emptied by an expiry/fail-all sweep
                    order.popleft()
                    self._drr_deficit.pop(t, None)
                    continue
                if self._drr_deficit.get(t, 0.0) < 1.0:
                    self._drr_deficit[t] = (self._drr_deficit.get(t, 0.0)
                                            + self.qos.weight_of(t))
                    order.rotate(-1)
                    continue
                self._drr_deficit[t] -= 1.0
                req = q.popleft()
                self._waiting_count -= 1
                if not q:
                    del self._tenant_queues[t]
                    order.remove(t)
                    self._drr_deficit.pop(t, None)
                if not order:
                    del self._drr_order[prio]
                return req
            if not order:
                del self._drr_order[prio]
        return None

    def num_waiting_for(self, tenant: str) -> int:
        """Waiting-queue depth for ONE tenant (per-tenant rate-limit
        budgets); the global count with QoS off."""
        if self.qos is None:
            return self._waiting_count
        with self._lock:
            q = self._tenant_queues.get(tenant)
            return len(q) if q else 0

    def _evict_slot(self, slot_idx: int, commit: bool = True,
                    device_done: bool = False):
        """Return a slot's pages to the pool and clear it.

        ``commit`` feeds the written-token prefix into the radix tree
        for future prefix hits; failure paths pass False because their
        page contents may be partially written.  Only tokens whose KV
        actually landed are ever committed: the final sampled token's
        KV never lands (the slot retires before the next decode step
        would write it), so committing it would let a later prefix hit
        attend over a garbage page slot.

        ``device_done``: the fused scan retired this slot itself (stop
        id or spent budget), so the device carry already holds it
        inactive and nothing the scan advances needs an upload — the
        pipeline stays primed across the finish (docs/decode-loop.md).
        """
        slot = self.slots[slot_idx]
        req = slot.request
        # a decoding slot only the host has finished (abort, deadline,
        # preemption, failure) is still live in the device carry
        device_live = bool(self.active[slot_idx]) and not device_done
        if self.prefix_cache is not None:
            # adapter KV must never enter the shared tree (it embeds the
            # adapter's k/v deltas); imports are foreign bytes
            exclusive = (req.kv_import is not None
                         or req.kv_chunked is not None
                         or req.kv_device is not None or bool(req.adapter))
            tokens = [] if exclusive else req.resume_tokens()[:slot.written]
            if commit and not exclusive:
                self.prefix_cache.release(tokens, slot.pages)
            else:
                self.prefix_cache.release_uncommitted(tokens, slot.pages)
        else:
            self.allocator.release(slot.pages)
        if slot.wpages:
            # both tables' pages go back together
            self.window_allocator.release(list(slot.wpages.values()))
            slot.wpages = {}
            self.page_tables[slot_idx, 1] = 0
        # reset the sampling row to greedy/no-mask: the sampler's
        # sort-skip and draw-skip gates read EVERY row, so one retired
        # top-p request would otherwise defeat them forever.  Greedy
        # rows are already in the reset state — skip the device updates
        # on the (common) greedy-traffic path.
        sp = req.params
        if sp.temperature > 0.0 or sp.top_k > 0 or sp.top_p < 1.0 \
                or sp.min_p > 0.0 or sp.has_penalties:
            self.sampling = self.sampling.reset_slot(slot_idx)
        # speculation state is per-slot: draft pages/position return to
        # the draft pool, the depth controller restarts, and the cached
        # n-gram index drops (rebuilt from resume_tokens on re-admission)
        if self.spec_draft is not None:
            self.spec_draft.release_slot(slot_idx)
        if self.spec_ctl is not None:
            self.spec_ctl.reset(slot_idx)
        self._ngram_idx.pop(slot_idx, None)
        self._release_grammar(slot_idx)
        slot.request = None
        slot.pages = []
        slot.prefilling = False
        slot.importing = False
        slot.prefill_tokens = []
        slot.prefill_pos = 0
        slot.prefill_t0 = 0.0
        slot.prefill_base = 0
        slot.staged_t0 = 0.0
        slot.position = 0
        slot.remaining = 0
        self.slot_adapters[slot_idx] = 0
        self.active[slot_idx] = False
        self._remaining[slot_idx] = 0
        self._batch_epoch += 1
        self._mark_state_dirty("slot_adapters")
        if device_live:
            self._mark_state_dirty("active", "left", why="finish")

    def _fail_request(self, req: Request, status: int = 500,
                      etype: str = "internal_error",
                      message: str = ""):
        """Terminate ONE request with a structured error the HTTP layer
        can surface (status/type/message), leaving the rest of the
        engine untouched.  Idempotent on req.error: the first failure
        report wins."""
        req.finish_reason = "error"
        req.finish_time = time.monotonic()
        if req.error is None:
            req.error = {"status": status, "type": etype,
                         "message": message or
                         f"request {req.req_id} failed in the engine"}
        if self.host_kv is not None:
            self.host_kv.discard(req.req_id)
        self.counters["requests_failed_total"] += 1
        self._finish_trace(req)
        req.out.put(None)

    def _expire_request(self, req: Request):
        """Deadline abort: a 408-style structured error; the request
        never consumed (or stops consuming) TPU time."""
        req.finish_reason = "deadline"
        req.finish_time = time.monotonic()
        if req.error is None:
            req.error = {"status": 408, "type": "deadline_exceeded",
                         "message": f"request {req.req_id} exceeded its "
                                    f"deadline before completing"}
        if self.host_kv is not None:
            self.host_kv.discard(req.req_id)
        self.counters["requests_expired_total"] += 1
        self._finish_trace(req)
        req.out.put(None)

    def _finish_trace(self, req: Request) -> None:
        """Record the request's decode + end-to-end spans and, when it
        crossed ``--slow-request-threshold-s``, dump its span tree to
        the log (the on-call entry point into /debug/trace)."""
        end = req.finish_time or time.monotonic()
        if req.first_token_time is not None:
            self.tracer.record("decode", req.trace_id,
                               req.first_token_time,
                               end - req.first_token_time,
                               tokens=len(req.output_tokens))
        self.tracer.record("request", req.trace_id, req.submit_time,
                           end - req.submit_time, req_id=req.req_id,
                           finish=req.finish_reason or "stop",
                           preemptions=req.preemptions)
        thr = self.cfg.slow_request_threshold_s
        if thr and end - req.submit_time >= thr:
            logger.warning(
                "slow request %s (trace %s): %.3fs e2e >= %.3fs "
                "threshold\n%s", req.req_id, req.trace_id,
                end - req.submit_time, thr,
                format_span_tree(self.tracer.spans(req.trace_id)))

    def _expire_deadlines(self) -> bool:
        """Sweep expired requests out of the waiting queue and the
        decode slots (throttled from step()).  Queue expiry is the
        cheap win — the request never touches the TPU; slot expiry
        frees pages mid-decode so a stuck client can't pin HBM."""
        now = time.monotonic()
        did = False
        with self._lock:
            if self.qos is not None:
                expired = []
                for tenant in list(self._tenant_queues):
                    q = self._tenant_queues[tenant]
                    dead = [r for r in q
                            if r.deadline is not None and now > r.deadline]
                    if dead:
                        keep = collections.deque(
                            r for r in q
                            if not (r.deadline is not None
                                    and now > r.deadline))
                        if keep:
                            self._tenant_queues[tenant] = keep
                        else:
                            # the pop path lazily sweeps the DRR order
                            del self._tenant_queues[tenant]
                        self._waiting_count -= len(dead)
                        expired.extend(dead)
            else:
                expired = [r for r in self.waiting
                           if r.deadline is not None and now > r.deadline]
                if expired:
                    keep = collections.deque(
                        r for r in self.waiting
                        if not (r.deadline is not None and now > r.deadline))
                    self.waiting = keep
                    self._waiting_count = len(keep)
        for r in expired:
            self._expire_request(r)
            did = True
        for i, slot in enumerate(self.slots):
            req = slot.request
            if req is not None and req.deadline is not None \
                    and now > req.deadline:
                self._evict_slot(i, commit=not slot.importing
                                 and not slot.prefilling)
                self._expire_request(req)
                did = True
        return did

    def _fail_active_slots(self):
        for i, slot in enumerate(self.slots):
            if slot.request is not None:
                req = slot.request
                self._evict_slot(i, commit=False)
                self._fail_request(req)

    def _fail_all(self):
        # an engine-fatal step may have died with a window in flight;
        # its readback is unreferenceable and the device-resident state
        # may alias donated-into-failure buffers — reset the pipeline
        # and force a full re-upload from the (authoritative) host side
        self._inflight = None
        self._first_pending.clear()
        self._dev_state.clear()
        self._mark_state_dirty()
        self._fail_active_slots()
        while True:
            req = self._pop_waiting()
            if req is None:
                break
            self._fail_request(req)
        self._recover_cache_if_poisoned()

    def _cache_poisoned(self) -> bool:
        """Read-only probe: was the KV pool donated into a failed step?"""
        try:
            return bool(self.cache.k.is_deleted())
        except Exception:
            return True

    def _recover_cache_if_poisoned(self):
        """A jitted step that raises AFTER buffer donation leaves
        ``self.cache`` pointing at deleted device memory; every later
        step would fail.  Rebuild a zeroed pool (in-flight requests were
        already failed, so the KV content is unreferenced)."""
        try:
            poisoned = self.cache.k.is_deleted()
        except Exception:
            poisoned = True
        # sampling and the penalty histogram are donated alongside the
        # cache; a failed step leaves them deleted too.  Everything in
        # flight is failed on this path, so fresh state is correct.
        try:
            sampling_poisoned = self.sampling.key.is_deleted()
        except Exception:
            sampling_poisoned = True
        if sampling_poisoned:
            self.sampling = SamplingState.create(len(self.slots),
                                                 self.cfg.seed)
        if self.token_counts is not None:
            try:
                counts_poisoned = self.token_counts.is_deleted()
            except Exception:
                counts_poisoned = True
            if counts_poisoned:
                self.token_counts = None    # lazily re-allocated
                self.prompt_seen = None
        if poisoned:
            logger.warning("KV cache was donated into a failed step; rebuilding")
            # device contents are gone: nothing in flight may survive and
            # the prefix tree must not advertise zeroed pages
            self._fail_active_slots()
            if self.prefix_cache is not None:
                from kaito_tpu.native import NativePrefixCache

                self.prefix_cache = NativePrefixCache(self._num_pages,
                                                      self.cfg.page_size)
                self.allocator = self.prefix_cache
            else:
                self.allocator = PageAllocator(self._num_pages)
            if self.two_kinds:
                self.window_allocator = PageAllocator(self._num_window_pages)
            self.cache = self._fresh_cache()

    def step(self) -> bool:
        """One scheduler iteration. Returns False when idle.

        Wraps the actual scheduling (``_step_inner``) with the flight
        recorder: every non-idle iteration appends one bounded timeline
        record (wall time, batch shape, token mix, KV pressure,
        preemption/shed/expiry deltas) and observes
        ``kaito:engine_step_seconds``.  Idle polls are not recorded —
        they would drown the signal and the histogram alike.
        """
        c = self.counters
        before = (c["prefill_steps_total"], c["decode_steps_total"],
                  c["generation_tokens_total"], c["prefill_tokens_total"],
                  c["preemptions_total"], c["requests_expired_total"],
                  c["requests_shed_total"])
        freed0 = c["window_pages_freed_total"]
        compiles0, compile_s0 = _COMPILES[0], _COMPILE_SECONDS[0]
        tick = self._tick
        t0 = time.monotonic()
        with self.phases.phase("engine.step", n=tick,
                               rows=self.num_running):
            did = self._step_inner()
        seconds, stall, stalled = self.phases.flush()
        if did:
            wall = time.monotonic() - t0
            self.step_hist.observe(wall)
            seconds["loop_stall"] = stall
            seconds["launch_stall"] = stalled.get("host.launch", 0.0)
            seconds["replay_stall"] = stalled.get("engine.decode.replay", 0.0)
            for phase, hist in self.phase_hists.items():
                hist.observe(seconds.get(phase, 0.0))
            # the same seconds on the record, engine.step left out
            # (it is the record's own dur); a part or a stall of its
            # own only where there was one
            extra = {name.removeprefix("engine.").removeprefix("host."):
                     round(sec, 6) for name, sec in seconds.items()
                     if name != "engine.step" and (
                         sec or name not in ("launch_stall", "replay_stall"))}
            compiled = _COMPILES[0] - compiles0
            if compiled:
                extra["compiles"] = compiled
                extra["compile_s"] = round(
                    _COMPILE_SECONDS[0] - compile_s0, 6)
                if self._heap_settled_at >= 0:
                    # the warm-up is over: a program first met now holds
                    # every stream for as long as it compiles
                    logger.warning("compiled %d program(s) in %.2f s inside "
                                   "step %d", compiled, extra["compile_s"],
                                   tick)
            if self.async_dispatch and self._step_drains:
                # what took the pipeline to depth 1 in this step
                extra["drain"] = ",".join(self._step_drains)
                self._step_drains.clear()
            if self._prefill_pack_note:
                # most prompts a prefill turn of this step took — the
                # /debug/timeline annotation for multi-prompt turns
                extra["prefill_pack"] = self._prefill_pack_note
                self._prefill_pack_note = 0
            self.timeline.add(
                t0, wall, **extra,
                running=self.num_running,
                waiting=self._waiting_count,
                prefill_steps=c["prefill_steps_total"] - before[0],
                decode_steps=c["decode_steps_total"] - before[1],
                decode_tokens=c["generation_tokens_total"] - before[2],
                prefill_tokens=c["prefill_tokens_total"] - before[3],
                preemptions=c["preemptions_total"] - before[4],
                expired=c["requests_expired_total"] - before[5],
                shed=c["requests_shed_total"] - before[6],
                kv_pages_used=(self.allocator.num_pages - 1
                               - self.allocator.available),
                **({"state_rows": self.state_rows_in_use}
                   if self.model.has_state else {}),
                # two kinds of page: what the schedule freed behind the
                # window in this step, and what is held
                **({"window_pages_freed":
                    c["window_pages_freed_total"] - freed0,
                    "window_pages_used": self.window_pages_in_use}
                   if self.two_kinds else {}))
        return did

    def _step_inner(self) -> bool:
        """Decode-priority scheduling: every iteration with active slots
        runs one decode step; prefill advances one bounded chunk every
        ``prefill_interleave`` iterations (every iteration when nothing
        is decoding), so a running batch keeps its token cadence while
        new prompts stream in.
        """
        FAILPOINTS.fire("engine.step")
        if self.async_dispatch:
            return self._step_async()
        did0 = False
        phase = self.phases.phase
        with phase("engine.schedule"):
            now = time.monotonic()
            # deadline sweep and export-registry GC are throttled: both
            # are O(queue+slots) walks that would otherwise tax every
            # iteration of the hot loop
            if now - self._last_deadline_sweep >= 0.05:
                self._last_deadline_sweep = now
                did0 = self._expire_deadlines()
            if now - self._last_export_tick >= 1.0:
                self._last_export_tick = now
                self.kv_exports.tick()
            # ensure BEFORE admitting: growth of running sequences must
            # not be starved by a fresh admission grabbing the last
            # pages (which would be preempted right back — wasted churn)
            la = 1
            if self.active.any():
                la = self._decode_lookahead()
                self._ensure_decode_pages(la)
            did = self._admit_new() or did0
            if self._advance_imports():
                did = True
            decoding = bool(self.active.any())
            # this loop plans before engine.decode opens: the span
            # carries the depth the plan chose
            spec = False
            if decoding:
                with self.phases.part("host.plan"):
                    spec = self._spec_ok()
                    if not spec:
                        la2 = self._plan_decode(la, did)
        steps_run = 0
        if spec:
            with phase("engine.decode", rows=self.num_running):
                steps_run = self._decode_speculative()
            if not steps_run:
                with phase("engine.schedule"), \
                        self.phases.part("host.plan"):
                    la2 = self._plan_decode(la, did)
        if decoding:
            if not steps_run:
                with phase("engine.decode", k=la2, rows=self.num_running):
                    if la2 > 1:
                        self._decode_multi(la2)
                    else:
                        self._decode_once()
                steps_run = la2
            did = True
        self._tick += 1
        # prefill cadence counts DECODE STEPS, not scheduler iterations:
        # a fused K-step dispatch advances the clock by K, so the
        # decode:prefill token ratio stays prefill_interleave:1 whether
        # or not fusion is engaged
        self._decode_since_prefill += steps_run
        if (not decoding) or self.cfg.prefill_interleave <= 1 \
                or self._decode_since_prefill >= self.cfg.prefill_interleave:
            with phase("engine.prefill"):
                prefilled = self._advance_prefills()
            if prefilled:
                did = True
                self._decode_since_prefill = 0
        return did

    def _plan_decode(self, la: int, admitted: bool) -> int:
        """How many steps the plain decode dispatch fuses, with their
        pages reserved.  Recomputed after admission: ensure-pages may
        have preempted (queue non-empty caps K at fused_under_load),
        and KV-import / spill-restore admissions begin decoding
        immediately — their slots post-date the reservation pass, so a
        fused dispatch must re-reserve lookahead pages first."""
        la2 = self._decode_lookahead()
        if la2 > 1 and (admitted or la2 > la):
            self._ensure_decode_pages(la2)
        return la2

    def _admit_new(self) -> bool:
        """Fill every free slot from the waiting queue (bookkeeping
        only — prefill compute happens in _advance_prefills)."""
        admitted = False
        while True:
            free_slot = next((i for i, s in enumerate(self.slots)
                              if s.request is None), None)
            if free_slot is None:
                # slot-level QoS preemption: a queued higher-priority
                # request claims a slot from a strictly lower class
                # instead of waiting out its whole decode — this is
                # what holds the guaranteed tenant's TTFT under a
                # best-effort flood (docs/qos.md degradation ladder)
                if self.qos is not None:
                    nxt = self._peek_waiting_priority()
                    victim = (None if nxt is None
                              else self._newest_slot(below_priority=nxt))
                    if victim is not None:
                        if self._inflight is not None:
                            # the victim resumes from resume_tokens():
                            # every token the device has produced for
                            # it must be replayed first (and the drain
                            # may free a slot by itself)
                            self._drain_pipeline("admission")
                            continue
                        self._preempt_slot(victim)
                        continue
                return admitted
            req = self._pop_waiting()
            if req is None:
                return admitted
            if req.aborted:
                if self.host_kv is not None:
                    self.host_kv.discard(req.req_id)
                req.out.put(None)
                admitted = True
                continue
            if req.expired:
                # queue expiry at admission: zero TPU time consumed
                self._expire_request(req)
                admitted = True
                continue
            try:
                if not self._admit(req, free_slot):
                    return admitted      # page OOM: requeued, stall admission
            except Exception:
                # fail THIS request; the loop (and other requests) live on
                # unless the cache was donated into the failed step
                logger.exception("admission failed for %s", req.req_id)
                self._fail_request(req)
                self._recover_cache_if_poisoned()
            admitted = True

    def _admit(self, req: Request, free_slot: int) -> bool:
        """Reserve prompt pages and stage the request into a slot.

        Reserve-on-demand: only the prompt (plus one decode token) is
        reserved here; decode grows the page list page-by-page, with
        preemption when the pool runs dry.
        """
        t_adm = time.monotonic()
        tokens = req.resume_tokens()
        n = len(tokens)
        cached = 0
        has_spill = (self.host_kv is not None and req.kv_import is None
                     and req.kv_chunked is None and req.kv_device is None
                     and self.host_kv.has(req.req_id))
        # leave one page of headroom per decoding slot so admissions
        # don't trigger immediate grow-preempt churn
        while True:
            headroom = sum(1 for i, s in enumerate(self.slots)
                           if s.request is not None and self.active[i])
            if self.allocator.available >= \
                    -(-(n + 1) // self.cfg.page_size) + headroom:
                break
            # QoS: a higher-priority admission may evict lower-class
            # sequences to make room (each eviction also shrinks the
            # headroom term, so recompute)
            if not self._preempt_one_lower(req):
                self._requeue_front(req)
                return False
        if self.prefix_cache is not None:
            # PD imports carry foreign KV bytes, spilled sequences
            # scatter host pages over their slots, and adapter requests
            # produce adapter-flavored KV (k/v deltas differ per
            # adapter): all acquire EXCLUSIVE pages (empty-token acquire
            # shares nothing) so they neither overwrite shared pages nor
            # inherit a cached prefix computed under different weights
            acquire_tokens = [] if (req.kv_import is not None
                                    or req.kv_chunked is not None
                                    or req.kv_device is not None
                                    or has_spill or req.adapter) else tokens
            res = self.prefix_cache.acquire(acquire_tokens, n + 1)
            while res is None and self._preempt_one_lower(req):
                res = self.prefix_cache.acquire(acquire_tokens, n + 1)
            if res is None:
                self._requeue_front(req)
                return False
            pages, cached = res
            # at least one suffix token must run to produce logits; the
            # overlap rewrites identical KV into the shared page
            cached = min(cached, n - 1)
        else:
            pages_needed = -(-(n + 1) // self.cfg.page_size)
            while pages_needed > self.allocator.available \
                    and self._preempt_one_lower(req):
                pass
            if pages_needed > self.allocator.available:
                self._requeue_front(req)
                return False
            pages = self.allocator.alloc(pages_needed)

        slot = self.slots[free_slot]
        table = np.zeros(self.page_tables.shape[1:], np.int32)
        # the full kind's table (the only one of a one-kind cache); the
        # window kind's fills as the prefill and the decode reach its
        # pages (_window_sync)
        (table[0] if self.two_kinds else table)[:len(pages)] = pages
        self.page_tables[free_slot] = table
        slot.request = req
        slot.pages = list(pages)
        self._admit_seq += 1
        slot.seq = self._admit_seq
        self.slot_adapters[free_slot] = self.adapter_index.get(req.adapter, 0)
        self._mark_state_dirty("page_tables", "slot_adapters")
        # stage prefill bookkeeping BEFORE anything that can raise, so a
        # failure path releases exactly the acquired token prefix (shared
        # refcounts included) via slot.written
        slot.prefilling = True
        slot.prefill_pos = cached
        slot.prefill_tokens = tokens
        if self.model.has_state:
            self.counters["state_resets_total"] += 1
            if req.preemptions:
                self.counters["state_recomputes_total"] += 1
        now = time.monotonic()
        slot.staged_t0 = now
        # queue wait only on FIRST admission — a resume after preemption
        # would re-count the whole lifetime as queue time
        if req.first_token_time is None and not req.preemptions:
            self.queue_wait_hist.observe(now - req.submit_time)
            self.tracer.record("queue.wait", req.trace_id, req.submit_time,
                               now - req.submit_time)
        self.tracer.record("admit", req.trace_id, t_adm, now - t_adm,
                           slot=free_slot, cached_tokens=cached,
                           pages=len(pages), resume=req.preemptions)
        try:
            self.sampling = self.sampling.set_slot(
                free_slot, temperature=req.params.temperature,
                top_k=req.params.top_k, top_p=req.params.top_p,
                seed=req.params.seed or self.counters["requests_total"],
                presence=req.params.presence_penalty,
                frequency=req.params.frequency_penalty,
                repetition=req.params.repetition_penalty,
                min_p=req.params.min_p)
            if req.params.has_penalties:
                self._ensure_penalty_state()
                V = self.md.arch.vocab_size
                # rows may hold a prior tenant's state (penalty-free
                # traffic never clears them); resumed requests rebuild
                # their own output counts
                if req.output_tokens:
                    row = np.bincount(
                        np.asarray(req.output_tokens), minlength=V
                    )[:V].astype(np.int32)
                    self.token_counts = self.token_counts.at[
                        free_slot].set(jnp.asarray(row))
                else:
                    self.token_counts = self.token_counts.at[
                        free_slot].set(0)
                # repetition penalty sees the PROMPT too (vLLM parity)
                pmask = np.zeros((V,), bool)
                pmask[np.clip(np.asarray(req.prompt_tokens), 0, V - 1)] = True
                self.prompt_seen = self.prompt_seen.at[free_slot].set(
                    jnp.asarray(pmask))
            if req.params.grammar is not None:
                self._install_grammar(free_slot, req)
            if req.kv_import is not None:
                self._start_imported(req, free_slot)
                return True
            if req.kv_device is not None:
                self._start_device_import(req, free_slot)
                return True
            if req.kv_chunked is not None:
                self._start_chunked_import(req, free_slot)
                return True
            if has_spill and self._try_restore(req, free_slot):
                return True       # resumed from host pages, no prefill
            if cached:
                self.counters["prefix_cached_tokens_total"] += cached
            # hit/miss accounting only for requests that were ELIGIBLE
            # for sharing (empty-token exclusive acquires are neither);
            # resumes after preemption don't re-count
            if (self.prefix_cache is not None and acquire_tokens
                    and not req.preemptions):
                key = ("prefix_cache_hits_total" if cached
                       else "prefix_cache_misses_total")
                self.counters[key] += 1
        except Exception:
            self._evict_slot(free_slot, commit=False)
            raise
        return True

    def _start_imported(self, req: Request, free_slot: int):
        """Decode-role start: scatter transferred KV pages and begin
        decoding at the prompt boundary (no prefill compute)."""
        from kaito_tpu.engine.pd import import_kv

        self._drain_pipeline("import")
        meta, payload, first = req.kv_import
        n = len(req.prompt_tokens)
        n_prompt_pages = -(-n // self.cfg.page_size)
        slot = self.slots[free_slot]
        with self.tracer.span("kv.import", req.trace_id,
                              bytes=len(payload), pages=n_prompt_pages):
            self.cache = import_kv(self.cache, slot.pages[:n_prompt_pages],
                                   payload, meta)
        if not req.prompt_counted:
            self.counters["prompt_tokens_total"] += n
            req.prompt_counted = True
        self._begin_decode(free_slot, first, n)

    def _start_device_import(self, req: Request, free_slot: int):
        """Colocated decode start: ONE device-to-device scatter of the
        prefill engine's staged canonical slab into this engine's
        pages — the bytes never touch the host."""
        from kaito_tpu.engine.pd import import_arrays

        self._drain_pipeline("import")
        meta, slabs, first = req.kv_device
        n = len(req.prompt_tokens)
        n_prompt_pages = -(-n // self.cfg.page_size)
        slot = self.slots[free_slot]
        with self.tracer.span("kv.import.device", req.trace_id,
                              pages=n_prompt_pages):
            # 2-tuple (k, v) or 4-tuple (k, v, k_scale, v_scale) slabs
            self.cache = import_arrays(self.cache,
                                       slot.pages[:n_prompt_pages],
                                       *slabs)
        # drop the slab references (unpin HBM) but KEEP the field as a
        # marker: _evict_slot reads it to keep imported pages out of
        # the shared prefix tree, like the other import kinds
        req.kv_device = (meta, None, first)
        self.counters["pd_device_handoffs_total"] += 1
        if not req.prompt_counted:
            self.counters["prompt_tokens_total"] += n
            req.prompt_counted = True
        self._begin_decode(free_slot, first, n)

    def _start_chunked_import(self, req: Request, free_slot: int):
        """Decode-role start, chunked path: the slot parks in the
        ``importing`` state; ``_advance_imports`` scatters chunks as
        they arrive and begins decode when the last one lands."""
        slot = self.slots[free_slot]
        slot.importing = True
        n = len(req.prompt_tokens)
        if not req.prompt_counted:
            self.counters["prompt_tokens_total"] += n
            req.prompt_counted = True

    def _advance_imports(self) -> bool:
        """Assemble arrived KV chunks for importing slots into host
        buffers — bounded work per call so a large transfer never
        stalls the decode cadence of other requests — then ONE device
        scatter and the decode transition when the last chunk lands."""
        from kaito_tpu.engine.pd import import_arrays

        did = False
        for i, slot in enumerate(self.slots):
            req = slot.request
            if req is None or not slot.importing:
                continue
            ci = req.kv_chunked
            err = ci.error
            transient = ci.transient
            if err is None:
                try:
                    FAILPOINTS.fire("engine.kv_import", req_id=req.req_id)
                    if ci.assemble():
                        did = True
                    if ci.complete and req.kv_prefix_tokens > 0:
                        # cluster-KV-pool fetch: only a PREFIX of the
                        # prompt's KV arrived — scatter it and hand the
                        # slot back to the prefill machinery for the
                        # remainder (docs/kv-pool.md)
                        self._finish_prefix_import(i, ci)
                        did = True
                    elif ci.complete:
                        n = len(req.prompt_tokens)
                        n_pages = -(-n // self.cfg.page_size)
                        with self.tracer.span("kv.import.chunked",
                                              req.trace_id,
                                              pages=n_pages):
                            self.cache = import_arrays(
                                self.cache, slot.pages[:n_pages],
                                *ci.full_arrays())
                        slot.importing = False
                        self._begin_decode(i, ci.first_token, n)
                        did = True
                except Exception as e:
                    # assembly/scatter exceptions are NOT transient:
                    # the bytes are wrong (shape/corruption), so the
                    # same transfer would fail again
                    err = f"{type(e).__name__}: {e}"
                    transient = False
            if err is not None:
                self._evict_slot(i, commit=False)
                if req.kv_prefix_tokens > 0:
                    # the pool is an optimization, never a correctness
                    # dependency: ANY fetch failure (transient or not)
                    # falls back to a full local prefill — the request
                    # still succeeds, just at cold TTFT
                    req.kv_chunked = None
                    req.kv_prefix_tokens = 0
                    self.counters["kv_pool_fetch_failures_total"] += 1
                    logger.warning("KV pool fetch for %s failed (%s); "
                                   "recomputing locally", req.req_id, err)
                    self._requeue_front(req)
                elif transient and req.kv_retries > 0:
                    # retry budget: fall back to LOCAL recompute — the
                    # request still succeeds (slower), and the prompt
                    # tokens are all here.  Clearing kv_chunked routes
                    # re-admission through the normal prefill path.
                    req.kv_retries -= 1
                    req.kv_chunked = None
                    self.counters["kv_import_retries_total"] += 1
                    logger.warning("KV import for %s failed transiently "
                                   "(%s); falling back to local recompute",
                                   req.req_id, err)
                    self._requeue_front(req)
                else:
                    logger.warning("KV import failed for %s: %s",
                                   req.req_id, err)
                    self._fail_request(req, status=502,
                                       etype="kv_transfer_failed",
                                       message=f"KV import failed: {err}")
                did = True
        return did

    def _finish_prefix_import(self, i: int, ci) -> None:
        """Scatter a completed cluster-pool PREFIX fetch and hand the
        slot back to the prefill machinery for the unfetched remainder.
        The fetched slab may cover more pages than were verified
        against this request's tokens — only the verified whole-page
        prefix is imported."""
        from kaito_tpu.engine.pd import import_arrays

        slot = self.slots[i]
        req = slot.request
        ps = self.cfg.page_size
        n_use = req.kv_prefix_tokens // ps
        arrs = ci.full_arrays()
        # contiguous COPIES, not views: a view would pin the full
        # assembly buffers for as long as the replicated store entry
        # lives
        k = np.ascontiguousarray(arrs[0][:, :n_use])
        v = np.ascontiguousarray(arrs[1][:, :n_use])
        ks = vs = None
        if len(arrs) == 4:
            ks = np.ascontiguousarray(arrs[2][:, :n_use])
            vs = np.ascontiguousarray(arrs[3][:, :n_use])
        # pad the scatter to the next power of two by REPEATING the last
        # page (same index, same bytes — an idempotent overwrite): the
        # scatter's XLA program is shaped by the page count, and pool
        # prefixes have arbitrary lengths, so unpadded imports would
        # recompile per distinct count and eat the TTFT the fetch saved
        pages = list(slot.pages[:n_use])
        kp, vp, ksp, vsp = k, v, ks, vs
        n_pad = 1 << max(0, n_use - 1).bit_length()
        if n_pad > n_use:
            reps = n_pad - n_use
            pages += [pages[-1]] * reps

            def _pad(a):
                return np.concatenate(
                    [a, np.repeat(a[:, -1:], reps, axis=1)], axis=1)
            kp, vp = _pad(k), _pad(v)
            if ks is not None:
                ksp, vsp = _pad(ks), _pad(vs)
        with self.tracer.span("kv.pool.import", req.trace_id,
                              pages=n_use):
            self.cache = import_arrays(self.cache, pages, kp, vp, ksp, vsp)
        slot.importing = False
        # _admit staged the prefill fields already (exclusive acquire,
        # prefill_pos = 0); skipping ahead makes _advance_prefills run
        # only the remainder — warm TTFT on a replica that never saw
        # this prefix before
        slot.prefill_pos = max(slot.prefill_pos, req.kv_prefix_tokens)
        self.counters["kv_pool_fetches_total"] += 1
        self.counters["kv_pool_fetched_tokens_total"] += req.kv_prefix_tokens
        # replicate into the local store: this replica becomes a holder
        # too, so the pool heals toward N copies and survives the
        # ORIGINAL holder scaling down (docs/kv-pool.md)
        if self.kv_pool is not None and len(req.pool_blocks) >= n_use:
            from kaito_tpu.engine.kv_pool import (HostExport, PoolEntry,
                                                  meta_nbytes, pool_key)

            blocks = list(req.pool_blocks[:n_use])
            key = pool_key(blocks)
            if not self.kv_pool.has(key):
                exp = HostExport(k, v, ks, vs, n_tokens=n_use * ps,
                                 model=self.md.name,
                                 prompt_tokens=req.prompt_tokens[:n_use * ps])
                self.kv_pool.put(PoolEntry(
                    key=key, blocks=blocks, n_tokens=n_use * ps,
                    n_pages=n_use, export=exp,
                    nbytes=meta_nbytes(exp.meta)))

    def _fail_prefill(self, i: int, e: Exception) -> None:
        """Fail the request staged in slot ``i`` over a prefill error
        and free the slot without committing its pages."""
        req = self.slots[i].request
        self._evict_slot(i, commit=False)
        self._fail_request(req, etype="prefill_failed",
                           message=f"prefill failed: "
                                   f"{type(e).__name__}: {e}")

    def _takes_cp(self, pos: int, n: int) -> bool:
        """A long fresh prompt takes the context-parallel single-shot
        path: the ring shards the memory the chunk budget was bounding,
        so the whole prompt runs in ONE dispatch at ~1/seq the
        latency."""
        return (self.model.cp is not None and pos == 0
                and n >= self.cfg.cp_min_tokens
                and self._bucket(n) % dict(
                    self.model.cp[0].shape)["sequence"] == 0)

    def _prefill_turn_budget(self) -> int:
        """Tokens a prefill turn of the serial scheduler may spend:
        one chunk of ``max_prefill_tokens`` for every
        ``prefill_interleave`` decode steps run since the last turn,
        and one when nothing decodes.  Steps that ran while nothing was
        staged earned nothing anyone waited for, so no more count than
        one window under load holds (``fused_under_load``)."""
        cfg = self.cfg
        every = max(1, cfg.prefill_interleave)
        steps = min(self._decode_since_prefill,
                    max(cfg.fused_under_load, every))
        return max(cfg.max_prefill_tokens, cfg.page_size) \
            * max(1, steps // every)

    def _advance_prefills(self) -> bool:
        """One prefill turn (docs/prefill.md).

        Staged slots are served round-robin.  The first pick is always
        taken and runs ONE bounded chunk, as it always did.  When it
        was a whole fresh prompt (position 0, the whole prompt within
        one chunk), the turn goes on to the next whole fresh prompts
        while they fit what is left of its budget
        (_prefill_turn_budget), each through the same one-row programs:
        admission then follows the free slots instead of taking one
        request an iteration whatever the batch's width.  A fresh
        prompt is never split to fit (the remainder would go down the
        context-prefill program); a context chunk or a context-parallel
        prompt takes a turn alone."""
        idxs = [i for i, s in enumerate(self.slots)
                if s.request is not None and s.prefilling
                and not s.importing]
        if not idxs:
            return False
        chunk = max(self.cfg.max_prefill_tokens, self.cfg.page_size)
        left = self._prefill_turn_budget()
        start = self._prefill_rr % len(idxs)
        picks = []
        for i in idxs[start:] + idxs[:start]:
            slot = self.slots[i]
            n = len(slot.prefill_tokens)
            whole = (slot.prefill_pos == 0 and n <= chunk
                     and not self._takes_cp(0, n))
            if picks and not (whole and n <= left):
                break
            picks.append(i)
            left -= n
            if not whole:
                break
        for ran, i in enumerate(picks, 1):
            self._prefill_rr += 1
            if not self._prefill_serial_chunk(i, len(picks)):
                break
        self.counters["prefill_turns_multi_total" if ran > 1
                      else "prefill_turns_single_total"] += 1
        self.prefill_pack_hist.observe(float(ran))
        if ran > 1:
            self._prefill_pack_note = max(self._prefill_pack_note, ran)
        return True

    def _prefill_serial_chunk(self, i: int, turn: int) -> bool:
        """Run ONE bounded prefill chunk for the staged slot ``i``,
        completing admission when the prompt is done; ``turn`` is the
        number of prompts its turn takes.  False when the chunk failed
        (the request is failed and the slot freed)."""
        slot = self.slots[i]
        req = slot.request
        tokens = slot.prefill_tokens
        n = len(tokens)
        budget = max(self.cfg.max_prefill_tokens, self.cfg.page_size)
        pos = slot.prefill_pos
        use_cp = self._takes_cp(pos, n)
        if use_cp:
            budget = n
        chunk = tokens[pos: pos + budget]
        m = len(chunk)
        bucket = self._bucket(m)
        t_first_chunk = time.monotonic()
        try:
            if self.two_kinds:
                # a fresh chunk attends over itself and leaves what the
                # next step's window reads; a later chunk reads the
                # window before it from the pages too
                self._window_sync(i, pos + m if pos == 0 else pos, pos + m)
            part = self.phases.part
            with self.phases.phase("engine.prefill.dispatch"):
                with part("host.args"):
                    ctoks = np.zeros((1, bucket), np.int32)
                    ctoks[0, :m] = chunk
                    aid = jnp.asarray(self.slot_adapters[i:i + 1])
                    # a copy: the CPU backend may alias a numpy buffer,
                    # and a window table's entries change right after
                    # the dispatch (_window_sync), before the program
                    # has run
                    args = (jnp.asarray(ctoks), jnp.asarray([m], np.int32),
                            jnp.asarray(self.page_tables[i][None].copy()))
                    FAILPOINTS.fire("engine.prefill", req_id=req.req_id)
                    if use_cp:
                        fn = self._prefill_cp_fn(bucket)
                        args += (aid,)
                    elif pos == 0 and (m == n or self.two_kinds):
                        # a whole fresh prompt; with two kinds of page
                        # also the first chunk of a longer one, which
                        # attends over itself: its window table holds
                        # the chunk's tail alone
                        fn = self._prefill_fn(bucket)
                        args += (aid, self._state_rows([i]))
                    else:
                        # chunk attends over the paged history (cached
                        # prefix + earlier chunks) — bounds per-step
                        # latency for long prompts (the feature vLLM
                        # gives the reference)
                        fn = self._prefill_ctx_fn(bucket)
                        args += (jnp.asarray([pos], np.int32), aid,
                                 self._state_rows([i]))
                with part("host.launch"):
                    self.cache, logits = fn(self.params, self.cache, *args)
                del args
        except Exception as e:
            logger.exception("prefill failed for %s", req.req_id)
            self._fail_prefill(i, e)
            self._recover_cache_if_poisoned()
            return False
        self.counters["prefill_steps_total"] += 1
        self.counters["prefill_tokens_total"] += m
        self.counters["prefill_rows_total"] += bucket
        wait = 0.0
        if not slot.prefill_t0:
            slot.prefill_t0 = t_first_chunk
            slot.prefill_base = pos
            if slot.staged_t0:
                wait = max(0.0, t_first_chunk - slot.staged_t0)
            self.prefill_wait_hist.observe(wait)
        self.tracer.record("prefill.chunk", req.trace_id, t_first_chunk,
                           time.monotonic() - t_first_chunk, pos=pos,
                           tokens=m, bucket=bucket, slot=i, cp=bool(use_cp),
                           pack=turn, queue_wait=round(wait, 6))
        slot.prefill_pos = pos + m
        if self.two_kinds and pos:
            # the chunk's own pages behind the window go back now: the
            # programs that could read them are queued ahead of any that
            # is given them
            self._window_sync(i, pos + m, pos + m)
        if slot.prefill_pos >= n:
            self._complete_prefills([(i, n)], logits)
        return True

    # what makes the host read a completed prefill's first token back
    # at once (docs/decode-loop.md): it must see the token before the
    # next launch can be built
    _FIRST_TOKEN_BLOCKS = ("grammar", "penalties", "stop_set",
                           "speculation")

    def _first_token_blocks(self, idxs: list[int]) -> Optional[str]:
        """Why the first tokens of these slots cannot wait on the
        device for the loop's next readback, or None.  Decided from
        what the engine can observe: the synchronous loop has no window
        to wait behind; speculation builds its windows from the host's
        tokens; a grammar's automaton steps on the host, penalty counts
        are updated from it, and a stop set wider than the device
        matrix is checked on it."""
        if not self.async_dispatch:
            return "sync_loop"
        if self.cfg.speculative_ngram > 0 or self.spec_draft is not None:
            return "speculation"
        for i in idxs:
            req = self.slots[i].request
            if self._gram_slots[i] is not None:
                return "grammar"
            if req.params.has_penalties:
                return "penalties"
            if len(self._stop_set(req)) > _STOP_WIDTH:
                return "stop_set"
        return None

    def _complete_prefills(self, done: list[tuple[int, int]],
                           logits) -> None:
        """The prompts of ``done`` [(slot, prompt length)] are written;
        ``logits`` [len(done), V] are their last positions'.  One
        program samples every first token and joins the rows to the
        decode carry on the device, queued behind the window in flight
        and the prefill (_first_token_step).  The host does not wait
        for it: the slots become active in its mirrors, so that the
        next launch reserves their pages and carries their stop ids,
        and the tokens are read back where the loop next waits anyway
        (_resolve_first_tokens).  Where the host must see the token
        first (_first_token_blocks) the same program runs without the
        carry, its outputs are read at once, and each row joins the
        carry from the host's mirrors as an imported one does."""
        idxs = [i for i, _ in done]
        why = self._first_token_blocks(idxs)
        rows = np.full((len(done), 4 + _STOP_WIDTH), -1, np.int32)
        counts = seen = grows = None
        for j, (i, n) in enumerate(done):
            slot = self.slots[i]
            req = slot.request
            if not req.prompt_counted:
                # resume-after-preempt re-prefills prompt+generated; only
                # the original prompt counts (once) toward the metric
                self.counters["prompt_tokens_total"] += len(req.prompt_tokens)
                req.prompt_counted = True
            self._enter_decode(i, n)
            rows[j, :4] = (i, n, slot.remaining, self._gram_state[i])
            if why is None:
                ids = sorted(self._stop_set(req))
                rows[j, 4:4 + len(ids)] = ids
            if req.params.has_penalties and self.token_counts is not None:
                counts, seen = self.token_counts, self.prompt_seen
            gs = self._gram_slots[i]
            if gs is not None:
                # zero rows for unconstrained slots are an exact no-op
                # on the logits
                if grows is None:
                    grows = np.zeros((len(done), self.md.arch.vocab_size),
                                     np.float32)
                grows[j] = self._gram_row(gs)
        st = self._dev_state
        carry = None
        if why is None and not self._state_dirty & self._DEVICE_ADVANCED \
                and all(f in st for f in self._CARRY_FIELDS):
            carry = tuple(st[f] for f in self._CARRY_FIELDS)
        # the blocking path's span is the wait it ends in, as it was
        # when the sample was read back inside it
        part = self.phases.part
        with self.phases.phase("engine.prefill.dispatch" if why is None
                               else "engine.prefill.wait"):
            t0 = time.monotonic()
            with part("host.args"):
                args = (jnp.asarray(logits), self.sampling, carry,
                        jnp.asarray(rows), counts, seen,
                        None if grows is None else jnp.asarray(grows))
            with part("host.launch"):
                carry, key, tok, lp = _first_token_step(*args)
            del args
            self.sampling = dataclasses.replace(self.sampling, key=key)
            if why is not None:
                # blocks on the logits, behind any window in flight
                toks, lps = (np.asarray(a).tolist() for a in (tok, lp))
        staged = [(i, self.slots[i].seq, n) for i, n in done]
        if why is None:
            if carry is not None:
                st.update(zip(self._CARRY_FIELDS, carry))
                self.counters["h2d_uploads_total"] += 1
            else:
                # a full upload from the mirrors is owed (after a drain,
                # if a window is in flight) and carries these slots too;
                # the tokens are resolved before it
                self._mark_state_dirty("positions", "active", "last_tokens",
                                       "left")
            self._start_readback(tok, lp)
            self._first_pending.append((staged, tok, lp, t0))
            self.counters["first_tokens_deferred_total"] += len(done)
            return
        if why in self.first_token_blocking:
            self.first_token_blocking[why] += len(done)
        self._land_first_tokens(staged, toks, lps, t0, join=True)

    @staticmethod
    def _start_readback(*arrays) -> None:
        """Start the copy to the host of arrays the loop reads later."""
        for arr in arrays:
            if arr is None:         # a program with no such output
                continue
            try:
                arr.copy_to_host_async()
            except Exception:      # backend without async copies
                pass

    def _resolve_first_tokens(self, ready_only: bool = False) -> bool:
        """Read back the first tokens still on the device, oldest
        first, and emit them (docs/decode-loop.md has the points this
        is called from).  ``ready_only`` stops at the first one whose
        program has not finished instead of waiting for it."""
        did = False
        while self._first_pending:
            staged, tok, lp, t0 = self._first_pending[0]
            if ready_only and not tok.is_ready():
                break
            self._first_pending.popleft()
            with self.phases.phase("engine.prefill.resolve"):
                toks, lps = (np.asarray(a).tolist() for a in (tok, lp))
                self._land_first_tokens(staged, toks, lps, t0, join=False)
                # the two device arrays die here, inside the span that
                # read them, never between phases (see _decode_once)
                del tok, lp
            did = True
        return did

    def _land_first_tokens(self, staged: list, toks: list, lps: list,
                           t0: float, join: bool) -> None:
        """The host half of a completed prefill, once its first token
        is known: cost model, time to first token, _emit, and for a
        request that ended on it the eviction.  ``join`` writes the row
        into the device carry from the mirrors, for a token the device
        program did not join itself."""
        now = time.monotonic()
        for (i, seq, n), tok, lp in zip(staged, toks, lps):
            slot = self.slots[i]
            req = slot.request
            if req is None or slot.seq != seq:
                continue        # evicted since: nobody waits for it
            if slot.prefill_t0:
                # taken once the token is back, so the elapsed time
                # covers real compute (plus scheduler interleaving — the
                # honest opportunity cost a transfer would avoid)
                self.pd_costs.note_prefill(n - slot.prefill_base,
                                           now - slot.prefill_t0)
            self._emit_first(i, tok, lp)
            if join and slot.request is req:
                self._join_device_batch(i)
        if self.first_token_resolve_hist is not None:
            self.first_token_resolve_hist.observe(time.monotonic() - t0)

    def _enter_decode(self, slot_idx: int, n: int) -> None:
        """A slot's prompt KV is in place (prefill completed or KV
        imported): from here the host plans it as a decoding row."""
        slot = self.slots[slot_idx]
        req = slot.request
        slot.prefilling = False
        slot.position = n
        slot.remaining = min(req.params.max_tokens - len(req.output_tokens),
                             self.cfg.max_model_len - n,
                             self._capacity_tokens - n)
        self.positions[slot_idx] = n
        self.active[slot_idx] = True
        self._remaining[slot_idx] = slot.remaining
        self._batch_epoch += 1

    def _emit_first(self, slot_idx: int, first: int,
                    first_lp: Optional[float]) -> None:
        """Emit a decoding slot's first token; a request that ends on
        it is evicted here.  ``first_lp`` is None on the PD-import path
        (the logits never existed on this engine)."""
        req = self.slots[slot_idx].request
        self.last_tokens[slot_idx] = first
        if req.first_token_time is None:
            req.first_token_time = time.monotonic()
        if req.params.has_penalties and self.token_counts is not None:
            self.token_counts = self.token_counts.at[
                slot_idx, first].add(1)
        self._emit(slot_idx, first, logprob=first_lp)

    def _begin_decode(self, slot_idx: int, first: int, n: int):
        """Transition a slot whose KV was imported to decoding and emit
        the first token that came with it."""
        req = self.slots[slot_idx].request
        self._enter_decode(slot_idx, n)
        self._emit_first(slot_idx, first, None)
        if self.slots[slot_idx].request is req:
            # still decoding after its first token: the device learns of
            # the slot now (a request that ended on it never reaches
            # the device at all)
            self._join_device_batch(slot_idx)

    # ------------------------------------------------------------------
    # Page growth + preemption
    # ------------------------------------------------------------------

    def _alloc_one_page(self) -> Optional[int]:
        if self.prefix_cache is not None:
            got = self.prefix_cache.alloc_raw(1)
            return got[0] if got else None
        try:
            return self.allocator.alloc(1)[0]
        except MemoryError:
            return None

    def _preempt_slot(self, victim: int):
        """Preempt a slot back to the front of the waiting queue; its
        generated tokens become part of the prompt on resume, so the
        client stream is seamless.  With the host offload tier, the
        victim's written KV spills to host RAM first, so resume is a
        page restore instead of a full recompute."""
        req = self.slots[victim].request
        logger.info("preempting %s (slot %d) to reclaim KV pages",
                    req.req_id, victim)
        will_requeue = len(req.resume_tokens()) + 1 <= self._capacity_tokens
        if will_requeue:
            # spill only sequences that will actually resume — a
            # length-capped sequence would leak a maximal host entry
            self._spill_slot(victim)
        req.preemptions += 1
        self.counters["preemptions_total"] += 1
        self.tracer.record("preempt", req.trace_id, time.monotonic(), 0.0,
                           slot=victim)
        # evict BEFORE clearing kv_import so imported (foreign) KV pages
        # release uncommitted — they must never enter the radix tree
        self._evict_slot(victim, commit=True)
        req.kv_import = None     # imported KV is consumed; resume recomputes
        req.kv_chunked = None
        req.kv_device = None
        req.kv_prefix_tokens = 0  # pool fetch (if any) is spent; resume
        # takes the normal prefill path
        if not will_requeue:
            # the sequence already fills the whole pool: it cannot be
            # re-admitted (resume needs more pages than exist), and all
            # its tokens were emitted — finish it at the length cap
            req.finish_reason = "length"
            req.finish_time = time.monotonic()
            self._finish_trace(req)
            req.out.put(None)
            self.counters["requests_finished_total"] += 1
            return
        self._requeue_front(req)

    def _spill_slot(self, slot_idx: int) -> None:
        """Copy a decoding slot's written KV pages into the host pool
        (async D2H) ahead of eviction; no-op when the tier is off or the
        slot holds imported/partial state."""
        slot = self.slots[slot_idx]
        req = slot.request
        if self.host_kv is None or req.kv_import is not None \
                or req.kv_chunked is not None or slot.prefilling:
            return
        written = slot.position
        n_pages = -(-written // self.cfg.page_size)
        if n_pages < 1:
            return
        from kaito_tpu.engine.host_offload import gather_pages

        # pad the id list to a power of two so gather/scatter compile
        # O(log pages_per_seq) programs, not one per page count; pad
        # slots gather/scatter the null page (garbage by design)
        bucket = 1 << (n_pages - 1).bit_length()
        ids = np.zeros((bucket,), np.int32)
        ids[:n_pages] = slot.pages[:n_pages]
        page_axis = 2 if self.pp_exec is not None else 1
        try:
            FAILPOINTS.fire("engine.spill", req_id=req.req_id)
            with self.tracer.span("kv.spill", req.trace_id,
                                  pages=n_pages):
                k_pages, v_pages = gather_pages(
                    self.cache.k, self.cache.v, jnp.asarray(ids),
                    page_axis=page_axis)
                ks_pages = vs_pages = None
                if self.cache.k_scale is not None:
                    # scale pools share the page axis; same gather
                    ks_pages, vs_pages = gather_pages(
                        self.cache.k_scale, self.cache.v_scale,
                        jnp.asarray(ids), page_axis=1)
                stored = self.host_kv.put(req.req_id, k_pages, v_pages,
                                          written, page_axis=page_axis,
                                          k_scale=ks_pages,
                                          v_scale=vs_pages)
            if stored:
                self.counters["host_kv_spilled_pages_total"] += n_pages
            # else: entry can never fit; resume recomputes
        except Exception:
            # the spill is an OPTIMIZATION: a failed D2H must not take
            # the request (or the engine) with it — drop the entry and
            # let resume recompute from tokens
            logger.exception("host-KV spill failed for %s; resume will "
                             "recompute", req.req_id)
            self.host_kv.discard(req.req_id)

    def _try_restore(self, req: Request, free_slot: int) -> bool:
        """Resume a spilled sequence by scattering its host pages back
        into the slot's freshly acquired pages (no prefill compute)."""
        entry = self.host_kv.pop(req.req_id) if self.host_kv else None
        if entry is None:
            return False
        slot = self.slots[free_slot]
        n_pages = -(-entry.written // self.cfg.page_size)
        if len(slot.pages) < n_pages \
                or entry.written != len(req.resume_tokens()) - 1:
            return False    # stale entry: fall back to recompute
        # mirror the spill's power-of-two padding; pad slots target the
        # null page, whose content is garbage by design
        page_axis = 2 if self.pp_exec is not None else 1
        bucket = entry.k.shape[page_axis]
        ids = np.zeros((bucket,), np.int32)
        ids[:n_pages] = slot.pages[:n_pages]
        from kaito_tpu.engine.host_offload import _HostShards

        ids, ek, ev = jnp.asarray(ids), entry.k, entry.v
        mesh = self.mesh or (self.pp_exec.mesh if self.pp_exec else None)
        if isinstance(ek, _HostShards):
            # multi-process entry: every lockstep process contributes
            # its shards; the slab comes back with its ORIGINAL pool
            # sharding, so the scatter below is shard-local
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            ek, ev = ek.rebuild(), ev.rebuild()
            ids = jax.device_put(np.asarray(ids),
                                 NamedSharding(mesh, P()))
        elif mesh is not None:
            # host-pool entries are committed to the host device; the
            # pool spans the mesh — replicate the operands first so the
            # jitted scatter sees one consistent device set
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            repl = NamedSharding(mesh, P())
            ids, ek, ev = (jax.device_put(x, repl) for x in (ids, ek, ev))
        with self.tracer.span("kv.restore", req.trace_id, pages=n_pages):
            k, v = self._scatter_pages_fn()(self.cache.k, self.cache.v,
                                            ids, ek, ev)
            ks, vs = self.cache.k_scale, self.cache.v_scale
            if entry.k_scale is not None and ks is not None:
                eks, evs = entry.k_scale, entry.v_scale
                if isinstance(eks, _HostShards):
                    eks, evs = eks.rebuild(), evs.rebuild()
                elif mesh is not None:
                    from jax.sharding import NamedSharding
                    from jax.sharding import PartitionSpec as P

                    repl = NamedSharding(mesh, P())
                    eks, evs = (jax.device_put(x, repl) for x in (eks, evs))
                ks, vs = self._scatter_scales_fn()(ks, vs, ids, eks, evs)
            self.cache = KVCache(k=k, v=v, k_scale=ks, v_scale=vs)
        self.counters["host_kv_restored_pages_total"] += n_pages
        n = len(req.resume_tokens())
        slot.prefilling = False
        slot.prefill_tokens = []
        slot.position = entry.written
        slot.remaining = min(
            req.params.max_tokens - len(req.output_tokens),
            self.cfg.max_model_len - entry.written,
            self._capacity_tokens - entry.written)
        self.positions[free_slot] = entry.written
        self.active[free_slot] = True
        # the pending input token is the last emitted output (its KV is
        # the next decode write); nothing new is emitted here
        self.last_tokens[free_slot] = req.output_tokens[-1]
        self._remaining[free_slot] = slot.remaining
        self._batch_epoch += 1
        self._mark_state_dirty("positions", "active", "last_tokens", "left",
                               why="admission")
        logger.debug("restored %s: %d pages, resuming at %d",
                     req.req_id, n_pages, entry.written)
        return True

    def _scatter_pages_fn(self):
        """Jitted restore-scatter; under a TP/PP mesh the donated pool
        is pinned to its original sharding so restores never re-lay-out
        the cache (which would recompile every decode program)."""
        fn = getattr(self, "_scatter_jit", None)
        if fn is None:
            from functools import partial as _partial

            from kaito_tpu.engine.host_offload import _scatter_impl

            kw = {}
            page_axis = 1
            if self.pp_exec is not None:
                page_axis = 2
                kw["out_shardings"] = (self.cache.k.sharding,
                                       self.cache.v.sharding)
            elif self.mesh is not None:
                sh = self._cache_sharding()
                kw["out_shardings"] = (sh, sh)
            fn = jax.jit(_partial(_scatter_impl, page_axis=page_axis),
                         donate_argnums=(0, 1), **kw)
            self._scatter_jit = fn
        return fn

    def _scatter_scales_fn(self):
        """Restore-scatter for the [L, pages, Hkv] scale pools (int8 KV
        mode only; PP is gated off so page_axis is always 1)."""
        fn = getattr(self, "_scatter_scales_jit", None)
        if fn is None:
            from functools import partial as _partial

            from kaito_tpu.engine.host_offload import _scatter_impl

            kw = {}
            if self.mesh is not None:
                sh = self._scale_sharding()
                kw["out_shardings"] = (sh, sh)
            fn = jax.jit(_partial(_scatter_impl, page_axis=1),
                         donate_argnums=(0, 1), **kw)
            self._scatter_scales_jit = fn
        return fn

    def _newest_slot(self, below_priority: Optional[int] = None
                     ) -> Optional[int]:
        """Preemption victim.  Legacy (QoS off): the newest-admitted
        sequence.  With QoS: the newest sequence of the LOWEST priority
        class present — a guaranteed tenant only yields once every
        lower class has.  ``below_priority`` restricts candidates to
        strictly lower classes (admission-side preemption must never
        evict a peer or better to make room)."""
        candidates = [i for i, s in enumerate(self.slots)
                      if s.request is not None]
        if below_priority is not None:
            candidates = [i for i in candidates
                          if self.slots[i].request.priority < below_priority]
        if not candidates:
            return None
        if self.qos is None:
            return max(candidates, key=lambda i: self.slots[i].seq)
        return max(candidates,
                   key=lambda i: (-self.slots[i].request.priority,
                                  self.slots[i].seq))

    def _peek_waiting_priority(self) -> Optional[int]:
        """Highest priority class with a queued request (QoS only)."""
        with self._lock:
            for prio in sorted(self._drr_order, reverse=True):
                if any(self._tenant_queues.get(t)
                       for t in self._drr_order[prio]):
                    return prio
        return None

    def _preempt_one_lower(self, req: Request) -> bool:
        """Admission-side preemption (QoS only): evict one strictly
        lower-priority sequence to make page room for ``req``.  Returns
        False when nothing lower is running — the request waits."""
        if self.qos is None:
            return False
        victim = self._newest_slot(below_priority=req.priority)
        if victim is None:
            return False
        if self._inflight is not None:
            # as in _admit_new: replay before anybody is requeued.  The
            # caller looks again — the drain may have freed the room
            self._drain_pipeline("admission")
            return True
        self._preempt_slot(victim)
        return True

    def _ensure_decode_pages(self, lookahead: int = 1):
        """Reserve-on-demand: before a decode step, every active slot
        must own the page its next KV write lands in (the next
        ``lookahead`` writes, for a fused multi-step dispatch); when the
        pool is dry, the newest-admitted sequence yields (requeue +
        recompute later) — even if it is the one that needs the page."""
        for i, slot in enumerate(self.slots):
            if not self.active[i] or slot.request is None:
                continue
            while not self._reserve_decode_pages(i, slot, lookahead):
                victim = self._newest_slot()
                if victim is None or victim == i:
                    # this slot is itself the newest (or the only one):
                    # it yields its pages and waits for the pool
                    self._preempt_slot(i)
                    break
                self._preempt_slot(victim)

    def _reserve_decode_pages(self, i: int, slot: "_Slot",
                              lookahead: int) -> bool:
        """Make slot ``i`` own the pages its next ``lookahead`` KV
        writes land in, in both tables where there are two; False when a
        pool is dry (what was taken stays taken)."""
        needed = self._pages_needed(slot, lookahead)
        table = self.page_tables[i, 0] if self.two_kinds \
            else self.page_tables[i]
        while len(slot.pages) < needed:
            page = self._alloc_one_page()
            if page is None:
                return False
            table[len(slot.pages)] = page
            slot.pages.append(page)
            self._mark_state_dirty("page_tables")
        if not self.two_kinds:
            return True
        steps = max(1, min(lookahead, slot.remaining))
        return self._window_sync(i, slot.position, slot.position + steps)

    def _window_sync(self, i: int, read_from: int, end: int) -> bool:
        """Make slot ``i``'s window table hold the pages of positions
        ``[read_from - window, end)`` and no other: the pages wholly
        behind go back to the window pool (the null page in their
        place), the missing ones ahead are taken from it.
        ``read_from`` is the first position whose step still reads the
        pages (a step at position p reads the ``window`` positions up
        to p), ``end`` the position after the last one written.  False
        when the pool cannot give the pages (nothing is taken then)."""
        slot = self.slots[i]
        ps = self.cfg.page_size
        first = max(0, read_from + 1 - self.md.arch.sliding_window) // ps
        last = (end - 1) // ps
        missing = [j for j in range(first, last + 1) if j not in slot.wpages]
        behind = [j for j in slot.wpages if j < first]
        if len(missing) > self.window_allocator.available + len(behind):
            return False
        table = self.page_tables[i, 1]
        if behind:
            self.window_allocator.release([slot.wpages.pop(j)
                                           for j in behind])
            table[behind] = 0
            self.counters["window_pages_freed_total"] += len(behind)
        for j, page in zip(missing,
                           self.window_allocator.alloc(len(missing))):
            slot.wpages[j] = page
            table[j] = page
        if behind or missing:
            self._mark_state_dirty("page_tables")
        return True

    def _penalty_args(self):
        """(counts, prompt_seen) for the decode programs: the live
        [S, V] state, or [1, 1] placeholders that compile the penalty
        path away."""
        if self.token_counts is not None:
            return self.token_counts, self.prompt_seen
        return (jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), bool))

    def _ensure_penalty_state(self):
        """First penalized admission: allocate the [S, V] histogram +
        prompt mask (the decode programs retrace once)."""
        if self.token_counts is None:
            S = len(self.slots)
            V = self.md.arch.vocab_size
            logger.info("allocating penalty state (%d x %d)", S, V)
            self.token_counts = jnp.zeros((S, V), jnp.int32)
            self.prompt_seen = jnp.zeros((S, V), bool)

    # ------------------------------------------------------------------
    # Grammar-constrained decoding state (docs/structured-output.md)
    # ------------------------------------------------------------------

    def _grammar_args(self):
        """(gmask, gtrans, gstate) for the decode programs: the packed
        live tables, or [1, 1] placeholders that compile the grammar
        path away (same discipline as _penalty_args)."""
        if self._gram_table is None:
            return (jnp.zeros((1, 1), jnp.float32),
                    jnp.zeros((1, 1), jnp.int32),
                    jnp.zeros((len(self.slots),), jnp.int32))
        self._refresh_grammar_device()
        return (self._dev_gmask, self._dev_gtrans,
                jnp.asarray(self._gram_state))

    def _refresh_grammar_device(self):
        """Re-upload the packed tables when their content changed.  The
        device arrays span the table's full (power-of-two) capacity, so
        installing a schema into spare rows re-uploads bytes but never
        changes shapes — the decode programs retrace only when the
        table actually grows."""
        tbl = self._gram_table
        if tbl is None or self._gram_version == tbl.version:
            return
        self._dev_gmask = jnp.asarray(tbl.mask)
        self._dev_gtrans = jnp.asarray(tbl.trans)
        self._gram_version = tbl.version

    def _sync_gram_state(self):
        """Recompute the absolute table row of every constrained slot
        from the host mirrors (table repack moves bases; admission /
        eviction changes membership) and mark it for re-upload."""
        tbl = self._gram_table
        for i, gs in enumerate(self._gram_slots):
            if gs is None:
                self._gram_state[i] = 0
                continue
            if gs.version != tbl.version:
                gs.base = tbl.base_of(gs.grammar.key)
                gs.version = tbl.version
            self._gram_state[i] = gs.base + gs.state
        self._mark_state_dirty("gstate")

    def _gram_row(self, gs: GrammarSlot) -> np.ndarray:
        """The slot's CURRENT 0/-inf mask row, padded to the model
        vocab (tokenizer vocab may be narrower)."""
        row = gs.grammar.mask_rows_f32()[gs.state]
        V = self.md.arch.vocab_size
        if row.shape[0] < V:
            row = np.pad(row, (0, V - row.shape[0]),
                         constant_values=np.float32(-np.inf))
        return row

    def _install_grammar(self, slot_idx: int, req: Request) -> None:
        """Pin the request's compiled grammar into the packed table and
        build the slot's host mirror.  Resume-after-preemption replays
        the already-generated output through the automaton, so the mask
        continues exactly where the evicted slot left off."""
        g = req.params.grammar
        if self._gram_table is None:
            V = self.md.arch.vocab_size
            logger.info("allocating grammar table (vocab %d)", V)
            self._gram_table = GrammarTable(V)
        base = self._gram_table.acquire(g)
        gs = GrammarSlot(grammar=g, base=base,
                         version=self._gram_table.version)
        for t in req.output_tokens:
            gs.advance(int(t))
        self._gram_slots[slot_idx] = gs
        if not req.preemptions and not req.output_tokens:
            self.grammar_cache.requests_total += 1
        # acquire may have grown/repacked the table: every slot's base
        # is re-derived, and the device copies refresh on next dispatch
        self._sync_gram_state()

    def _release_grammar(self, slot_idx: int) -> None:
        gs = self._gram_slots[slot_idx]
        if gs is None:
            return
        self._gram_table.release(gs.grammar.key)
        self._gram_slots[slot_idx] = None
        self._gram_state[slot_idx] = 0
        self._mark_state_dirty("gstate")

    def _truncate_for_grammar(self, slot_idx: int, p: list) -> list:
        """Clip a speculative proposal at the first grammar-invalid
        token (walking the automaton host-side, without mutating the
        slot's live state).  The surviving prefix is exactly what
        masked verification could ever accept, so clipping here only
        saves wasted verify positions."""
        gs = self._gram_slots[slot_idx]
        if gs is None or not p:
            return p
        st, out = gs.state, []
        for t in p:
            if not gs.grammar.allows(st, int(t)):
                break
            out.append(t)
            st = gs.grammar.advance(st, int(t))
        return out

    def _gram_rows_for(self, slot_idx: int, p: list, W: int) -> np.ndarray:
        """Absolute mask-table row per verify-window position: position
        j holds the grammar state BEFORE the token verified at j (the
        state after j accepted proposal tokens).  Unconstrained slots
        get row 0 (the reserved no-op row)."""
        row = np.zeros((W,), np.int32)
        gs = self._gram_slots[slot_idx]
        if gs is None:
            return row
        st = gs.state
        for j in range(W):
            row[j] = gs.base + st
            if j < len(p):
                st = gs.grammar.advance(st, int(p[j]))
        return row

    def _launch_decode_once(self) -> list:
        """Dispatch one decode step; returns its device outputs
        [next_tokens, lps]."""
        part = self.phases.part
        with part("host.args"):
            counts_in, seen = self._penalty_args()
            gmask, gtrans, gstate = self._grammar_args()
            args = (counts_in, seen,
                    jnp.asarray(self.last_tokens),
                    jnp.asarray(self.positions),
                    jnp.asarray(self.page_tables),
                    jnp.asarray(self.active),
                    jnp.asarray(self.slot_adapters),
                    gmask, gtrans, gstate)
        with part("host.launch"):
            cache, sampling, counts, next_tokens, lps, stats = \
                self._decode_fn(self.params, self.cache, self.sampling, *args)
        del args
        self.cache = cache
        self.sampling = sampling
        if self.token_counts is not None:
            self.token_counts = counts
        self.counters["decode_steps_total"] += 1
        self._count_decode_rows(self.active)
        self._count_moe_stats(stats)
        return [next_tokens, lps]

    def _decode_once(self):
        # Releasing a device array yields the interpreter lock, and the
        # handler threads that a replay has just woken take it: each
        # array dies inside the phase that made it wait, never between
        # phases — the launch's temporaries when the launch returns,
        # the outputs at the end of the replay that read them.
        phase = self.phases.phase
        with phase("engine.decode.dispatch"):
            out = self._launch_decode_once()
        # one bulk D2H + tolist per dispatch: the replay loop then works
        # on Python ints/floats instead of paying a scalar conversion
        # per token
        with phase("engine.decode.wait"):
            toks, lps = (np.asarray(a).tolist() for a in out)
        with phase("engine.decode.replay"):
            for i, slot in enumerate(self.slots):
                if not self.active[i]:
                    continue
                self.positions[i] += 1
                slot.position += 1
                self._emit(i, toks[i], logprob=lps[i])
                self.last_tokens[i] = toks[i]
            out.clear()

    def _decode_lookahead(self) -> int:
        """How many decode steps the next dispatch may fuse.  Full
        ``run_ahead`` in steady-state decode (nothing waiting, nothing
        prefilling); capped at ``fused_under_load`` when requests are
        waiting or prefilling, so fusion keeps amortizing dispatch
        overhead in the sustained-admission regime — the normal serving
        state — while admissions and prefill chunks still land every
        few steps.  Always 1 when an abort is pending (host-side
        knowledge; the 1-step path retires it promptly) or a slot's
        stop set overflows the fixed device matrix.  K is clamped to
        the batch's max remaining budget (power-of-two bucketed, so at
        most log2(run_ahead) compiled programs) and to what the free
        page pool covers — speculative lookahead pages must never
        preempt a running sequence."""
        K = self.run_ahead
        if K <= 1 or self.pp_exec is not None:
            return 1
        busy = self._waiting_count > 0
        max_rem = 0
        for i, s in enumerate(self.slots):
            if s.request is None:
                continue
            if s.request.aborted:
                return 1
            if s.prefilling:
                busy = True
                continue
            if self.active[i]:
                if len(self._stop_set(s.request)) > _STOP_WIDTH:
                    return 1
                max_rem = max(max_rem, s.remaining)
        if busy:
            K = min(K, self.cfg.fused_under_load)
            if K <= 1:
                return 1
        if max_rem < K:
            # every slot finishes within the window: shrink the scan so
            # it doesn't burn full-batch steps past the last real token
            K = 1 << max(0, max_rem.bit_length() - 1)
        # halve under page pressure instead of dropping straight to
        # single-step: the power-of-two buckets keep compile count low
        while K > 1 and not self._lookahead_fits(K):
            K //= 2
        return max(1, K)

    def _pages_needed(self, slot: "_Slot", lookahead: int) -> int:
        """Pages a decoding slot must own for its next ``lookahead``
        KV writes: they cover positions [position, position+steps-1],
        where a slot whose budget ends earlier goes inactive in-scan
        and never writes past position + remaining - 1."""
        steps = max(1, min(lookahead, slot.remaining))
        return (slot.position + steps - 1) // self.cfg.page_size + 1

    def _lookahead_fits(self, K: int) -> bool:
        """True when every active slot's next-K page growth comes out
        of the free pool — i.e. _ensure_decode_pages(K) will not have
        to preempt anybody for speculative pages."""
        extra = 0
        for i, slot in enumerate(self.slots):
            if not self.active[i] or slot.request is None:
                continue
            extra += max(0, self._pages_needed(slot, K) - len(slot.pages))
        # the window kind's pool holds every slot's few pages by its
        # sizing (_window_pool_cap): the full kind's decides
        return extra <= self.allocator.available

    def _decode_multi(self, K: int):
        """One fused K-step decode dispatch; replay the emitted-token
        trace through the single-step _emit path (stop handling,
        eviction, streaming) on the host."""
        with self.phases.phase("engine.decode.dispatch"):
            win = self._launch_decode_multi(K)
        self._retire_window(win)

    def _launch_decode_multi(self, K: int) -> list:
        """Dispatch one fused K-step window; returns it as
        [K, toks, acts, lps], the three still on the device."""
        fn = self._decode_multi_fns.get(K)
        if fn is None:
            fn = self._decode_multi_fns[K] = self._build_decode_multi_fn(K)
        part = self.phases.part
        with part("host.args"):
            stop_dev = self._stop_matrix()
            counts_in, seen = self._penalty_args()
            gmask, gtrans, gstate = self._grammar_args()
            args = (counts_in, seen,
                    jnp.asarray(self.last_tokens),
                    jnp.asarray(self.positions),
                    jnp.asarray(self.page_tables),
                    jnp.asarray(self.active),
                    jnp.asarray(self.slot_adapters),
                    stop_dev,
                    jnp.asarray(self._remaining),
                    gmask, gtrans, gstate)
            owners = self._slot_owners()
        with part("host.launch"):
            cache, sampling, counts, toks, acts, lps, stats = fn(
                self.params, self.cache, self.sampling, *args)
        del args
        self.cache = cache
        self.sampling = sampling
        if self.token_counts is not None:
            self.token_counts = counts
        self.counters["decode_steps_total"] += K
        return [K, toks, acts, lps, owners, stats]

    def _count_moe_stats(self, stats) -> None:
        """Add a decode program's expert-layer counters ([held experts
        (one call each), held experts that got a pair, pairs held, pairs
        routed], already on the host or on its way) to the engine's."""
        if stats is None:
            return
        calls, touched, held, routed = np.asarray(stats).tolist()
        c = self.counters
        c["moe_expert_calls_total"] += calls
        c["moe_experts_touched_total"] += touched
        c["moe_pairs_held_total"] += held
        c["moe_pairs_routed_total"] += routed

    def _count_decode_rows(self, active: np.ndarray) -> None:
        """Count a dispatch's slot-steps from its ``active`` flags
        ([S] for one step, a window's [K, S] trace): all of them, and
        those that decoded nothing."""
        self.counters["decode_rows_total"] += active.size
        self.counters["decode_rows_idle_total"] += \
            active.size - int(np.count_nonzero(active))

    def _slot_owners(self) -> list:
        """Each slot's admission number (0: free), recorded with a
        window at its launch."""
        return [s.seq if s.request is not None else 0 for s in self.slots]

    def _replay_window(self, K: int, toks, acts, lps, owners: list):
        """Replay one fused window's [K, S] trace through the
        single-step _emit path (stop handling, eviction, streaming).
        The scan already deactivated finished slots on-device, so this
        is reconciliation, not control.  One bulk tolist per array
        keeps the K x S inner loop on Python scalars.

        A row is replayed only into the request that owned its slot
        when the window was launched (``owners``): a slot whose request
        the host has retired since, or that a later admission has taken
        over, gets none of the window's tokens (docs/decode-loop.md)."""
        self._count_decode_rows(acts)
        toks = toks.tolist()          # [K, S]
        acts = acts.tolist()          # [K, S] — device active BEFORE step k
        lps = lps.tolist()            # [K, S]
        # slots whose first token is still on the device: the loop
        # resolves it before it retires a window that holds the row
        # (_retire_window), so none of these is active in this trace
        held = {i for staged, *_ in self._first_pending
                for i, _, _ in staged}
        for k in range(K):
            tk, ak, lk = toks[k], acts[k], lps[k]
            for i, slot in enumerate(self.slots):
                # slot.request goes None when _emit retires it mid-trace
                if not ak[i] or slot.request is None \
                        or slot.seq != owners[i]:
                    continue
                if i in held:
                    # never a row's later tokens before its first
                    self._resolve_first_tokens()
                    held = ()
                    if slot.request is None:
                        continue
                self.positions[i] += 1
                slot.position += 1
                self._emit(i, tk[i], logprob=lk[i])
                self.last_tokens[i] = tk[i]

    # ------------------------------------------------------------------
    # Zero-bubble async decode loop (docs/decode-loop.md)
    # ------------------------------------------------------------------
    #
    # Device-resident loop state + a two-deep dispatch pipeline: window
    # N+1 is dispatched straight from the jitted scan's final carry
    # while window N's [K, S] trace rides back via an async readback,
    # so host postprocess (stop replay, _emit, streaming, scheduling)
    # overlaps device compute.  The scan already deactivates slots
    # in-scan on stop/budget, so the host replay is reconciliation, not
    # control.  What only the host knows (abort, preempt, spill,
    # deadline eviction) drains the pipeline to depth 1 first — those
    # paths read resume_tokens()/host mirrors and must see every
    # emitted token; an admission into a free slot and its prefill do
    # not, and the prefill's first token stays on the device until the
    # loop next waits (_complete_prefills).

    def _mark_state_dirty(self, *names: str, why: str = "") -> None:
        """Host mutated loop-state mirrors: re-upload them at the next
        async dispatch (no-op when the async loop is off).  With no
        args, marks everything (full re-sync).  ``why`` is the drain
        reason counted if a window in flight has to be retired for it
        (the first one given since the last upload)."""
        if not self.async_dispatch:
            return
        self._state_dirty.update(names or self._STATE_FIELDS)
        if why and not self._dirty_reason:
            self._dirty_reason = why

    def _join_device_batch(self, slot_idx: int) -> None:
        """Make a slot that has just begun decoding live in the loop
        state the next window is launched from, for a first token the
        host knows (a KV import's, a blocking path's: a prefill's own
        program joins the row itself, _first_token_step).  With a
        window in flight the host mirrors of the other slots lag the
        device, so only this slot's row is written into the carry, by
        one small program queued behind that window: nothing is
        drained, nothing is rolled back.  The window in flight was
        launched with the slot inactive, so its trace holds no row for
        it."""
        if not self.async_dispatch:
            return
        st = self._dev_state
        if self._state_dirty & self._DEVICE_ADVANCED \
                or any(f not in st for f in self._CARRY_FIELDS):
            # a full upload from the mirrors is already owed (after a
            # drain, if a window is in flight): it carries this slot too
            self._mark_state_dirty("positions", "active", "last_tokens",
                                   "left")
            return
        row = np.asarray(
            [slot_idx, self.last_tokens[slot_idx], self.positions[slot_idx],
             self._remaining[slot_idx], self._gram_state[slot_idx]], np.int32)
        with self.phases.part("host.launch"):
            st.update(zip(self._CARRY_FIELDS, _patch_carry_row(
                *(st[f] for f in self._CARRY_FIELDS), row)))
        self.counters["h2d_uploads_total"] += 1

    def _stop_matrix(self):
        """Device [S, _STOP_WIDTH] stop matrix, cached on the batch
        epoch: stop sets are per-request immutable, so batch membership
        changes (admit/evict/restore) are the only invalidation.  Both
        decode loops use this — the sync fused path stops rebuilding it
        from Python loops on every dispatch."""
        epoch, dev = self._stop_cache
        if epoch == self._batch_epoch and dev is not None:
            return dev
        S = len(self.slots)
        stop = np.full((S, _STOP_WIDTH), -1, np.int32)
        for i, slot in enumerate(self.slots):
            if slot.request is None or not self.active[i]:
                continue
            ids = sorted(self._stop_set(slot.request))
            stop[i, :len(ids)] = ids
        dev = jnp.asarray(stop)
        self._stop_cache = (self._batch_epoch, dev)
        return dev

    def _device_state(self) -> dict:
        """The device-resident loop state for the next dispatch.  Only
        fields the host dirtied since the last dispatch are uploaded
        (counted in kaito:engine_h2d_uploads_total — ~zero per dispatch
        in steady state); everything else is the previous scan's carry,
        already on device."""
        src = {"last_tokens": self.last_tokens,
               "positions": self.positions,
               "active": self.active,
               "page_tables": self.page_tables,
               "slot_adapters": self.slot_adapters,
               "left": self._remaining,
               "gstate": self._gram_state}
        for name in self._STATE_FIELDS:
            if name in self._state_dirty or name not in self._dev_state:
                # a copy: the CPU backend may alias a numpy buffer
                # instead of copying it, and the host writes its mirrors
                # again (an admission, a completed prefill) while the
                # window launched from this upload is still queued
                self._dev_state[name] = jnp.asarray(src[name].copy())
                self.counters["h2d_uploads_total"] += 1
        self._state_dirty.clear()
        self._dirty_reason = ""
        return self._dev_state

    def _retire_window(self, win: list, drain: str = "") -> None:
        """Block on window N's readback and replay its trace through
        the normal _emit path.  In the async loop window N+1 is usually
        already executing on device by the time this runs — the block
        overlaps its compute instead of serializing with it; the
        synchronous fused path retires the window it just dispatched.
        The window is taken over: ``win`` is emptied at the end of the
        replay, so its device arrays die there (see _decode_once).
        ``drain`` names what forced a retire with nothing launched
        behind it, on the wait's span."""
        attrs = {"drain": drain} if drain else {}
        # a first token that is back already goes out before the
        # window's tokens; none is waited for here
        self._resolve_first_tokens(ready_only=True)
        with self.phases.phase("engine.decode.wait", **attrs):
            # blocks until the readback lands
            host = [np.asarray(a) for a in win[1:4]]
        with self.phases.phase("engine.decode.replay"):
            self._count_moe_stats(win[5])
            self._replay_window(win[0], *host, win[4])
            win.clear()
        # the prefills completed since this window's launch are queued
        # right behind it, and the next window behind them: their first
        # tokens are back or nearly so, and must be out before that
        # window's replay emits the rows' later ones
        self._resolve_first_tokens()

    # what can force the pipeline back to depth 1 (docs/decode-loop.md)
    _DRAIN_REASONS = ("finish", "admission", "import", "dirty_carry",
                      "page_pressure", "sync_decode", "speculation",
                      "deadline", "idle")

    def _drain_pipeline(self, reason: str) -> None:
        """Retire any in-flight window (pipeline back to depth 1).
        After this, host mirrors are fully reconciled and paths that
        read resume_tokens()/positions (preempt, spill, evict, abort,
        spec) are safe.  Counted by ``reason`` when a window was in
        flight."""
        win, self._inflight = self._inflight, None
        if win is not None:
            self.drain_counts[reason] += 1
            self._step_drains.append(reason)
            self._retire_window(win, drain=reason)
        else:
            # no window to wait behind: the first tokens still on the
            # device are part of what the mirrors must hold
            self._resolve_first_tokens()

    def _drain_reason(self) -> Optional[str]:
        """Why the window in flight must be retired before this step
        schedules, or None.  Admission into a free slot, prefill
        progress and a finish the scan applied itself do not: they
        leave the rows the device is advancing alone.  What does: a
        finish only the host knows (it dirtied the carry, and the slot
        must not be refilled while the device still runs it), an abort
        waiting for the single-step path, and a chunked KV import, whose
        slot changes hands between host and device state."""
        if self._inflight is None:
            return None
        if self._state_dirty & self._DEVICE_ADVANCED:
            return self._dirty_reason or "dirty_carry"
        for slot in self.slots:
            req = slot.request
            if req is None:
                continue
            if req.aborted:
                return "sync_decode"
            if slot.importing:
                return "import"
        return None

    def _needs_sync_decode(self) -> bool:
        """Conditions only the single-step host loop handles: pending
        aborts (host-side knowledge) and stop sets wider than the
        on-device matrix."""
        for i, s in enumerate(self.slots):
            if s.request is None:
                continue
            if s.request.aborted:
                return True
            if self.active[i] \
                    and len(self._stop_set(s.request)) > _STOP_WIDTH:
                return True
        return False

    def _decode_async(self, K: int) -> None:
        """Dispatch one K-step fused window from device-resident state
        and enqueue its readback; retire the PREVIOUS window after the
        new one is on the device stream."""
        fn = self._decode_multi_state_fns.get(K)
        if fn is None:
            fn = self._decode_multi_state_fns[K] = \
                self._build_decode_multi_fn(K, with_state=True)
        if self._state_dirty & self._DEVICE_ADVANCED:
            # the host mirrors of scan-advanced fields lag the window
            # in flight: re-uploading them now would roll the device
            # state back (double-granted budget, replayed positions).
            # Reconcile first, then upload.
            self._drain_pipeline(self._dirty_reason or "dirty_carry")
        primed = self._inflight is not None
        self.counters["decode_windows_primed_total" if primed
                      else "decode_windows_unprimed_total"] += 1
        part = self.phases.part
        with self.phases.phase("engine.decode.dispatch"):
            with part("host.args"):
                stop_dev = self._stop_matrix()
                state = self._device_state()
                counts_in, seen = self._penalty_args()
                gmask, gtrans, _ = self._grammar_args()
                owners = self._slot_owners()
            with part("host.launch"):
                cache, sampling, counts, toks, acts, lps, stats, carry = fn(
                    self.params, self.cache, self.sampling, counts_in, seen,
                    state["last_tokens"], state["positions"],
                    state["page_tables"], state["active"],
                    state["slot_adapters"], stop_dev, state["left"],
                    gmask, gtrans, state["gstate"])
            # the launch's temporaries die here, not when this function
            # returns behind the previous window's replay (see
            # _decode_once): released there, each gave the lock to the
            # handler threads the replay had just woken, 20 ms an
            # iteration of engine.decode outside every child at 96 slots
            del counts_in, seen, gmask, gtrans
            self.cache = cache
            self.sampling = sampling
            if self.token_counts is not None:
                self.token_counts = counts
            nxt, pos, act, left, gst = carry
            self._dev_state.update(last_tokens=nxt, positions=pos,
                                   active=act, left=left, gstate=gst)
            self._start_readback(toks, acts, lps, stats)
            self.counters["decode_steps_total"] += K
        prev, self._inflight = self._inflight, [K, toks, acts, lps, owners,
                                                stats]
        if prev is not None:
            self._retire_window(prev)

    def _step_async(self) -> bool:
        """The async twin of _step_inner: same decode-priority
        schedule, but fused dispatches go through the two-deep pipeline
        and host work for window N runs while window N+1 computes.
        Admission into a free slot, prefill and a finish the scan
        applied itself leave the pipeline primed; what still takes it
        to depth 1 is named by _DRAIN_REASONS."""
        did0 = False
        phase = self.phases.phase
        # a drain inside a phase nests its own decode.wait and
        # decode.replay spans there: the innermost span names the work
        with phase("engine.schedule"):
            # first tokens still on the device: with no window in
            # flight the loop has no later wait to read them at (an
            # engine that was idle when the prompt came); with one,
            # only those that are back already
            did0 = self._resolve_first_tokens(
                ready_only=self._inflight is not None)
            now = time.monotonic()
            if now - self._last_deadline_sweep >= 0.05:
                self._last_deadline_sweep = now
                # queue expiry never touches device state; slot expiry
                # evicts (reads written prefixes) — reconcile first
                if self._inflight is not None and any(
                        s.request is not None
                        and s.request.deadline is not None
                        and now > s.request.deadline
                        for s in self.slots):
                    self._drain_pipeline("deadline")
                did0 = self._expire_deadlines() or did0
            if now - self._last_export_tick >= 1.0:
                self._last_export_tick = now
                self.kv_exports.tick()
            reason = self._drain_reason()
            if reason is not None:
                self._drain_pipeline(reason)
            pend = self._inflight[0] if self._inflight is not None else 0
            la = 1
            if self.active.any():
                la = self._decode_lookahead()
                if pend and not self._lookahead_fits(la + pend):
                    # reservation must also cover the window in flight;
                    # when the pool can't, fall back to depth 1 so
                    # _ensure_decode_pages may preempt safely
                    self._drain_pipeline("page_pressure")
                    pend = 0
                self._ensure_decode_pages(la + pend)
            did = self._admit_new() or did0
            if self._advance_imports():
                did = True
            decoding = bool(self.active.any())
        steps_run = 0
        if decoding:
            part = self.phases.part
            with phase("engine.decode", rows=self.num_running):
                # the planning before a launch: Python loops over the
                # slots.  A drain inside a part nests its own
                # decode.wait and decode.replay spans there, as one
                # inside engine.schedule does
                with part("host.plan"):
                    sync = self._needs_sync_decode()
                    spec = not sync and self._spec_ok()
                if sync:
                    self._drain_pipeline("sync_decode")
                    self._decode_once()
                    self._mark_state_dirty()
                    steps_run = 1
                elif spec:
                    # speculation windows depend on each window's
                    # accepted length — inherently depth-1, but it still
                    # reads the reconciled host mirrors
                    self._drain_pipeline("speculation")
                    steps_run = self._decode_speculative()
                    self._mark_state_dirty()
                if steps_run:
                    # launched into an idle device
                    self.counters["decode_windows_unprimed_total"] += 1
                    did = True
                elif bool(self.active.any()):
                    with part("host.plan"):
                        la2 = self._decode_lookahead()
                        pend = self._inflight[0] \
                            if self._inflight is not None else 0
                        while la2 > 1 \
                                and not self._lookahead_fits(la2 + pend):
                            la2 //= 2
                        if pend and not self._lookahead_fits(la2 + pend):
                            self._drain_pipeline("page_pressure")
                            pend = 0
                        if did or la2 + pend > la:
                            self._ensure_decode_pages(la2 + pend)
                    self._decode_async(la2)
                    steps_run = la2
                    did = True
        elif self._inflight is not None:
            # nothing left active on the host: the trailing window may
            # still hold final tokens — retire it now
            with phase("engine.decode"):
                self._drain_pipeline("idle")
            did = True
        self._tick += 1
        self._decode_since_prefill += steps_run
        if (not decoding) or self.cfg.prefill_interleave <= 1 \
                or self._decode_since_prefill >= self.cfg.prefill_interleave:
            with phase("engine.prefill"):
                prefilled = self._advance_prefills()
            if prefilled:
                did = True
                self._decode_since_prefill = 0
        return did

    # ------------------------------------------------------------------
    # n-gram (prompt-lookup) speculative decoding
    # ------------------------------------------------------------------

    def _spec_ok(self) -> bool:
        """Speculate only when it is exact and cheap: engine opted in,
        no PP executor (the verify path drives the model directly), and
        the batch small enough that the on-device [B, W, V] verify
        logits stay negligible.  The n-gram-only path additionally
        requires every active slot greedy (acceptance is deterministic
        argmax equality); a draft-configured engine speculates for
        greedy AND pure-temperature sampling (Leviathan rejection
        sampling is distribution-preserving), but top-k/top-p/min-p
        masks and penalties modify the target distribution mid-window
        and keep the plain path."""
        cfg = self.cfg
        draft = self.spec_draft is not None
        if (cfg.speculative_ngram <= 0 and not draft) \
                or self.pp_exec is not None:
            return False
        n_active = 0
        for i, s in enumerate(self.slots):
            if s.request is None or not self.active[i]:
                continue
            n_active += 1
            p = s.request.params
            if p.has_penalties or s.request.aborted:
                return False
            if p.temperature > 0.0:
                if not draft:
                    return False
                if p.top_k > 0 or p.top_p < 1.0 or p.min_p > 0.0:
                    return False
        return 0 < n_active <= cfg.speculative_max_batch

    def _propose(self, slot_idx: int, req: Request) -> list[int]:
        """Prompt-lookup proposal: find the last earlier occurrence of
        the sequence's trailing n-gram and propose the tokens that
        followed it (vLLM's ngram speculator recipe).

        The lookup structure is a per-request last-occurrence index
        (spec.NgramIndex), built once from resume_tokens on the slot's
        first proposal and append-updated by ``_emit`` — not a rescan
        of the trailing context every step."""
        k = self.cfg.speculative_min_match
        K = self.cfg.speculative_ngram
        if K <= 0:
            return []
        idx = self._ngram_idx.get(slot_idx)
        if idx is None or idx.k != k:
            idx = NgramIndex(k, req.resume_tokens())
            self._ngram_idx[slot_idx] = idx
        return idx.propose(K)

    def _verify_fn(self, W: int):
        key = ("verify", W)
        fn = self._prefill_fns.get(key)
        if fn is None:
            model = self.model

            @partial(jax.jit, donate_argnums=(1,))
            @phase_scope("verify")
            def verify(params, cache, tokens, true_lens, page_tables,
                       start_pos, adapter_ids, gmask, grows):
                if gmask.shape[0] > 1:
                    # constrained rows: greedy targets are the argmax of
                    # the MASKED logits (matches the plain decode path
                    # bit-exactly); reported logprobs stay on the model
                    # distribution (OpenAI logprob semantics)
                    cache, logits = model.verify_window_logits(
                        params, cache, tokens, true_lens, page_tables,
                        start_pos, adapter_ids=adapter_ids)
                    masked = logits + gmask[grows]
                    targets = jnp.argmax(masked, axis=-1).astype(jnp.int32)
                    lps = jnp.take_along_axis(
                        jax.nn.log_softmax(logits, axis=-1),
                        targets[..., None], axis=-1)[..., 0]
                    return cache, targets, lps
                return model.verify_window(params, cache, tokens,
                                           true_lens, page_tables,
                                           start_pos,
                                           adapter_ids=adapter_ids)

            fn = self._prefill_fns[key] = verify
        return fn

    def _verify_accept_fn(self, W: int):
        """Fused verify + accept for the draft path: ONE program runs
        the [B, W] target forward AND the Leviathan rejection sampler —
        the [B, W, V] logits never leave the device (the greedy n-gram
        path keeps the leaner argmax-only ``_verify_fn``)."""
        key = ("verify_accept", W)
        fn = self._prefill_fns.get(key)
        if fn is None:
            model = self.model

            @partial(jax.jit, donate_argnums=(1,))
            @phase_scope("verify")
            def verify_accept(params, cache, tokens, true_lens,
                              page_tables, start_pos, adapter_ids,
                              draft_logits, prop_len, temperature,
                              onehot_q, keys, gmask, grows):
                cache, logits = model.verify_window_logits(
                    params, cache, tokens, true_lens, page_tables,
                    start_pos, adapter_ids=adapter_ids)
                grammar_rows = gmask[grows] if gmask.shape[0] > 1 else None
                out, n_emit, lps, new_keys = spec_verify_sample(
                    logits, draft_logits, tokens[:, 1:], prop_len,
                    temperature, onehot_q, keys,
                    grammar_rows=grammar_rows)
                return cache, out, n_emit, lps, new_keys

            fn = self._prefill_fns[key] = verify_accept
        return fn

    @property
    def spec_depth(self) -> float:
        """Mean adaptive speculation depth over active slots (0 when
        draft speculation is off, idle, or fully fallen back)."""
        if self.spec_ctl is None:
            return 0.0
        idxs = [i for i, s in enumerate(self.slots)
                if s.request is not None and self.active[i]]
        return self.spec_ctl.mean_depth(idxs)

    def _decode_speculative(self) -> int:
        """One windowed verify dispatch over a COMPACT batch of the
        speculating slots (padded to speculative_max_batch so one
        program serves every step; the [B, W, V] verify logits stay
        bounded by the gate's B, not max_num_seqs).  Every covered slot
        advances by its accepted-proposal prefix plus one bonus token.
        Returns the max tokens any slot emitted (the prefill-cadence
        clock), or 0 when speculation should not run this step (no
        proposals anywhere, or the page pool cannot fund the window
        without preempting) — the caller falls through to the normal
        decode paths."""
        if self.spec_draft is not None:
            return self._decode_speculative_draft()
        W = self.cfg.speculative_ngram + 1
        rows: list[int] = []          # compact row -> slot index
        proposals: list[list[int]] = []
        any_proposal = False
        for i, slot in enumerate(self.slots):
            if slot.request is None or not self.active[i]:
                continue
            p = self._propose(i, slot.request)
            # never speculate past the budget: tokens beyond remaining
            # would be emitted-and-truncated work
            p = p[: max(0, slot.remaining - 1)]
            # constrained slots: clip at the first grammar-invalid token
            p = self._truncate_for_grammar(i, p)
            any_proposal = any_proposal or bool(p)
            rows.append(i)
            proposals.append(p)
        if not rows or not any_proposal:
            return 0      # nothing to verify: the fused path is cheaper
        if not self._lookahead_fits(W):
            # same invariant as the fused path: speculative pages must
            # never preempt a running sequence
            return 0
        self._ensure_decode_pages(W)
        B = self.cfg.speculative_max_batch
        toks = np.zeros((B, W), np.int32)
        tl = np.zeros((B,), np.int32)
        sp = np.zeros((B,), np.int32)
        tables = np.zeros((B, self.pages_per_seq), np.int32)
        aids = np.zeros((B,), np.int32)
        grows = np.zeros((B, W), np.int32)
        for r, (i, p) in enumerate(zip(rows, proposals)):
            window = [int(self.last_tokens[i])] + p
            toks[r, : len(window)] = window
            tl[r] = len(window)
            sp[r] = self.slots[i].position
            tables[r] = self.page_tables[i]
            aids[r] = self.slot_adapters[i]
            grows[r] = self._gram_rows_for(i, p, W)
        gmask, _, _ = self._grammar_args()
        cache, targets, lps = self._verify_fn(W)(
            self.params, self.cache, jnp.asarray(toks),
            jnp.asarray(tl), jnp.asarray(tables), jnp.asarray(sp),
            jnp.asarray(aids), gmask, jnp.asarray(grows))
        self.cache = cache
        # one bulk D2H + tolist per window: acceptance and replay run on
        # Python scalars, not per-token np conversions
        targets = np.asarray(targets).tolist()
        lps = np.asarray(lps).tolist()
        self.counters["decode_steps_total"] += 1
        self.counters["spec_steps_total"] += 1
        max_emitted = 0
        for r, (i, p) in enumerate(zip(rows, proposals)):
            slot = self.slots[i]
            if slot.request is None:
                continue
            trow, lrow = targets[r], lps[r]
            a = 0
            while a < len(p) and p[a] == trow[a]:
                a += 1
            emitted = p[:a] + [trow[a]]
            self.counters["spec_proposed_tokens_total"] += len(p)
            self.counters["spec_accepted_tokens_total"] += a
            want_lp = slot.request.params.logprobs
            for j, t in enumerate(emitted):
                if slot.request is None:
                    break        # retired mid-window (stop/budget/abort)
                self.positions[i] += 1
                slot.position += 1
                self._emit(i, t, logprob=lrow[j] if want_lp else None)
                self.last_tokens[i] = t
            max_emitted = max(max_emitted, len(emitted))
        return max_emitted

    def _decode_speculative_draft(self) -> int:
        """Draft-model speculative step (docs/speculative.md): every
        active slot becomes one row of a single [B, W] verify window.
        Draft-mode rows carry an autoregressive proposal from the
        co-resident draft at the controller's per-slot depth; fallback
        rows carry an n-gram proposal (one-hot q); rows with nothing to
        propose ride along as a plain one-token step (prop_len = 0 —
        the worst case costs exactly one verify step).  Acceptance is
        Leviathan rejection sampling fused into the verify program, so
        sampled slots speculate too and greedy stays bit-exact.

        Returns the max tokens any slot emitted, or 0 to fall through
        to the plain fused decode (all controllers fallen back with no
        n-gram hits — the bottom rung of the fallback ladder)."""
        cfg = self.cfg
        runner = self.spec_draft
        ctl = self.spec_ctl
        W = max(cfg.speculative_draft_k, cfg.speculative_ngram) + 1
        rows = [i for i, slot in enumerate(self.slots)
                if slot.request is not None and self.active[i]]
        if not rows:
            return 0
        B = cfg.speculative_max_batch

        # plan: per-slot draft depth (0 = this round proposes nothing
        # with the draft; the slot's draft KV may still be catching up)
        depths: dict[int, int] = {}
        for i in rows:
            slot = self.slots[i]
            depth = 0
            if ctl.mode(i) == "draft":
                depth = min(ctl.depth(i), max(0, slot.remaining - 1),
                            cfg.speculative_draft_k)
                if depth > 0:
                    pos = slot.position
                    ok = runner.sync(i, pos, slot.request.resume_tokens) \
                        and runner.ensure_pages(i, pos + depth)
                    if not ok:
                        depth = 0     # mid-catch-up: plain step this round
            depths[i] = depth
        k_exec = max([depths[i] for i in rows], default=0)
        if k_exec > 0:
            # pow2 program buckets, clamped to the verify window: with
            # a non-pow2 speculative_draft_k the rounding must not push
            # past W-1 — the verify program carries exactly W-1 draft
            # positions (and every planned depth is <= W-1 already, so
            # the clamp never cuts below a slot's depth)
            k_exec = min(1 << (k_exec - 1).bit_length(), W - 1)
            # the proposal scan writes k_exec draft-KV positions for
            # every drafting row, not depths[i]: reserve pages for the
            # full bucket; a slot that can't is demoted to a plain
            # ride-along step this round
            for i in rows:
                if depths[i] > 0 and not runner.ensure_pages(
                        i, self.slots[i].position + k_exec):
                    depths[i] = 0
            if not any(depths[i] > 0 for i in rows):
                k_exec = 0

        # n-gram fallback proposals (controller-demoted slots)
        proposals: dict[int, list[int]] = {}
        any_prop = k_exec > 0
        for i in rows:
            p: list[int] = []
            if depths[i] == 0 and ctl.mode(i) == "ngram":
                if cfg.speculative_ngram > 0:
                    slot = self.slots[i]
                    p = self._propose(i, slot.request)
                    p = p[: max(0, min(slot.remaining - 1, W - 1))]
                # probation must tick whether or not the n-gram
                # proposer is enabled — it is what re-arms the draft
                ctl.note_fallback_round(i)
            proposals[i] = p
            any_prop = any_prop or bool(p)
        if not any_prop:
            return 0              # plain decode: nothing to verify
        if not self._lookahead_fits(W):
            # the speculative-page invariant: lookahead pages must never
            # preempt a running sequence (draft pages are pool-private
            # and can't either — spec.DraftRunner)
            return 0
        self._ensure_decode_pages(W)

        slot_map = np.full((B,), -1, np.int64)
        toks = np.zeros((B, W), np.int32)
        tl = np.zeros((B,), np.int32)
        sp = np.zeros((B,), np.int32)
        tables = np.zeros((B, self.pages_per_seq), np.int32)
        aids = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        onehot = np.ones((B,), bool)
        draft_rows = np.zeros((B,), bool)
        last = np.zeros((B,), np.int32)
        for r, i in enumerate(rows):
            slot = self.slots[i]
            slot_map[r] = i
            sp[r] = slot.position
            tables[r] = self.page_tables[i]
            aids[r] = self.slot_adapters[i]
            temps[r] = slot.request.params.temperature
            last[r] = int(self.last_tokens[i])
            draft_rows[r] = depths[i] > 0
            # draft rows verify against the draft's real q; n-gram /
            # empty rows are deterministic proposers (one-hot q)
            onehot[r] = depths[i] <= 0

        grammar = None
        if self._gram_table is not None:
            gmask_d, gtrans_d, _ = self._grammar_args()
            grows0 = np.zeros((B,), np.int32)
            for r, i in enumerate(rows):
                gs = self._gram_slots[i]
                if gs is not None:
                    grows0[r] = gs.base + gs.state
            grammar = (gmask_d, gtrans_d, jnp.asarray(grows0))

        if k_exec > 0:
            props, dlogits = runner.propose(
                slot_map, last, sp, temps, draft_rows, k_exec,
                grammar=grammar)
            if k_exec < W - 1:
                dlogits = jnp.pad(
                    dlogits, ((0, 0), (0, W - 1 - k_exec), (0, 0)))
            props = np.asarray(props).tolist()
            for r, i in enumerate(rows):
                if depths[i] > 0:
                    proposals[i] = props[r][:depths[i]]
        else:
            dlogits = jnp.zeros((B, W - 1, self.md.arch.vocab_size),
                                jnp.float32)

        grows = np.zeros((B, W), np.int32)
        prop_len = np.zeros((B,), np.int32)
        for r, i in enumerate(rows):
            # masked drafting already keeps constrained proposals valid;
            # the clip is load-bearing for the n-gram fallback rows (and
            # defensive for the draft rows)
            proposals[i] = self._truncate_for_grammar(i, proposals[i])
            window = [last[r]] + proposals[i]
            toks[r, : len(window)] = window
            tl[r] = len(window)
            prop_len[r] = len(proposals[i])
            grows[r] = self._gram_rows_for(i, proposals[i], W)

        keys = runner.gather_keys(slot_map)
        gmask_v, _, _ = self._grammar_args()
        cache, out, n_emit, lps, new_keys = self._verify_accept_fn(W)(
            self.params, self.cache, jnp.asarray(toks),
            jnp.asarray(tl), jnp.asarray(tables), jnp.asarray(sp),
            jnp.asarray(aids), dlogits, jnp.asarray(prop_len),
            jnp.asarray(temps), jnp.asarray(onehot), keys, gmask_v,
            jnp.asarray(grows))
        self.cache = cache
        runner.scatter_keys(slot_map, new_keys)
        out = np.asarray(out).tolist()
        n_emit = np.asarray(n_emit).tolist()
        lps = np.asarray(lps).tolist()
        self.counters["decode_steps_total"] += 1
        self.counters["spec_steps_total"] += 1
        if k_exec > 0:
            self.counters["spec_draft_steps_total"] += 1

        max_emitted = 0
        for r, i in enumerate(rows):
            slot = self.slots[i]
            if slot.request is None:
                continue
            p = proposals[i]
            e = n_emit[r]
            a = e - 1       # accepted proposal prefix
            if depths[i] > 0:
                self.counters["spec_draft_rows_total"] += 1
                self.counters["spec_draft_proposed_tokens_total"] += len(p)
                self.counters["spec_draft_accepted_tokens_total"] += a
                ctl.observe(i, len(p), a)
            elif p:
                self.counters["spec_proposed_tokens_total"] += len(p)
                self.counters["spec_accepted_tokens_total"] += a
            want_lp = slot.request.params.logprobs
            emitted = out[r][:e]
            lrow = lps[r]
            for j, t in enumerate(emitted):
                if slot.request is None:
                    break        # retired mid-window (stop/budget/abort)
                self.positions[i] += 1
                slot.position += 1
                self._emit(i, t, logprob=lrow[j] if want_lp else None)
                self.last_tokens[i] = t
            if slot.request is not None and depths[i] > 0:
                # the proposal scan wrote draft KV at sp..sp+k_exec-1
                # (valid prefix sp+k_exec).  On a full-depth full-accept
                # round the new position is sp+k_exec+1 — one past what
                # was written — so commit only what exists and let
                # sync() backfill the last accepted token's KV next
                # round.  Every other round commits the new position
                # exactly (rejected-position writes get overwritten
                # before anything can attend to them).
                runner.commit(i, min(slot.position, int(sp[r]) + k_exec))
            max_emitted = max(max_emitted, len(emitted))
        return max_emitted

    def _stop_set(self, req: Request) -> set:
        stop_ids = set(req.params.stop_token_ids)
        eos = self.tokenizer.eos_token_id
        if eos is not None and not req.params.ignore_eos:
            stop_ids.add(eos)
        return stop_ids

    def _emit(self, slot_idx: int, token: int,
              logprob: Optional[float] = None):
        """Deliver one generated token; retire the slot when finished."""
        slot = self.slots[slot_idx]
        req = slot.request
        assert req is not None
        if self.itl_hist is not None:
            # the one stamp site all retire paths share: plain decode,
            # speculative replay and async-dispatch replay each land in
            # _emit per retired token (the PR-13 drain invariants make
            # the replay point the correct client-visible instant)
            now = self._itl_time()
            last = req.last_emit_time
            req.last_emit_time = now
            if last is not None:
                gap = now - last
                self.itl_hist.observe(gap)
                if gap > self._itl_stall_s:
                    self.counters["itl_stalls_total"] += 1
                obs = self.itl_observer
                if obs is not None:
                    obs(gap, req.tenant)
        req.output_tokens.append(token)
        gs = self._gram_slots[slot_idx]
        if gs is not None:
            # host mirror of the device grammar state: the fused/async
            # scans advanced it on-device already, so no dirty-mark —
            # this keeps the mirror exact for the next sync upload,
            # preemption replay, and speculation walks
            gs.advance(token)
            self._gram_state[slot_idx] = gs.base + gs.state
        ngram_idx = self._ngram_idx.get(slot_idx)
        if ngram_idx is not None:
            ngram_idx.append(token)
        if req.params.logprobs:
            req.output_logprobs.append(logprob)
        slot.remaining -= 1
        self._remaining[slot_idx] = slot.remaining
        self.counters["generation_tokens_total"] += 1

        stop_ids = self._stop_set(req)
        finished = token in stop_ids or slot.remaining <= 0 or req.aborted
        if token not in stop_ids:
            req.out.put(token)
        if finished:
            req.finish_reason = "stop" if token in stop_ids else "length"
            req.finish_time = time.monotonic()
            if req.export_kv:
                from kaito_tpu.engine.pd import stage_export

                # engine thread does only the on-device gather; a
                # background copier drains to host chunk-by-chunk so
                # the decode cadence never stalls on a D2H of the
                # whole request (pd.py design note)
                n = len(req.prompt_tokens)
                n_pages = -(-n // self.cfg.page_size)
                # lazy_drain: the D2H copies start on the first HOST
                # consumer (meta/chunk pull); a COLOCATED decode engine
                # grabs the device slabs instead and the transfer never
                # touches the host (the NIXL-device-path analogue)
                with self.tracer.span("kv.export.stage", req.trace_id,
                                      pages=n_pages):
                    exp = stage_export(
                        self.cache, slot.pages[:n_pages], n_tokens=n,
                        model=self.md.name,
                        prompt_tokens=list(req.prompt_tokens),
                        first_token=req.output_tokens[0], lazy_drain=True,
                        trace_id=req.trace_id)
                    if req.adapter:
                        # the decode role only reuses same-adapter KV
                        # (base exports keep the pre-adapter wire meta
                        # byte-for-byte)
                        exp.meta["adapter"] = req.adapter
                    self.kv_exports.put(req.req_id, exp)
            if self.kv_pool is not None:
                # publish BEFORE _evict_slot: the gather needs the
                # slot's page ids while they still belong to this
                # request (the gather copies, so release is safe after)
                try:
                    self._publish_prefix(slot_idx)
                except Exception:
                    # publishing is an optimization; a failure must
                    # never take the finished request down with it
                    logger.exception("KV pool publish failed for %s",
                                     req.req_id)
            self._finish_trace(req)
            req.out.put(None)
            if self.host_kv is not None:
                self.host_kv.discard(req.req_id)
            # a stop id or a spent budget is what the fused scan checks
            # too (same stop matrix, same countdown): it has retired the
            # slot on the device already.  An abort alone it cannot see.
            self._evict_slot(slot_idx, commit=True,
                             device_done=(token in stop_ids
                                          or slot.remaining <= 0))
            self.counters["requests_finished_total"] += 1

    def _publish_prefix(self, slot_idx: int) -> None:
        """Publish a finished request's whole-page prompt-prefix KV
        into the replica-local pool store (docs/kv-pool.md).  Engine
        thread does only the on-device gather (stage_export); the D2H
        drain runs on the staged export's background copier.  Adapter
        requests publish too: their pool_blocks chain is SEEDED with
        the adapter name (kv_pool.prompt_pool_blocks), so their entries
        can only ever match same-adapter requests — and the export meta
        carries the adapter for the fetch-side authority check."""
        from kaito_tpu.engine.kv_pool import PoolEntry, meta_nbytes, pool_key
        from kaito_tpu.engine.pd import stage_export

        slot = self.slots[slot_idx]
        req = slot.request
        if not req.pool_blocks:
            return
        ps = self.cfg.page_size
        # whole pages only, and never more pages than hash blocks: the
        # advert pairs page i with block hash i, so an unhashed tail
        # page would be unreachable anyway
        n_pages = min(len(req.prompt_tokens) // ps, len(req.pool_blocks))
        min_tok = self.cfg.kv_pool_min_tokens or ps
        if n_pages * ps < min_tok:
            return
        blocks = list(req.pool_blocks[:n_pages])
        key = pool_key(blocks)
        if self.kv_pool.has(key):
            return
        with self.tracer.span("kv.pool.publish", req.trace_id,
                              pages=n_pages):
            exp = stage_export(self.cache, slot.pages[:n_pages],
                               n_tokens=n_pages * ps, model=self.md.name,
                               prompt_tokens=req.prompt_tokens[:n_pages * ps],
                               first_token=-1, trace_id=req.trace_id)
        if req.adapter:
            # fetch-side authority: the importer refuses an entry whose
            # adapter disagrees with the request's (base entries keep
            # the pre-adapter wire meta byte-for-byte)
            exp.meta["adapter"] = req.adapter
        self.kv_pool.put(PoolEntry(key=key, blocks=blocks,
                                   n_tokens=n_pages * ps, n_pages=n_pages,
                                   export=exp, nbytes=meta_nbytes(exp.meta)))
        self.counters["kv_pool_published_total"] += 1

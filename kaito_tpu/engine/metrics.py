"""Prometheus text-exposition metrics, dependency-free.

The serving metric surface the rest of the system consumes: the
controller's benchmark probe, the KEDA scaler and the InferencePool EPP
all scrape :5000/metrics, the way they scrape vLLM's gauges in the
reference (SURVEY.md §5 "Metrics/logging"; names kept close to vLLM's
``vllm:*`` series so dashboards translate mechanically to ``kaito:*``).

Also reused by the DP router (per-backend counters, breaker gauges,
upstream latency histograms) and the tuning sidecar — see
docs/observability.md for the full inventory.
"""

from __future__ import annotations

import bisect
import os
import threading
import time
from typing import Iterable, Mapping, Optional


def process_rss_bytes() -> float:
    """Resident set size of this process, dependency-free: /proc on
    Linux, getrusage fallback elsewhere, 0.0 when neither works."""
    try:
        with open("/proc/self/statm") as f:
            return float(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        pass
    try:
        import resource

        return float(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) \
            * 1024.0
    except Exception:
        return 0.0


class Counter:
    def __init__(self, name: str, help_: str, registry: "Optional[Registry]",
                 labels: tuple[str, ...] = ()):
        self.name, self.help = name, help_
        self.label_names = labels
        self._values: dict[tuple, float] = {}
        self._lock = threading.Lock()
        if registry is not None:
            registry.register(self)

    def inc(self, amount: float = 1.0, **labels):
        key = tuple(str(labels.get(l, "")) for l in self.label_names)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        key = tuple(str(labels.get(l, "")) for l in self.label_names)
        with self._lock:
            return self._values.get(key, 0.0)

    def collect(self) -> Iterable[str]:
        with self._lock:
            values = sorted(self._values.items())
        yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} counter"
        if not values:
            # a labelled family with no samples emits nothing: an
            # unlabelled `name 0` here would clash with labelled
            # samples the moment the first one appears
            if not self.label_names:
                yield f"{self.name} 0"
            return
        for key, v in values:
            yield f"{self.name}{_fmt_labels(self.label_names, key)} {_fmt(v)}"


class Gauge:
    """Unlabelled (the original surface: ``.value`` / ``set(v)`` / a
    scalar ``fn``) or labelled like Counter/Histogram.  A labelled
    gauge stores one value per label set via ``set(v, **labels)``; a
    labelled ``fn`` computes the whole family at scrape time and must
    return a mapping of label-value tuples to floats (the router's
    breaker state and the SLO burn rates are time-derived, so they
    can't be stored)."""

    def __init__(self, name: str, help_: str, registry: "Optional[Registry]",
                 fn=None, labels: tuple[str, ...] = ()):
        self.name, self.help = name, help_
        self.fn = fn
        self.label_names = labels
        self.value = 0.0
        self._values: dict[tuple, float] = {}
        self._lock = threading.Lock()
        if registry is not None:
            registry.register(self)

    def set(self, v: float, **labels):
        if self.label_names:
            key = tuple(str(labels.get(l, "")) for l in self.label_names)
            with self._lock:
                self._values[key] = float(v)
        else:
            self.value = float(v)

    def labelled_value(self, **labels) -> float:
        key = tuple(str(labels.get(l, "")) for l in self.label_names)
        with self._lock:
            return self._values.get(key, 0.0)

    def clear(self) -> None:
        """Drop every stored series (per-CR gauges are rebuilt from a
        full listing each resync, so deleted objects must not linger)."""
        with self._lock:
            self._values.clear()

    def collect(self) -> Iterable[str]:
        yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} gauge"
        if self.label_names:
            if self.fn is not None:
                computed = self.fn() or {}
                items = sorted(
                    (tuple(str(x) for x in k), v)
                    for k, v in computed.items())
            else:
                with self._lock:
                    items = sorted(self._values.items())
            for key, v in items:
                yield (f"{self.name}"
                       f"{_fmt_labels(self.label_names, key)} {_fmt(v)}")
            return
        v = self.fn() if self.fn is not None else self.value
        yield f"{self.name} {_fmt(v)}"


class Histogram:
    DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                       0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

    def __init__(self, name: str, help_: str, registry: "Optional[Registry]",
                 buckets: Optional[tuple] = None,
                 labels: tuple[str, ...] = ()):
        self.name, self.help = name, help_
        self.buckets = tuple(buckets or self.DEFAULT_BUCKETS)
        self.label_names = labels
        # aggregate across all label values — `percentile()` and the
        # unlabelled exposition read these
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._total = 0
        # label-values tuple -> [counts, sum, total] (labelled families)
        self._series: dict[tuple, list] = {}
        self._lock = threading.Lock()
        if registry is not None:
            registry.register(self)

    def observe_many(self, values: Iterable[float]) -> None:
        """Unlabelled observations under one acquisition of the lock:
        for a hot path that can hand its values over in bulk (one per
        streamed token, at the end of the stream)."""
        idxs = [(bisect.bisect_left(self.buckets, v), v) for v in values]
        with self._lock:
            for idx, v in idxs:
                self._sum += v
                self._counts[idx] += 1
            self._total += len(idxs)

    def observe(self, v: float, **labels):
        idx = len(self.buckets)
        for i, b in enumerate(self.buckets):
            if v <= b:
                idx = i
                break
        with self._lock:
            self._sum += v
            self._total += 1
            self._counts[idx] += 1
            if self.label_names:
                key = tuple(str(labels.get(l, ""))
                            for l in self.label_names)
                s = self._series.get(key)
                if s is None:
                    s = self._series[key] = [
                        [0] * (len(self.buckets) + 1), 0.0, 0]
                s[0][idx] += 1
                s[1] += v
                s[2] += 1

    def percentile(self, q: float) -> float:
        """Approximate quantile from bucket counts (upper bound),
        aggregated across all label values."""
        with self._lock:
            if not self._total:
                return 0.0
            target = q * self._total
            cum = 0
            for i, b in enumerate(self.buckets):
                cum += self._counts[i]
                if cum >= target:
                    return b
            return float("inf")

    def _emit_series(self, label_names, label_values, counts, sum_,
                     total) -> Iterable[str]:
        cum = 0
        for i, b in enumerate(self.buckets):
            cum += counts[i]
            lbl = _fmt_labels(label_names + ("le",),
                              label_values + (_fmt(b),))
            yield f"{self.name}_bucket{lbl} {cum}"
        cum += counts[-1]
        lbl = _fmt_labels(label_names + ("le",), label_values + ("+Inf",))
        yield f"{self.name}_bucket{lbl} {cum}"
        lbl = _fmt_labels(label_names, label_values)
        yield f"{self.name}_sum{lbl} {_fmt(sum_)}"
        yield f"{self.name}_count{lbl} {total}"

    def collect(self) -> Iterable[str]:
        # snapshot under the lock, format outside it: a concurrent
        # observe() must never see buckets inconsistent with _count/_sum
        with self._lock:
            if self.label_names:
                series = [(k, list(s[0]), s[1], s[2])
                          for k, s in sorted(self._series.items())]
            else:
                counts, sum_, total = list(self._counts), self._sum, self._total
        yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} histogram"
        if self.label_names:
            for key, c, s, t in series:
                yield from self._emit_series(self.label_names, key, c, s, t)
        else:
            yield from self._emit_series((), (), counts, sum_, total)


def _fmt(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _escape_label_value(v) -> str:
    # exposition format: backslash first, then quote and newline
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(names, values) -> str:
    if not names:
        return ""
    inner = ",".join(f'{n}="{_escape_label_value(v)}"'
                     for n, v in zip(names, values))
    return "{" + inner + "}"


class Registry:
    def __init__(self):
        self._metrics = []

    def register(self, m):
        """Accepts any object with a ``collect() -> Iterable[str]``
        method — custom collectors (e.g. the router's breaker-state
        gauges, computed at scrape time) register alongside metrics."""
        self._metrics.append(m)

    def expose(self) -> str:
        lines: list[str] = []
        for m in self._metrics:
            lines.extend(m.collect())
        return "\n".join(lines) + "\n"


class _GrammarCollector:
    """Gated ``kaito:grammar_*`` family (docs/structured-output.md).

    Emits nothing until the grammar cache has served a constrained
    request (``GrammarCache.touched``), so a deployment that never
    sends ``response_format``/``tools`` keeps a byte-identical
    exposition — the same discipline as the KV-pool and adapter
    families, but gated at scrape time because the first constrained
    request can arrive long after metric registration."""

    def __init__(self, engine):
        self.engine = engine

    def collect(self) -> Iterable[str]:
        cache = getattr(self.engine, "grammar_cache", None)
        if cache is None or not cache.touched:
            return
        name = "kaito:grammar_compile_seconds"
        yield (f"# HELP {name} Schema/regex -> token-mask grammar "
               f"compile latency")
        yield f"# TYPE {name} histogram"
        counts = list(cache.compile_bucket_counts)
        cum = 0
        for i, edge in enumerate(cache.compile_buckets):
            cum += counts[i]
            yield f'{name}_bucket{{le="{_fmt(edge)}"}} {cum}'
        cum += counts[-1]
        yield f'{name}_bucket{{le="+Inf"}} {cum}'
        yield f"{name}_sum {_fmt(cache.compile_sum_seconds)}"
        yield f"{name}_count {cache.compile_count}"
        stats = cache.stats()
        for key, help_ in (
                ("grammar_cache_hits_total",
                 "Constrained requests served a precompiled grammar"),
                ("grammar_cache_misses_total",
                 "Constrained requests that compiled a new grammar"),
                ("grammar_cache_evictions_total",
                 "Grammars LRU-evicted from the compile cache"),
                ("grammar_requests_total",
                 "Requests admitted with a decoding grammar attached"),
                ("grammar_cache_entries",
                 "Grammars resident in the compile cache")):
            mname = f"kaito:{key}"
            yield f"# HELP {mname} {help_}"
            yield f"# TYPE {mname} gauge"
            yield f"{mname} {_fmt(stats.get(key, 0))}"


class EngineMetrics:
    """The engine's metric family (names mirror vLLM's so the KEDA
    scaler/EPP configs translate 1:1)."""

    def __init__(self, engine=None, qos=None):
        self.registry = Registry()
        r = self.registry
        # per-tenant slices exist ONLY with a QoS config: collect()
        # emits HELP/TYPE lines even for an empty family, and the
        # QoS-off exposition must stay byte-identical (docs/qos.md)
        self.tenant_shed = None
        self.tenant_served = None
        if qos is not None:
            self.tenant_shed = Counter(
                "kaito:requests_shed_total",
                "Requests shed by admission control, per tenant", r,
                labels=("tenant",))
            self.tenant_served = Counter(
                "kaito:requests_served_total",
                "Requests completed, per tenant", r, labels=("tenant",))
        self.prompt_tokens = Counter(
            "kaito:prompt_tokens_total", "Prefill tokens processed", r)
        self.generation_tokens = Counter(
            "kaito:generation_tokens_total", "Tokens generated", r)
        self.request_success = Counter(
            "kaito:request_success_total", "Requests finished", r,
            labels=("finished_reason",))
        self.requests_rejected = Counter(
            "kaito:request_rejected_total", "Requests rejected (rate limit)", r)
        self.requests_shed = Counter(
            "kaito:request_shed_total",
            "Requests shed by admission control (429 + Retry-After)", r,
            labels=("reason",))
        self.ttft = Histogram(
            "kaito:time_to_first_token_seconds", "Time to first token", r)
        self.tpot = Histogram(
            "kaito:time_per_output_token_seconds",
            "Per-request MEAN time per output token "
            "((finish - first_token) / (n_out - 1)); decode stalls "
            "average out — see kaito:inter_token_latency_seconds (--itl) "
            "for true per-token gaps", r,
            buckets=(0.002, 0.005, 0.01, 0.02, 0.04, 0.06, 0.08, 0.1, 0.25,
                     0.5, 1.0))
        self.e2e_latency = Histogram(
            "kaito:e2e_request_latency_seconds", "End-to-end request latency", r)
        # one observation per streamed token (server._stream_chunk)
        self.stream_chunk = Histogram(
            "kaito:http_stream_chunk_seconds",
            "Handler-thread time per streamed token: detokenize, "
            "serialise, write", r,
            buckets=(0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025,
                     0.005, 0.01, 0.025, 0.05, 0.1, 0.25))
        # what those tokens cost the interpreter: thread CPU seconds of
        # the streaming handlers, added once a stream at the moment its
        # chunk seconds are (server._chunk_times); over
        # http_stream_chunk_seconds_count, the CPU a token costs
        self.stream_cpu = Counter(
            "kaito:http_stream_cpu_seconds_total",
            "Thread CPU seconds of streaming handlers between the "
            "start of a stream's token loop and its end", r)
        # process-level gauges: fleet rollups use uptime to tell a
        # restarted replica (counters reset, uptime tiny) from a quiet
        # one, and RSS to spot a leaking replica before the OOM-killer
        self._started_monotonic = time.monotonic()
        Gauge("kaito:process_uptime_seconds",
              "Seconds since this serving process started", r,
              fn=lambda: time.monotonic() - self._started_monotonic)
        Gauge("kaito:process_resident_memory_bytes",
              "Resident set size of the serving process", r,
              fn=process_rss_bytes)
        if engine is not None:
            # the engine owns its step/queue-wait histograms (observed
            # from the scheduler thread); expose them through this
            # registry rather than duplicating series
            for attr in ("step_hist", "queue_wait_hist", "prefill_pack_hist",
                         "prefill_wait_hist", "first_token_resolve_hist"):
                h = getattr(engine, attr, None)
                if h is not None:
                    r.register(h)
            for h in getattr(engine, "phase_hists", {}).values():
                r.register(h)

            # true per-token ITL (--itl): itl_hist is None when the
            # feature is off, so neither family exists and the
            # exposition stays byte-identical
            if getattr(engine, "itl_hist", None) is not None:
                r.register(engine.itl_hist)
                Gauge("kaito:itl_stalls_total",
                      "Inter-token gaps exceeding the ITL SLO target "
                      "(--slo-itl-p99-ms)", r,
                      fn=lambda: engine.counters.get("itl_stalls_total", 0))

            def _slots_total():
                slots = getattr(engine, "slots", None)
                if slots is not None:
                    return len(slots)
                return engine.cfg.max_num_seqs * max(
                    1, engine.cfg.data_parallel)

            def _occupancy():
                return engine.num_running / max(1, _slots_total())

            Gauge("kaito:batch_occupancy",
                  "Active decode slots / max batch size", r, fn=_occupancy)
            # absolute slot gauges next to the ratio: fleet rollups sum
            # these across replicas (a ratio can't be summed)
            Gauge("kaito:active_slots", "Decode slots occupied right now",
                  r, fn=lambda: engine.num_running)
            Gauge("kaito:slots_total", "Decode slot capacity", r,
                  fn=_slots_total)
            Gauge("kaito:num_requests_running", "Active decode slots", r,
                  fn=lambda: engine.num_running)
            Gauge("kaito:num_requests_waiting", "Queued requests", r,
                  fn=lambda: engine.num_waiting)
            Gauge("kaito:kv_cache_usage_perc", "KV page pool usage", r,
                  fn=lambda: 1.0 - engine.allocator.available /
                  max(engine.allocator.num_pages - 1, 1))
            Gauge("kaito:kv_pages_total", "Total KV pages", r,
                  fn=lambda: engine.allocator.num_pages - 1)
            # page size gauge: the benchmark probe derives concurrency
            # from KV capacity and must not hardcode the page size
            Gauge("kaito:kv_page_size", "Tokens per KV page", r,
                  fn=lambda: engine.cfg.page_size)
            Gauge("kaito:num_preemptions_total", "Sequences preempted", r,
                  fn=lambda: engine.counters["preemptions_total"])
            if hasattr(engine, "compile_totals"):
                # process-wide and monotone: a step that compiled
                # carries its share on its timeline record, and the log
                # names it once the warm-up is over
                Gauge("kaito:engine_compiles_total",
                      "Programs compiled (or fetched from the compile "
                      "cache) since the process started", r,
                      fn=lambda: engine.compile_totals()[0])
                Gauge("kaito:engine_compile_seconds_total",
                      "Seconds those compiles took", r,
                      fn=lambda: engine.compile_totals()[1])
            Gauge("kaito:engine_decode_rows_total",
                  "Slot-steps the decode programs ran (slots x steps of "
                  "every step and window replayed)", r,
                  fn=lambda: engine.counters.get("decode_rows_total", 0))
            Gauge("kaito:engine_decode_rows_idle_total",
                  "Slot-steps of them whose slot was not decoding: the "
                  "attention kernel copies no KV page for such a row", r,
                  fn=lambda: engine.counters.get(
                      "decode_rows_idle_total", 0))
            Gauge("kaito:engine_prefill_turns_multi_total",
                  "Prefill turns of the serial scheduler that ran two or "
                  "more whole staged prompts", r,
                  fn=lambda: engine.counters.get(
                      "prefill_turns_multi_total", 0))
            Gauge("kaito:engine_prefill_turns_single_total",
                  "Prefill turns of the serial scheduler that ran one "
                  "prompt or one chunk", r,
                  fn=lambda: engine.counters.get(
                      "prefill_turns_single_total", 0))
            Gauge("kaito:engine_prefill_tokens_total",
                  "Prompt tokens the prefill programs were given (a "
                  "chunk's own length)", r,
                  fn=lambda: engine.counters.get("prefill_tokens_total", 0))
            Gauge("kaito:engine_prefill_rows_total",
                  "Rows those programs ran: a chunk runs in the smallest "
                  "of prefill_buckets that holds it, padding included", r,
                  fn=lambda: engine.counters.get("prefill_rows_total", 0))
            if getattr(getattr(engine, "model", None), "has_conv", False):
                # rows of conv state (docs/kv-cache.md)
                Gauge("kaito:engine_conv_state_pool_bytes",
                      "Bytes of the short-convolution layers' state pool "
                      "(the last inputs of every slot and conv layer)",
                      r, fn=lambda: engine.cache.state_pool_bytes)
            if getattr(getattr(engine, "model", None), "has_state", False):
                # the second kind of state in the cache (docs/kv-cache.md)
                Gauge("kaito:engine_state_pool_bytes",
                      "Bytes of the per-slot recurrent-state pool (mixer "
                      "state and convolution tail of every slot and layer)",
                      r, fn=lambda: engine.cache.state_pool_bytes)
                Gauge("kaito:engine_state_rows_in_use",
                      "Rows of the recurrent-state pool that hold a "
                      "sequence's state", r,
                      fn=lambda: engine.state_rows_in_use)
                Gauge("kaito:engine_state_resets_total",
                      "Rows of the state pool reset at an admission (the "
                      "first prefill chunk starts from zeros)", r,
                      fn=lambda: engine.counters["state_resets_total"])
                Gauge("kaito:engine_state_recomputes_total",
                      "Resumes after preemption that rebuilt a recurrent "
                      "state by recompute", r,
                      fn=lambda: engine.counters["state_recomputes_total"])
            if getattr(engine, "two_kinds", False):
                # two kinds of page in the cache (docs/kv-cache.md)
                Gauge("kaito:engine_window_pages_in_use",
                      "Pages of the window layers' pool that sequences "
                      "hold (a sequence holds window/page_size + 2 at "
                      "most while it decodes)", r,
                      fn=lambda: engine.window_pages_in_use)
                Gauge("kaito:engine_window_pages_freed_total",
                      "Window pages returned to their pool because every "
                      "position in them had fallen a window behind", r,
                      fn=lambda: engine.counters["window_pages_freed_total"])
                Gauge("kaito:engine_window_pool_bytes",
                      "Bytes of the window layers' page pool", r,
                      fn=lambda: engine.cache.window_pool_bytes)
                Gauge("kaito:engine_sequences_live",
                      "Slots that hold a request (prefilling or "
                      "decoding): what the window pages are held by", r,
                      fn=lambda: sum(1 for s in engine.slots
                                     if s.request is not None))
            if getattr(engine, "latent_bytes_per_token", 0):
                # a latent-attention model's pool (docs/kv-cache.md)
                Gauge("kaito:engine_latent_pool_bytes",
                      "Bytes of the latent page pool, as stored", r,
                      fn=lambda: engine.latent_pool_bytes)
                Gauge("kaito:engine_latent_bytes_per_token",
                      "Bytes a cached token holds in the latent pool "
                      "across all layers, stored lanes included", r,
                      fn=lambda: engine.latent_bytes_per_token)
            if getattr(getattr(engine, "cache", None), "moe_stats",
                       None) is not None:
                # a shared expert layer's counters over the decode
                # steps, counted on the device (docs/observability.md)
                for key, text in (
                        ("moe_expert_calls_total",
                         "Held experts x expert layers x decode steps: "
                         "one call of the expert kernel's group each"),
                        ("moe_experts_touched_total",
                         "Of those calls, the ones whose expert got a "
                         "routed pair (the others read nothing)"),
                        ("moe_pairs_held_total",
                         "(token, expert) pairs of decoding rows whose "
                         "expert is held here"),
                        ("moe_pairs_routed_total",
                         "(token, expert) pairs the router chose for "
                         "decoding rows, held here or not")):
                    Gauge(f"kaito:engine_{key}", text, r,
                          fn=lambda key=key: engine.counters[key])
            Gauge("kaito:prefix_cached_tokens_total",
                  "Prompt tokens served from the prefix cache", r,
                  fn=lambda: engine.counters["prefix_cached_tokens_total"])
            # per-request hit/miss split: the EPP and the e2e routing
            # suite judge affinity quality from these (docs/routing.md)
            Gauge("kaito:prefix_cache_hits_total",
                  "Requests admitted with a nonzero cached prefix", r,
                  fn=lambda: engine.counters.get(
                      "prefix_cache_hits_total", 0))
            Gauge("kaito:prefix_cache_misses_total",
                  "Cache-eligible requests admitted with no cached prefix",
                  r, fn=lambda: engine.counters.get(
                      "prefix_cache_misses_total", 0))
            Gauge("kaito:host_kv_spilled_pages_total",
                  "KV pages spilled to the host offload tier", r,
                  fn=lambda: engine.counters["host_kv_spilled_pages_total"])
            Gauge("kaito:host_kv_restored_pages_total",
                  "KV pages restored from the host offload tier", r,
                  fn=lambda: engine.counters["host_kv_restored_pages_total"])
            Gauge("kaito:host_kv_bytes_used",
                  "Bytes held by the host KV offload tier", r,
                  fn=lambda: engine.host_kv.used_bytes
                  if engine.host_kv else 0)
            # host-tier effectiveness split (folded into fleet
            # aggregates by runtime/fleet.py): entries + hit/miss lets
            # a rollup compute a cluster-wide host-tier hit rate, and
            # evictions tells capacity pressure from churn
            Gauge("kaito:host_kv_entries",
                  "Sequences parked in the host KV offload tier", r,
                  fn=lambda: len(engine.host_kv) if engine.host_kv else 0)
            Gauge("kaito:host_kv_hits_total",
                  "Host KV offload pops that found the sequence", r,
                  fn=lambda: engine.host_kv.hits if engine.host_kv else 0)
            Gauge("kaito:host_kv_misses_total",
                  "Host KV offload pops that came up empty", r,
                  fn=lambda: engine.host_kv.misses if engine.host_kv else 0)
            Gauge("kaito:host_kv_evictions_total",
                  "Entries LRU-evicted from the host KV offload tier", r,
                  fn=lambda: engine.host_kv.evicted_entries
                  if engine.host_kv else 0)
            if getattr(engine, "kv_pool", None) is not None:
                # cluster KV pool (docs/kv-pool.md): families exist
                # ONLY with the pool enabled — collect() emits
                # HELP/TYPE even for zero-valued series, and the
                # pool-off exposition must stay byte-identical
                pool = engine.kv_pool
                Gauge("kaito:kv_pool_entries",
                      "Prefix entries in the cluster KV pool store", r,
                      fn=lambda: len(pool))
                Gauge("kaito:kv_pool_bytes_used",
                      "Host bytes held by the cluster KV pool store", r,
                      fn=lambda: pool.used_bytes)
                Gauge("kaito:kv_pool_published_total",
                      "Prefix entries published to the pool store", r,
                      fn=lambda: pool.published_total)
                Gauge("kaito:kv_pool_evictions_total",
                      "Prefix entries LRU-evicted from the pool store", r,
                      fn=lambda: pool.evictions_total)
                Gauge("kaito:kv_pool_hits_total",
                      "Pool fetch handshakes served from the store", r,
                      fn=lambda: pool.hits_total)
                Gauge("kaito:kv_pool_misses_total",
                      "Pool fetch handshakes that missed (evicted)", r,
                      fn=lambda: pool.misses_total)
                Gauge("kaito:kv_pool_fetches_total",
                      "Cross-replica prefix fetches imported", r,
                      fn=lambda: engine.counters.get(
                          "kv_pool_fetches_total", 0))
                Gauge("kaito:kv_pool_fetched_tokens_total",
                      "Prompt tokens imported via cross-replica fetch", r,
                      fn=lambda: engine.counters.get(
                          "kv_pool_fetched_tokens_total", 0))
                Gauge("kaito:kv_pool_fetch_failures_total",
                      "Prefix fetches that fell back to local recompute",
                      r, fn=lambda: engine.counters.get(
                          "kv_pool_fetch_failures_total", 0))
            if getattr(engine, "kv_tier", None) is not None:
                # tier-3 SSD spill (docs/kv-pool.md "Tier 3: SSD"):
                # families exist ONLY with the disk tier enabled —
                # same byte-identical-off discipline as the pool
                tier = engine.kv_tier
                Gauge("kaito:kv_tier_hits_total",
                      "Local tiered-probe hits by serving tier", r,
                      labels=("tier",),
                      fn=lambda: {
                          ("host",): float(engine.counters.get(
                              "kv_tier_host_hits_total", 0)),
                          ("disk",): float(engine.counters.get(
                              "kv_tier_disk_hits_total", 0))})
                Gauge("kaito:kv_tier_entries",
                      "Prefix entries resident in the SSD tier", r,
                      fn=lambda: len(tier))
                Gauge("kaito:kv_tier_bytes_used",
                      "SSD bytes held by the disk tier (slabs + meta)",
                      r, fn=lambda: tier.used_bytes)
                Gauge("kaito:kv_tier_spills_total",
                      "Host-LRU victims persisted to the SSD tier", r,
                      fn=lambda: tier.spills_total)
                Gauge("kaito:kv_tier_evictions_total",
                      "Entries pruned from the SSD tier by its byte "
                      "budget", r, fn=lambda: tier.evictions_total)
                Gauge("kaito:kv_tier_errors_total",
                      "Corrupt slabs, failed writes, truncated reads "
                      "in the SSD tier", r,
                      fn=lambda: tier.errors_total)
                Gauge("kaito:kv_tier_import_tokens_total",
                      "Prompt tokens imported from the local host/SSD "
                      "tiers instead of recomputed", r,
                      fn=lambda: engine.counters.get(
                          "kv_tier_import_tokens_total", 0))
                Gauge("kaito:kv_tier_spill_drops_total",
                      "Evicted entries dropped because the spill queue "
                      "was full", r,
                      fn=lambda: engine.counters.get(
                          "kv_tier_spill_drops_total", 0))
                Gauge("kaito:kv_tier_disk_read_bytes_per_s",
                      "Measured EWMA SSD read bandwidth feeding the "
                      "break-even veto (0 before the first sample)", r,
                      fn=lambda: (engine.pd_costs.snapshot().get(
                          "disk_bytes_s") or 0.0))
            if getattr(engine, "async_dispatch", False):
                # two-deep decode dispatch (docs/decode-loop.md): the
                # families exist wherever that loop runs, and only
                # there — the dispatch-gap histogram above is gated the
                # same way (engine attr is None under the synchronous
                # loop, whose exposition they leave byte-identical)
                Gauge("kaito:engine_h2d_uploads_total",
                      "Loop-state arrays uploaded host-to-device at "
                      "decode dispatch (~zero per dispatch in steady "
                      "state)", r,
                      fn=lambda: engine.counters.get(
                          "h2d_uploads_total", 0))
                Gauge("kaito:engine_decode_windows_primed_total",
                      "Decode windows launched while another was in "
                      "flight (its host work overlaps this one)", r,
                      fn=lambda: engine.counters.get(
                          "decode_windows_primed_total", 0))
                Gauge("kaito:engine_decode_windows_unprimed_total",
                      "Decode windows launched with none in flight "
                      "(after a drain, or single-step and speculative "
                      "dispatches)", r,
                      fn=lambda: engine.counters.get(
                          "decode_windows_unprimed_total", 0))
                Gauge("kaito:engine_decode_drains_total",
                      "Windows in flight retired with nothing launched "
                      "behind them, by what forced it", r,
                      labels=("reason",),
                      fn=lambda: {(k,): float(v) for k, v
                                  in dict(engine.drain_counts).items()})
                Gauge("kaito:engine_first_tokens_deferred_total",
                      "First tokens sampled and joined to the decode "
                      "carry on the device, read back where the loop "
                      "next waited", r,
                      fn=lambda: engine.counters.get(
                          "first_tokens_deferred_total", 0))
                Gauge("kaito:engine_first_tokens_blocking_total",
                      "First tokens the host read back at once, "
                      "behind the window in flight and the prefill", r,
                      fn=lambda: sum(dict(
                          engine.first_token_blocking).values()))
                Gauge("kaito:engine_first_tokens_blocking_by_reason_total",
                      "First tokens read back at once, by what the "
                      "host had to see the token for", r,
                      labels=("reason",),
                      fn=lambda: {(k,): float(v) for k, v in dict(
                          engine.first_token_blocking).items()})
            if getattr(engine, "devprof", None) is not None:
                # sampled device-time attribution (engine/devprof.py):
                # families exist ONLY with --devprof-interval-s > 0 —
                # same byte-identical-off discipline as the KV pool.
                # Gauges read the LAST sampled window (0.0 before the
                # first capture lands, so the schema is stable from
                # scrape one).
                dp = engine.devprof
                r.register(dp.capture_hist)
                Gauge("kaito:device_bucket_pct",
                      "Share of device wall in each op class for the "
                      "last sampled window (buckets + idle sum to 100)",
                      r, labels=("bucket",), fn=dp.bucket_pct)
                Gauge("kaito:device_phase_pct",
                      "Share of device wall attributed to each "
                      "named-scope engine phase (kaito/<phase>)", r,
                      labels=("phase",), fn=dp.phase_pct)
                Gauge("kaito:device_comm_pct",
                      "Collective share of device wall, last window", r,
                      fn=dp.comm_pct)
                Gauge("kaito:device_comm_compute_overlap_pct",
                      "Share of collective time co-scheduled with "
                      "compute on another unit (hidden, not serialized)",
                      r, fn=dp.overlap_pct)
                Gauge("kaito:device_copy_overlap_pct",
                      "Share of copy/DMA time overlapped with other "
                      "work", r, fn=dp.copy_overlap_pct)
                Gauge("kaito:device_idle_pct",
                      "Idle share of device wall, last window", r,
                      fn=dp.idle_pct)
                Gauge("kaito:device_phase_attributed_pct",
                      "Share of busy device time carrying a kaito/* "
                      "phase marker", r,
                      fn=lambda: dp._lastval("phase_attributed_pct"))
                Gauge("kaito:device_matmul_pct_of_peak_flops",
                      "Window decode throughput vs chip peak FLOPs "
                      "(windowed mfu_pct)", r,
                      fn=lambda: dp._lastval("matmul_pct_of_peak_flops"))
                Gauge("kaito:device_hbm_pct_of_peak",
                      "Window weight-stream bandwidth vs chip peak HBM",
                      r, fn=lambda: dp._lastval("hbm_pct_of_peak"))
                Gauge("kaito:device_windows_total",
                      "Devprof windows captured and parsed", r,
                      fn=lambda: dp.windows_total)
                Gauge("kaito:device_windows_skipped_total",
                      "Devprof windows skipped (manual profile active "
                      "or backend refused)", r,
                      fn=lambda: dp.windows_skipped)
                Gauge("kaito:device_window_errors_total",
                      "Devprof windows whose dump failed to parse", r,
                      fn=lambda: dp.parse_errors)
            if getattr(engine, "adapter_cache", None) is not None:
                # dynamic multi-LoRA cache (docs/multi-lora.md):
                # families exist ONLY with the cache enabled — same
                # byte-identical-off discipline as the KV pool above
                a_cache = engine.adapter_cache
                Gauge("kaito:adapter_resident",
                      "Adapters resident in the HBM slot table", r,
                      fn=lambda: len(a_cache))
                Gauge("kaito:adapter_slots_total",
                      "HBM adapter slot capacity", r,
                      fn=lambda: a_cache.slots)
                Gauge("kaito:adapter_loads_total",
                      "Adapter installs into an HBM slot (boot, "
                      "hot-load, fault-back-in)", r,
                      fn=lambda: a_cache.loads_total)
                Gauge("kaito:adapter_evictions_total",
                      "Adapters evicted or deleted from the slot table",
                      r, fn=lambda: a_cache.evictions_total)
                Gauge("kaito:adapter_hits_total",
                      "Submissions that found their adapter resident", r,
                      fn=lambda: a_cache.hits_total)
                Gauge("kaito:adapter_faults_total",
                      "Submissions that faulted their adapter back in "
                      "from the host tier", r,
                      fn=lambda: a_cache.faults_total)
                Gauge("kaito:adapter_host_entries",
                      "Adapters parked in the host-RAM overflow tier", r,
                      fn=lambda: len(a_cache.host)
                      if a_cache.host is not None else 0)
                Gauge("kaito:adapter_host_bytes_used",
                      "Bytes held by the host-RAM adapter tier", r,
                      fn=lambda: a_cache.host.used_bytes
                      if a_cache.host is not None else 0)
            failures = getattr(engine, "adapter_load_failures", None)
            if getattr(engine, "adapter_cache", None) is not None \
                    or failures:
                # refusal counter, labelled by reason (base_mismatch,
                # rank_overflow, unreadable, no_targets, capacity).
                # Present with the cache on, or on the static boot path
                # once a refusal was actually counted — a no-adapter
                # exposition stays byte-identical
                Gauge("kaito:adapter_load_failures_total",
                      "Adapter loads refused, by reason", r,
                      labels=("reason",),
                      fn=lambda: {(k,): float(v)
                                  for k, v in (failures or {}).items()})
            Gauge("kaito:pd_device_handoffs_total",
                  "Colocated device-to-device KV hand-offs", r,
                  fn=lambda: engine.counters.get(
                      "pd_device_handoffs_total", 0))
            # failure-domain isolation counters (docs/failure-domains.md)
            Gauge("kaito:requests_failed_total",
                  "Requests that died request-scoped (structured error)", r,
                  fn=lambda: engine.counters.get("requests_failed_total", 0))
            Gauge("kaito:requests_expired_total",
                  "Requests aborted at their deadline (408)", r,
                  fn=lambda: engine.counters.get("requests_expired_total", 0))
            Gauge("kaito:kv_import_retries_total",
                  "Transient KV-transfer failures retried as local recompute",
                  r, fn=lambda: engine.counters.get(
                      "kv_import_retries_total", 0))
            Gauge("kaito:engine_fatal_total",
                  "Engine-fatal failures (every in-flight request failed)", r,
                  fn=lambda: engine.counters.get("engine_fatal_total", 0))
            # speculative decoding (docs/speculative.md): proposer-mode
            # label splits the n-gram and draft-model paths so accept
            # rate per mode is a direct PromQL ratio; kaito:spec_depth
            # is the controller's mean adaptive depth across active
            # slots (0 while in n-gram fallback / speculation off)
            Gauge("kaito:spec_proposed_tokens_total",
                  "Speculative tokens proposed", r, labels=("mode",),
                  fn=lambda: {
                      ("ngram",): engine.counters.get(
                          "spec_proposed_tokens_total", 0),
                      ("draft",): engine.counters.get(
                          "spec_draft_proposed_tokens_total", 0)})
            Gauge("kaito:spec_accepted_tokens_total",
                  "Speculative tokens accepted by the target", r,
                  labels=("mode",),
                  fn=lambda: {
                      ("ngram",): engine.counters.get(
                          "spec_accepted_tokens_total", 0),
                      ("draft",): engine.counters.get(
                          "spec_draft_accepted_tokens_total", 0)})
            Gauge("kaito:spec_depth",
                  "Mean adaptive speculation depth over active slots", r,
                  fn=lambda: getattr(engine, "spec_depth", 0.0))
            if getattr(engine, "grammar_cache", None) is not None:
                # structured output (docs/structured-output.md): the
                # collector itself gates on first constrained use
                r.register(_GrammarCollector(engine))
            # live-calibrated break-even constants (0 until the first
            # observed transfer / prefill provides a sample)
            Gauge("kaito:pd_measured_net_bytes_s",
                  "EWMA observed KV transfer bandwidth", r,
                  fn=lambda: (getattr(engine, "pd_costs", None)
                              and engine.pd_costs.snapshot()
                              .get("net_bytes_s") or 0))
            Gauge("kaito:pd_measured_prefill_tok_s",
                  "EWMA observed prefill throughput", r,
                  fn=lambda: (getattr(engine, "pd_costs", None)
                              and engine.pd_costs.snapshot()
                              .get("prefill_tok_s") or 0))

    def observe_request(self, req) -> None:
        if req.first_token_time:
            self.ttft.observe(req.first_token_time - req.submit_time)
        if req.finish_time:
            self.e2e_latency.observe(req.finish_time - req.submit_time)
            n_out = len(req.output_tokens)
            if req.first_token_time and n_out > 1:
                self.tpot.observe(
                    (req.finish_time - req.first_token_time) / (n_out - 1))
            self.request_success.inc(finished_reason=req.finish_reason or "stop")
            if self.tenant_served is not None and getattr(req, "tenant", ""):
                self.tenant_served.inc(tenant=req.tenant)
        self.prompt_tokens.inc(len(req.prompt_tokens))
        self.generation_tokens.inc(len(req.output_tokens))

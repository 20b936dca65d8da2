"""OpenAI-compatible HTTP server.

The front door of the in-pod runtime — same contract the reference's
vLLM wrapper exposes on port 5000 (``presets/workspace/inference/vllm/
inference_api.py``): ``/v1/completions``, ``/v1/chat/completions`` (with
SSE streaming), ``/v1/models``, ``/health``, Prometheus ``/metrics``,
KAITO config-file merge, LoRA adapter directory discovery, and
queue-depth 429 rate limiting.  Stdlib HTTP only — the engine thread
does the work; handler threads just stream queues.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import logging
import os
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from kaito_tpu.engine.chat import render_chat
from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, SamplingParams
from kaito_tpu.engine.metrics import EngineMetrics
from kaito_tpu.engine.rate_limit import RateLimiter
from kaito_tpu.runtime.slo import (SLOTargets, SLOWatchdog,
                                   engine_chip_count)
from kaito_tpu.utils.tracing import (chrome_trace, make_request_id,
                                     parse_traceparent, sanitize_request_id,
                                     timeline_trace)

logger = logging.getLogger(__name__)

# one profiler per process (jax.profiler is process-global)
_PROFILE_LOCK = threading.Lock()


def _clock_sync(st) -> None:
    """A zero-length span that carries ``time.monotonic_ns()``: emitted
    right after a trace starts and right before it stops, it places
    whatever was stamped with ``time.monotonic()`` (the span ring, the
    step timeline) on the trace's clock."""
    with st.engine.phases.annotate("clock.sync", mono_ns=time.monotonic_ns()):
        pass


def _profile_auto_stop(st) -> None:
    """Timer target for /start_profile {"seconds": N}: stop the trace
    unless a manual /stop_profile already did."""
    import jax

    with _PROFILE_LOCK:
        if not getattr(st, "_profiling", False):
            return
        try:
            _clock_sync(st)
            jax.profiler.stop_trace()
            logger.info("profiler trace auto-stopped")
        except Exception:
            logger.exception("profiler auto-stop failed")
        finally:
            st._profiling = False
            st._profile_timer = None


from kaito_tpu.engine.adapters import discover_adapters  # noqa: E402




def token_surface_forms(tokenizer, ids, window: int = 8) -> list:
    """Per-token surface strings via bounded-window incremental decode:
    full-prefix decode per token is O(n^2) on the handler thread, and
    per-id decode strips SentencePiece space markers / garbles
    multi-byte codepoints.  A few tokens of left context make byte
    merges decode correctly."""
    out = []
    ids = list(ids)
    for i in range(len(ids)):
        lo = max(0, i - window)
        prev = tokenizer.decode(ids[lo:i]) if i > lo else ""
        cur = tokenizer.decode(ids[lo:i + 1])
        out.append(cur[len(prev):])
    return out


def _device_identity() -> dict:
    """The device as jax reports it — on /health of both the loading
    stub and the live server."""
    import jax

    dev = jax.local_devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count()}


class ServerState:
    def __init__(self, engine: InferenceEngine, cfg: EngineConfig):
        self.engine = engine
        self.cfg = cfg
        # multi-tenant QoS (docs/qos.md): the engine already parsed the
        # config; the limiter, metrics and SLO watchdog share it so the
        # whole degradation ladder attributes pressure per tenant
        self.qos = getattr(engine, "qos", None)
        self.metrics = EngineMetrics(engine, qos=self.qos)
        self.limiter = RateLimiter(cfg.max_queue_len, cfg.disable_rate_limit,
                                   kv_shed_threshold=cfg.kv_shed_threshold,
                                   qos=self.qos)
        # the probe-errors counter is limiter-owned; expose it through
        # the shared registry (same adoption as the engine histograms)
        self.metrics.registry.register(self.limiter.probe_errors)
        self.model_name = cfg.served_model_name or engine.md.name
        self.adapters = discover_adapters(cfg.adapters_dir)
        self.started = time.time()
        # north-star SLO watchdog: config targets, env override on top
        # (KAITO_SLO_* wins so operators can retune without a rollout)
        itl_on = any(getattr(e, "itl_hist", None) is not None
                     for e in self._engines())
        self.slo = SLOWatchdog(
            targets=SLOTargets.from_env(SLOTargets(
                ttft_p50_s=cfg.slo_ttft_p50_ms / 1000.0,
                ttft_p99_s=cfg.slo_ttft_p99_ms / 1000.0,
                itl_p99_s=cfg.slo_itl_p99_ms / 1000.0,
                tokens_per_sec_per_chip=cfg.slo_tokens_per_sec_per_chip,
                availability=cfg.slo_availability)),
            chips=engine_chip_count(engine),
            per_tenant=self.qos is not None,
            itl_enabled=itl_on,
            role=cfg.role
            or os.environ.get("KAITO_INFERENCE_ROLE", ""))
        self.slo.register_metrics(self.metrics.registry)
        # per-token ITL: the engine's retire-path stamp feeds the
        # watchdog's itl windows directly (gap + tenant)
        if itl_on:
            for e in self._engines():
                if getattr(e, "itl_hist", None) is not None:
                    e.itl_observer = self.slo.observe_itl
        # incident flight recorder (utils/flightrec.py): only with
        # --flight-dir — no dir means no recorder, no watcher thread,
        # no kaito:flight_bundles_total family, /debug/flight 403
        self.flight = None
        self.flight_watcher = None
        if cfg.flight_dir:
            from kaito_tpu.engine.metrics import Gauge
            from kaito_tpu.utils.flightrec import (FlightRecorder,
                                                   FlightWatcher,
                                                   engine_flight_snapshot)

            self.flight = FlightRecorder(
                cfg.flight_dir,
                collect=lambda: engine_flight_snapshot(
                    self.engine, slo=self.slo, cfg=self.cfg),
                max_bundles=cfg.flight_max_bundles)

            def _fatal_total() -> int:
                return sum(int(e.counters.get("engine_fatal_total", 0))
                           for e in self._engines())

            self.flight_watcher = FlightWatcher(
                self.flight, slo_snapshot=self.slo.snapshot,
                fatal_count=_fatal_total)
            self.flight_watcher.start()
            Gauge("kaito:flight_bundles_total",
                  "Flight-recorder bundles written since process start",
                  self.metrics.registry,
                  fn=lambda: float(self.flight.bundles_total))
        self._profile_timer: Optional[threading.Timer] = None

    def _engines(self):
        return getattr(self.engine, "engines", None) or [self.engine]


class OpenAIHandler(BaseHTTPRequestHandler):
    state: ServerState  # injected via server factory
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # route through logging, not stderr
        logger.debug("%s " + fmt, self.address_string(), *args)

    # ---------------- helpers ----------------

    def _intake_trace(self):
        """Resolve this request's end-to-end trace id: the client's
        ``X-Request-Id`` wins, then the trace-id of an inbound W3C
        ``traceparent``, else a fresh id.  Every response echoes it
        (docs/observability.md trace-header contract)."""
        hdr = (sanitize_request_id(self.headers.get("X-Request-Id"))
               or parse_traceparent(self.headers.get("traceparent")))
        self._rid_client = hdr is not None
        self._rid = hdr or make_request_id()

    def _json(self, code: int, obj: dict, headers: Optional[dict] = None):
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        rid = getattr(self, "_rid", None)
        if rid:
            self.send_header("X-Request-Id", rid)
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        self.wfile.write(body)

    def _error(self, code: int, message: str,
               etype: str = "invalid_request_error",
               headers: Optional[dict] = None):
        err = {"message": message, "type": etype}
        rid = getattr(self, "_rid", None)
        if rid:
            err["request_id"] = rid
        self._json(code, {"error": err}, headers=headers)

    def _request_error(self, req) -> None:
        """Surface a request's structured engine error (scoped failure
        or deadline abort) as the HTTP response."""
        err = req.error or {"status": 500, "type": "internal_error",
                            "message": "request failed in the engine"}
        self._error(int(err.get("status", 500)),
                    err.get("message", "request failed"),
                    err.get("type", "internal_error"))

    def _read_body(self) -> Optional[dict]:
        try:
            n = int(self.headers.get("Content-Length", "0"))
            raw = self.rfile.read(n) if n else b"{}"
            return json.loads(raw or b"{}")
        except (ValueError, json.JSONDecodeError):
            self._error(400, "invalid JSON body")
            return None

    def _intake_tenant(self, body: dict) -> Optional[tuple[str, str]]:
        """Resolve this request's (tenant id, priority-class name) from
        the ``X-Kaito-Tenant`` / ``X-Kaito-Priority`` headers (body
        ``tenant`` / ``priority`` fields as fallback, docs/qos.md).
        Sends a 400 and returns None on an invalid value.  With QoS
        off, the tenant still rides along for tracing but nothing
        downstream reads it."""
        from kaito_tpu.engine.qos import valid_tenant

        tenant = (self.headers.get("X-Kaito-Tenant")
                  or body.get("tenant") or "").strip()
        priority = (self.headers.get("X-Kaito-Priority")
                    or body.get("priority") or "").strip()
        if tenant and not valid_tenant(tenant):
            self._error(400, "invalid tenant id (label-safe, max 64 chars)")
            return None
        qos = self.state.qos
        if priority and qos is not None and priority not in qos.classes:
            self._error(400, f"unknown priority class {priority!r}")
            return None
        return tenant, priority

    def _sse_start(self):
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Transfer-Encoding", "chunked")
        rid = getattr(self, "_rid", None)
        if rid:
            self.send_header("X-Request-Id", rid)
        self.end_headers()

    def _sse_send(self, obj) -> None:
        data = b"data: " + (obj if isinstance(obj, bytes) else
                            json.dumps(obj).encode()) + b"\n\n"
        self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))

    def _sse_end(self):
        data = b"data: [DONE]\n\n"
        self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
        self.wfile.write(b"0\r\n\r\n")

    # ---------------- routes ----------------

    def do_GET(self):
        st = self.state
        self._intake_trace()
        if self.path == "/health":
            self._json(200, self._health())
        elif self.path == "/metrics":
            body = st.metrics.registry.expose().encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path.startswith("/pd/kv/"):
            rest = self.path[len("/pd/kv/"):]
            if rest.endswith("/meta"):
                self._pd_kv_meta(rest[:-len("/meta")])
            elif "/chunk/" in rest:
                rid, _, idx = rest.partition("/chunk/")
                self._pd_kv_chunk(rid, idx)
            else:
                self._pd_kv(rest)
        elif self.path == "/debug/kv_pool":
            self._kv_pool_advert()
        elif self.path.startswith("/kv_pool/"):
            rest = self.path[len("/kv_pool/"):]
            if rest.endswith("/meta"):
                self._kv_pool_meta(rest[:-len("/meta")])
            elif "/chunk/" in rest:
                key, _, idx = rest.partition("/chunk/")
                self._kv_pool_chunk(key, idx)
            else:
                self._error(404, f"no route {self.path}")
        elif self.path in ("/ui", "/ui/"):
            # single-pod demo: the DemoUI chat page served in-process
            # (the standalone proxy pod lives in kaito_tpu/ui)
            from kaito_tpu.ui import serve_page

            serve_page(self)
        elif self.path == "/v1/models":
            models = [{"id": st.model_name, "object": "model",
                       "owned_by": "kaito-tpu", "root": st.model_name}]
            # with the dynamic cache, the listing reflects RUNTIME
            # residency (hot-loads appear, deletes disappear) instead
            # of the boot-time discovery snapshot
            snap_fn = getattr(st.engine, "adapter_snapshot", None)
            snap = snap_fn() if callable(snap_fn) else None
            if snap is not None:
                names = sorted({e["name"] for e in snap["resident"]}
                               | set(snap["host_tier"]))
            else:
                names = list(st.adapters)
            for name in names:
                models.append({"id": name, "object": "model",
                               "owned_by": "kaito-tpu", "parent": st.model_name})
            self._json(200, {"object": "list", "data": models})
        elif self.path == "/v1/adapters":
            self._adapters_get()
        elif self.path.startswith("/debug/trace"):
            self._debug_trace()
        elif self.path.startswith("/debug/timeline"):
            self._debug_timeline()
        elif self.path.startswith("/debug/slo"):
            self._json(200, st.slo.snapshot())
        elif self.path.startswith("/debug/device"):
            self._debug_device()
        elif self.path.startswith("/debug/flight"):
            self._debug_flight_get()
        else:
            self._error(404, f"no route {self.path}")

    def _sub_engines(self) -> list:
        """Engine groups behind this server: the DP facade exposes its
        groups via `.engines`; a plain engine is its own only group."""
        return list(getattr(self.state.engine, "engines",
                            [self.state.engine]))

    def _health(self) -> dict:
        """What a client that cannot import jax needs to tell WHERE and
        HOW this server runs: the device as jax reports it, the paths
        the engine actually selected, and each local device's memory
        (None fields on a backend that reports none, i.e. the CPU)."""
        import jax

        engines = self._sub_engines()
        body = {
            "status": "ok",
            **_device_identity(),
            "jax_version": jax.__version__,
            "attention": engines[0].attention_path,
            "prefix_cache": ("native" if all(
                e.prefix_cache is not None for e in engines) else "off"),
            "devices": [],
        }
        combine = engines[0].model.moe_combine
        if combine:
            body["moe_combine"] = combine
        if engines[0].moe_tiles:
            body["moe_tiles"] = engines[0].moe_tiles
        if engines[0].latent_weights:
            body["latent_weights"] = engines[0].latent_weights
        arch = engines[0].md.arch
        if arch.conv_layers or arch.gdn_layers:
            # what mixes the layers' tokens, where not attention alone
            other = ({"conv": arch.conv_layers} if arch.conv_layers
                     else {"gated_delta_rule": arch.gdn_layers})
            body["mixers"] = {**other,
                              "full_attention": arch.attention_layers(0)}
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            body["devices"].append({
                "id": d.id,
                **{k: stats.get(k) for k in (
                    "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}})
        # self-measured HBM sizing + estimator drift (first group's:
        # every group runs the same config): the benchmark probe folds
        # this into status.performance
        if engines[0].sizing_report:
            body["hbm_sizing"] = engines[0].sizing_report
        return body

    def _debug_trace(self):
        """Chrome trace-event JSON of recorded spans (Perfetto-loadable),
        merged across engine groups; `?trace_id=` filters to one
        request's span tree.  ``metadata.dropped`` counts ring-overflow
        evictions so span-tree gaps read as overflow, not as missing
        instrumentation."""
        from urllib.parse import parse_qs, urlparse

        q = parse_qs(urlparse(self.path).query)
        tid = q.get("trace_id", [None])[0]
        spans = []
        dropped = 0
        for e in self._sub_engines():
            tr = getattr(e, "tracer", None)
            if tr is not None:
                spans.extend(tr.spans(tid))
                dropped += getattr(tr, "dropped", 0)
        self._json(200, chrome_trace(spans, dropped=dropped))

    def _debug_timeline(self):
        """Chrome trace-event JSON of the engine-step flight recorder,
        merged across engine groups."""
        recs = []
        dropped = 0
        for e in self._sub_engines():
            tl = getattr(e, "timeline", None)
            if tl is not None:
                recs.extend(tl.records())
                dropped += getattr(tl, "dropped", 0)
        self._json(200, timeline_trace(recs, dropped=dropped))

    def _debug_device(self):
        """Last-window device-time attribution from the sampling
        profiler (engine/devprof.py), per engine group.  403 when
        sampling is off — the devprof-off surface must stay
        byte-identical to the pre-devprof server."""
        profs = [(e, getattr(e, "devprof", None))
                 for e in self._sub_engines()]
        profs = [(e, p) for e, p in profs if p is not None]
        if not profs:
            return self._error(
                403, "device profiler disabled (--devprof-interval-s)")
        if len(profs) == 1:
            return self._json(200, profs[0][1].snapshot())
        self._json(200, {"groups": [dict(p.snapshot(), group=gi)
                                    for gi, (_, p) in enumerate(profs)]})

    def _debug_flight_get(self):
        """Incident flight recorder (utils/flightrec.py): list bundles
        at ``/debug/flight``, fetch one at ``/debug/flight/<name>``.
        403 when ``--flight-dir`` is unset — the flight-off surface
        stays byte-identical to the pre-flight server."""
        rec = self.state.flight
        if rec is None:
            return self._error(
                403, "flight recorder disabled (--flight-dir)")
        rest = self.path[len("/debug/flight"):].strip("/")
        if not rest:
            return self._json(200, {"dir": rec.dir,
                                    "bundles_total": rec.bundles_total,
                                    "bundles": rec.list()})
        raw = rec.read(rest)
        if raw is None:
            return self._error(404, f"no bundle {rest!r}")
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(raw)))
        self.end_headers()
        self.wfile.write(raw)

    def _debug_flight_post(self):
        """Manual trigger for live debugging: snapshot now."""
        from kaito_tpu.utils.flightrec import TRIGGER_MANUAL

        rec = self.state.flight
        if rec is None:
            return self._error(
                403, "flight recorder disabled (--flight-dir)")
        name = rec.record(TRIGGER_MANUAL, reason="POST /debug/flight")
        if name is None:
            return self._error(500, "flight bundle write failed")
        self._json(200, {"bundle": name})

    def do_DELETE(self):
        self._intake_trace()
        if self.path.startswith("/pd/kv/"):
            # decode side declined the transfer (below break-even):
            # release the staged export instead of waiting out the TTL
            if not self._pd_enabled():
                return self._error(403, "P/D disaggregation disabled")
            rid = self.path[len("/pd/kv/"):]
            gone = self.state.engine.kv_exports.pop(rid) is not None
            self._json(200 if gone else 404, {"released": gone})
        elif self.path.startswith("/v1/adapters/"):
            self._adapters_delete(self.path[len("/v1/adapters/"):])
        else:
            self._error(404, f"no route {self.path}")

    def do_POST(self):
        self._intake_trace()
        if self.path == "/v1/completions":
            self._completions(chat=False)
        elif self.path == "/v1/chat/completions":
            self._completions(chat=True)
        elif self.path == "/pd/prefill":
            self._pd_prefill()
        elif self.path == "/v1/adapters":
            self._adapters_post()
        elif self.path == "/start_profile":
            self._profile(start=True)
        elif self.path == "/stop_profile":
            self._profile(start=False)
        elif self.path.startswith("/debug/flight"):
            self._debug_flight_post()
        else:
            self._error(404, f"no route {self.path}")

    def _profile(self, start: bool):
        """vLLM-parity profiler toggles (/start_profile, /stop_profile;
        the reference wrapper exposes them when the torch profiler dir
        is set) — TPU-native shape: a jax.profiler trace (XPlane/
        perfetto) written under KAITO_PROFILE_DIR.  The trace holds the
        device's operations and the serving loop's phase spans
        (docs/observability.md); the per-call Python tracer hooks every
        thread and slows the host code a trace is taken to size, so it
        is on only for a body that says ``{"python_tracer": true}``.
        ``_clock_sync`` marks both ends."""
        import jax

        st = self.state
        # the body is optional JSON; always consume it (an unread
        # payload would desync the next request on keep-alive)
        n = int(self.headers.get("Content-Length", "0") or 0)
        raw = self.rfile.read(n) if n else b""
        seconds = 0.0
        python_tracer = False
        if start and raw:
            try:
                opts = json.loads(raw) or {}
                seconds = float(opts.get("seconds", 0))
                python_tracer = opts.get("python_tracer", False)
            except (ValueError, json.JSONDecodeError, AttributeError,
                    TypeError):
                return self._error(400, "invalid JSON body")
            if seconds < 0:
                return self._error(400, "'seconds' must be >= 0")
            if not isinstance(python_tracer, bool):
                return self._error(400, "'python_tracer' must be a boolean")
        prof_dir = os.environ.get("KAITO_PROFILE_DIR", "/tmp/kaito-profile")
        with _PROFILE_LOCK:
            active = getattr(st, "_profiling", False)
            try:
                if start:
                    if active:
                        return self._error(409, "profiler already running")
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = int(python_tracer)
                    jax.profiler.start_trace(prof_dir,
                                             profiler_options=options)
                    _clock_sync(st)
                    st._profiling = True
                    if seconds:
                        # bounded capture: auto-stop after `seconds` so
                        # a fire-and-forget client can't leave the
                        # process-global profiler running forever
                        timer = threading.Timer(
                            seconds, _profile_auto_stop, args=(st,))
                        timer.daemon = True
                        st._profile_timer = timer
                        timer.start()
                    logger.info("profiler trace started -> %s%s", prof_dir,
                                f" (auto-stop in {seconds:g}s)"
                                if seconds else "")
                    body = {"status": "started", "dir": prof_dir}
                    if seconds:
                        body["auto_stop_seconds"] = seconds
                        # armed wall-clock deadline, so a client can
                        # tell a pending auto-stop from an unbounded
                        # capture without re-deriving it
                        body["auto_stop_deadline"] = time.time() + seconds
                    return self._json(200, body)
                if not active:
                    return self._error(409, "profiler not running")
                timer = getattr(st, "_profile_timer", None)
                if timer is not None:
                    timer.cancel()
                    st._profile_timer = None
                _clock_sync(st)
                jax.profiler.stop_trace()
                st._profiling = False
                logger.info("profiler trace stopped")
                return self._json(200, {"status": "stopped",
                                        "dir": prof_dir})
            except Exception as e:
                st._profiling = False
                return self._error(500, f"profiler error: {e}",
                                   "internal_error")

    # ---------------- P/D disaggregation side-channel ----------------

    def _score_prompt(self, body: dict, tokens: list, prompt_text: str,
                      want_lp: bool):
        """completions echo+max_tokens=0: return the prompt with its
        per-token logprobs (lm-eval loglikelihood scoring)."""
        st = self.state
        if not want_lp:
            return self._error(400, "'echo' with max_tokens=0 requires "
                                    "logprobs")
        try:
            lps = st.engine.score_prompt(tokens)
        except ValueError as e:
            return self._error(400, str(e))
        tok_strs = token_surface_forms(st.engine.tokenizer, tokens)
        offsets, pos = [], 0
        for s_ in tok_strs:
            offsets.append(pos)
            pos += len(s_)
        choice = {"index": 0, "text": prompt_text, "finish_reason": "stop",
                  "logprobs": {"tokens": tok_strs, "token_logprobs": lps,
                               "top_logprobs": None,
                               "text_offset": offsets}}
        self._json(200, {
            "id": f"cmpl-{uuid.uuid4().hex[:20]}",
            "object": "text_completion", "created": int(time.time()),
            "model": body.get("model") or st.model_name,
            "choices": [choice],
            "usage": {"prompt_tokens": len(tokens), "completion_tokens": 0,
                      "total_tokens": len(tokens)}})

    def _pd_enabled(self) -> bool:
        return bool(self.state.cfg.pd_enabled)

    def _pd_prefill(self):
        if not self._pd_enabled():
            return self._error(403, "P/D disaggregation disabled on this pod")
        """Prefill-role entry: run the prompt, stage its KV for pull,
        return the first sampled token (reference counterpart: the
        NixlConnector side-channel + llm-d routing sidecar)."""
        st = self.state
        body = self._read_body()
        if body is None:
            return
        prompt = body.get("prompt", "")
        if not isinstance(prompt, str) or not prompt:
            return self._error(400, "'prompt' must be a non-empty string")
        # adapter-aware prefill: the "model" field selects an adapter
        # exactly like /v1/completions; the staged meta records it so
        # the decode role only reuses same-adapter KV
        adapter = ""
        model_field = body.get("model") or ""
        if model_field and model_field not in (st.model_name,
                                               st.engine.md.name):
            a_cache = getattr(st.engine, "adapter_cache", None)
            if model_field in getattr(st.engine, "adapter_index", {}) \
                    or (a_cache is not None and a_cache.has(model_field)):
                adapter = model_field
            else:
                return self._error(404, f"model {model_field!r} not found")
        tokens = st.engine.tokenizer.encode(prompt)
        params = SamplingParams(
            max_tokens=1,
            temperature=float(body.get("temperature", 1.0)),
            top_k=int(body.get("top_k", 0) or 0),
            top_p=float(body.get("top_p", 1.0)),
            seed=int(body.get("seed", 0) or 0),
            ignore_eos=True)
        try:
            req = st.engine.submit(tokens, params,
                                   req_id=f"pd-{uuid.uuid4().hex[:16]}",
                                   export_kv=True, adapter=adapter,
                                   trace_id=self._rid)
        except ValueError as e:
            return self._error(400, str(e))
        toks = list(req.stream())
        if not toks and req.finish_reason in ("error", "deadline"):
            return self._request_error(req)
        self._json(200, {"req_id": req.req_id,
                         "request_id": self._rid,
                         "first_token": req.output_tokens[0],
                         "n_tokens": len(tokens),
                         "prompt_tokens": tokens})

    def _pd_kv(self, req_id: str):
        """Legacy single-blob pull (small transfers / compat); the
        chunked endpoints below are the serving path."""
        if not self._pd_enabled():
            return self._error(403, "P/D disaggregation disabled on this pod")
        from kaito_tpu.engine.pd import pack_transfer

        # pop is the atomic claim (a concurrent duplicate pull gets a
        # clean 404, never a chunk-consumption race); on any failure the
        # export is RE-PUT so the decode side can retry — whole_blob()
        # is idempotent (cached), so the retry returns the same bytes.
        reg = self.state.engine.kv_exports
        exp = reg.pop(req_id)
        if exp is None:
            return self._error(404, f"no staged KV for {req_id}")
        try:
            blob = pack_transfer(exp.meta, exp.whole_blob())
        except Exception as e:
            reg.put(req_id, exp)
            return self._error(500, f"KV export drain failed: {e}")
        try:
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Content-Length", str(len(blob)))
            self.end_headers()
            self.wfile.write(blob)
        except OSError:
            # client vanished mid-body: keep the export (cached blob)
            # for the retry; TTL reclaims it if none comes
            reg.put(req_id, exp)
            raise

    def _pd_kv_meta(self, req_id: str):
        """Chunk-plan handshake: meta (shape/dtype/model/chunk plans)
        without consuming anything."""
        if not self._pd_enabled():
            return self._error(403, "P/D disaggregation disabled on this pod")
        exp = self.state.engine.kv_exports.get(req_id)
        if exp is None:
            return self._error(404, f"no staged KV for {req_id}")
        # a remote puller is here: start the (lazy) D2H drain now so
        # the chunk pulls overlap the remaining copies
        exp.ensure_draining()
        self._json(200, {"meta": exp.meta, "n_chunks": exp.n_chunks})

    def _pd_kv_chunk(self, req_id: str, idx: str):
        """Pull ONE chunk; blocks until the background copier has
        landed it (overlapping the puller with the remaining D2H
        copies).  Chunks are consumed on read; the staged entry drops
        once every chunk is served."""
        if not self._pd_enabled():
            return self._error(403, "P/D disaggregation disabled on this pod")
        reg = self.state.engine.kv_exports
        exp = reg.get(req_id)
        if exp is None:
            return self._error(404, f"no staged KV for {req_id}")
        try:
            # consume is the atomic claim (a duplicate pull gets a clean
            # 410); a write that fails re-stages the chunk so the
            # puller's retry still finds it
            data = exp.get_chunk(int(idx))
        except (IndexError, ValueError) as e:
            return self._error(400, str(e))
        except KeyError as e:
            return self._error(410, str(e))
        except Exception as e:
            return self._error(500, f"chunk read failed: {e}")
        try:
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
        except OSError:
            # client vanished mid-write: un-consume for the retry, and
            # re-put in case a concurrent observer saw fully_served and
            # dropped the registry entry while the write was in flight
            exp.restage_chunk(int(idx), data)
            reg.put(req_id, exp)
            raise
        reg.drop_served(req_id)

    # ---------------- cluster-wide KV pool (docs/kv-pool.md) ----------

    def _kv_pool(self):
        """The replica-local prefix store, or None when the feature is
        off (every pool route 403s then — with the pool disabled the
        server's observable surface is byte-identical to before)."""
        return getattr(self.state.engine, "kv_pool", None)

    def _kv_pool_advert(self):
        """Holder advert for the EPP's cluster-wide prefix→holder
        index: the store's key set with per-page block-hash chains.
        Metadata only — KV bytes move exclusively over the chunked
        wire below."""
        pool = self._kv_pool()
        if pool is None:
            return self._error(403, "KV pool disabled on this pod")
        from kaito_tpu.engine.kv_pool import pool_block_chars

        ps = self.state.engine.cfg.page_size
        cap = int(getattr(self.state.engine.cfg, "kv_pool_advert_max", 0))
        total = len(pool)
        entries = pool.advert(max_entries=cap)
        self._json(200, {"enabled": True, "page_size": ps,
                         "block_chars": pool_block_chars(ps),
                         "total": total,
                         "capped": bool(cap and total > len(entries)),
                         "entries": entries})

    def _kv_pool_meta(self, key: str):
        """Fetch handshake: chunk plans plus the entry's EXACT prompt
        tokens — the fetcher trims to the longest common whole-page
        token prefix before importing (hashes index, tokens decide).
        A dropped entry is a 404 the fetcher treats as a miss."""
        pool = self._kv_pool()
        if pool is None:
            return self._error(403, "KV pool disabled on this pod")
        entry = pool.get(key)
        if entry is None:
            return self._error(404, f"no pool entry {key}")
        exp = entry.export
        exp.ensure_draining()
        self._json(200, {"meta": exp.meta, "n_chunks": exp.n_chunks,
                         "n_tokens": entry.n_tokens,
                         "prompt_tokens": list(exp.prompt_tokens)})

    def _kv_pool_chunk(self, key: str, idx: str):
        """Pull ONE chunk of a pool entry over the same wire format as
        the PD hand-off.  NEVER consumed: unlike a PD export (one
        producer, one consumer) a pool entry serves arbitrarily many
        fetches until the LRU evicts it."""
        pool = self._kv_pool()
        if pool is None:
            return self._error(403, "KV pool disabled on this pod")
        entry = pool.peek(key)
        if entry is None:
            return self._error(410, f"pool entry {key} dropped")
        try:
            data = entry.export.get_chunk(int(idx), consume=False)
        except (IndexError, ValueError) as e:
            return self._error(400, str(e))
        except Exception as e:
            return self._error(500, f"chunk read failed: {e}")
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    # ---------------- dynamic multi-LoRA admin (docs/multi-lora.md) ---

    def _adapters_get(self):
        """Resident-adapter snapshot: the admin listing AND the advert
        the EPP's adapter scraper folds into its affinity index.  403
        when the dynamic cache is off — with no adapter config the
        server's observable surface is byte-identical to before (same
        gating as the KV pool)."""
        snap_fn = getattr(self.state.engine, "adapter_snapshot", None)
        snap = snap_fn() if callable(snap_fn) else None
        if snap is None:
            return self._error(403, "adapter cache disabled on this pod")
        self._json(200, snap)

    def _resolve_adapter_source(self, source: str) -> str:
        """Resolve a POST /v1/adapters source to a local artifact dir.
        ``path://`` (or a bare path) is operator-local trust; remote
        pulls — ``hub://<repo-id>`` (huggingface) and ``oras://<ref>``
        (the registry scheme ModelMirror publishes adapters under) —
        are allowed only when the source matches an
        --adapter-source-allowlist prefix ("" = local paths only, the
        pd_source_allowlist trust model)."""
        import shutil
        import subprocess
        import tempfile

        if source.startswith("path://"):
            source = source[len("path://"):]
        if "://" not in source:
            if not os.path.isdir(source):
                raise ValueError(
                    f"adapter path {source!r} is not a directory")
            return source
        scheme = source.split("://", 1)[0]
        if scheme not in ("hub", "oras"):
            raise ValueError(
                f"unsupported adapter source scheme {scheme!r} "
                f"(path://, hub://, oras://)")
        allow = [p for p in
                 self.state.cfg.adapter_source_allowlist.split(",") if p]
        if not any(source.startswith(pref) for pref in allow):
            raise PermissionError(
                f"adapter source {source!r} not in "
                f"--adapter-source-allowlist")
        dest = tempfile.mkdtemp(prefix="kaito-adapter-")
        try:
            if scheme == "hub":
                from kaito_tpu.runtime.weight_fetch import fetch_from_hub

                fetch_from_hub(source[len("hub://"):], dest)
            else:
                subprocess.run(
                    ["oras", "pull", source[len("oras://"):], "-o", dest],
                    check=True, capture_output=True, timeout=600)
        except Exception as e:
            shutil.rmtree(dest, ignore_errors=True)
            raise RuntimeError(f"adapter pull from {source} failed: {e}") \
                from None
        return dest

    def _adapters_post(self):
        """Hot-load an adapter into the slot table — no restart, no
        recompile (the buffers keep their shapes; docs/multi-lora.md)."""
        st = self.state
        if getattr(st.engine, "adapter_cache", None) is None:
            return self._error(403, "adapter cache disabled on this pod")
        body = self._read_body()
        if body is None:
            return
        from kaito_tpu.engine.qos import valid_tenant

        name = str(body.get("name") or "").strip()
        source = str(body.get("source") or "").strip()
        if not name or not source:
            return self._error(400, "'name' and 'source' are required")
        if not valid_tenant(name):
            return self._error(400, "adapter name must be label-safe "
                                    "(max 64 chars)")
        try:
            path = self._resolve_adapter_source(source)
        except PermissionError as e:
            return self._error(403, str(e))
        except ValueError as e:
            return self._error(400, str(e))
        except RuntimeError as e:
            return self._error(502, str(e))
        from kaito_tpu.engine.adapter_cache import (AdapterBusyError,
                                                    AdapterLoadError)

        try:
            slot = st.engine.load_adapter_dynamic(name, path)
        except AdapterBusyError as e:
            return self._error(409, str(e))
        except AdapterLoadError as e:
            return self._error(422, str(e), "adapter_load_error")
        except ValueError as e:
            return self._error(400, str(e))
        self._json(200, {"loaded": name, "slot": slot})

    def _adapters_delete(self, name: str):
        """Drop an adapter from both cache tiers.  409 while in-flight
        requests pin it; 404 when the cache holds no trace of it."""
        st = self.state
        if getattr(st.engine, "adapter_cache", None) is None:
            return self._error(403, "adapter cache disabled on this pod")
        from kaito_tpu.engine.adapter_cache import AdapterBusyError
        from urllib.parse import unquote

        name = unquote(name).strip()
        try:
            gone = st.engine.delete_adapter(name)
        except AdapterBusyError as e:
            return self._error(409, str(e))
        if not gone:
            return self._error(404, f"no adapter {name!r}")
        self._json(200, {"deleted": name})

    def _submit_with_pool_fetch(self, url: str, key: str,
                                tokens: list, params, *,
                                timeout_s: float = 0.0, tenant: str = "",
                                priority: str = "", adapter: str = "",
                                pool_blocks=None):
        """Cluster-pool fetch: the EPP picked THIS replica but told us
        (X-Kaito-KV-Fetch headers) that a peer holds the prompt's
        prefix KV.  Pull it over the chunked wire and prefill only the
        remainder.  Returns None on ANY ineligibility or failure — the
        caller falls back to a plain submit; the pool is an
        optimization, never a correctness dependency."""
        import urllib.request

        from kaito_tpu.engine.kv_pool import common_prefix_pages
        from kaito_tpu.engine.pd import ChunkPlan, should_transfer

        eng = self.state.engine
        url = url.rstrip("/")
        # same trust boundary as PD pulls: the allowlist (when set)
        # bounds whose bytes may enter this engine's KV pool
        allow = [p for p in self.state.cfg.pd_source_allowlist.split(",")
                 if p]
        if allow and not any(url.startswith(pref) for pref in allow):
            logger.info("kv_pool fetch source %s not in allowlist", url)
            return None
        try:
            with urllib.request.urlopen(f"{url}/kv_pool/{key}/meta",
                                        timeout=10) as r:
                hs = json.loads(r.read())
            meta = hs["meta"]
            plans = [ChunkPlan.from_json(c) for c in meta["chunks"]]
            entry_tokens = hs.get("prompt_tokens") or []
        except Exception as e:
            logger.info("kv_pool meta pull from %s failed: %s", url, e)
            return None
        ps = eng.cfg.page_size
        # token-level verification: the block hashes only INDEXED this
        # entry; what gets imported is decided by comparing real tokens
        n_pages = common_prefix_pages(tokens, entry_tokens, ps)
        if n_pages <= 0:
            return None
        n_prefix = n_pages * ps
        # the EPP already modeled transfer-vs-recompute with fleet
        # knowledge; the engine vetoes only when its own MEASURED rates
        # disagree (a fresh replica has none — exactly the scale-out
        # case the pool exists for)
        costs = getattr(eng, "pd_costs", None)
        snap = costs.snapshot() if costs is not None else {}
        if snap.get("net_bytes_s") and snap.get("prefill_tok_s"):
            cache = getattr(eng, "cache", None)
            kv_itemsize = cache.k.dtype.itemsize if cache is not None else 2
            scale_bpt = 0.0
            if cache is not None \
                    and getattr(cache, "k_scale", None) is not None:
                arch = eng.md.arch
                scale_bpt = (8.0 * arch.num_layers * arch.num_kv_heads
                             / max(1, ps))
            if not should_transfer(n_prefix, eng.md.arch, kv_itemsize,
                                   scale_bytes_per_token=scale_bpt,
                                   measured=costs):
                logger.info("kv_pool fetch below measured break-even "
                            "(%d tokens); recomputing locally", n_prefix)
                return None
        try:
            req = eng.submit_with_kv_prefix(
                tokens, meta, plans, n_prefix, params,
                req_id=f"cmpl-{uuid.uuid4().hex[:20]}",
                timeout_s=timeout_s, trace_id=self._rid,
                tenant=tenant, priority=priority, adapter=adapter,
                pool_blocks=pool_blocks)
        except ValueError as e:
            logger.info("kv_pool fetch submit rejected: %s", e)
            return None

        def pull():
            ci = req.kv_chunked
            try:
                t0 = time.monotonic()
                nbytes = 0
                for i in range(len(plans)):
                    with urllib.request.urlopen(
                            f"{url}/kv_pool/{key}/chunk/{i}",
                            timeout=60) as r:
                        data = r.read()
                    nbytes += len(data)
                    ci.feed(i, data)
                    eng._wake.set()
                if costs is not None:
                    costs.note_transfer(nbytes, time.monotonic() - t0)
            except Exception as e:
                # the engine's prefix-import error path converts ANY
                # pool-fetch failure into a full local prefill
                ci.set_error(f"pool chunk pull from {url} failed: {e}",
                             transient=True)
                eng._wake.set()

        threading.Thread(target=pull, daemon=True,
                         name="kv-pool-puller").start()
        return req

    def _submit_with_local_tier(self, tokens: list, params, *,
                                timeout_s: float = 0.0, tenant: str = "",
                                priority: str = "", adapter: str = "",
                                pool_blocks=None):
        """Local tiered probe (docs/kv-pool.md "Tier 3: SSD"): before
        asking a remote peer or recomputing, check whether THIS
        replica already holds the prompt's prefix — in the host-RAM
        pool store (tier 2) or demoted to the SSD slab directory
        (tier 3).  Runs only when the disk tier is enabled; returns
        None on any ineligibility or miss and the caller falls through
        to the remote-fetch hint / plain submit."""
        eng = self.state.engine
        tier = getattr(eng, "kv_tier", None)
        pool = getattr(eng, "kv_pool", None)
        if tier is None or pool is None or not pool_blocks:
            return None

        from kaito_tpu.engine.kv_pool import common_prefix_pages, pool_key
        from kaito_tpu.engine.pd import ChunkPlan, should_import_from_disk

        ps = eng.cfg.page_size
        costs = getattr(eng, "pd_costs", None)

        def _submit(meta, plans, n_prefix):
            return eng.submit_with_kv_prefix(
                tokens, meta, plans, n_prefix, params,
                req_id=f"cmpl-{uuid.uuid4().hex[:20]}",
                timeout_s=timeout_s, trace_id=self._rid,
                tenant=tenant, priority=priority, adapter=adapter,
                pool_blocks=pool_blocks)

        # -- tier 2: host-RAM store, longest resident prefix of the
        # request's block chain.  peek() during the scan (no hit/miss
        # skew); one get() on the chosen key registers the hit and the
        # LRU touch, same accounting a remote meta handshake gets.
        entry = None
        for n in range(len(pool_blocks), 0, -1):
            e = pool.peek(pool_key(pool_blocks[:n]))
            if e is not None:
                entry = e
                break
        if entry is not None:
            exp = entry.export
            n_pages = common_prefix_pages(tokens, exp.prompt_tokens, ps)
            if n_pages > 0:
                n_prefix = n_pages * ps
                try:
                    req = _submit(exp.meta, exp.plans, n_prefix)
                except ValueError as e:
                    logger.info("kv_tier host import rejected: %s", e)
                    return None
                pool.get(entry.key)
                eng.counters["kv_tier_host_hits_total"] += 1
                eng.counters["kv_tier_import_tokens_total"] += n_prefix

                def feed_host():
                    ci = req.kv_chunked
                    try:
                        exp.ensure_draining()
                        for i in range(len(exp.plans)):
                            # consume=False: pool entries serve many
                            # readers (the /chunk endpoint contract)
                            ci.feed(i, exp.get_chunk(i, consume=False))
                            eng._wake.set()
                    except Exception as e:
                        ci.set_error(f"host tier feed failed: {e}",
                                     transient=True)
                        eng._wake.set()

                threading.Thread(target=feed_host, daemon=True,
                                 name="kv-tier-host-feeder").start()
                return req

        # -- tier 3: SSD slab directory
        hit = tier.lookup_longest(pool_blocks)
        if hit is None:
            return None
        key, dmeta = hit
        meta = dmeta["meta"]
        entry_tokens = dmeta.get("prompt_tokens") or []
        n_pages = common_prefix_pages(tokens, entry_tokens, ps)
        if n_pages <= 0:
            return None
        n_prefix = n_pages * ps
        nbytes = sum(int(s) for s in dmeta["chunk_sizes"])
        # break-even: measured SSD read rate vs measured prefill rate;
        # priors never veto (same discipline as the remote fetch path)
        if not should_import_from_disk(nbytes, n_prefix, costs):
            logger.info("kv_tier disk read below measured break-even "
                        "(%d tokens); recomputing locally", n_prefix)
            return None
        try:
            plans = [ChunkPlan.from_json(c) for c in meta["chunks"]]
            req = _submit(meta, plans, n_prefix)
        except (KeyError, ValueError) as e:
            logger.info("kv_tier disk import rejected: %s", e)
            return None
        eng.counters["kv_tier_disk_hits_total"] += 1
        eng.counters["kv_tier_import_tokens_total"] += n_prefix

        def feed_disk():
            ci = req.kv_chunked
            try:
                t0 = time.monotonic()
                fed = 0
                for i in range(len(plans)):
                    data = tier.read_chunk(key, i, dmeta)
                    fed += len(data)
                    ci.feed(i, data)
                    eng._wake.set()
                if costs is not None:
                    costs.note_disk_read(fed, time.monotonic() - t0)
            except Exception as e:
                # corrupt/truncated slab → the engine's prefix-import
                # error path falls back to a clean full local prefill
                ci.set_error(f"disk tier read of {key} failed: {e}",
                             transient=True)
                eng._wake.set()

        threading.Thread(target=feed_disk, daemon=True,
                         name="kv-tier-disk-feeder").start()
        return req

    def _adopt_handoff_trace(self, meta: dict) -> None:
        """PD decode role: when the client sent no trace header, adopt
        the trace id the prefill role stamped into the staged meta, so
        both roles' spans land under ONE id."""
        if not getattr(self, "_rid_client", False) and meta.get("trace_id"):
            self._rid = str(meta["trace_id"])

    def _submit_with_transfer(self, kv_src: dict, params,
                              timeout_s: float = 0.0,
                              tenant: str = "", priority: str = "",
                              adapter: str = ""):
        """Continue decoding from a remote prefill's KV.

        Chunked overlapped pull: a handshake fetches the chunk plan,
        the request is admitted immediately, and a background puller
        streams chunks into the engine (which scatters them between
        decode steps).  For prompts below the transfer-vs-recompute
        break-even (pd.should_transfer), the KV move is skipped
        entirely and the prompt prefills locally — cheaper than the
        wire for short prompts.  ``force: true`` in the kv_transfer
        body pins the transfer path (tests / operator override).

        Adapter requests ride the hand-off only for SAME-adapter
        reuse: the staged meta records which adapter (if any) the
        prefill ran under, and a mismatch is refused — prefix KV
        computed under different deltas would silently skew decode."""
        import urllib.request

        from kaito_tpu.engine.pd import ChunkPlan, should_transfer

        if not self._pd_enabled():
            self._error(403, "P/D disaggregation disabled on this pod")
            return None
        url = kv_src.get("source_url", "").rstrip("/")
        req_id = kv_src.get("req_id", "")
        if not url or not req_id:
            self._error(400, "kv_transfer needs source_url and req_id")
            return None
        allow = [p for p in self.state.cfg.pd_source_allowlist.split(",") if p]
        if allow and not any(url.startswith(pref) for pref in allow):
            self._error(403, f"kv_transfer source {url!r} not in allowlist")
            return None
        prompt_tokens = kv_src.get("prompt_tokens") or []
        first = int(kv_src.get("first_token", 0))
        eng = self.state.engine
        # colocated source => device-to-device hand-off (no host, no
        # wire, and trivially above any break-even); "wire": "http"
        # forces the chunked path (tests / operator override)
        if kv_src.get("wire", "auto") != "http":
            src_eng = lookup_local_engine(url)
            if src_eng is not None:
                staged = src_eng.kv_exports.pop(req_id)
                if staged is not None:
                    # the prefill engine staged the true token list; a
                    # client claiming different tokens must not scatter
                    # this slab under them
                    if (staged.prompt_tokens
                            and list(prompt_tokens) != staged.prompt_tokens):
                        src_eng.kv_exports.put(req_id, staged)
                        self._error(400, "kv_transfer prompt_tokens do not "
                                         "match the staged prefill")
                        return None
                    if str(staged.meta.get("adapter") or "") != adapter:
                        src_eng.kv_exports.put(req_id, staged)
                        self._error(
                            409, f"kv_transfer adapter mismatch: prefill "
                                 f"ran {staged.meta.get('adapter') or 'base'!r}, "
                                 f"request wants {adapter or 'base'!r}")
                        return None
                    slabs = staged.device_slabs()
                    if slabs is not None:
                        logger.info("kv_transfer %s: colocated source, "
                                    "device-to-device hand-off", req_id)
                        self._adopt_handoff_trace(staged.meta)
                        try:
                            return eng.submit_with_kv_device(
                                prompt_tokens, first, staged.meta, slabs,
                                params,
                                req_id=f"cmpl-{uuid.uuid4().hex[:20]}",
                                timeout_s=timeout_s,
                                trace_id=self._rid, tenant=tenant,
                                priority=priority, adapter=adapter)
                        except ValueError:
                            # a rejected submit must not destroy the
                            # prefill result: re-stage for retry/wire
                            src_eng.kv_exports.put(req_id, staged)
                            raise
                    # a remote drain already released the slabs: put it
                    # back and fall through to the wire path
                    src_eng.kv_exports.put(req_id, staged)
        cache = getattr(eng, "cache", None)
        kv_itemsize = cache.k.dtype.itemsize if cache is not None else 2
        # an int8 pool transfers fp32 page scales alongside the codes:
        # ~8*L*Hkv/page_size extra bytes per token on the wire
        scale_bpt = 0.0
        if cache is not None and getattr(cache, "k_scale", None) is not None:
            arch = eng.md.arch
            scale_bpt = (8.0 * arch.num_layers * arch.num_kv_heads
                         / max(1, eng.cfg.page_size))
        # the recompute fallback re-samples the first token locally, so
        # it is only equivalence-preserving for greedy requests; sampled
        # requests always honor the prefill pod's first_token via the
        # transfer path
        if (not kv_src.get("force") and params.temperature == 0.0
                and not should_transfer(
                    len(prompt_tokens), eng.md.arch, kv_itemsize,
                    scale_bytes_per_token=scale_bpt,
                    measured=getattr(eng, "pd_costs", None))):
            # below break-even: local prefill beats the wire.  Release
            # the staged export so the prefill pod doesn't hold it to
            # TTL, then admit as a plain request (greedy output is
            # identical; the prefill pod's first token is re-derived).
            logger.info("kv_transfer below break-even (%d tokens); "
                        "recomputing locally", len(prompt_tokens))

            def _release():
                # off the request path: an unreachable prefill pod must
                # not add its timeout to a request that no longer needs
                # it (TTL reclaims the export if this fails)
                try:
                    urllib.request.urlopen(urllib.request.Request(
                        f"{url}/pd/kv/{req_id}", method="DELETE"),
                        timeout=10)
                except Exception:
                    pass
            threading.Thread(target=_release, daemon=True,
                             name="pd-release").start()
            return eng.submit(prompt_tokens, params,
                              req_id=f"cmpl-{uuid.uuid4().hex[:20]}",
                              adapter=adapter,
                              timeout_s=timeout_s, trace_id=self._rid)
        try:
            with urllib.request.urlopen(f"{url}/pd/kv/{req_id}/meta",
                                        timeout=30) as r:
                hs = json.loads(r.read())
            meta = hs["meta"]
            plans = [ChunkPlan.from_json(c) for c in meta["chunks"]]
        except Exception as e:
            self._error(502, f"KV meta pull from {url} failed: {e}")
            return None
        if str(meta.get("adapter") or "") != adapter:
            self._error(409, f"kv_transfer adapter mismatch: prefill ran "
                             f"{meta.get('adapter') or 'base'!r}, request "
                             f"wants {adapter or 'base'!r}")
            return None
        self._adopt_handoff_trace(meta)
        try:
            req = eng.submit_with_kv_chunked(
                prompt_tokens, first, meta, plans, params,
                req_id=f"cmpl-{uuid.uuid4().hex[:20]}",
                timeout_s=timeout_s, trace_id=self._rid,
                tenant=tenant, priority=priority, adapter=adapter)
        except ValueError as e:
            self._error(400, str(e))
            return None

        def pull():
            ci = req.kv_chunked
            try:
                t0 = time.monotonic()
                nbytes = 0
                for i in range(len(plans)):
                    with urllib.request.urlopen(
                            f"{url}/pd/kv/{req_id}/chunk/{i}",
                            timeout=120) as r:
                        data = r.read()
                    nbytes += len(data)
                    ci.feed(i, data)
                    eng._wake.set()
                # pure wire time, measured where the bytes move: from
                # before the FIRST chunk request to the last byte read
                # (no admission wait, no scatter latency) — this is the
                # link-bandwidth sample the break-even model consumes
                costs = getattr(eng, "pd_costs", None)
                if costs is not None:
                    costs.note_transfer(nbytes, time.monotonic() - t0)
            except Exception as e:
                # a puller network error is TRANSIENT: the engine's
                # retry budget falls back to local recompute instead of
                # failing the request
                ci.set_error(f"chunk pull from {url} failed: {e}",
                             transient=True)
                eng._wake.set()

        threading.Thread(target=pull, daemon=True,
                         name="pd-chunk-puller").start()
        return req

    # ---------------- generation ----------------

    @contextlib.contextmanager
    def _chunk_times(self):
        """The seconds each streamed token took on this thread —
        detokenize, serialise, write; the wait for the token outside —
        handed to ``kaito:http_stream_chunk_seconds`` in one piece when
        the stream ends: per token the loop pays two clock reads, an
        append and its ``http.stream.chunk`` span, and no lock.  With
        them goes what the stream cost the interpreter, not the clock:
        this thread's CPU seconds over the token loop, two reads a
        stream (the first where the loop is entered: until its first
        token arrives the thread sleeps on the request's queue)."""
        seconds: list[float] = []
        cpu0 = time.thread_time()
        try:
            yield seconds
        finally:
            metrics = self.state.metrics
            metrics.stream_chunk.observe_many(seconds)
            if seconds:
                metrics.stream_cpu.inc(time.thread_time() - cpu0)

    def _stream_tool_calls(self, st, req, base, body, forced: bool):
        """SSE tail for chat requests with tools (the role delta is
        already sent).  Forced calls (tool_choice required/named) are
        grammar-constrained to the JSON envelope, so name + argument
        bytes stream incrementally as they decode; auto mode buffers to
        end-of-generation and then emits EITHER content or tool_calls
        deltas — a client accumulator must never see both interleaved."""
        from kaito_tpu.engine.parsers import (
            StreamingToolCallParser,
            parse_message,
            tool_call_deltas,
        )

        def send(delta, finish=None):
            chunk = dict(base)
            chunk["choices"] = [{"index": 0, "delta": delta,
                                 "finish_reason": finish}]
            self._sse_send(chunk)

        ids: list[int] = []
        finish = "stop"
        if forced:
            parser = StreamingToolCallParser()
            sent = ""
            span = st.engine.phases.annotate
            with self._chunk_times() as chunk_s:
                for tok in req.stream():
                    t0 = time.perf_counter()
                    with span("http.stream.chunk", rid=self._rid):
                        ids.append(tok)
                        text = st.engine.tokenizer.decode(ids)
                        # mid-codepoint: wait for more bytes
                        if not text.endswith("�"):
                            delta_text, sent = text[len(sent):], text
                            for d in parser.feed(delta_text):
                                send({"tool_calls": [d]})
                    chunk_s.append(time.perf_counter() - t0)
            tail = st.engine.tokenizer.decode(ids)[len(sent):]
            for d in parser.feed(tail) + parser.finish():
                send({"tool_calls": [d]})
            finish = "tool_calls"
        else:
            for tok in req.stream():
                ids.append(tok)
            text = st.engine.tokenizer.decode(ids)
            parsed = parse_message(
                text,
                reasoning=bool(getattr(st.engine.md,
                                       "reasoning_parser", None)),
                tools=True,
                tool_mode=getattr(st.engine.md, "tool_call_parser", ""))
            if parsed.content:
                send({"content": parsed.content})
            if parsed.tool_calls:
                for d in tool_call_deltas(parsed.tool_calls):
                    send({"tool_calls": [d]})
                finish = "tool_calls"
            else:
                finish = req.finish_reason or "stop"
        send({}, finish=finish)
        self._sse_end()
        st.metrics.observe_request(req)
        st.slo.observe_request(req)
        st.limiter.note_tokens(
            req.tenant, len(req.prompt_tokens) + len(req.output_tokens))

    def _completions(self, chat: bool):
        """One completion or chat request.  ``http.request`` spans the
        intake on this thread — parse, encode, ``submit`` — and is
        closed by ``_serve_completion`` once the engine has the
        request; waiting for tokens and streaming them is outside it."""
        with contextlib.ExitStack() as intake:
            intake.enter_context(self.state.engine.phases.annotate(
                "http.request", rid=self._rid))
            self._serve_completion(chat, intake)

    def _serve_completion(self, chat: bool, intake: contextlib.ExitStack):
        st = self.state
        body = self._read_body()
        if body is None:
            return
        qos_ids = self._intake_tenant(body)
        if qos_ids is None:
            return
        tenant, priority = qos_ids
        shed = st.limiter.shed_reason(st.engine, tenant=tenant)
        if shed is not None:
            reason, shed_tenant = shed["reason"], shed["tenant"]
            st.metrics.requests_rejected.inc()
            st.metrics.requests_shed.inc(reason=reason)
            if st.metrics.tenant_shed is not None:
                st.metrics.tenant_shed.inc(tenant=shed_tenant or "default")
            st.slo.note_shed(tenant=shed_tenant)
            try:
                # best-effort: the flight recorder reports shed pressure
                # per step (the DP facade's computed counters drop this)
                st.engine.counters["requests_shed_total"] += 1
            except (KeyError, TypeError):
                pass
            retry_after = st.limiter.retry_after_s(st.engine, key=self._rid)
            messages = {
                "queue_full": "engine queue full, retry later",
                "tenant_queue_full": "tenant queue budget exhausted, "
                                     "retry later",
                "tenant_rate": "tenant token budget exhausted, retry later",
                "kv_pressure": "KV page pool saturated, retry later",
            }
            self._error(429, messages.get(reason, "over capacity"),
                        "rate_limit_error",
                        headers={"Retry-After": retry_after})
            return

        # grammar-constrained decoding intake (docs/structured-output.md):
        # response_format + tools/tool_choice validate and COMPILE here,
        # in the request thread, before admission — the step thread only
        # ever sees a finished CompiledGrammar.  Structural mistakes are
        # 400; a well-formed schema the compiler rejects is 422.
        from kaito_tpu.engine.grammar import (
            GrammarError, GrammarSpec, canonical_schema,
            spec_from_response_format, tool_envelope_schema,
        )

        tools = body.get("tools")
        tool_choice = body.get("tool_choice")
        if not chat and (tools is not None or tool_choice is not None):
            return self._error(400, "'tools' and 'tool_choice' are only "
                                    "supported on /v1/chat/completions")
        forced_tools = False
        grammar_spec = None
        use_tools = False
        try:
            if chat:
                messages = body.get("messages")
                if not isinstance(messages, list) or not messages:
                    return self._error(400, "'messages' must be a non-empty list")
                if tools is not None:
                    if not isinstance(tools, list) or not tools or not all(
                            isinstance(t, dict) for t in tools):
                        return self._error(
                            400, "'tools' must be a non-empty list of "
                                 "tool objects")
                if tool_choice is not None and not tools:
                    return self._error(
                        400, "'tool_choice' requires 'tools'")
                if tools:
                    choice = tool_choice if tool_choice is not None \
                        else "auto"
                    named = None
                    if isinstance(choice, dict):
                        named = (choice.get("function") or {}).get("name")
                        if choice.get("type") != "function" or not named:
                            return self._error(
                                400, "'tool_choice' object must be "
                                     '{"type": "function", "function": '
                                     '{"name": ...}}')
                    elif choice not in ("auto", "none", "required"):
                        return self._error(
                            400, f"unknown tool_choice {choice!r}")
                    if named is not None or choice == "required":
                        # forced call: constrain generation to the pure
                        # JSON envelope and parse it directly
                        try:
                            env = tool_envelope_schema(
                                tools,
                                names=[named] if named else None)
                        except GrammarError as e:
                            return self._error(400, str(e))
                        grammar_spec = GrammarSpec(
                            "json_schema", canonical_schema(env))
                        forced_tools = True
                        use_tools = True
                    elif choice == "auto":
                        use_tools = True
                if use_tools:
                    # advertise tools in the model's own call wire
                    # format (the preset's tool_call_parser mode);
                    # parse_message reads it back out. Merge into an
                    # existing system message so chat templates that
                    # keep only one system block see both.
                    from kaito_tpu.engine.parsers import render_tools_prompt

                    messages = list(messages)
                    tp = render_tools_prompt(
                        tools, mode=getattr(st.engine.md,
                                            "tool_call_parser", "")
                        or "hermes")
                    if messages and messages[0].get("role") == "system":
                        messages[0] = {
                            "role": "system",
                            "content": (messages[0].get("content", "")
                                        + "\n\n" + tp)}
                    else:
                        messages = [{"role": "system", "content": tp}] \
                            + messages
                prompt_text = render_chat(st.engine.tokenizer, messages,
                                          model_id=st.engine.md.name)
            else:
                prompt = body.get("prompt", "")
                if isinstance(prompt, list):
                    prompt = prompt[0] if prompt else ""
                if not isinstance(prompt, str) or prompt == "":
                    return self._error(400, "'prompt' must be a non-empty string")
                prompt_text = prompt

            rf = body.get("response_format")
            if rf is not None:
                if grammar_spec is not None:
                    return self._error(
                        400, "'response_format' cannot be combined with "
                             "a forced tool_choice (both constrain the "
                             "output grammar)")
                try:
                    grammar_spec = spec_from_response_format(rf)
                except GrammarError as e:
                    return self._error(400, str(e))
            grammar = None
            if grammar_spec is not None:
                if not getattr(st.engine.cfg, "structured_output", True):
                    return self._error(
                        400, "structured output is disabled on this "
                             "server (structured_output=false)",
                        "structured_output_disabled")
                try:
                    grammar = st.engine.grammar_cache.get(
                        grammar_spec, st.engine.tokenizer)
                except GrammarError as e:
                    # well-formed request, uncompilable grammar (state
                    # cap, tokenizer dead end, unsupported construct)
                    return self._error(422, str(e),
                                       "invalid_grammar_error")

            # logprobs: per-generated-token log p of the chosen token
            # under the model distribution; top-k ALTERNATIVES are not
            # implemented, so requests for them fail loudly
            if chat:
                want_lp = bool(body.get("logprobs"))
                if int(body.get("top_logprobs", 0) or 0) > 0:
                    return self._error(400, "top_logprobs alternatives are "
                                            "not supported")
            else:
                lp_param = body.get("logprobs")
                want_lp = lp_param not in (None, False, 0)
                if want_lp and int(lp_param) > 1:
                    return self._error(400, "logprobs > 1 (top-k "
                                            "alternatives) is not supported")
            stream = bool(body.get("stream", False))
            if want_lp and stream:
                return self._error(400, "logprobs are not supported with "
                                        "streaming")
            # echo + logprobs + max_tokens=0: prompt SCORING (the
            # lm-eval loglikelihood contract); echo with generation is
            # out of scope
            echo = bool(body.get("echo", False)) and not chat
            if echo and int(body.get("max_tokens") or 0) > 0:
                return self._error(400, "'echo' is only supported with "
                                        "max_tokens=0 (prompt scoring)")
            n_choices = int(body.get("n", 1) or 1)
            if not 1 <= n_choices <= 16:
                return self._error(400, "'n' must be between 1 and 16")
            if n_choices > 1 and stream:
                return self._error(400, "'n' > 1 is not supported with "
                                        "streaming")
            params = SamplingParams(
                max_tokens=int(body.get("max_tokens") or 128),
                temperature=float(body.get("temperature", 1.0)),
                top_k=int(body.get("top_k", 0) or 0),
                top_p=float(body.get("top_p", 1.0)),
                seed=int(body.get("seed", 0) or 0),
                logprobs=want_lp,
                presence_penalty=float(body.get("presence_penalty", 0.0)
                                       or 0.0),
                frequency_penalty=float(body.get("frequency_penalty", 0.0)
                                        or 0.0),
                repetition_penalty=float(body.get("repetition_penalty", 1.0)
                                         or 1.0),
                min_p=float(body.get("min_p", 0.0) or 0.0),
                # vLLM extra-param parity: benchmarking/tests pin exact
                # generation lengths with ignore_eos
                ignore_eos=bool(body.get("ignore_eos", False)),
                grammar=grammar,
            )
            # per-request deadline (seconds); 0/absent falls back to the
            # server default (cfg.request_timeout_s).  Expired requests
            # are aborted with a 408-style structured error before (or
            # while) consuming TPU time.
            timeout_s = float(body.get("timeout", 0) or 0)
            if timeout_s < 0:
                return self._error(400, "'timeout' must be >= 0")
        except (TypeError, ValueError) as e:
            return self._error(400, f"bad parameter: {e}")

        stop = body.get("stop")
        stop_strs = [stop] if isinstance(stop, str) else list(stop or [])
        tokens = st.engine.tokenizer.encode(prompt_text)
        kv_src = body.get("kv_transfer")
        # per-request adapter routing: the "model" field selects a
        # discovered adapter, exactly like the reference serves adapters
        # as models (inference_api.py:417-498).  With the dynamic cache,
        # host-tier adapters count too — submission faults them back in.
        adapter = ""
        model_field = body.get("model") or ""
        if model_field and model_field not in (st.model_name,
                                               st.engine.md.name):
            a_cache = getattr(st.engine, "adapter_cache", None)
            if model_field in getattr(st.engine, "adapter_index", {}) \
                    or (a_cache is not None and a_cache.has(model_field)):
                adapter = model_field
            elif getattr(st.engine, "adapters_merged", False) \
                    and model_field in st.adapters:
                adapter = ""      # TP/PP: adapters merged into base weights
            else:
                return self._error(404, f"model {model_field!r} not found")
        if not adapter and tenant and st.qos is not None:
            # tenant->adapter mapping (docs/multi-lora.md): when the
            # model field didn't pick one, X-Kaito-Tenant can — the
            # QoS config pins a tenant's traffic to its fine-tune
            adapter = st.qos.adapter_of(tenant)
            if adapter and not (
                    adapter in getattr(st.engine, "adapter_index", {})
                    or (getattr(st.engine, "adapter_cache", None)
                        is not None
                        and st.engine.adapter_cache.has(adapter))):
                return self._error(
                    503, f"tenant adapter {adapter!r} is not loaded on "
                         f"this replica", "adapter_unavailable")
        # cluster-wide KV pool (docs/kv-pool.md): hash the request the
        # SAME way the EPP does (extract_prompt_text on the body, not
        # the rendered template) so finished prefixes publish under
        # exactly the hashes the fleet index computes.  The adapter
        # name seeds the chain — KV computed under adapter deltas must
        # never hash-match base KV (or another adapter's).
        pool_blocks: list = []
        if getattr(st.engine, "kv_pool", None) is not None:
            from kaito_tpu.engine.kv_pool import prompt_pool_blocks
            from kaito_tpu.runtime.routing import extract_prompt_text

            pool_blocks = prompt_pool_blocks(extract_prompt_text(body),
                                             st.engine.cfg.page_size,
                                             adapter=adapter)
        if kv_src and n_choices > 1:
            return self._error(400, "'n' > 1 is not supported with "
                                    "KV transfer")
        if echo:
            # AFTER model-field routing: unknown models 404 above, and
            # per-request adapters can't be scored (the scorer runs the
            # base forward)
            if adapter:
                return self._error(400, "prompt scoring with a per-request "
                                        "adapter is not supported")
            if kv_src:
                return self._error(400, "prompt scoring with KV transfer "
                                        "is not supported")
            return self._score_prompt(body, tokens, prompt_text, want_lp)
        if n_choices > 1 and not params.seed:
            # pin the primary's seed NOW so choice seeds never collide
            # with the engine's auto-seed counter
            import dataclasses as _dc

            params = _dc.replace(
                params, seed=int(uuid.uuid4().hex[:8], 16) | 1)
        try:
            if kv_src:
                req = self._submit_with_transfer(kv_src, params,
                                                 timeout_s=timeout_s,
                                                 tenant=tenant,
                                                 priority=priority,
                                                 adapter=adapter)
                if req is None:
                    return  # error already sent
                tokens = req.prompt_tokens
            else:
                req = None
                if getattr(st.engine, "kv_tier", None) is not None:
                    # tier-3 enabled: probe the LOCAL host/SSD tiers
                    # before any remote peer and before recompute
                    req = self._submit_with_local_tier(
                        tokens, params, timeout_s=timeout_s,
                        tenant=tenant, priority=priority,
                        adapter=adapter, pool_blocks=pool_blocks)
                fetch_url = self.headers.get("X-Kaito-KV-Fetch", "")
                fetch_key = self.headers.get("X-Kaito-KV-Fetch-Key", "")
                if (req is None
                        and getattr(st.engine, "kv_pool", None) is not None
                        and fetch_url and fetch_key):
                    # the EPP routed here with a fetch hint: a peer
                    # replica holds this prompt's prefix KV.  Adapter
                    # requests participate — their seeded hash chain
                    # (and the meta authority check) confines the
                    # fetch to same-adapter entries.
                    req = self._submit_with_pool_fetch(
                        fetch_url, fetch_key, tokens, params,
                        timeout_s=timeout_s, tenant=tenant,
                        priority=priority, adapter=adapter,
                        pool_blocks=pool_blocks)
                if req is None:
                    req = st.engine.submit(
                        tokens, params,
                        req_id=f"cmpl-{uuid.uuid4().hex[:20]}",
                        adapter=adapter, timeout_s=timeout_s,
                        trace_id=self._rid, tenant=tenant,
                        priority=priority, pool_blocks=pool_blocks)
        except ValueError as e:
            return self._error(400, str(e))
        # conversation identity (docs/routing.md "Session affinity"):
        # opaque client id the EPP pins turn N to turn N-1's holder
        # with; carried on the Request for tracing/debug parity
        session = self.headers.get("X-Kaito-Session", "").strip()
        if session:
            req.session = session[:128]
        intake.close()

        # extra choices decode CONCURRENTLY with the first (one engine
        # request per choice, seeds offset from the pinned primary seed
        # so sampled paths diverge)
        extra_reqs = []
        for ci in range(1, n_choices):
            import dataclasses as _dc

            p_i = _dc.replace(params, seed=params.seed + ci)
            try:
                extra_reqs.append(st.engine.submit(
                    tokens, p_i, req_id=f"{req.req_id}-{ci}",
                    adapter=adapter, timeout_s=timeout_s,
                    trace_id=self._rid, tenant=tenant, priority=priority))
            except ValueError as e:
                for r in [req] + extra_reqs:
                    st.engine.abort(r)
                return self._error(400, str(e))
        created = int(time.time())
        obj = "chat.completion" if chat else "text_completion"
        base = {"id": req.req_id, "object": obj + (".chunk" if stream else ""),
                "created": created, "model": body.get("model") or st.model_name}

        if stream:
            self._sse_start()
            if chat:
                first = dict(base)
                first["choices"] = [{"index": 0, "delta": {"role": "assistant"},
                                     "finish_reason": None}]
                self._sse_send(first)
            if chat and use_tools:
                return self._stream_tool_calls(st, req, base, body,
                                               forced_tools)
            sent_text = ""
            ids: list[int] = []
            stopped = False
            span = st.engine.phases.annotate
            with self._chunk_times() as chunk_s:
                for tok in req.stream():
                    t0 = time.perf_counter()
                    with span("http.stream.chunk", rid=self._rid):
                        ids.append(tok)
                        text = st.engine.tokenizer.decode(ids)
                        # mid-codepoint: wait for more bytes
                        if not text.endswith("�"):
                            delta = text[len(sent_text):]
                            sent_text = text
                            if stop_strs and any(s in sent_text
                                                 for s in stop_strs):
                                cut = min(sent_text.find(s)
                                          for s in stop_strs
                                          if s in sent_text)
                                delta = sent_text[:cut][
                                    len(sent_text) - len(delta):]
                                st.engine.abort(req)
                                stopped = True
                            if delta:
                                chunk = dict(base)
                                chunk["choices"] = [{
                                    "index": 0,
                                    **({"delta": {"content": delta}}
                                       if chat else {"text": delta}),
                                    "finish_reason": None}]
                                self._sse_send(chunk)
                    chunk_s.append(time.perf_counter() - t0)
                    if stopped:
                        break
            # flush text withheld by the mid-codepoint guard
            if not stopped and ids:
                tail = st.engine.tokenizer.decode(ids)[len(sent_text):]
                if tail:
                    chunk = dict(base)
                    chunk["choices"] = [{
                        "index": 0,
                        **({"delta": {"content": tail}} if chat else {"text": tail}),
                        "finish_reason": None}]
                    self._sse_send(chunk)
            fin = dict(base)
            fin["choices"] = [{"index": 0,
                               **({"delta": {}} if chat else {"text": ""}),
                               "finish_reason": "stop" if stopped else
                               (req.finish_reason or "stop")}]
            self._sse_send(fin)
            self._sse_end()
            st.metrics.observe_request(req)
            st.slo.observe_request(req)
            st.limiter.note_tokens(
                req.tenant, len(req.prompt_tokens) + len(req.output_tokens))
            return

        choices = []
        total_completion = 0
        all_reqs = [req] + extra_reqs
        outs = [list(r.stream()) for r in all_reqs]   # drain every choice
        if any(r.finish_reason in ("error", "deadline") for r in all_reqs):
            # request-scoped failure or deadline abort: surface the
            # structured engine error (408/5xx) instead of a 200 with
            # silently truncated text
            bad = next(r for r in all_reqs
                       if r.finish_reason in ("error", "deadline"))
            st.metrics.observe_request(req)
            st.slo.observe_request(bad)
            return self._request_error(bad)
        for idx, (r, out_ids) in enumerate(zip(all_reqs, outs)):
            total_completion += len(out_ids)
            text = st.engine.tokenizer.decode(out_ids)
            finish = r.finish_reason or "stop"
            stop_cut = False
            for s in stop_strs:
                if s in text:
                    text = text[: text.find(s)]
                    finish = "stop"
                    stop_cut = True
            lp_block = None
            if params.logprobs:
                tok_strs = token_surface_forms(st.engine.tokenizer,
                                               out_ids)
                lps = list(r.output_logprobs[:len(out_ids)])
                if stop_cut:
                    # align the entries with the RETURNED (trimmed)
                    # text, not the raw generation
                    kept, acc = len(out_ids), 0
                    for i, s_ in enumerate(tok_strs):
                        if acc >= len(text):
                            kept = i
                            break
                        acc += len(s_)
                    tok_strs, lps = tok_strs[:kept], lps[:kept]
                if chat:
                    lp_block = {"content": [
                        {"token": s_, "logprob": l_,
                         "bytes": list(s_.encode())}
                        for s_, l_ in zip(tok_strs, lps)]}
                else:
                    offsets, pos = [], len(prompt_text)
                    for s_ in tok_strs:
                        offsets.append(pos)
                        pos += len(s_)
                    lp_block = {"tokens": tok_strs, "token_logprobs": lps,
                                "top_logprobs": None,
                                "text_offset": offsets}
            if chat:
                # tool-call + reasoning post-processing, gated
                # per-preset exactly like the reference's parser flags
                # (generator.go)
                from kaito_tpu.engine.parsers import (
                    parse_forced_tool_call,
                    parse_message,
                )

                if forced_tools:
                    # grammar-forced envelope: direct JSON parse, no
                    # wire-format scan (docs/structured-output.md)
                    parsed = parse_forced_tool_call(text)
                else:
                    parsed = parse_message(
                        text,
                        reasoning=bool(getattr(st.engine.md,
                                               "reasoning_parser", None)),
                        tools=use_tools,
                        tool_mode=getattr(st.engine.md,
                                          "tool_call_parser", ""))
                message = {"role": "assistant", "content": parsed.content}
                if parsed.reasoning_content is not None:
                    message["reasoning_content"] = parsed.reasoning_content
                if parsed.tool_calls:
                    message["tool_calls"] = parsed.tool_calls
                choice = {"index": idx, "message": message,
                          "finish_reason": parsed.finish_reason or finish}
                if params.logprobs:
                    choice["logprobs"] = lp_block
            else:
                choice = {"index": idx, "text": text, "logprobs": lp_block,
                          "finish_reason": finish}
            choices.append(choice)
        usage = {"prompt_tokens": len(tokens),
                 "completion_tokens": total_completion,
                 "total_tokens": len(tokens) + total_completion}
        resp = dict(base)
        resp.update({"choices": choices, "usage": usage})
        st.metrics.observe_request(req)
        st.slo.observe_request(req)
        # post-paid token budgets: debit every choice's actual usage
        for r in all_reqs:
            st.limiter.note_tokens(
                r.tenant, len(r.prompt_tokens) + len(r.output_tokens))
        self._json(200, resp)


# Colocated P/D: engines served from THIS process, keyed by base URL.
# When a kv_transfer's source_url resolves here, the hand-off is a
# device-to-device copy of the staged slab — no host bounce, no wire
# (the single-host MRI / shared-slice case of the reference's NIXL
# device path, preset_inferences.go:909-938).
_LOCAL_PD_ENGINES: dict[str, InferenceEngine] = {}
_LOCAL_PD_LOCK = threading.Lock()


def lookup_local_engine(url: str) -> Optional[InferenceEngine]:
    with _LOCAL_PD_LOCK:
        return _LOCAL_PD_ENGINES.get(url.rstrip("/"))


class _PDServer(ThreadingHTTPServer):
    """HTTP server that registers its engine for colocated P/D and
    unregisters when it stops serving (shutdown or close) — ports get
    reused across tests, and a stale entry would pin the engine's KV
    cache and divert future colocated lookups to a dead engine."""

    _pd_urls: tuple[str, ...] = ()
    # the listen backlog: socketserver's 5 drops the SYNs of a burst of
    # clients (192 opened at once lost 16 to ETIMEDOUT on the chip)
    # while the accept loop waits for the interpreter lock
    request_queue_size = 1024

    def _pd_unregister(self):
        with _LOCAL_PD_LOCK:
            for u in self._pd_urls:
                if _LOCAL_PD_ENGINES.get(u) is self.state.engine:
                    del _LOCAL_PD_ENGINES[u]

    def _cancel_profile_timer(self):
        # a pending /start_profile auto-stop must not fire into a
        # torn-down process (stop_trace on a dead backend)
        st = getattr(self, "state", None)
        timer = getattr(st, "_profile_timer", None) if st else None
        if timer is not None:
            timer.cancel()
            st._profile_timer = None

    def _stop_flight_watcher(self):
        st = getattr(self, "state", None)
        watcher = getattr(st, "flight_watcher", None) if st else None
        if watcher is not None:
            watcher.stop()

    def shutdown(self):
        self._pd_unregister()
        self._cancel_profile_timer()
        self._stop_flight_watcher()
        super().shutdown()

    def server_close(self):
        self._pd_unregister()
        self._cancel_profile_timer()
        self._stop_flight_watcher()
        super().server_close()


def make_server(engine: InferenceEngine, cfg: EngineConfig,
                host: str = "0.0.0.0", port: Optional[int] = None) -> ThreadingHTTPServer:
    state = ServerState(engine, cfg)
    handler = type("Handler", (OpenAIHandler,), {"state": state})
    server = _PDServer((host, port if port is not None else cfg.port),
                       handler)
    server.state = state  # type: ignore[attr-defined]
    bound = server.server_address[1]
    hosts = {"127.0.0.1", "localhost"}
    if host not in ("0.0.0.0", "::", ""):
        hosts.add(host)
    urls = tuple(f"http://{h}:{bound}" for h in sorted(hosts))
    server._pd_urls = urls
    with _LOCAL_PD_LOCK:
        for u in urls:
            _LOCAL_PD_ENGINES[u] = engine
    return server


class _LoadingHandler(BaseHTTPRequestHandler):
    """Pre-engine stub: answers probes while weights load/compile.

    The reference wrapper serves a /metrics stub + download progress
    BEFORE vLLM is up (inference_api.py:265-415) so Prometheus scrapes
    and kubelet probes don't read as failures during multi-minute model
    loads; same contract here — /health returns 503 "loading" (startup
    probes keep waiting instead of flapping) and /metrics exposes a
    loading gauge.
    """

    protocol_version = "HTTP/1.1"
    started: float = 0.0   # stamped by start_loading_stub's subclass
    device: dict = {}      # likewise: where the engine is loading

    def log_message(self, *a):
        pass

    def _body(self, code, body, ctype):
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        if self.path == "/health":
            self._body(503, json.dumps(
                {"status": "loading",
                 "seconds": round(time.time() - self.started, 1),
                 **self.device}).encode(),
                "application/json")
        elif self.path == "/metrics":
            body = ("# HELP kaito:engine_loading 1 while weights "
                    "load/compile\n# TYPE kaito:engine_loading gauge\n"
                    f"kaito:engine_loading 1\n"
                    f"kaito:engine_loading_seconds "
                    f"{time.time() - self.started:.1f}\n").encode()
            self._body(200, body, "text/plain; version=0.0.4")
        else:
            self._body(503, b'{"error": "engine loading"}',
                       "application/json")

    def do_POST(self):
        # drain the body: an unread POST payload would desync the next
        # request on a keep-alive connection
        n = int(self.headers.get("Content-Length", "0") or 0)
        if n:
            self.rfile.read(n)
        self._body(503, b'{"error": {"message": "engine loading", '
                        b'"type": "unavailable"}}', "application/json")


def start_loading_stub(host: str, port: int) -> ThreadingHTTPServer:
    """Serve the loading stub until the engine is constructed; caller
    shuts it down right before binding the real server.  The stub
    already says which device the engine is loading onto, so a client
    that expected another platform need not wait out the load."""
    handler = type("LoadingHandler", (_LoadingHandler,),
                   {"started": time.time(), "device": _device_identity()})
    stub = ThreadingHTTPServer((host, port), handler)
    threading.Thread(target=stub.serve_forever, daemon=True,
                     name="loading-stub").start()
    return stub


def load_config_file(cfg: EngineConfig, path: str) -> EngineConfig:
    """Merge a KAITO config YAML over the engine config (same mechanism
    as the reference's --kaito-config-file: user YAML from the Workspace
    ``inference.config`` ConfigMap wins over defaults)."""
    import yaml

    with open(path) as f:
        data = yaml.safe_load(f) or {}
    section = data.get("vllm") or data.get("engine") or data
    mapped = {}
    alias = {
        "max-model-len": "max_model_len", "max_model_len": "max_model_len",
        "max-num-seqs": "max_num_seqs", "max_num_seqs": "max_num_seqs",
        "served-model-name": "served_model_name",
        "served_model_name": "served_model_name",
        "tensor-parallel-size": "tensor_parallel",
        "tensor_parallel_size": "tensor_parallel",
        "pipeline-parallel-size": "pipeline_parallel",
        "pipeline_parallel_size": "pipeline_parallel",
        "data-parallel-size": "data_parallel",
        "data_parallel_size": "data_parallel",
        "sequence-parallel-size": "sequence_parallel",
        "sequence_parallel_size": "sequence_parallel",
        "page-size": "page_size", "page_size": "page_size",
        # vLLM's name for the prefill chunk budget a step
        "max-num-batched-tokens": "max_prefill_tokens",
        "max_num_batched_tokens": "max_prefill_tokens",
        "dtype": "dtype", "kv-cache-dtype": "kv_dtype",
        "quantization": "quantization",
        "seed": "seed", "port": "port",
        "structured-output": "structured_output",
        "structured_output": "structured_output",
        "grammar-cache-entries": "grammar_cache_entries",
        "grammar_cache_entries": "grammar_cache_entries",
        "grammar-max-states": "grammar_max_states",
        "grammar_max_states": "grammar_max_states",
    }
    for k, v in (section or {}).items():
        if k in alias and v is not None:
            mapped[alias[k]] = v
    return cfg.replace(**mapped)


def main(argv=None):
    from kaito_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(prog="kaito-tpu-serve")
    ap.add_argument("--model", default="tiny-llama-test")
    ap.add_argument("--port", type=int, default=5000)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--max-model-len", type=int, default=0)
    ap.add_argument("--max-num-seqs", type=int, default=8)
    ap.add_argument("--tensor-parallel-size", type=int,
                    default=int(os.environ.get("KAITO_TENSOR_PARALLEL", "1")))
    ap.add_argument("--pipeline-parallel-size", type=int,
                    default=int(os.environ.get("KAITO_PIPELINE_PARALLEL", "1")))
    ap.add_argument("--expert-parallel-size", type=int,
                    default=int(os.environ.get("KAITO_EXPERT_PARALLEL", "1")))
    ap.add_argument("--data-parallel-size", type=int,
                    default=int(os.environ.get("KAITO_DATA_PARALLEL", "1")))
    ap.add_argument("--sequence-parallel-size", type=int,
                    default=int(os.environ.get("KAITO_SEQUENCE_PARALLEL",
                                               "1")),
                    help="context-parallel prefill degree (mesh sequence "
                         "axis; long prompts run one ring-attention "
                         "dispatch instead of serial chunks)")
    ap.add_argument("--served-model-name", default="")
    ap.add_argument("--dtype", default="")
    ap.add_argument("--kv-cache-dtype", default=os.environ.get(
        "KAITO_KV_CACHE_DTYPE", ""),
        choices=["", "auto", "bfloat16", "float32", "int8"],
        help="KV page-pool dtype (vLLM flag-name parity). 'int8' "
             "quantizes K/V pages with per-page-per-head fp32 scales: "
             "~2x KV capacity and half the HBM read per decode step. "
             "Default/'auto' follows --dtype")
    ap.add_argument("--quantization", default=os.environ.get(
        "KAITO_QUANTIZATION", ""), choices=["", "int8", "int4"],
        help="weight-only quantization (vLLM flag-name parity): "
             "'int8' = per-out-channel symmetric, 'int4' = packed "
             "two-per-byte with per-group (g=128) scales and a fused "
             "Pallas dequant matmul on TPU (docs/quantization.md). "
             "Default off (bf16 weights)")
    ap.add_argument("--kaito-config-file", default="")
    ap.add_argument("--kaito-adapters-dir", default="")
    ap.add_argument("--adapter-slots", type=int,
                    default=int(os.environ.get("KAITO_ADAPTER_SLOTS", "0")),
                    help="dynamic multi-LoRA cache: HBM slot-table "
                         "capacity (docs/multi-lora.md). 0 = off — the "
                         "static boot-discovery path, /v1/adapters 403 "
                         "and the /metrics exposition stay byte-"
                         "identical")
    ap.add_argument("--adapter-rmax", type=int,
                    default=int(os.environ.get("KAITO_ADAPTER_RMAX", "16")),
                    help="max servable adapter rank; higher-rank loads "
                         "are refused (rank_overflow)")
    ap.add_argument("--adapter-host-bytes", type=int,
                    default=int(os.environ.get("KAITO_ADAPTER_HOST_BYTES",
                                               str(256 << 20))),
                    help="host-RAM overflow tier for evicted adapters "
                         "(fault back in without an operator round "
                         "trip; 0 disables the tier)")
    ap.add_argument("--adapter-allow-base-mismatch", action="store_true",
                    default=os.environ.get(
                        "KAITO_ADAPTER_ALLOW_BASE_MISMATCH", "") == "true",
                    help="serve adapters whose recorded base model "
                         "disagrees with the serving model (default: "
                         "refuse, counted as "
                         "adapter_load_failures{reason='base_mismatch'})")
    ap.add_argument("--adapter-source-allowlist",
                    default=os.environ.get("KAITO_ADAPTER_ALLOWLIST", ""),
                    help="comma-separated prefixes POST /v1/adapters may "
                         "pull from (hub://, oras://); '' = local paths "
                         "only")
    ap.add_argument("--weights-dir",
                    default=os.environ.get("KAITO_WEIGHTS_DIR", ""))
    ap.add_argument("--pd-enabled", action="store_true",
                    default=os.environ.get("KAITO_PD_ENABLED", "") == "true")
    ap.add_argument("--pd-source-allowlist",
                    default=os.environ.get("KAITO_PD_ALLOWLIST", ""))
    ap.add_argument("--kv-pool", action="store_true",
                    default=os.environ.get("KAITO_KV_POOL", "") == "true",
                    help="cluster-wide KV pool (docs/kv-pool.md): publish "
                         "finished prompt prefixes for cross-replica fetch "
                         "and serve them over the chunked PD wire "
                         "(default off; off keeps behavior and /metrics "
                         "byte-identical)")
    ap.add_argument("--kv-pool-bytes", type=int,
                    default=int(os.environ.get("KAITO_KV_POOL_BYTES",
                                               str(1 << 30))),
                    help="host bytes for the replica-local prefix store")
    ap.add_argument("--kv-pool-disk-bytes", type=int,
                    default=int(os.environ.get("KAITO_KV_POOL_DISK_BYTES",
                                               "0")),
                    help="tier-3 SSD budget under the pool (docs/"
                         "kv-pool.md \"Tier 3: SSD\"): host-LRU victims "
                         "demote to a bounded slab directory and misses "
                         "probe it before remote peers (0 = no disk "
                         "tier; off keeps behavior and /metrics "
                         "byte-identical)")
    ap.add_argument("--kv-pool-disk-dir",
                    default=os.environ.get("KAITO_KV_POOL_DISK_DIR", ""),
                    help="slab directory for the SSD tier ('' = "
                         "<tempdir>/kaito-kv-tier)")
    ap.add_argument("--kv-pool-advert-max", type=int,
                    default=int(os.environ.get("KAITO_KV_POOL_ADVERT_MAX",
                                               "0")),
                    help="cap /debug/kv_pool adverts to the freshest N "
                         "entries per EPP scrape (0 = unlimited)")
    ap.add_argument("--async-dispatch", action="store_true", default=None,
                    help="force the two-deep decode dispatch loop "
                         "(docs/decode-loop.md): device-resident loop state, "
                         "host postprocess overlapped with device compute. "
                         "Unset, the engine resolves it: on where the "
                         "backend is an accelerator (single process, no "
                         "pipeline parallelism), off on the CPU backend; "
                         "KAITO_ASYNC_DISPATCH=1/0 pins it")
    ap.add_argument("--comm-overlap", action="store_true",
                    default=os.environ.get("KAITO_COMM_OVERLAP", "")
                    .strip().lower() not in ("", "0", "false", "off"),
                    help="collective-compute overlap for TP decode "
                         "(docs/multichip.md): pipelined ring "
                         "reduce-scatter/all-gather in place of the "
                         "monolithic all-reduce, plus layer-ahead "
                         "quantized-slab prefetch (default off; off "
                         "keeps dispatch, numerics and /metrics "
                         "byte-identical; ignored off a TP>=2 mesh)")
    ap.add_argument("--kaito-disable-rate-limit", action="store_true")
    ap.add_argument("--enable-prefix-caching", dest="enable_prefix_caching",
                    action="store_true", default=True,
                    help="native radix-tree prefix reuse (default on; "
                         "vLLM flag-name parity)")
    ap.add_argument("--no-enable-prefix-caching", dest="enable_prefix_caching",
                    action="store_false")
    ap.add_argument("--kaito-kv-cache-cpu-memory-utilization", type=float,
                    default=float(os.environ.get(
                        "KAITO_KV_CPU_MEM_UTIL", "0")),
                    help="fraction of host RAM for the KV offload tier "
                         "(0 disables; reference contract "
                         "inference_api.py:503-556)")
    ap.add_argument("--max-queue-len", type=int, default=256)
    ap.add_argument("--prefill-pack", type=int, default=1,
                    help=argparse.SUPPRESS)
    ap.add_argument("--qos-config",
                    default=os.environ.get("KAITO_QOS_CONFIG", ""),
                    help="multi-tenant QoS classes as inline JSON or "
                         "@path to a file (docs/qos.md); '' = off "
                         "(single implicit tenant, legacy scheduling)")
    ap.add_argument("--max-pages", type=int, default=0,
                    help="KV page-pool size override (0 = size from "
                         "free HBM; vLLM num_gpu_blocks_override parity)")
    ap.add_argument("--speculative-ngram", type=int,
                    default=int(os.environ.get("KAITO_SPEC_NGRAM", "0")),
                    help="prompt-lookup speculative decoding: propose up "
                         "to N tokens per step (0 = off; exact greedy "
                         "equivalence)")
    ap.add_argument("--speculative-draft",
                    default=os.environ.get("KAITO_SPEC_DRAFT", ""),
                    help="draft preset for two-model speculative decoding "
                         "(must share the target's tokenizer; '' = off). "
                         "Greedy output stays bit-exact; sampled output "
                         "stays distribution-identical (rejection "
                         "sampling). See docs/speculative.md")
    ap.add_argument("--speculative-draft-k", type=int,
                    default=int(os.environ.get("KAITO_SPEC_DRAFT_K", "4")),
                    help="max adaptive speculation depth per slot (the "
                         "accept-rate controller moves within [1, K] and "
                         "falls back to n-gram/plain on poor acceptance)")
    ap.add_argument("--speculative-draft-weights-dir",
                    default=os.environ.get("KAITO_SPEC_DRAFT_WEIGHTS", ""),
                    help="safetensors dir for the draft's weights "
                         "('' = synthetic)")
    ap.add_argument("--request-timeout-s", type=float, default=0.0,
                    help="server-default request deadline in seconds "
                         "(0 = none); expired requests get 408-style "
                         "errors before consuming TPU time")
    ap.add_argument("--kv-shed-threshold", type=float, default=0.0,
                    help="shed new requests with 429 + Retry-After when "
                         "KV page usage crosses this fraction while a "
                         "queue exists (0 = off)")
    ap.add_argument("--kv-import-retries", type=int, default=1,
                    help="transient KV-transfer failures fall back to "
                         "local recompute this many times per request")
    ap.add_argument("--slow-request-threshold-s", type=float, default=0.0,
                    help="dump a request's span tree to the log when its "
                         "end-to-end latency crosses this (0 = off); see "
                         "docs/observability.md")
    ap.add_argument("--no-structured-output", dest="structured_output",
                    action="store_false", default=os.environ.get(
                        "KAITO_STRUCTURED_OUTPUT", "1") != "0",
                    help="reject response_format / forced tool_choice "
                         "with a typed 400 (docs/structured-output.md); "
                         "on by default and pay-per-use")
    ap.add_argument("--grammar-cache-entries", type=int,
                    default=int(os.environ.get(
                        "KAITO_GRAMMAR_CACHE_ENTRIES", "64")),
                    help="compiled-schema LRU entries "
                         "(docs/structured-output.md cache sizing)")
    ap.add_argument("--grammar-max-states", type=int,
                    default=int(os.environ.get(
                        "KAITO_GRAMMAR_MAX_STATES", "512")),
                    help="DFA state cap per grammar; each state costs "
                         "O(vocab) bytes in the packed device mask table")
    ap.add_argument("--devprof-interval-s", type=float,
                    default=float(os.environ.get(
                        "KAITO_DEVPROF_INTERVAL_S", "0")),
                    help="sampled device-time attribution "
                         "(docs/observability.md): capture a short "
                         "jax.profiler window this often and fold it "
                         "into comm/compute/idle buckets on /metrics "
                         "and /debug/device (0 = off; off keeps the "
                         "exposition byte-identical and /debug/device "
                         "answers 403)")
    ap.add_argument("--devprof-window-s", type=float,
                    default=float(os.environ.get(
                        "KAITO_DEVPROF_WINDOW_S", "0.25")),
                    help="capture length of each sampled devprof window")
    ap.add_argument("--itl", action="store_true",
                    default=os.environ.get("KAITO_ITL", "")
                    in ("1", "true"),
                    help="stamp every retired token and expose true "
                         "per-token inter-token latency "
                         "(kaito:inter_token_latency_seconds + the "
                         "watchdog's itl_p99 SLI); off keeps the "
                         "exposition and the decode path byte-identical")
    ap.add_argument("--slo-itl-p99-ms", type=float,
                    default=float(os.environ.get(
                        "KAITO_SLO_ITL_P99_MS", "250")),
                    help="ITL p99 SLO target (ms); gaps beyond it count "
                         "as stalls and burn the itl_p99 budget")
    ap.add_argument("--inference-role",
                    default=os.environ.get("KAITO_INFERENCE_ROLE", ""),
                    help="serving role this replica's SLO burn "
                         "attributes to (prefill/decode; '' = unified) "
                         "— set by the MRI role annotation")
    ap.add_argument("--flight-dir",
                    default=os.environ.get("KAITO_FLIGHT_DIR", ""),
                    help="directory for incident flight-recorder "
                         "bundles (written on SLO page, engine-fatal "
                         "and SIGTERM-with-in-flight triggers; '' = "
                         "off, /debug/flight answers 403)")
    ap.add_argument("--flight-max-bundles", type=int,
                    default=int(os.environ.get(
                        "KAITO_FLIGHT_MAX_BUNDLES", "16")),
                    help="bundles kept under --flight-dir (LRU by mtime)")
    args = ap.parse_args(argv)

    import jax

    # multi-host rendezvous BEFORE any backend use: pod 0 is the JAX
    # coordinator (the role Ray's head node plays for the reference,
    # interface.go:534-560); single-process runs are a no-op
    from kaito_tpu.parallel.mesh import initialize_distributed

    initialize_distributed()

    on_tpu = jax.devices()[0].platform not in ("cpu",)
    cfg = EngineConfig(
        model=args.model, port=args.port, max_model_len=args.max_model_len,
        max_num_seqs=args.max_num_seqs, served_model_name=args.served_model_name,
        tensor_parallel=args.tensor_parallel_size,
        pipeline_parallel=args.pipeline_parallel_size,
        expert_parallel=args.expert_parallel_size,
        data_parallel=args.data_parallel_size,
        sequence_parallel=args.sequence_parallel_size,
        dtype=args.dtype or ("bfloat16" if on_tpu else "float32"),
        kv_dtype=(args.kv_cache_dtype
                  if args.kv_cache_dtype not in ("", "auto") else
                  args.dtype or ("bfloat16" if on_tpu else "float32")),
        adapters_dir=args.kaito_adapters_dir,
        adapter_slots=args.adapter_slots,
        adapter_rmax=args.adapter_rmax,
        adapter_host_bytes=args.adapter_host_bytes,
        adapter_allow_base_mismatch=args.adapter_allow_base_mismatch,
        adapter_source_allowlist=args.adapter_source_allowlist,
        weights_dir=args.weights_dir,
        quantization=args.quantization,
        pd_enabled=args.pd_enabled,
        pd_source_allowlist=args.pd_source_allowlist,
        kv_pool_enabled=args.kv_pool,
        kv_pool_bytes=args.kv_pool_bytes,
        kv_pool_disk_bytes=args.kv_pool_disk_bytes,
        kv_pool_disk_dir=args.kv_pool_disk_dir,
        kv_pool_advert_max=args.kv_pool_advert_max,
        async_dispatch=args.async_dispatch,
        comm_overlap=args.comm_overlap,
        disable_rate_limit=args.kaito_disable_rate_limit,
        enable_prefix_caching=args.enable_prefix_caching,
        host_kv_offload_bytes=int(
            args.kaito_kv_cache_cpu_memory_utilization
            * os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")),
        max_queue_len=args.max_queue_len,
        qos_config=args.qos_config,
        max_pages=args.max_pages,
        speculative_ngram=args.speculative_ngram,
        speculative_draft=args.speculative_draft,
        speculative_draft_k=args.speculative_draft_k,
        speculative_draft_weights_dir=args.speculative_draft_weights_dir,
        request_timeout_s=args.request_timeout_s,
        kv_shed_threshold=args.kv_shed_threshold,
        kv_import_retries=args.kv_import_retries,
        slow_request_threshold_s=args.slow_request_threshold_s,
        structured_output=args.structured_output,
        grammar_cache_entries=args.grammar_cache_entries,
        grammar_max_states=args.grammar_max_states,
        devprof_interval_s=args.devprof_interval_s,
        devprof_window_s=args.devprof_window_s,
        itl_enabled=args.itl,
        slo_itl_p99_ms=args.slo_itl_p99_ms,
        role=args.inference_role,
        flight_dir=args.flight_dir,
        flight_max_bundles=args.flight_max_bundles,
    )
    if args.kaito_config_file:
        cfg = load_config_file(cfg, args.kaito_config_file)

    logging.basicConfig(level=logging.INFO)
    if args.prefill_pack != 1:
        logger.warning("--prefill-pack %d: prefill packing was removed; "
                       "the serial scheduler serves", args.prefill_pack)
    # probes/Prometheus must not flap during the minutes-long weight
    # load + compile: serve a loading stub on the real port until the
    # engine exists (reference inference_api.py:265-415)
    stub = None
    if jax.process_index() == 0:
        try:
            stub = start_loading_stub(args.host, cfg.port)
        except OSError:
            logger.warning("loading stub could not bind %s:%d; probes "
                           "will see connection refused during load",
                           args.host, cfg.port)
    if "/" in cfg.model:
        # auto-generated presets render the FULL org/model id into
        # --model; the pod resolves it the same way the controller did
        # (committed catalog first, HF hub second)
        from kaito_tpu.models.hub import install_default_fetcher

        install_default_fetcher()
    if jax.process_count() > 1:
        # leader-only HTTP; workers follow the step broadcast headless
        from kaito_tpu.engine.multihost import MultiHostEngine

        if cfg.data_parallel > 1:
            raise ValueError("in-engine data_parallel is single-host; "
                             "scale multi-host deployments with "
                             "InferenceSet replicas")
        engine = MultiHostEngine(cfg)
        if not engine.is_leader:
            logger.info("worker process %d: joining lockstep loop",
                        jax.process_index())
            engine.run_worker()
            return
        engine.start()
    elif cfg.data_parallel > 1:
        # reference tier 1: N engine groups on one node behind one
        # HTTP front (interface.go:500-512 --data-parallel-size)
        from kaito_tpu.engine.dp import DataParallelEngine

        engine = DataParallelEngine(cfg)
        engine.start()
    else:
        engine = InferenceEngine(cfg)
        engine.start()
    if stub is not None:
        stub.shutdown()
        stub.server_close()
    server = make_server(engine, cfg, host=args.host)
    # SIGTERM (what a kubelet, or a parent that started this server,
    # sends) takes the same graceful path as ^C: raising
    # KeyboardInterrupt re-enters the teardown below, the engine stops
    # and the process exits 0.  With a flight recorder, requests still
    # in flight (a drain that was going to lose work) snapshot the
    # black box first — the third flight trigger.
    import signal

    def _on_sigterm(signum, frame):
        st = server.state
        in_flight = engine.num_running + engine.num_waiting
        if st.flight is not None and in_flight > 0:
            st.flight.record(
                "sigterm", reason=f"{in_flight} request(s) in flight")
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _on_sigterm)
    logger.info("serving %s on %s:%d", cfg.model, args.host, cfg.port)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        engine.stop()


if __name__ == "__main__":
    main()

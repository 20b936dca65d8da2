"""In-engine data parallelism: N engine replica groups behind one front.

The serving counterpart of the reference's tier 1, which launches vLLM
with ``--data-parallel-size=<GPUs>`` so one pod runs N engine groups on
one node (`/root/reference/pkg/model/interface.go:500-512`).  TPU-native
shape: the visible chips partition into ``data_parallel`` groups of
``tensor_parallel x expert_parallel`` devices; each group runs a full
``InferenceEngine`` (own mesh, own weights copy, own KV pool, own
scheduler thread), and this facade load-balances requests across them
while exposing ONE engine surface to the HTTP server — aggregate
counters, summed page-pool metrics, shared adapter registry.

Routing is least-loaded (waiting + running) at submit time; aborts
route back to the owning group via the request's ``_dp_group`` tag.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

import jax

from kaito_tpu.engine.config import EngineConfig
from kaito_tpu.engine.engine import InferenceEngine, Request, SamplingParams

logger = logging.getLogger(__name__)


class _AggregatePool:
    """Summed allocator view for the /metrics gauges."""

    def __init__(self, engines):
        self._engines = engines

    @property
    def available(self) -> int:
        return sum(e.allocator.available for e in self._engines)

    @property
    def num_pages(self) -> int:
        # gauges compute usable pages as num_pages - 1 per pool; keep
        # that identity for the aggregate (N pools reserve N null pages)
        return sum(e.allocator.num_pages - 1 for e in self._engines) + 1


class _AggregateHostKV:
    def __init__(self, engines):
        self._engines = engines

    @property
    def used_bytes(self) -> int:
        return sum(e.host_kv.used_bytes for e in self._engines
                   if e.host_kv is not None)


class DataParallelEngine:
    """N InferenceEngine groups, one engine surface."""

    def __init__(self, cfg: EngineConfig, metadata=None):
        dp = cfg.data_parallel
        if dp < 2:
            raise ValueError(f"data_parallel must be >= 2, got {dp}")
        if cfg.pipeline_parallel > 1:
            raise ValueError("data_parallel does not compose with "
                             "pipeline_parallel in-engine; scale PP "
                             "deployments with InferenceSet replicas")
        if cfg.pd_enabled:
            raise ValueError("P/D disaggregation routes KV by page id; "
                             "run it with data_parallel=1 per role")
        group = (max(1, cfg.tensor_parallel) * max(1, cfg.expert_parallel)
                 * max(1, cfg.sequence_parallel))
        devices = jax.devices()
        if len(devices) < dp * group:
            raise ValueError(
                f"data_parallel={dp} x (sp*ep*tp)={group} needs "
                f"{dp * group} devices, have {len(devices)}")
        self.cfg = cfg
        self.engines: list[InferenceEngine] = []
        for g in range(dp):
            mesh = self._group_mesh(devices[g * group:(g + 1) * group], cfg)
            eng = InferenceEngine(cfg.replace(data_parallel=1),
                                  metadata=metadata, mesh=mesh)
            self.engines.append(eng)
        first = self.engines[0]
        self.md = first.md
        self.tokenizer = first.tokenizer
        self.adapter_index = first.adapter_index
        self.adapters_merged = first.adapters_merged
        self.allocator = _AggregatePool(self.engines)
        self.host_kv = (_AggregateHostKV(self.engines)
                        if any(e.host_kv is not None for e in self.engines)
                        else None)
        # one histogram family across groups: every group's scheduler
        # observes into the SAME (thread-safe) series, so /metrics
        # exposes one kaito:engine_step_seconds for the whole pod.
        # Tracers/timelines stay per-group — the server's /debug/trace
        # and /debug/timeline merge across `self.engines`.
        for e in self.engines[1:]:
            e.step_hist = first.step_hist
            e.queue_wait_hist = first.queue_wait_hist
            e.phase_hists = first.phase_hists
        self.step_hist = first.step_hist
        self.queue_wait_hist = first.queue_wait_hist
        self.phase_hists = first.phase_hists
        # handler threads use only its annotate (no shared totals)
        self.phases = first.phases
        self.compile_totals = first.compile_totals      # process-wide
        self._rr = 0
        self._lock = threading.Lock()
        logger.info("data-parallel serving: %d groups x %d device(s)",
                    dp, group)

    @staticmethod
    def _group_mesh(devices, cfg: EngineConfig):
        """Per-group mesh.  Even a 1-device group gets an explicit mesh
        so its weights/KV land on ITS device (not the process default)."""
        from kaito_tpu.parallel.mesh import build_mesh
        from kaito_tpu.parallel.plan import make_mesh_spec

        spec = make_mesh_spec(sequence=max(1, cfg.sequence_parallel),
                              expert=max(1, cfg.expert_parallel),
                              tensor=max(1, cfg.tensor_parallel))
        return build_mesh(spec, devices)

    # ------------------------------------------------------------------
    # Engine surface (what the HTTP server and metrics touch)
    # ------------------------------------------------------------------

    @property
    def counters(self) -> dict:
        agg: dict = {}
        for e in self.engines:
            for k, v in e.counters.items():
                agg[k] = agg.get(k, 0) + v
        return agg

    @property
    def qos(self):
        # all groups parsed the same cfg.qos_config
        return self.engines[0].qos

    @property
    def num_waiting(self) -> int:
        return sum(e.num_waiting for e in self.engines)

    def num_waiting_for(self, tenant: str) -> int:
        return sum(e.num_waiting_for(tenant) for e in self.engines)

    @property
    def num_running(self) -> int:
        return sum(e.num_running for e in self.engines)

    def _pick(self) -> InferenceEngine:
        """Least-loaded group (waiting+running); the scan starts at a
        rotating offset so ties (an idle fleet) still round-robin."""
        n = len(self.engines)
        with self._lock:
            self._rr = (self._rr + 1) % n
            start = self._rr
        return min((self.engines[(start + i) % n] for i in range(n)),
                   key=lambda e: e.num_waiting + e.num_running)

    def submit(self, prompt_tokens, params: SamplingParams,
               req_id: Optional[str] = None, export_kv: bool = False,
               adapter: str = "",
               timeout_s: Optional[float] = None,
               trace_id: Optional[str] = None,
               tenant: str = "", priority: str = "",
               pool_blocks: Optional[list] = None) -> Request:
        if export_kv:
            raise RuntimeError("P/D KV export requires data_parallel=1")
        eng = self._pick()
        req = eng.submit(prompt_tokens, params, req_id=req_id,
                         adapter=adapter, timeout_s=timeout_s,
                         trace_id=trace_id, tenant=tenant,
                         priority=priority, pool_blocks=pool_blocks)
        req._dp_group = eng
        return req

    def abort(self, req: Request) -> None:
        getattr(req, "_dp_group", self.engines[0]).abort(req)

    def submit_with_kv(self, *a, **kw):
        raise RuntimeError("P/D KV import requires data_parallel=1")

    def submit_with_kv_chunked(self, *a, **kw):
        raise RuntimeError("P/D KV import requires data_parallel=1")

    @property
    def kv_exports(self):
        return self.engines[0].kv_exports

    def generate(self, prompt: str,
                 params: Optional[SamplingParams] = None) -> str:
        params = params or SamplingParams()
        toks = self.tokenizer.encode(prompt)
        req = self.submit(toks, params)
        return self.tokenizer.decode(list(req.stream()))

    def start(self):
        for e in self.engines:
            e.start()

    def stop(self):
        for e in self.engines:
            e.stop()

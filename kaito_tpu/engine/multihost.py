"""Multi-host serving: leader-only HTTP + SPMD lockstep worker loop.

The TPU-native replacement for the reference's Ray leader/worker serving
bootstrap (`/root/reference/pkg/model/interface.go:534-560`
buildMultiNodeRayCommand + multi-node-serving.sh): where the reference
starts a Ray head on pod 0 and vLLM drives remote workers over NCCL,
here every pod joins `jax.distributed` (coordinator = pod 0 via the
headless-service DNS, `kaito_tpu/parallel/mesh.py:initialize_distributed`),
and the engine's jitted steps run as ONE SPMD program over the global
mesh — XLA's collectives replace NCCL, and there is no remote-actor
layer at all.

Design: the scheduler is deterministic given (request stream, step
index), so instead of broadcasting every scheduling decision, the
leader broadcasts only the REQUEST STREAM — each step begins with a
small broadcast of newly submitted requests/aborts (usually empty), and
every process then runs the identical scheduler + identical jitted
step.  Host-visible step outputs (sampled tokens) are replicated across
processes by construction, so each process advances its own copy of the
engine state without further communication.

Leader (process 0) serves HTTP; workers run the same loop headless.
Worker health = coordinator TCP liveness (`kaito_tpu/runtime/health.py`),
matching the reference's multi-node-health-check.py contract.
"""

from __future__ import annotations

import collections
import json
import logging
import time
from typing import Optional

import jax
import numpy as np

from kaito_tpu.engine.engine import InferenceEngine, Request, SamplingParams

logger = logging.getLogger(__name__)

_PAD = 4096   # blob padding quantum: bounds the broadcast compile cache


def broadcast_blob(blob: Optional[bytes]) -> bytes:
    """Leader (process 0) passes bytes, workers pass None; all return
    the leader's bytes.  Two fixed-shape broadcasts (length, padded
    payload) so the underlying collectives compile once per quantum."""
    from jax.experimental import multihost_utils

    n = np.zeros((1,), np.int32)
    if blob is not None:
        n[0] = len(blob)
    n = np.asarray(multihost_utils.broadcast_one_to_all(n))
    size = int(n[0])
    if size == 0:
        return b""
    padded = -(-size // _PAD) * _PAD
    buf = np.zeros((padded,), np.uint8)
    if blob is not None:
        buf[:size] = np.frombuffer(blob, np.uint8)
    out = np.asarray(multihost_utils.broadcast_one_to_all(buf))
    return out[:size].tobytes()


def _wire_request(req: Request) -> dict:
    p = req.params
    return {
        "req_id": req.req_id,
        "tokens": req.prompt_tokens,
        "max_tokens": p.max_tokens,
        "temperature": p.temperature,
        "top_k": p.top_k,
        "top_p": p.top_p,
        "stop": list(p.stop_token_ids),
        "seed": p.seed,
        "ignore_eos": p.ignore_eos,
        "logprobs": p.logprobs,
        "presence_penalty": p.presence_penalty,
        "frequency_penalty": p.frequency_penalty,
        "repetition_penalty": p.repetition_penalty,
        "min_p": p.min_p,
        "adapter": req.adapter,
        "trace_id": req.trace_id,
        "tenant": req.tenant,
        "priority": req.priority,
    }


def _unwire_request(item: dict) -> Request:
    params = SamplingParams(
        max_tokens=item["max_tokens"], temperature=item["temperature"],
        top_k=item["top_k"], top_p=item["top_p"],
        stop_token_ids=tuple(item["stop"]), seed=item["seed"],
        ignore_eos=item["ignore_eos"],
        logprobs=bool(item.get("logprobs", False)),
        presence_penalty=float(item.get("presence_penalty", 0.0)),
        frequency_penalty=float(item.get("frequency_penalty", 0.0)),
        repetition_penalty=float(item.get("repetition_penalty", 1.0)),
        min_p=float(item.get("min_p", 0.0)))
    return Request(item["req_id"], list(item["tokens"]), params,
                   adapter=item.get("adapter", ""),
                   trace_id=item.get("trace_id") or item["req_id"],
                   tenant=item.get("tenant", ""),
                   priority=int(item.get("priority", 0)))


class MultiHostEngine(InferenceEngine):
    """Engine whose scheduler runs in lockstep on every process.

    On the leader, ``submit`` stages requests for the next step-boundary
    broadcast instead of enqueueing directly, so no process ever sees a
    request before the others.
    """

    def __init__(self, cfg, metadata=None, params=None, mesh=None):
        if cfg.pd_enabled:
            raise ValueError("P/D disaggregation runs single-host per "
                             "role (each role scales with InferenceSet "
                             "replicas, not multi-host lockstep)")
        self.is_leader = jax.process_index() == 0
        super().__init__(cfg, metadata=metadata, params=params, mesh=mesh)
        self._staged: "collections.deque[Request]" = collections.deque()
        self._live: dict[str, Request] = {}
        self._abort_requested: set[str] = set()

    def submit(self, prompt_tokens, params, req_id=None,
               export_kv=False, adapter: str = "",
               timeout_s=None, trace_id=None,
               tenant: str = "", priority: str = "",
               pool_blocks=None) -> Request:
        # pool_blocks (the server's KV-pool publish hashes) are dropped:
        # publishing gathers device pages, which the leader must not do
        # alone while the workers sit in the step broadcast
        if not self.is_leader:
            raise RuntimeError("submit() is leader-only; workers receive "
                               "requests via the step broadcast")
        if export_kv:
            raise ValueError("PD export is single-host per role")
        if adapter and adapter not in self.adapter_index:
            raise ValueError(f"unknown adapter {adapter!r}")
        self._validate_submit(prompt_tokens, params)
        with self._lock:
            self.counters["requests_total"] += 1
            # pin the auto-seed NOW: the _admit-time fallback reads
            # counters that advance at different moments on leader vs
            # workers, which would diverge the replicated sampling state
            if not params.seed:
                import dataclasses

                params = dataclasses.replace(
                    params, seed=self.counters["requests_total"])
            rid = req_id or f"req-{self.counters['requests_total']}"
            t, prio = self._resolve_qos(tenant, priority)
            req = Request(rid,
                          list(prompt_tokens), params, adapter=adapter,
                          deadline=self._deadline_for(timeout_s),
                          trace_id=trace_id or rid,
                          tenant=t, priority=prio)
            self._staged.append(req)
        self._wake.set()
        return req

    def abort(self, req: Request) -> None:
        """Route aborts through the step broadcast: every process must
        see the abort at the same step boundary, or the lockstep engine
        states diverge."""
        with self._lock:
            self._abort_requested.add(req.req_id)
        self._wake.set()

    def _expire_deadlines(self) -> bool:
        """Deadline expiry must be deterministic across processes: the
        wire format is clock-free, so worker replicas carry no deadline
        and a local wall-clock sweep would expire a request on the
        leader only — diverging the lockstep schedulers.  The leader
        instead converts expirations into broadcast aborts, so every
        process retires the request at the same step boundary."""
        if not self.is_leader:
            return False
        now = time.monotonic()
        did = False
        with self._lock:
            live = list(self._live.values()) + list(self._staged)
            for r in live:
                if r.deadline is not None and now > r.deadline \
                        and not r.aborted and r.finish_time is None:
                    if r.error is None:
                        r.error = {"status": 408,
                                   "type": "deadline_exceeded",
                                   "message": f"request {r.req_id} exceeded "
                                              "its deadline before completing"}
                    self.counters["requests_expired_total"] += 1
                    self._abort_requested.add(r.req_id)
                    r.deadline = None      # one broadcast abort per request
                    did = True
        if did:
            self._wake.set()
        return did

    def submit_with_kv_chunked(self, *a, **kw):
        raise RuntimeError(
            "P/D KV import is not supported on a multi-host engine: the "
            "request stream is broadcast at step boundaries and a "
            "leader-only import would diverge the replicas")

    def submit_with_kv(self, *a, **kw):
        raise RuntimeError("PD KV import is single-host per role")

    @property
    def num_waiting(self) -> int:
        with self._lock:
            return self._waiting_count + len(self._staged)

    # ------------------------------------------------------------------
    # Lockstep loop
    # ------------------------------------------------------------------

    def _gather_payload(self) -> bytes:
        items: list[Request] = []
        with self._lock:
            while self._staged:
                items.append(self._staged.popleft())
            self._pending_apply = items
            aborts = sorted(self._abort_requested)
            self._abort_requested.clear()
        payload = {
            "reqs": [_wire_request(r) for r in items],
            "aborts": aborts,
            "stop": self._stop.is_set(),
        }
        return json.dumps(payload).encode()

    def _apply_payload(self, payload: dict):
        if self.is_leader:
            reqs = self._pending_apply
        else:
            reqs = [_unwire_request(item) for item in payload["reqs"]]
            with self._lock:
                self.counters["requests_total"] += len(reqs)
        with self._lock:
            for req in reqs:
                self._waiting_count += 1
                if self.qos is None:
                    self.waiting.append(req)
                else:
                    self._qos_push_locked(req)
                self._live[req.req_id] = req
        for rid in payload["aborts"]:
            req = self._live.get(rid)
            if req is not None:
                req.aborted = True
                # the abort crossed the step broadcast: every process
                # records it under the request's end-to-end trace id
                self.tracer.record("abort.broadcast",
                                   req.trace_id or rid,
                                   time.monotonic(), 0.0, req_id=rid)

    def _prune_live(self):
        for rid in [rid for rid, r in self._live.items()
                    if r.finish_time is not None]:
            self._live.pop(rid, None)

    def _loop(self):
        logger.info("multi-host lockstep loop: process %d/%d (%s)",
                    jax.process_index(), jax.process_count(),
                    "leader" if self.is_leader else "worker")
        while True:
            blob = self._gather_payload() if self.is_leader else None
            blob = broadcast_blob(blob)
            payload = json.loads(blob)
            self._apply_payload(payload)
            if payload["stop"]:
                logger.info("stop broadcast received; draining")
                self._fail_all()
                self._stop.set()
                return
            try:
                did_work = self.step()
            except Exception:
                logger.exception("engine loop failure; failing in-flight "
                                 "requests")
                self._fail_all()
                continue
            self._prune_live()
            if not did_work and self.is_leader:
                # idle throttle: workers block in the next broadcast
                self._wake.wait(timeout=0.02)
                self._wake.clear()

    def run_worker(self):
        """Blocking worker entry (no HTTP): follow the leader until the
        stop broadcast."""
        if self.is_leader:
            raise RuntimeError("run_worker() is for non-leader processes")
        self._loop()

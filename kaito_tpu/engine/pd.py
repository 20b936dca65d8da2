"""Prefill/decode disaggregation: chunked, overlapped KV hand-off.

The TPU-native replacement for the reference's NIXL side-channel
(``preset_inferences.go:909-938`` + vLLM NixlConnector,
``inference_api.py:499-515``): the prefill engine exports a request's
KV pages, ships them over the pod side-channel (HTTP on the engine
port), and the decode engine scatters them into its own pages and
continues from the prompt boundary — no prefill compute on the decode
slice.

Round-4 design (replaces the whole-request-blob hand-off, which
serialized hundreds of MB synchronously for a 70B prefill at 8k):

- The prefill engine stages a COMPACT DEVICE COPY of the request's
  pages (one on-device gather on the engine thread — no host sync,
  no decode stall), then a background copier drains it to host
  chunk-by-chunk (~8 MiB chunks over layer/page ranges).  A chunk is
  fetchable the moment it lands, so the decode side's pulls overlap
  the remaining device→host copies.
- The decode engine admits the request immediately and scatters
  arriving chunks from its scheduler loop — bounded work per step, so
  the import overlaps with ongoing decode of other requests.  Decode
  of the imported request begins when its last chunk lands.
- ``should_transfer`` is the transfer-vs-recompute break-even model:
  for short prompts, recomputing the prefill locally is cheaper than
  moving the KV, and the serving layer falls back to a local prefill.

Wire format: each chunk is ``{json header}\\n`` + raw K bytes + raw V
bytes (dtype preserved via ``ml_dtypes`` names, so bf16 KV round-trips
without up-cast).
"""

from __future__ import annotations

import json
import logging
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kaito_tpu.engine.devprof import phase_scope

try:  # registers 'bfloat16' & friends with np.dtype()
    import ml_dtypes  # noqa: F401
except ImportError:  # pragma: no cover - ml_dtypes ships with jax
    pass

from kaito_tpu.engine.kv_cache import KVCache
from kaito_tpu.utils.failpoints import FAILPOINTS

logger = logging.getLogger(__name__)

CHUNK_TARGET_BYTES = 8 << 20
STAGE_TTL_S = 120.0
# lazy_drain staged exports pin HBM until the first consumer starts the
# D2H copy; after this grace window the registry starts the drain itself
# so an unpulled export degrades to host memory, never a pinned-HBM leak
EXPORT_DRAIN_GRACE_S = 5.0


# ---------------------------------------------------------------------------
# chunk planning + wire format
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ChunkPlan:
    """A [layer_lo:layer_hi, page_lo:page_hi] slab of a request's KV."""

    layer_lo: int
    layer_hi: int
    page_lo: int
    page_hi: int

    def to_json(self) -> list[int]:
        return [self.layer_lo, self.layer_hi, self.page_lo, self.page_hi]

    @staticmethod
    def from_json(v) -> "ChunkPlan":
        return ChunkPlan(*map(int, v))


def plan_chunks(n_layers: int, n_pages: int, bytes_per_layer_page: int,
                target_bytes: int = CHUNK_TARGET_BYTES) -> list[ChunkPlan]:
    """Split [n_layers, n_pages] into ~target_bytes slabs.

    Whole layers are grouped while they fit; a single layer wider than
    the target splits over page ranges.  ``bytes_per_layer_page`` counts
    K and V together."""
    plans: list[ChunkPlan] = []
    layer_bytes = max(1, n_pages * bytes_per_layer_page)
    if layer_bytes <= target_bytes:
        layers_per = max(1, target_bytes // layer_bytes)
        for lo in range(0, n_layers, layers_per):
            plans.append(ChunkPlan(lo, min(lo + layers_per, n_layers),
                                   0, n_pages))
    else:
        pages_per = max(1, target_bytes // bytes_per_layer_page)
        for layer in range(n_layers):
            for p in range(0, n_pages, pages_per):
                plans.append(ChunkPlan(layer, layer + 1, p,
                                       min(p + pages_per, n_pages)))
    return plans


def serialize_chunk(k: np.ndarray, v: np.ndarray,
                    k_scale: Optional[np.ndarray] = None,
                    v_scale: Optional[np.ndarray] = None) -> bytes:
    head = {"shape": list(k.shape),
            "v_shape": list(v.shape),
            "dtype": str(k.dtype)}
    body = k.tobytes() + v.tobytes()
    if k_scale is not None:
        # quantized KV: the fp32 page-scale slabs ride the same chunk
        head["ks_shape"] = list(k_scale.shape)
        head["vs_shape"] = list(v_scale.shape)
        body += (np.ascontiguousarray(k_scale, np.float32).tobytes()
                 + np.ascontiguousarray(v_scale, np.float32).tobytes())
    return json.dumps(head).encode() + b"\n" + body


def deserialize_chunk(payload: bytes) -> tuple[
        np.ndarray, np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]:
    head, _, body = payload.partition(b"\n")
    meta = json.loads(head)
    k_shape = tuple(meta["shape"])
    # V carries its OWN shape: MLA caches hold a zero-size V placeholder
    # (create_kv_cache), so V must never be assumed K-shaped on the wire.
    v_shape = tuple(meta.get("v_shape", meta["shape"]))
    dt = np.dtype(meta["dtype"])
    nk = int(np.prod(k_shape)) * dt.itemsize
    nv = int(np.prod(v_shape)) * dt.itemsize
    ks_shape = tuple(meta["ks_shape"]) if "ks_shape" in meta else None
    vs_shape = tuple(meta["vs_shape"]) if "vs_shape" in meta else None
    nks = int(np.prod(ks_shape)) * 4 if ks_shape is not None else 0
    nvs = int(np.prod(vs_shape)) * 4 if vs_shape is not None else 0
    if len(body) != nk + nv + nks + nvs:
        raise ValueError(f"chunk body is {len(body)} bytes, expected "
                         f"{nk + nv + nks + nvs} for K {k_shape} + V "
                         f"{v_shape} {dt}"
                         + (f" + scales {ks_shape}/{vs_shape}"
                            if ks_shape is not None else ""))
    k = np.frombuffer(body[:nk], dt).reshape(k_shape)
    v = np.frombuffer(body[nk:nk + nv], dt).reshape(v_shape)
    ks = vs = None
    if ks_shape is not None:
        off = nk + nv
        ks = np.frombuffer(body[off:off + nks], np.float32).reshape(ks_shape)
        vs = np.frombuffer(body[off + nks:], np.float32).reshape(vs_shape)
    return k, v, ks, vs


# ---------------------------------------------------------------------------
# one-shot export/import (DP-local hand-off and small transfers)
# ---------------------------------------------------------------------------

def _gather_canonical(cache: KVCache, pages: list[int]):
    """Device gather of a request's pages in the CANONICAL layer-major
    layout, from either a flat ([L, P, ...]) or pipeline-staged
    ([S, L/S, P, ...]) pool.  Returns ``(k, v, k_scale, v_scale)``;
    the scales are None for non-quantized pools (and always for staged
    pools — int8 KV is gated off under pipeline parallelism)."""
    idx = jnp.asarray(pages, jnp.int32)
    if cache.k.ndim == 6:                # stage-split pool
        S, Lps = cache.k.shape[0], cache.k.shape[1]
        return (cache.k[:, :, idx].reshape((S * Lps, len(pages))
                                           + cache.k.shape[3:]),
                cache.v[:, :, idx].reshape((S * Lps, len(pages))
                                           + cache.v.shape[3:]),
                None, None)
    ks = cache.k_scale[:, idx] if cache.k_scale is not None else None
    vs = cache.v_scale[:, idx] if cache.v_scale is not None else None
    return cache.k[:, idx], cache.v[:, idx], ks, vs


def export_kv(cache: KVCache, pages: list[int]) -> tuple[dict, bytes]:
    """Gather a request's pages to host in one shot (canonical wire
    layout, layout-independent like stage_export).

    Returns (meta, payload).  The chunked path below supersedes this for
    serving; it remains the simple primitive for tests and in-process
    hand-off."""
    k_dev, v_dev, ks_dev, vs_dev = _gather_canonical(cache, pages)
    k = np.asarray(k_dev)                # [L, n, ps, Hkv, D]
    v = np.asarray(v_dev)
    ks = np.asarray(ks_dev) if ks_dev is not None else None
    vs = np.asarray(vs_dev) if vs_dev is not None else None
    meta = {"shape": list(k.shape), "v_shape": list(v.shape),
            "dtype": str(k.dtype)}
    if ks is not None:
        meta["ks_shape"] = list(ks.shape)
        meta["vs_shape"] = list(vs.shape)
    return meta, serialize_chunk(k, v, ks, vs)


def import_kv(cache: KVCache, pages: list[int], payload: bytes,
              meta: dict) -> KVCache:
    """Scatter a one-shot transfer into the local pool."""
    k, v, ks, vs = deserialize_chunk(payload)
    return import_arrays(cache, pages, k, v, ks, vs)


@partial(jax.jit, static_argnames=("page_axis",))
@phase_scope("kv_import")
def _scatter_slab(dst, idx, src, *, page_axis: int):
    """The import scatter as ONE jitted program so the kv_import phase
    scope reaches the HLO metadata (an eager ``.at[].set`` dispatches
    as a bare ``jit(scatter)`` program that no caller-side scope can
    tag).  jit caches per (shape, page_axis) like every other bucketed
    program here."""
    if page_axis == 2:        # stage-major pipeline pool
        return dst.at[:, :, idx].set(src)
    return dst.at[:, idx].set(src)


def import_arrays(cache: KVCache, pages: list[int], k: np.ndarray,
                  v: np.ndarray,
                  k_scale: Optional[np.ndarray] = None,
                  v_scale: Optional[np.ndarray] = None) -> KVCache:
    """Scatter fully-assembled canonical [L, n_pages, ...] K/V into the
    pool in ONE device update (the single-copy cost a chunked receive
    pays at completion).

    The wire layout is CANONICAL (layer-major) regardless of either
    engine's parallelism: a pipeline-staged pool ([S, L/S, P, ...],
    ndim 6) reshapes the slab to stage-major before the scatter, so a
    pp-prefill engine can hand KV to a flat-TP decode engine and vice
    versa."""
    staged = cache.k.ndim == k.ndim + 1
    L = (cache.k.shape[0] * cache.k.shape[1]) if staged else cache.k.shape[0]
    expect = (L, len(pages)) + tuple(cache.k.shape[3 if staged else 2:])
    if tuple(k.shape) != expect:
        raise ValueError(f"KV shape mismatch: got {k.shape}, cache wants {expect}")
    if (cache.k_scale is not None) != (k_scale is not None):
        # never silently cast bf16 wire bytes into an int8 pool (or drop
        # the scales of an int8 slab into a bf16 pool)
        raise ValueError(
            "KV quantization mismatch: "
            + ("pool is int8 but the transfer carries no page scales"
               if cache.k_scale is not None else
               "transfer carries page scales but the pool is not int8")
            + " — prefill and decode roles must run the same "
              "--kv-cache-dtype")
    dt = cache.k.dtype
    idx = jnp.asarray(pages, jnp.int32)
    kj, vj = jnp.asarray(k, dt), jnp.asarray(v, dt)
    if staged:
        # each slab reshapes with its OWN trailing dims (MLA caches
        # carry a zero-size V tail, so V must not borrow K's shape)
        S = cache.k.shape[0]
        return KVCache(
            k=_scatter_slab(cache.k, idx,
                            kj.reshape((S, L // S) + k.shape[1:]),
                            page_axis=2),
            v=_scatter_slab(cache.v, idx,
                            vj.reshape((S, L // S) + v.shape[1:]),
                            page_axis=2))
    new_ks, new_vs = cache.k_scale, cache.v_scale
    if k_scale is not None:
        expect_s = (L, len(pages), cache.k_scale.shape[-1])
        if tuple(k_scale.shape) != expect_s:
            raise ValueError(f"KV scale shape mismatch: got {k_scale.shape}, "
                             f"cache wants {expect_s}")
        new_ks = _scatter_slab(cache.k_scale, idx,
                               jnp.asarray(k_scale, jnp.float32),
                               page_axis=1)
        new_vs = _scatter_slab(cache.v_scale, idx,
                               jnp.asarray(v_scale, jnp.float32),
                               page_axis=1)
    return KVCache(k=_scatter_slab(cache.k, idx, kj, page_axis=1),
                   v=_scatter_slab(cache.v, idx, vj, page_axis=1),
                   k_scale=new_ks, v_scale=new_vs)


def pack_transfer(meta: dict, payload: bytes) -> bytes:
    head = json.dumps(meta).encode()
    return head + b"\n" + payload


def unpack_transfer(blob: bytes) -> tuple[dict, bytes]:
    head, _, payload = blob.partition(b"\n")
    return json.loads(head), payload


# ---------------------------------------------------------------------------
# prefill side: staged export with background D2H copier
# ---------------------------------------------------------------------------

class StagedExport:
    """A finished prefill's KV, draining device→host chunk by chunk.

    Construction happens on the engine thread and does only an
    on-device gather (compact [L, n_pages, ...] copies of K and V) —
    the expensive host copies run on a background thread, one chunk at
    a time, releasing the device arrays after the final chunk so HBM
    is pinned only while the drain runs."""

    def __init__(self, k_dev, v_dev, meta: dict, plans: list[ChunkPlan],
                 prompt_tokens: list[int], first_token: int,
                 lazy_drain: bool = False, ks_dev=None, vs_dev=None):
        self.meta = meta
        self.plans = plans
        self.prompt_tokens = prompt_tokens
        self.first_token = first_token
        self.created = time.monotonic()
        # refreshed by KVExportRegistry.get() so a slow multi-chunk pull
        # keeps the entry alive — TTL GC ages on this, not on `created`
        self.last_access = self.created
        self._k_dev, self._v_dev = k_dev, v_dev
        self._ks_dev, self._vs_dev = ks_dev, vs_dev
        self._chunks: list[Optional[bytes]] = [None] * len(plans)
        self._ready = [threading.Event() for _ in plans]
        self._error: Optional[str] = None
        self._served = 0
        self._lock = threading.Lock()
        self._blob_lock = threading.Lock()
        self._blob: Optional[bytes] = None
        # lazy_drain defers the device→host copies until the first HOST
        # consumer shows up (meta handshake / chunk pull): a COLOCATED
        # decode engine then takes the device slabs directly and the
        # bytes never touch the host at all
        self._drain_lock = threading.Lock()
        self._drain_started = False
        if not lazy_drain:
            self.ensure_draining()

    @property
    def draining(self) -> bool:
        """Has the D2H copier been started (lazy or eager)?"""
        with self._drain_lock:
            return self._drain_started

    def ensure_draining(self) -> None:
        """Start the device→host copier once (idempotent)."""
        with self._drain_lock:
            if self._drain_started:
                return
            self._drain_started = True
        threading.Thread(target=self._drain, daemon=True,
                         name="pd-export-copier").start()

    def device_slabs(self):
        """The staged canonical device copies ``(k_dev, v_dev)`` — plus
        ``(ks_dev, vs_dev)`` when the pool is quantized — for a colocated
        device-to-device hand-off, or None once the drain has released
        them.  The returned references stay valid even if the drain
        finishes afterwards (the arrays are refcounted)."""
        with self._drain_lock:
            if self._k_dev is None:
                return None
            if self._ks_dev is not None:
                return self._k_dev, self._v_dev, self._ks_dev, self._vs_dev
            return self._k_dev, self._v_dev

    def _drain(self):
        try:
            FAILPOINTS.fire("pd.export_drain")
            for i, p in enumerate(self.plans):
                k = np.asarray(self._k_dev[p.layer_lo:p.layer_hi,
                                           p.page_lo:p.page_hi])
                v = np.asarray(self._v_dev[p.layer_lo:p.layer_hi,
                                           p.page_lo:p.page_hi])
                ks = vs = None
                if self._ks_dev is not None:
                    ks = np.asarray(self._ks_dev[p.layer_lo:p.layer_hi,
                                                 p.page_lo:p.page_hi])
                    vs = np.asarray(self._vs_dev[p.layer_lo:p.layer_hi,
                                                 p.page_lo:p.page_hi])
                self._chunks[i] = serialize_chunk(k, v, ks, vs)
                self._ready[i].set()
        except Exception as e:  # device wedge / shape bug: fail loudly
            self._error = f"{type(e).__name__}: {e}"
            for ev in self._ready:
                ev.set()
        finally:
            with self._drain_lock:
                self._k_dev = self._v_dev = None   # unpin HBM
                self._ks_dev = self._vs_dev = None

    @property
    def n_chunks(self) -> int:
        return len(self.plans)

    def get_chunk(self, i: int, timeout: float = 60.0,
                  consume: bool = True) -> bytes:
        """Block until chunk ``i`` has landed on host; return its bytes.
        ``consume`` frees the chunk after the read (each chunk is pulled
        once), bounding staged host memory."""
        if not 0 <= i < len(self.plans):
            raise IndexError(f"chunk {i} out of range ({len(self.plans)})")
        self.ensure_draining()
        if not self._ready[i].wait(timeout):
            raise TimeoutError(f"chunk {i} not ready after {timeout:.0f}s")
        if self._error:
            raise RuntimeError(f"export copier failed: {self._error}")
        with self._lock:
            data = self._chunks[i]
            if data is None:
                raise KeyError(f"chunk {i} already consumed")
            if consume:
                self._chunks[i] = None
                self._served += 1
        # chaos hook: an armed "pd.chunk" corrupt point flips bytes on
        # the wire path so receive-side checksumming/shape checks are
        # exercised end to end
        return FAILPOINTS.corrupt("pd.chunk", data, chunk=i)

    def restage_chunk(self, i: int, data: bytes) -> None:
        """Put a consumed chunk back (a send failed after the claim) so
        the receiver's retry finds it."""
        with self._lock:
            if self._chunks[i] is None:
                self._chunks[i] = data
                self._served -= 1

    @property
    def fully_served(self) -> bool:
        with self._lock:
            return self._served >= len(self.plans)

    def wait_all(self, timeout: float = 120.0) -> None:
        self.ensure_draining()
        deadline = time.monotonic() + timeout
        for ev in self._ready:
            if not ev.wait(max(0.0, deadline - time.monotonic())):
                raise TimeoutError("export copier did not finish")
        if self._error:
            raise RuntimeError(f"export copier failed: {self._error}")

    def whole_blob(self) -> bytes:
        """Assemble the legacy single-payload wire form (meta header +
        one serialized slab covering every page).  Consumes the staged
        chunks into a cached blob, so the call is IDEMPOTENT: a retried
        or concurrent pull gets the same bytes instead of racing the
        first caller for per-chunk consumption.  Failures before any
        chunk is consumed (wait_all timeout / copier error) leave the
        chunks intact for a later retry."""
        with self._blob_lock:
            if self._blob is None:
                self.wait_all()
                shape = tuple(self.meta["shape"])
                v_shape = tuple(self.meta.get("v_shape", self.meta["shape"]))
                dt = np.dtype(self.meta["dtype"])
                k = np.empty(shape, dt)
                v = np.empty(v_shape, dt)
                ks = vs = None
                if "ks_shape" in self.meta:
                    ks = np.empty(tuple(self.meta["ks_shape"]), np.float32)
                    vs = np.empty(tuple(self.meta["vs_shape"]), np.float32)
                for i, p in enumerate(self.plans):
                    ck, cv, cks, cvs = deserialize_chunk(self.get_chunk(i))
                    k[p.layer_lo:p.layer_hi, p.page_lo:p.page_hi] = ck
                    v[p.layer_lo:p.layer_hi, p.page_lo:p.page_hi] = cv
                    if ks is not None:
                        ks[p.layer_lo:p.layer_hi, p.page_lo:p.page_hi] = cks
                        vs[p.layer_lo:p.layer_hi, p.page_lo:p.page_hi] = cvs
                self._blob = serialize_chunk(k, v, ks, vs)
            return self._blob


def stage_export(cache: KVCache, pages: list[int], *, n_tokens: int,
                 model: str, prompt_tokens: list[int],
                 first_token: int, lazy_drain: bool = False,
                 trace_id: str = "") -> StagedExport:
    """Engine-thread entry: on-device gather + chunk plan; returns the
    staged export whose copier is already draining.

    A pipeline-staged pool ([S, L/S, P, ...]) gathers on the page axis
    and reshapes to the CANONICAL layer-major wire layout, so the
    receiving engine's parallelism doesn't have to match."""
    k_dev, v_dev, ks_dev, vs_dev = _gather_canonical(cache, pages)
    L, n_pages = int(k_dev.shape[0]), int(k_dev.shape[1])
    per_layer_page = int(np.prod(k_dev.shape[2:])
                         + np.prod(v_dev.shape[2:])) * k_dev.dtype.itemsize
    if ks_dev is not None:
        per_layer_page += int(np.prod(ks_dev.shape[2:])
                              + np.prod(vs_dev.shape[2:])) * 4
    plans = plan_chunks(L, n_pages, per_layer_page)
    meta = {"shape": [int(s) for s in k_dev.shape],
            "v_shape": [int(s) for s in v_dev.shape],
            "dtype": str(k_dev.dtype), "n_tokens": n_tokens,
            "model": model, "chunks": [p.to_json() for p in plans]}
    if ks_dev is not None:
        meta["ks_shape"] = [int(s) for s in ks_dev.shape]
        meta["vs_shape"] = [int(s) for s in vs_dev.shape]
    if trace_id:
        # trace identity rides the handoff meta so the decode role's
        # spans land under the SAME X-Request-Id (docs/observability.md)
        meta["trace_id"] = trace_id
    return StagedExport(k_dev, v_dev, meta, plans, prompt_tokens,
                        first_token, lazy_drain=lazy_drain,
                        ks_dev=ks_dev, vs_dev=vs_dev)


class KVExportRegistry:
    """Prefill-side staging area: finished prefills wait here until the
    decode engine pulls them (TTL-bounded so abandoned transfers don't
    pin host memory)."""

    def __init__(self, ttl_s: float = STAGE_TTL_S):
        self._items: dict[str, StagedExport] = {}
        self._lock = threading.Lock()
        self.ttl_s = ttl_s

    def put(self, req_id: str, exp: StagedExport) -> None:
        with self._lock:
            self._gc()
            self._items[req_id] = exp

    def get(self, req_id: str) -> Optional[StagedExport]:
        """Non-consuming lookup (chunked pulls consume chunk-by-chunk;
        the entry auto-drops once every chunk has been served)."""
        with self._lock:
            exp = self._items.get(req_id)
            if exp is not None and exp.fully_served:
                del self._items[req_id]
                return None
            if exp is not None:
                exp.last_access = time.monotonic()
            return exp

    def pop(self, req_id: str) -> Optional[StagedExport]:
        with self._lock:
            return self._items.pop(req_id, None)

    def drop_served(self, req_id: str) -> None:
        """Remove the entry if its chunks are exhausted."""
        with self._lock:
            exp = self._items.get(req_id)
            if exp is not None and exp.fully_served:
                del self._items[req_id]

    def _gc(self) -> None:
        # age on last_access, not created: a multi-chunk pull slower
        # than ttl_s would otherwise lose the entry between chunks
        now = time.monotonic()
        dead = [k for k, e in self._items.items()
                if now - getattr(e, "last_access", e.created) > self.ttl_s]
        for k in dead:
            del self._items[k]

    def tick(self, grace_s: float = EXPORT_DRAIN_GRACE_S) -> None:
        """Periodic maintenance, called from the engine's step loop:
        (a) TTL-GC abandoned entries (previously only ``put`` did this,
        so the LAST export of a burst could linger forever), and
        (b) start the D2H drain of any lazy_drain entry older than the
        grace window whose colocated consumer never showed up — the
        staged device slabs move to host and unpin HBM."""
        now = time.monotonic()
        with self._lock:
            self._gc()
            stale = [e for e in self._items.values()
                     if not e.draining and now - e.created > grace_s]
        for e in stale:
            e.ensure_draining()

    def __len__(self) -> int:
        """Live (not-yet-exhausted) entries.  A fully-served export is
        logically gone the moment its last chunk is claimed — physical
        removal may lag by one handler turn (the endpoint drops it
        after the final write), so counting it would race observers."""
        with self._lock:
            return sum(1 for e in self._items.values()
                       if not e.fully_served)


# ---------------------------------------------------------------------------
# decode side: chunked receive state (scattered by the scheduler loop)
# ---------------------------------------------------------------------------

class ChunkedImport:
    """Receive-side state for one request's in-flight KV transfer.

    The server's puller thread ``feed``s chunks as they arrive; the
    engine's scheduler loop drains them into preallocated host buffers
    between decode steps (bounded deserialize+memcpy work per step, so
    the transfer overlaps decode of other requests).  When the last
    chunk lands, ONE device scatter moves the assembled slab into the
    page pool — same single-copy cost as a whole-blob import, without
    its serialized wire wait.

    The inactivity timeout measures chunk ARRIVAL (refreshed per feed),
    never scatter progress or admission-queue wait: a transfer whose
    bytes are all local must not be failed because the pod is busy."""

    def __init__(self, meta: dict, plans: list[ChunkPlan],
                 first_token: int, deadline_s: float = 120.0):
        self.meta = meta
        self.plans = plans
        self.first_token = first_token
        self.deadline_s = deadline_s
        self.n_scattered = 0          # chunks assembled into host buffers
        self._pending: list[tuple[int, bytes]] = []
        self._n_fed = 0
        self._last_fed = time.monotonic()
        self._error: Optional[str] = None
        self._transient = False
        self._lock = threading.Lock()
        shape = tuple(meta["shape"])
        v_shape = tuple(meta.get("v_shape", meta["shape"]))
        dt = np.dtype(meta["dtype"])
        self._k_full = np.empty(shape, dt)
        self._v_full = np.empty(v_shape, dt)
        self._ks_full = self._vs_full = None
        if "ks_shape" in meta:
            self._ks_full = np.empty(tuple(meta["ks_shape"]), np.float32)
            self._vs_full = np.empty(tuple(meta["vs_shape"]), np.float32)

    @property
    def n_chunks(self) -> int:
        return len(self.plans)

    def feed(self, idx: int, payload: bytes) -> None:
        with self._lock:
            self._pending.append((idx, payload))
            self._n_fed += 1
            self._last_fed = time.monotonic()

    def set_error(self, msg: str, transient: bool = False) -> None:
        """``transient`` marks failures worth a retry-by-recompute
        (a network drop the puller reports immediately) as opposed to
        permanent ones (shape/corruption) — the engine reads it to
        decide between the local-prefill fallback and failing the
        request."""
        with self._lock:
            self._error = msg
            self._transient = transient

    @property
    def transient(self) -> bool:
        with self._lock:
            return getattr(self, "_transient", False)

    @property
    def error(self) -> Optional[str]:
        with self._lock:
            if self._error:
                return self._error
            if (self._n_fed < self.n_chunks
                    and time.monotonic() - self._last_fed > self.deadline_s):
                # a stall already burned deadline_s of wall clock: fail
                # fast (permanent) rather than silently doubling the
                # client's latency with a recompute
                return (f"KV transfer stalled: no chunk for "
                        f"{self.deadline_s:.0f}s "
                        f"({self._n_fed}/{self.n_chunks} arrived)")
        return None

    def assemble(self, max_n: int = 4) -> int:
        """Deserialize up to ``max_n`` arrived chunks into the host
        buffers (bounds per-step work); returns how many landed."""
        with self._lock:
            got, self._pending = self._pending[:max_n], self._pending[max_n:]
        for idx, payload in got:
            p = self.plans[idx]
            k, v, ks, vs = deserialize_chunk(payload)
            expect = (p.layer_hi - p.layer_lo,
                      p.page_hi - p.page_lo) + self._k_full.shape[2:]
            expect_v = (p.layer_hi - p.layer_lo,
                        p.page_hi - p.page_lo) + self._v_full.shape[2:]
            if tuple(k.shape) != expect or tuple(v.shape) != expect_v:
                raise ValueError(f"chunk {idx} shape mismatch: got "
                                 f"K {k.shape} V {v.shape}, plan wants "
                                 f"K {expect} V {expect_v}")
            if (ks is not None) != (self._ks_full is not None):
                raise ValueError(f"chunk {idx} quantization mismatch: "
                                 f"chunk scales={'yes' if ks is not None else 'no'}, "
                                 f"meta scales="
                                 f"{'yes' if self._ks_full is not None else 'no'}")
            self._k_full[p.layer_lo:p.layer_hi, p.page_lo:p.page_hi] = k
            self._v_full[p.layer_lo:p.layer_hi, p.page_lo:p.page_hi] = v
            if ks is not None:
                self._ks_full[p.layer_lo:p.layer_hi,
                              p.page_lo:p.page_hi] = ks
                self._vs_full[p.layer_lo:p.layer_hi,
                              p.page_lo:p.page_hi] = vs
            self.n_scattered += 1
        return len(got)

    @property
    def complete(self) -> bool:
        return self.n_scattered >= self.n_chunks

    def full_arrays(self) -> tuple:
        """``(k, v)`` or ``(k, v, k_scale, v_scale)`` — star-unpack into
        :func:`import_arrays`."""
        assert self.complete
        if self._ks_full is not None:
            return self._k_full, self._v_full, self._ks_full, self._vs_full
        return self._k_full, self._v_full


# ---------------------------------------------------------------------------
# transfer-vs-recompute break-even
# ---------------------------------------------------------------------------

class TransferCostModel:
    """Live-calibrated constants for the break-even decision.

    The static knobs in :func:`transfer_cost` are order-of-magnitude
    priors; this model replaces them with EWMA self-measurements as the
    engine observes REAL work: completed chunked KV imports calibrate
    the effective link bandwidth, completed prefills calibrate the
    recompute rate (including scheduler interleaving — the true
    opportunity cost of a local prefill).  Until a side has a sample,
    the static prior for that side stays in effect, so cold-start
    behavior is unchanged."""

    def __init__(self, alpha: float = 0.3):
        self.alpha = alpha
        self._lock = threading.Lock()
        self.net_bytes_s: Optional[float] = None
        self.prefill_tok_s: Optional[float] = None
        self.disk_bytes_s: Optional[float] = None
        self.transfer_samples = 0
        self.prefill_samples = 0
        self.disk_samples = 0

    def _ewma(self, cur: Optional[float], x: float) -> float:
        return x if cur is None else (1 - self.alpha) * cur + self.alpha * x

    def note_transfer(self, nbytes: int, seconds: float) -> None:
        if nbytes <= 0 or seconds <= 1e-6:
            return
        with self._lock:
            self.net_bytes_s = self._ewma(self.net_bytes_s,
                                          nbytes / seconds)
            self.transfer_samples += 1

    def note_prefill(self, tokens: int, seconds: float) -> None:
        if tokens <= 0 or seconds <= 1e-6:
            return
        with self._lock:
            self.prefill_tok_s = self._ewma(self.prefill_tok_s,
                                            tokens / seconds)
            self.prefill_samples += 1

    def note_disk_read(self, nbytes: int, seconds: float) -> None:
        """Calibrate the SSD tier's effective read bandwidth from a
        completed slab read (chunk bytes / wall seconds, including
        page-cache effects — the rate the break-even actually sees)."""
        if nbytes <= 0 or seconds <= 1e-6:
            return
        with self._lock:
            self.disk_bytes_s = self._ewma(self.disk_bytes_s,
                                           nbytes / seconds)
            self.disk_samples += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"net_bytes_s": self.net_bytes_s,
                    "prefill_tok_s": self.prefill_tok_s,
                    "disk_bytes_s": self.disk_bytes_s,
                    "transfer_samples": self.transfer_samples,
                    "prefill_samples": self.prefill_samples,
                    "disk_samples": self.disk_samples}

def estimate_params(arch) -> int:
    """Approximate parameter count from the architecture dims (embed +
    per-layer attn/mlp), enough for a FLOPs estimate."""
    H = arch.hidden_size
    attn = H * (arch.num_heads * arch.head_dim) \
        + 2 * H * (arch.num_kv_heads * arch.head_dim) \
        + (arch.num_heads * arch.head_dim) * H
    n_exp = getattr(arch, "num_experts", 0) or 1
    mlp = 3 * H * arch.intermediate_size * n_exp
    return arch.vocab_size * H * 2 + arch.num_layers * (attn + mlp)


def transfer_cost(n_tokens: int, arch, dtype_bytes: int = 2, *,
                  net_bytes_s: float = 2.5e9, chip_flops: float = 1.97e14,
                  mfu: float = 0.35,
                  scale_bytes_per_token: float = 0.0,
                  measured: Optional[TransferCostModel] = None) -> dict:
    """Estimate KV-transfer time vs local prefill recompute time.

    Static defaults: ~20 Gb/s effective pod-to-pod DCN, v5e bf16 peak
    with a conservative prefill MFU — order-of-magnitude PRIORS only
    used when ``measured`` has no sample for that side.  Once the
    engine has observed real transfers/prefills, the measured EWMA
    rates drive the decision (mid-range prompts on a fast link sit
    near the boundary, where a 4x prior error flips it the wrong
    way).

    ``scale_bytes_per_token`` adds the fp32 page-scale overhead of an
    int8 pool (8 * L * Hkv / page_size per token) so the break-even for
    a quantized hand-off sees its true wire volume: ~half the bf16
    bytes, which MOVES the boundary toward transferring."""
    kv_bytes = (2 * arch.num_layers * n_tokens * arch.num_kv_heads
                * arch.head_dim * dtype_bytes)
    kv_bytes = int(kv_bytes + scale_bytes_per_token * n_tokens)
    m = measured.snapshot() if measured is not None else {}
    net = m.get("net_bytes_s") or net_bytes_s
    transfer_s = kv_bytes / net
    if m.get("prefill_tok_s"):
        recompute_s = n_tokens / m["prefill_tok_s"]
    else:
        recompute_s = (2.0 * estimate_params(arch) * n_tokens
                       / (chip_flops * mfu))
    return {"kv_bytes": kv_bytes, "transfer_s": transfer_s,
            "recompute_s": recompute_s,
            "calibrated": bool(m.get("net_bytes_s")
                               or m.get("prefill_tok_s"))}


def should_transfer(n_tokens: int, arch, dtype_bytes: int = 2, **kw) -> bool:
    c = transfer_cost(n_tokens, arch, dtype_bytes, **kw)
    return c["transfer_s"] < c["recompute_s"]


def should_import_from_disk(nbytes: int, n_tokens: int,
                            measured: Optional[TransferCostModel]) -> bool:
    """Break-even for the SSD tier: import unless BOTH the disk read
    rate and the prefill rate have real samples AND the measured read
    time exceeds the measured recompute time.  Same measured-rates-only
    veto discipline as the remote fetch path — priors never veto,
    because a wrong prior silently disabling the tier is worse than an
    occasional slow read (the read overlaps the scheduler anyway)."""
    if measured is None:
        return True
    m = measured.snapshot()
    if not (m.get("disk_bytes_s") and m.get("prefill_tok_s")):
        return True
    read_s = nbytes / m["disk_bytes_s"]
    recompute_s = n_tokens / m["prefill_tok_s"]
    return read_s < recompute_s

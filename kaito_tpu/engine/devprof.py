"""Sampling device profiler: continuous device-time attribution.

The manual ``/start_profile`` toggle (server.py) writes a raw XPlane
dump for a human to stare at in TensorBoard.  That answers "what
happened in the five seconds I remembered to capture" — not "what is
the fleet's comm/compute/idle split right now".  This module closes
that gap: on a configurable cadence it captures a short
``jax.profiler`` window around live engine steps into a private
tmpdir, parses the emitted trace, classifies every device slice into
buckets, and folds the result into the same three surfaces every
other engine signal uses (gated ``kaito:device_*`` families,
``GET /debug/device`` JSON, fleet aggregates).

Two parse paths, tried in order per window:

``*.xplane.pb``
    The XPlane protobuf XLA always emits.  Decoded with a hand-written
    protobuf *wire* reader (no generated bindings, no new deps): we
    only need plane/line/event framing plus the per-program HloProto
    stashed in the ``/host:metadata`` plane, whose instruction →
    ``metadata.op_name`` map is what carries the ``jax.named_scope``
    phase markers (``kaito/decode`` …) from the dispatch sites into
    the classifier.

``*.trace.json.gz``
    The chrome-trace JSON sibling — the pure-JSON fallback that runs
    on CPU CI and doubles as the fixture format for classifier tests.

Bucket math is exact by construction: per track, slices are clipped
against the running high-water mark before bucketing, so
``sum(buckets) + idle == device wall`` without needing the trace to be
overlap-free.  Overlap percentages measure cross-track co-scheduling:
a collective slice counts as "overlapped" for the fraction of its
duration during which some *other* track runs compute — i.e. the
comm is hidden, not serialized.  On a single-track host (CPU CI) both
overlap figures are structurally 0.0.
"""
from __future__ import annotations

import glob
import gzip
import json
import logging
import os
import re
import shutil
import tempfile
import threading
import time
from bisect import bisect_left
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Tuple

logger = logging.getLogger(__name__)

BUCKETS = ("matmul", "attention", "collective", "copy", "other", "idle")

#: Engine phases marked with ``jax.named_scope("kaito/<phase>")``
#: *inside* the jitted step bodies (``phase_scope`` below; engine.py /
#: spec.py / pd.py).  The scope string survives tracing into HLO
#: ``metadata.op_name``, which is how a device slice lands in a phase
#: here.
PHASES = ("decode", "prefill", "verify", "draft", "kv_import")

_PHASE_RE = re.compile(r"kaito/([a-z_]+)")

# Ordered op-name rule table.  First match wins; collectives outrank
# everything (a fused all-reduce+add must count as comm), copies next
# (DMA engines report e.g. "dynamic-update-slice fusion.3 copy"), then
# attention (scope- or kernel-named), then dense math, else other.
OP_RULES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("collective", ("all-reduce", "allreduce", "reduce-scatter",
                    "reducescatter", "all-gather", "allgather",
                    "all-to-all", "alltoall", "collective-permute",
                    "collectivepermute", "ppermute", "psum",
                    "send", "recv")),
    ("copy", ("copy", "memcpy", "h2d", "d2h", "dma", "infeed",
              "outfeed", "transfer")),
    ("attention", ("attention", "attn", "flash", "softmax")),
    ("matmul", ("dot", "conv", "einsum", "matmul", "gemm")),
)


def classify(op_name: str, name: str = "") -> str:
    """Map one device slice to a bucket via the ordered rule table.

    ``op_name`` is the scoped HLO metadata name when available (it
    carries named_scope context like ``.../attention/dot_general``);
    ``name`` is the bare event/instruction name and acts as fallback
    signal.  Matching is case-insensitive substring."""
    text = f"{op_name} {name}".lower()
    for bucket, needles in OP_RULES:
        for needle in needles:
            if needle in text:
                return bucket
    return "other"


def phase_of(op_name: str) -> Optional[str]:
    m = _PHASE_RE.search(op_name)
    if m and m.group(1) in PHASES:
        return m.group(1)
    return None


def phase_scope(phase: str):
    """Decorator that tags every op of a jitted step function with
    ``kaito/<phase>`` for the profiler.

    Must sit UNDER the ``jax.jit`` decorator (i.e. wrap the function
    jit traces): jit resets the name stack when tracing begins, so a
    ``named_scope`` entered around the *call* never reaches the HLO
    metadata — the scope only lands if it is active while the body
    itself is traced.  ``functools.wraps`` exposes the real signature
    to jit so ``donate_argnums`` resolve against the underlying
    argument list."""
    import functools

    import jax

    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(f"kaito/{phase}"):
                return fn(*args, **kwargs)
        return scoped
    return deco


@dataclass
class Slice:
    """One device-time interval: an op execution on one track."""
    name: str          # bare event / HLO instruction name
    op_name: str       # scoped metadata op_name ("" when unresolved)
    t0_us: float
    dur_us: float
    track: str         # "<plane>/<line>" — one executor unit
    device: bool = True

    @property
    def t1_us(self) -> float:
        return self.t0_us + self.dur_us


# ----------------------------------------------------------------------
# Protobuf wire reader (XPlane + embedded HloProto)
# ----------------------------------------------------------------------

def _uvarint(buf: bytes, pos: int) -> Tuple[int, int]:
    result = 0
    shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not (b & 0x80):
            return result, pos
        shift += 7


def _fields(buf: bytes) -> Iterable[Tuple[int, int, object]]:
    """Yield (field_number, wire_type, value) over one message body.

    Length-delimited values come back as bytes; varints as ints; fixed
    32/64-bit as raw bytes (nothing here needs them decoded)."""
    pos, n = 0, len(buf)
    while pos < n:
        tag, pos = _uvarint(buf, pos)
        fno, wt = tag >> 3, tag & 7
        if wt == 0:
            val, pos = _uvarint(buf, pos)
        elif wt == 1:
            val = buf[pos:pos + 8]
            pos += 8
        elif wt == 2:
            ln, pos = _uvarint(buf, pos)
            val = buf[pos:pos + ln]
            pos += ln
        elif wt == 5:
            val = buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield fno, wt, val


def _first(buf: bytes, fno: int, default=None):
    for f, _, v in _fields(buf):
        if f == fno:
            return v
    return default


def _hlo_op_names(hlo_proto: bytes) -> Dict[str, str]:
    """instruction name -> metadata.op_name from a serialized HloProto.

    HloProto.hlo_module=1; HloModuleProto.computations=3;
    HloComputationProto.instructions=2; HloInstructionProto.name=1,
    .metadata=7 (OpMetadata); OpMetadata.op_name=2."""
    out: Dict[str, str] = {}
    module = _first(hlo_proto, 1)
    if not module:
        return out
    for f, _, comp in _fields(module):
        if f != 3:
            continue
        for f2, _, instr in _fields(comp):
            if f2 != 2:
                continue
            name = b""
            op_name = b""
            for f3, _, v in _fields(instr):
                if f3 == 1:
                    name = v
                elif f3 == 7:
                    op_name = _first(v, 2, b"")
            if name and op_name:
                out[name.decode("utf-8", "replace")] = (
                    op_name.decode("utf-8", "replace"))
    return out


def _plane_event_metadata(plane: bytes) -> Dict[int, bytes]:
    """XPlane.event_metadata map: id -> serialized XEventMetadata."""
    out: Dict[int, bytes] = {}
    for f, _, entry in _fields(plane):
        if f != 4:
            continue
        key = 0
        val = b""
        for fk, _, v in _fields(entry):
            if fk == 1:
                key = v
            elif fk == 2:
                val = v
        out[key] = val
    return out


_INFRA_MARKERS = ("::",)       # ThunkExecutor::, ThreadpoolListener:: …


def parse_xplane(raw: bytes) -> List[Slice]:
    """Flatten an XSpace protobuf into device ``Slice`` records.

    Prefers ``/device:*`` planes (real accelerators).  When none
    exist — CPU CI — falls back to the XLA executor lines of the
    ``/host:CPU`` plane (``tf_XLATfrtCpuClient/...``), filtering infra
    (``::``-qualified) and python (``$``-prefixed) events so only op
    executions count as busy time."""
    planes = [v for f, wt, v in _fields(raw) if f == 1 and wt == 2]
    # Pass 1: harvest every embedded HloProto for the scoped-op_name map
    # (the "/host:metadata" plane stows one per compiled program).
    hlo_map: Dict[str, str] = {}
    named: List[Tuple[str, bytes]] = []
    for plane in planes:
        pname = (_first(plane, 2, b"") or b"").decode("utf-8", "replace")
        named.append((pname, plane))
        for md in _plane_event_metadata(plane).values():
            for f, _, stat in _fields(md):
                if f != 5:
                    continue
                blob = _first(stat, 6)
                if isinstance(blob, bytes) and len(blob) > 16:
                    try:
                        hlo_map.update(_hlo_op_names(blob))
                    except (ValueError, IndexError):
                        pass

    device_planes = [(n, p) for n, p in named if n.startswith("/device:")]
    host_fallback = not device_planes
    if host_fallback:
        device_planes = [(n, p) for n, p in named
                         if n.startswith("/host:") and "metadata" not in n]

    slices: List[Slice] = []
    for pname, plane in device_planes:
        md_names = {
            mid: (_first(md, 2, b"") or b"").decode("utf-8", "replace")
            for mid, md in _plane_event_metadata(plane).items()}
        for f, _, line in _fields(plane):
            if f != 3:
                continue
            lname = (_first(line, 2, b"") or b"").decode("utf-8", "replace")
            if host_fallback and "XLA" not in lname:
                continue   # host plane: only XLA executor threads are
                           # device-time proxies; skip GC/dispatch lines
            ts_ns = _first(line, 3, 0)
            track = f"{pname}/{lname or _first(line, 1, 0)}"
            for f2, _, ev in _fields(line):
                if f2 != 4:
                    continue
                mid = dur_ps = off_ps = 0
                for f3, _, v in _fields(ev):
                    if f3 == 1:
                        mid = v
                    elif f3 == 2:
                        off_ps = v
                    elif f3 == 3:
                        dur_ps = v
                name = md_names.get(mid, "")
                if not dur_ps or not name:
                    continue
                if name.startswith("$") or any(
                        m in name for m in _INFRA_MARKERS):
                    continue
                slices.append(Slice(
                    name=name,
                    op_name=hlo_map.get(name, ""),
                    t0_us=ts_ns / 1e3 + off_ps / 1e6,
                    dur_us=dur_ps / 1e6,
                    track=track,
                    device=not host_fallback))
    return slices


# ----------------------------------------------------------------------
# Chrome trace-event fallback (pure JSON; also the test-fixture format)
# ----------------------------------------------------------------------

def parse_trace_events(doc: dict) -> List[Slice]:
    """Flatten a chrome-trace document into ``Slice`` records.

    Device tracks are processes whose ``process_name`` contains
    ``/device:``; with none present, XLA executor threads
    (``XLATfrtCpuClient``-style ``thread_name``) stand in, mirroring
    the XPlane fallback.  Fixture events may carry explicit
    ``args.op_name`` / ``args.phase`` — real jax dumps carry scoped
    names under ``args.long_name``."""
    events = doc.get("traceEvents", [])
    proc_names: Dict[object, str] = {}
    thread_names: Dict[Tuple[object, object], str] = {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        nm = (ev.get("args") or {}).get("name", "")
        if ev.get("name") == "process_name":
            proc_names[ev.get("pid")] = nm
        elif ev.get("name") == "thread_name":
            thread_names[(ev.get("pid"), ev.get("tid"))] = nm

    device_pids = {p for p, n in proc_names.items() if "/device:" in n}
    host_fallback = not device_pids

    slices: List[Slice] = []
    for ev in events:
        if ev.get("ph") != "X" or not ev.get("dur"):
            continue
        pid, tid = ev.get("pid"), ev.get("tid")
        if device_pids:
            if pid not in device_pids:
                continue
        elif "XLA" not in thread_names.get((pid, tid), ""):
            continue
        name = ev.get("name", "")
        if name.startswith("$") or any(m in name for m in _INFRA_MARKERS):
            continue
        args = ev.get("args") or {}
        op_name = args.get("op_name") or args.get("long_name") or ""
        if args.get("phase"):
            op_name = f"{op_name} kaito/{args['phase']}"
        slices.append(Slice(
            name=name, op_name=op_name,
            t0_us=float(ev["ts"]), dur_us=float(ev["dur"]),
            track=f"{proc_names.get(pid, pid)}/{tid}",
            device=not host_fallback))
    return slices


# ----------------------------------------------------------------------
# Window summary: buckets, overlap, phases, roofline
# ----------------------------------------------------------------------

def _merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    if not intervals:
        return []
    intervals.sort()
    out = [list(intervals[0])]
    for t0, t1 in intervals[1:]:
        if t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return [(a, b) for a, b in out]


def _leaf_pieces(ts: List[Slice]) -> List[Tuple[float, float, Slice]]:
    """Flatten one track's (possibly nested) events into disjoint leaf
    pieces.  XLA emits control-flow ops (``while``/``cond``) as
    envelope events whose body ops nest INSIDE them on the same line;
    time covered by a child must be bucketed by the child — the child
    carries the scoped op metadata, the envelope usually carries none —
    and the envelope keeps only its uncovered remainder.  Output is
    sorted by start and pairwise disjoint for properly nested input;
    the caller's high-water clip mops up any malformed overlap."""
    pieces: List[Tuple[float, float, Slice]] = []
    stack: List[list] = []       # [slice, emitted-up-to cursor]

    def emit(entry: list, upto: float) -> None:
        s, cur = entry
        end = min(upto, s.t1_us)
        if end > cur:
            pieces.append((cur, end, s))

    for s in sorted(ts, key=lambda s: (s.t0_us, -s.dur_us)):
        while stack and stack[-1][0].t1_us <= s.t0_us:
            done = stack.pop()
            emit(done, done[0].t1_us)
        if stack:
            top = stack[-1]
            emit(top, s.t0_us)
            top[1] = max(top[1], min(s.t1_us, top[0].t1_us))
        stack.append([s, s.t0_us])
    while stack:
        done = stack.pop()
        emit(done, done[0].t1_us)
    pieces.sort(key=lambda p: (p[0], -(p[1] - p[0])))
    return pieces


def summarize_window(slices: List[Slice],
                     roofline: Optional[dict] = None,
                     window_tokens: float = 0.0,
                     capture_s: float = 0.0) -> dict:
    """Fold one captured window's slices into the bucket breakdown.

    The invariant the tests pin — ``sum(bucket_pct.values()) == 100``
    within float noise — holds by construction: per track, events are
    first flattened to disjoint leaf pieces (``_leaf_pieces``: nested
    children win over their control-flow envelopes), then each piece
    is clipped against the running high-water mark before it is
    bucketed, so nested/overlapping events can never double-count, and
    idle is defined as the exact remainder of the per-track wall."""
    if not slices:
        return _empty_summary(capture_s)

    by_track: Dict[str, List[Slice]] = {}
    for s in slices:
        by_track.setdefault(s.track, []).append(s)

    t_min = min(s.t0_us for s in slices)
    t_max = max(s.t1_us for s in slices)
    span_us = max(t_max - t_min, 1e-9)
    n_tracks = len(by_track)
    wall_us = span_us * n_tracks

    bucket_us = {b: 0.0 for b in BUCKETS}
    phase_us: Dict[str, float] = {p: 0.0 for p in PHASES}
    attributed_us = 0.0
    busy_us = 0.0
    # cross-track overlap inputs: merged compute / non-copy busy spans
    compute_by_track: Dict[str, List[Tuple[float, float]]] = {}
    busy_by_track: Dict[str, List[Tuple[float, float]]] = {}
    collectives: List[Slice] = []
    copies: List[Slice] = []

    for track, ts in by_track.items():
        cursor = -float("inf")
        comp: List[Tuple[float, float]] = []
        busy: List[Tuple[float, float]] = []
        for p0, p1, s in _leaf_pieces(ts):
            start = max(p0, cursor)
            if start >= p1:
                continue   # malformed overlap: already accounted
            dur = p1 - start
            cursor = p1
            bucket = classify(s.op_name, s.name)
            bucket_us[bucket] += dur
            busy_us += dur
            busy.append((start, p1))
            if bucket in ("matmul", "attention", "other"):
                comp.append((start, p1))
            elif bucket == "collective":
                collectives.append(Slice(s.name, s.op_name, start, dur,
                                         track, s.device))
            elif bucket == "copy":
                copies.append(Slice(s.name, s.op_name, start, dur,
                                    track, s.device))
            ph = phase_of(s.op_name)
            if ph is not None:
                phase_us[ph] += dur
                attributed_us += dur
        compute_by_track[track] = _merged(comp)
        busy_by_track[track] = _merged(busy)

    bucket_us["idle"] = max(wall_us - busy_us, 0.0)

    def _cross_track_overlap(subject: List[Slice],
                             spans: Dict[str, List[Tuple[float, float]]]
                             ) -> float:
        """Fraction (%) of subject time co-scheduled with work on
        another track — the 'hidden behind compute' share."""
        total = sum(s.dur_us for s in subject)
        if total <= 0.0:
            return 0.0
        starts = {tr: [a for a, _ in iv] for tr, iv in spans.items()}
        hidden = 0.0
        for s in subject:
            cover: List[Tuple[float, float]] = []
            for tr, iv in spans.items():
                if tr == s.track:
                    continue
                j = max(0, bisect_left(starts[tr], s.t0_us) - 1)
                while j < len(iv):
                    a, b = iv[j]
                    if a >= s.t1_us:
                        break
                    lo, hi = max(a, s.t0_us), min(b, s.t1_us)
                    if hi > lo:
                        cover.append((lo, hi))
                    j += 1
            hidden += sum(b - a for a, b in _merged(cover))
        return 100.0 * hidden / total

    comm_overlap_pct = _cross_track_overlap(collectives, compute_by_track)
    copy_overlap_pct = _cross_track_overlap(copies, busy_by_track)

    pct = {b: 100.0 * v / wall_us for b, v in bucket_us.items()}
    phase_pct = {p: 100.0 * v / wall_us for p, v in phase_us.items()}
    attributed_pct = (100.0 * attributed_us / busy_us) if busy_us else 0.0

    # Achieved-vs-peak rates: window token throughput against the chip
    # peaks, attributed to the buckets that consume them (matmul ⇒
    # FLOPs, everything ⇒ HBM).
    matmul_pct_of_peak = hbm_pct_of_peak = 0.0
    if roofline and capture_s > 0 and window_tokens > 0:
        tok_s = window_tokens / capture_s
        pf = float(roofline.get("peak_flops", 0.0))
        pb = float(roofline.get("peak_bytes_s", 0.0))
        params = float(roofline.get("params", 0.0))
        bpt = float(roofline.get("bytes_per_tok", 0.0))
        if pf > 0 and params > 0:
            matmul_pct_of_peak = 100.0 * tok_s * 2.0 * params / pf
        if pb > 0 and bpt > 0:
            hbm_pct_of_peak = 100.0 * tok_s * bpt / pb

    return {
        "ts": time.time(),
        "capture_s": round(capture_s, 6),
        "n_slices": len(slices),
        "n_tracks": n_tracks,
        "wall_us": round(wall_us, 3),
        "busy_us": round(busy_us, 3),
        "bucket_pct": {b: round(v, 3) for b, v in pct.items()},
        "comm_pct": round(pct["collective"], 3),
        "comm_compute_overlap_pct": round(comm_overlap_pct, 3),
        "copy_overlap_pct": round(copy_overlap_pct, 3),
        "phase_pct": {p: round(v, 3) for p, v in phase_pct.items()},
        "phase_attributed_pct": round(attributed_pct, 3),
        "window_tokens": window_tokens,
        "matmul_pct_of_peak_flops": round(matmul_pct_of_peak, 3),
        "hbm_pct_of_peak": round(hbm_pct_of_peak, 3),
    }


def _empty_summary(capture_s: float = 0.0) -> dict:
    return {
        "ts": time.time(),
        "capture_s": round(capture_s, 6),
        "n_slices": 0,
        "n_tracks": 0,
        "wall_us": 0.0,
        "busy_us": 0.0,
        "bucket_pct": {b: 0.0 for b in BUCKETS},
        "comm_pct": 0.0,
        "comm_compute_overlap_pct": 0.0,
        "copy_overlap_pct": 0.0,
        "phase_pct": {p: 0.0 for p in PHASES},
        "phase_attributed_pct": 0.0,
        "window_tokens": 0.0,
        "matmul_pct_of_peak_flops": 0.0,
        "hbm_pct_of_peak": 0.0,
    }


# ----------------------------------------------------------------------
# The sampler
# ----------------------------------------------------------------------

class DeviceProfiler:
    """Background sampler: every ``interval_s`` capture a ``window_s``
    ``jax.profiler`` trace, fold it into a window summary, keep a ring.

    Never raises out of the sampling path — a failed capture or parse
    increments a counter and the loop moves on; the serving path must
    not notice the profiler exists (the acceptance gate holds decode
    throughput within 1% of sampling-off at default cadence).

    Plays nice with the manual ``/start_profile`` toggle: if a trace is
    already active ``jax.profiler.start_trace`` raises and the window is
    counted as skipped, never stolen."""

    def __init__(self, interval_s: float, window_s: float = 0.25,
                 ring: int = 16,
                 roofline: Optional[dict] = None,
                 tokens_fn: Optional[Callable[[], float]] = None):
        self.interval_s = float(interval_s)
        self.window_s = float(window_s)
        self.roofline = roofline
        self.tokens_fn = tokens_fn
        self.windows = deque(maxlen=max(int(ring), 1))
        self.windows_total = 0
        self.windows_skipped = 0
        self.parse_errors = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        # registry=None: EngineMetrics adopts it when metrics are wired,
        # same deal as the engine's step/queue histograms.
        from kaito_tpu.engine.metrics import Histogram
        self.capture_hist = Histogram(
            "kaito:device_capture_seconds",
            "Wall time spent capturing+parsing one devprof window",
            registry=None,
            buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0))

    # -- lifecycle ------------------------------------------------------

    def start(self):
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="devprof")
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.window_s + 10)
            self._thread = None

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            self.sample_window()

    # -- one window -----------------------------------------------------

    def sample_window(self) -> Optional[dict]:
        """Capture + parse one window synchronously.  Returns the
        summary dict, or None when the window was skipped/failed."""
        t0 = time.perf_counter()
        tok0 = self._tokens()
        tmp = tempfile.mkdtemp(prefix="kaito-devprof-")
        try:
            import jax
            try:
                jax.profiler.start_trace(tmp)
            except Exception as e:  # noqa: BLE001
                # an already-running manual /start_profile capture, or
                # a backend without profiler support
                self.windows_skipped += 1
                logger.debug("devprof window skipped: %s", e)
                return None
            try:
                self._stop.wait(self.window_s)
            finally:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    self.windows_skipped += 1
                    return None
            capture_s = time.perf_counter() - t0
            try:
                slices = self._parse_dump(tmp)
            except Exception:
                logger.debug("devprof parse failed", exc_info=True)
                self.parse_errors += 1
                return None
            summary = summarize_window(
                slices, roofline=self.roofline,
                window_tokens=max(self._tokens() - tok0, 0.0),
                capture_s=capture_s)
            self.capture_hist.observe(time.perf_counter() - t0)
            with self._lock:
                self.windows.append(summary)
                self.windows_total += 1
            return summary
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def _tokens(self) -> float:
        if self.tokens_fn is None:
            return 0.0
        try:
            return float(self.tokens_fn())
        except Exception:
            return 0.0

    @staticmethod
    def _parse_dump(root: str) -> List[Slice]:
        """Locate and parse the newest profiler dump under ``root``."""
        pbs = sorted(glob.glob(os.path.join(
            root, "**", "*.xplane.pb"), recursive=True),
            key=os.path.getmtime)
        if pbs:
            with open(pbs[-1], "rb") as f:
                return parse_xplane(f.read())
        jsons = sorted(glob.glob(os.path.join(
            root, "**", "*.trace.json.gz"), recursive=True),
            key=os.path.getmtime)
        if jsons:
            with gzip.open(jsons[-1], "rt", encoding="utf-8") as f:
                return parse_trace_events(json.load(f))
        raise FileNotFoundError(f"no profiler dump under {root}")

    # -- read side ------------------------------------------------------

    def last(self) -> Optional[dict]:
        with self._lock:
            return self.windows[-1] if self.windows else None

    def snapshot(self) -> dict:
        with self._lock:
            ring = list(self.windows)
        return {
            "interval_s": self.interval_s,
            "window_s": self.window_s,
            "windows_total": self.windows_total,
            "windows_skipped": self.windows_skipped,
            "parse_errors": self.parse_errors,
            "last": ring[-1] if ring else None,
            "ring": ring,
        }

    # metric accessors — gauges read the last window, 0.0 before the
    # first capture so exposition is schema-stable from step one
    def _lastval(self, key: str) -> float:
        last = self.last()
        return float(last[key]) if last else 0.0

    def comm_pct(self) -> float:
        return self._lastval("comm_pct")

    def overlap_pct(self) -> float:
        return self._lastval("comm_compute_overlap_pct")

    def copy_overlap_pct(self) -> float:
        return self._lastval("copy_overlap_pct")

    def idle_pct(self) -> float:
        last = self.last()
        return float(last["bucket_pct"]["idle"]) if last else 0.0

    def bucket_pct(self) -> Dict[Tuple[str, ...], float]:
        last = self.last()
        src = last["bucket_pct"] if last else {b: 0.0 for b in BUCKETS}
        return {(b,): float(src.get(b, 0.0)) for b in BUCKETS}

    def phase_pct(self) -> Dict[Tuple[str, ...], float]:
        last = self.last()
        src = last["phase_pct"] if last else {p: 0.0 for p in PHASES}
        return {(p,): float(src.get(p, 0.0)) for p in PHASES}

"""Bounded two-tier LoRA adapter cache (docs/multi-lora.md).

The static boot path (``engine/adapters.py``) sizes its stacked buffers
from whatever the adapter directory held at startup, so "add a
fine-tune" means "restart the fleet".  This module is the dynamic
counterpart — the S-LoRA/Punica serving discipline on TPU:

- **HBM slot table** — the same stacked per-target layout the layer
  scan already consumes (``{group: {f"{t}_a": [L, S+1, in, rmax],
  f"{t}_b": [L, S+1, rmax, out]}}``, slot 0 = all-zeros base), but
  pre-allocated to a FIXED capacity of ``slots`` adapters at rank
  ``rmax``.  Hot-loading an adapter is an in-place ``at[:, slot].set``
  of its padded factors — every buffer keeps its shape, dtype and
  sharding, so the jitted decode programs can never retrace
  (pinned by a jit-cache-size assertion in tests/test_multi_lora.py).
- **Host-RAM tier** — a byte-budgeted LRU of evicted adapters' raw
  factors (same discipline as ``host_offload.HostKVPool``): an adapter
  squeezed out of HBM faults back in on its next request instead of
  requiring an operator round trip to the registry.

Correctness model: a slot referenced by any in-flight request is
PINNED — the engine supplies ``busy_fn`` and the cache refuses to
evict or overwrite a busy slot (the decode step indexes factors by
slot id; swapping one under an active sequence would silently change
its weights mid-generation).  Dropping an idle adapter is always safe:
the next request faults it back from the host tier or the admin
surface reloads it from its source.
"""

from __future__ import annotations

import collections
import logging
import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

# load-refusal reasons (the label values of
# kaito:adapter_load_failures_total)
REASON_BASE_MISMATCH = "base_mismatch"
REASON_RANK_OVERFLOW = "rank_overflow"
REASON_UNREADABLE = "unreadable"
REASON_NO_TARGETS = "no_targets"
REASON_CAPACITY = "capacity"


class AdapterLoadError(ValueError):
    """A load the cache refused; ``reason`` is the counter label."""

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason


class AdapterBusyError(RuntimeError):
    """Eviction/overwrite refused: in-flight requests pin the slot."""


class HostAdapterEntry:
    __slots__ = ("factors", "r", "scaling", "base", "nbytes")

    def __init__(self, factors: dict, r: int, scaling: float,
                 base: str, nbytes: int):
        self.factors = factors
        self.r = r
        self.scaling = scaling
        self.base = base
        self.nbytes = nbytes


class HostAdapterTier:
    """Byte-budgeted LRU of evicted adapters' raw host factors, keyed
    by adapter name (the ``HostKVPool`` discipline: same-key overwrite
    discards first, oversize entries are refused, eviction pops the
    least-recently-used end)."""

    def __init__(self, max_bytes: int):
        self.max_bytes = max_bytes
        self.used_bytes = 0
        self._entries: "collections.OrderedDict[str, HostAdapterEntry]" = \
            collections.OrderedDict()
        self.hits = 0          # pop() found the adapter (fault-back-in)
        self.misses = 0        # pop() came up empty (evicted/never held)
        self.evicted_entries = 0

    def __len__(self) -> int:
        return len(self._entries)

    def names(self) -> list[str]:
        return list(self._entries)

    def put(self, name: str, entry: HostAdapterEntry) -> bool:
        self.discard(name)     # same-key overwrite must not double-count
        if entry.nbytes > self.max_bytes:
            return False
        while (self.used_bytes + entry.nbytes > self.max_bytes
               and self._entries):
            _, old = self._entries.popitem(last=False)
            self.used_bytes -= old.nbytes
            self.evicted_entries += 1
        self._entries[name] = entry
        self.used_bytes += entry.nbytes
        return True

    def pop(self, name: str) -> Optional[HostAdapterEntry]:
        entry = self._entries.pop(name, None)
        if entry is not None:
            self.used_bytes -= entry.nbytes
            self.hits += 1
        else:
            self.misses += 1
        return entry

    def has(self, name: str) -> bool:
        return name in self._entries

    def discard(self, name: str) -> None:
        entry = self._entries.pop(name, None)
        if entry is not None:
            self.used_bytes -= entry.nbytes


class AdapterCache:
    """Fixed-capacity HBM slot table + host-RAM overflow tier.

    ``serve_lora`` is THE buffer tree the engine mounts at
    ``params["serve_lora"]`` — the cache mutates its leaves in place
    (functionally: each hot-load replaces a leaf with a same-shape
    ``at[].set`` result), so the engine never rebuilds its param tree
    and the decode programs never retrace.
    """

    def __init__(self, model, *, slots: int, rmax: int,
                 base_model: str = "", host_bytes: int = 0,
                 allow_base_mismatch: bool = False, mesh=None):
        if slots < 1:
            raise ValueError("adapter cache needs at least one slot")
        if rmax < 1:
            raise ValueError("adapter rmax must be positive")
        if model.is_mla:
            raise ValueError("per-request adapters are not supported on "
                             "MLA models")
        self.slots = slots
        self.rmax = rmax
        self.base_model = base_model
        self.allow_base_mismatch = allow_base_mismatch
        self._model = model
        self._mesh = mesh
        self._lock = threading.RLock()
        # engine hook: True when in-flight work references the adapter
        # (waiting queue or an active decode slot) — pinned slots are
        # never evicted or overwritten
        self.busy_fn: Callable[[str], bool] = lambda name: False
        # resident state: name -> slot (1-based; 0 is the base lane).
        # name_to_slot is handed to the engine as its adapter_index and
        # mutated IN PLACE so both sides always see the same residency.
        self.name_to_slot: dict[str, int] = {}
        self._slot_names: list[str] = [""] * (slots + 1)
        self._lru: "collections.OrderedDict[str, None]" = \
            collections.OrderedDict()
        self._meta: dict[str, dict] = {}
        self.host = HostAdapterTier(host_bytes) if host_bytes > 0 else None
        # counters (exposed as kaito:adapter_* when the cache is on)
        self.loads_total = 0         # installs into an HBM slot
        self.evictions_total = 0     # HBM slots reclaimed
        self.hits_total = 0          # ensure() found the adapter resident
        self.faults_total = 0        # ensure() pulled it back from host
        self.load_failures: dict[str, int] = {}
        # pre-allocate every per-request-servable target at full
        # capacity: [L, slots+1, in, rmax] / [L, slots+1, rmax, out].
        # MoE groups keep dense attention adapters only (the expert MLP
        # path has no LoRA sites) — mirrors adapters.load_adapter_stacks.
        self._specs: dict[str, dict[str, tuple[int, int]]] = {}
        serve_lora: dict = {}
        for g in model.groups:
            specs = model._layer_specs(g.moe, g.kind)
            targets = (("q", "k", "v", "o") if g.moe
                       else ("q", "k", "v", "o", "gate", "up", "down"))
            group_buf: dict = {}
            gspec: dict[str, tuple[int, int]] = {}
            for t in targets:
                if t not in specs:
                    continue
                in_dim, out_dim = specs[t][0]
                gspec[t] = (in_dim, out_dim)
                group_buf[f"{t}_a"] = jnp.zeros(
                    (g.count, slots + 1, in_dim, rmax), model.dtype)
                group_buf[f"{t}_b"] = jnp.zeros(
                    (g.count, slots + 1, rmax, out_dim), model.dtype)
            if group_buf:
                self._specs[g.name] = gspec
                serve_lora[g.name] = group_buf
        if not serve_lora:
            raise ValueError("model exposes no per-request-servable "
                             "LoRA targets")
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            serve_lora = jax.device_put(serve_lora,
                                        NamedSharding(mesh, P()))
        self.serve_lora = serve_lora
        nbytes = sum(x.nbytes for b in serve_lora.values()
                     for x in b.values())
        logger.info("adapter cache: %d HBM slots (rmax=%d, %.1f MiB)%s",
                    slots, rmax, nbytes / 2**20,
                    "" if self.host is None else
                    f" + {host_bytes / 2**20:.0f} MiB host tier")

    # -- residency ---------------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self.name_to_slot)

    def resident(self) -> list[str]:
        with self._lock:
            return list(self.name_to_slot)

    def has(self, name: str) -> bool:
        with self._lock:
            return (name in self.name_to_slot
                    or (self.host is not None and self.host.has(name)))

    def ensure(self, name: str) -> int:
        """Slot index of ``name``, faulting it back from the host tier
        if HBM evicted it.  Raises KeyError when the cache holds no
        trace of the adapter (the admin surface must re-load it)."""
        with self._lock:
            slot = self.name_to_slot.get(name)
            if slot is not None:
                self._lru.move_to_end(name)
                self.hits_total += 1
                return slot
            entry = self.host.pop(name) if self.host is not None else None
            if entry is None:
                raise KeyError(name)
            slot = self._install_locked(name, entry.factors, r=entry.r,
                                        scaling=entry.scaling,
                                        base=entry.base,
                                        validate_base=False)
            self.faults_total += 1
            return slot

    # -- loading -----------------------------------------------------------

    def _refuse(self, reason: str, message: str) -> AdapterLoadError:
        self.load_failures[reason] = self.load_failures.get(reason, 0) + 1
        logger.warning("adapter load refused (%s): %s", reason, message)
        return AdapterLoadError(reason, message)

    def load_from_path(self, name: str, path: str) -> int:
        """Load a kaito-tpu-lora-v1 artifact directory into a slot."""
        from kaito_tpu.tuning.lora import load_adapter

        try:
            adapter, cfg, base = load_adapter(path)
        except Exception as e:
            raise self._refuse(REASON_UNREADABLE,
                               f"adapter {name!r} at {path}: {e}") from None
        return self.install(name, adapter, r=cfg.r, scaling=cfg.scaling,
                            base=base)

    def install(self, name: str, factors: dict, *, r: int,
                scaling: float, base: str = "") -> int:
        """Install raw adapter factors (``{group}/{t}_lora_a`` flat keys
        or the nested trainer tree) into an HBM slot; returns the slot
        index.  Refusals raise :class:`AdapterLoadError` with a counted
        reason; a pinned-full table raises with reason "capacity"."""
        with self._lock:
            if (base and self.base_model and base != self.base_model
                    and not self.allow_base_mismatch):
                raise self._refuse(
                    REASON_BASE_MISMATCH,
                    f"adapter {name!r} targets base {base!r}, serving "
                    f"{self.base_model!r} (pass --adapter-allow-base-"
                    f"mismatch to serve it anyway)")
            if r > self.rmax:
                raise self._refuse(
                    REASON_RANK_OVERFLOW,
                    f"adapter {name!r} rank {r} exceeds the slot table's "
                    f"rmax {self.rmax} (restart with a larger "
                    f"--adapter-rmax)")
            flat = _flatten_factors(factors)
            if not any(self._factor_targets(flat)):
                raise self._refuse(
                    REASON_NO_TARGETS,
                    f"adapter {name!r} carries no per-request-servable "
                    f"targets")
            return self._install_locked(name, flat, r=r, scaling=scaling,
                                        base=base, validate_base=False)

    def _factor_targets(self, flat: dict):
        for gname, gspec in self._specs.items():
            for t in gspec:
                if f"{gname}/{t}_lora_a" in flat:
                    yield gname, t

    def _install_locked(self, name: str, factors: dict, *, r: int,
                        scaling: float, base: str,
                        validate_base: bool) -> int:
        flat = _flatten_factors(factors)
        slot = self.name_to_slot.get(name)
        if slot is not None and self.busy_fn(name):
            raise AdapterBusyError(
                f"adapter {name!r} is serving in-flight requests")
        if slot is None:
            slot = self._free_slot_locked()
        self._write_slot(slot, flat, scaling)
        prev = self._slot_names[slot]
        if prev and prev != name:
            self.name_to_slot.pop(prev, None)
            self._lru.pop(prev, None)
        self._slot_names[slot] = name
        self.name_to_slot[name] = slot
        self._lru[name] = None
        self._lru.move_to_end(name)
        self._meta[name] = {"r": r, "scaling": scaling, "base": base,
                            "nbytes": sum(np.asarray(a).nbytes
                                          for a in flat.values())}
        self.loads_total += 1
        logger.info("adapter %s -> slot %d (r=%d)", name, slot, r)
        return slot

    def _free_slot_locked(self) -> int:
        if len(self.name_to_slot) < self.slots:
            used = set(self.name_to_slot.values())
            for s in range(1, self.slots + 1):
                if s not in used:
                    return s
        # full: evict the least-recently-used adapter nobody is serving
        for victim in self._lru:
            if not self.busy_fn(victim):
                return self._evict_locked(victim)
        raise self._refuse(
            REASON_CAPACITY,
            f"all {self.slots} adapter slots pinned by in-flight "
            f"requests")

    def _evict_locked(self, name: str) -> int:
        slot = self.name_to_slot.pop(name)
        self._lru.pop(name, None)
        meta = self._meta.pop(name, {})
        self._slot_names[slot] = ""
        self.evictions_total += 1
        if self.host is not None:
            # demote the factors to the host tier so the next request
            # for this adapter faults it back instead of 404ing
            entry = HostAdapterEntry(
                factors=self._read_slot(slot, meta),
                r=int(meta.get("r", self.rmax)),
                scaling=float(meta.get("scaling", 1.0)),
                base=str(meta.get("base", "")),
                nbytes=int(meta.get("nbytes", 0)) or 1)
            self.host.put(name, entry)
        logger.info("adapter %s evicted from slot %d%s", name, slot,
                    "" if self.host is None else " (host tier)")
        return slot

    def _write_slot(self, slot: int, flat: dict, scaling: float) -> None:
        """Donate the padded factors into lane ``slot`` of every target
        buffer.  Targets the adapter does not carry are ZEROED — a
        reused slot must not leak its previous occupant's deltas.
        Every write is a same-shape ``at[].set``, so shape, dtype and
        sharding are preserved and the jit cache stays warm."""
        for gname, gspec in self._specs.items():
            buf = self.serve_lora[gname]
            for t, (in_dim, out_dim) in gspec.items():
                a = flat.get(f"{gname}/{t}_lora_a")
                b = flat.get(f"{gname}/{t}_lora_b")
                if a is not None and b is not None:
                    a = np.asarray(a, np.float32)       # [L, in, r]
                    b = np.asarray(b, np.float32)       # [L, r, out]
                    pa = np.zeros((a.shape[0], in_dim, self.rmax),
                                  np.float32)
                    pa[:, :, :a.shape[-1]] = a
                    pb = np.zeros((b.shape[0], self.rmax, out_dim),
                                  np.float32)
                    pb[:, :b.shape[1], :] = b * scaling
                else:
                    L = buf[f"{t}_a"].shape[0]
                    pa = np.zeros((L, in_dim, self.rmax), np.float32)
                    pb = np.zeros((L, self.rmax, out_dim), np.float32)
                buf[f"{t}_a"] = buf[f"{t}_a"].at[:, slot].set(
                    pa.astype(self._model.dtype))
                buf[f"{t}_b"] = buf[f"{t}_b"].at[:, slot].set(
                    pb.astype(self._model.dtype))

    def _read_slot(self, slot: int, meta: dict) -> dict:
        """Raw (unpadded, unscaled) factors of lane ``slot`` copied to
        host — what the host tier stores for fault-back-in."""
        r = int(meta.get("r", self.rmax)) or self.rmax
        scaling = float(meta.get("scaling", 1.0)) or 1.0
        out: dict = {}
        for gname, gspec in self._specs.items():
            buf = self.serve_lora[gname]
            for t in gspec:
                a = np.asarray(buf[f"{t}_a"][:, slot], np.float32)
                b = np.asarray(buf[f"{t}_b"][:, slot], np.float32)
                if not a.any() and not b.any():
                    continue
                out[f"{gname}/{t}_lora_a"] = a[:, :, :r]
                out[f"{gname}/{t}_lora_b"] = b[:, :r, :] / scaling
        return out

    # -- removal -----------------------------------------------------------

    def remove(self, name: str) -> bool:
        """Drop an adapter from BOTH tiers (the DELETE /v1/adapters
        semantics — no fault-back-in afterwards).  Returns False when
        the cache holds no trace of it; raises AdapterBusyError when
        in-flight requests pin it."""
        with self._lock:
            dropped = False
            if name in self.name_to_slot:
                if self.busy_fn(name):
                    raise AdapterBusyError(
                        f"adapter {name!r} is serving in-flight requests")
                slot = self.name_to_slot.pop(name)
                self._lru.pop(name, None)
                self._meta.pop(name, None)
                self._slot_names[slot] = ""
                self._write_slot(slot, {}, 1.0)
                self.evictions_total += 1
                dropped = True
            if self.host is not None and self.host.has(name):
                self.host.discard(name)
                dropped = True
            return dropped

    # -- introspection -----------------------------------------------------

    def snapshot(self) -> dict:
        """The ``GET /v1/adapters`` payload (and the advert the EPP's
        adapter scraper folds into its affinity index)."""
        with self._lock:
            resident = [{"name": n, "slot": s,
                         "r": int(self._meta.get(n, {}).get("r", 0)),
                         "base": str(self._meta.get(n, {}).get("base", ""))}
                        for n, s in sorted(self.name_to_slot.items(),
                                           key=lambda kv: kv[1])]
            out = {
                "enabled": True,
                "slots": self.slots,
                "rmax": self.rmax,
                "resident": resident,
                "host_tier": (sorted(self.host.names())
                              if self.host is not None else []),
                "loads_total": self.loads_total,
                "evictions_total": self.evictions_total,
                "hits_total": self.hits_total,
                "faults_total": self.faults_total,
                "load_failures": dict(self.load_failures),
            }
            return out


def _flatten_factors(factors: dict) -> dict:
    """Accept either the flat ``{group}/{t}_lora_a`` artifact layout
    (``tuning.lora.extract_adapter``) or the nested trainer tree and
    return the flat form."""
    if all(isinstance(v, dict) for v in factors.values()) and factors:
        flat: dict = {}
        for gname, stack in factors.items():
            for k, v in stack.items():
                flat[f"{gname}/{k}"] = v
        return flat
    return dict(factors)

"""Serving-side LoRA adapter loading.

Counterpart of the reference wrapper's ``--kaito-adapters-dir``
discovery + vLLM LoRARequest plumbing (``inference_api.py:417-498``):
at startup the engine scans the adapter directory and loads every
adapter (kaito_tpu.tuning.lora format) into STACKED per-target buffers
— ``[L, n_adapters+1, in, r_max]`` factors that ride the layer scan —
so each request selects its adapter by index at runtime (index 0 is the
all-zeros base).  Requests choose an adapter with the ``model`` field,
exactly like the reference serves adapters as selectable models.

``apply_adapters_to_params`` (merge-into-base) remains for the TP/PP
paths where the stacked buffers aren't wired yet.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)


def discover_adapters(adapters_dir: str) -> dict[str, str]:
    """Find adapters: subdirectories holding an adapter config."""
    found: dict[str, str] = {}
    if not adapters_dir or not os.path.isdir(adapters_dir):
        return found
    for name in sorted(os.listdir(adapters_dir)):
        path = os.path.join(adapters_dir, name)
        if os.path.isdir(path) and (
            os.path.exists(os.path.join(path, "adapter_config.json"))
            or os.path.exists(os.path.join(path, "adapter.msgpack"))
        ):
            found[name] = path
    return found


def load_adapter_stacks(model, adapters_dir: str, base_model: str = "",
                        allow_base_mismatch: bool = False,
                        refusals: Optional[dict] = None) -> tuple[dict, dict]:
    """Build the serve-time stacked LoRA buffers.

    Returns ``(serve_lora, name_to_index)`` where serve_lora is
    ``{group: {f"{t}_a": [L, n+1, in, rmax], f"{t}_b": [L, n+1, rmax, out]}}``
    (adapter 0 all-zeros = base model; alpha/r scaling folded into B)
    and name_to_index maps adapter names to their runtime index.
    Empty dicts when no adapters are present.

    An adapter whose recorded base model disagrees with the serving
    model is REFUSED (skipped and counted into ``refusals`` under
    ``"base_mismatch"`` — the kaito:adapter_load_failures_total label)
    rather than warned about and served: a wrong-base delta silently
    degrades every response routed at it.  ``allow_base_mismatch``
    (--adapter-allow-base-mismatch) restores the old behavior for
    intentionally cross-based adapters.
    """
    from kaito_tpu.tuning.lora import load_adapter

    def _count(reason: str) -> None:
        if refusals is not None:
            refusals[reason] = refusals.get(reason, 0) + 1

    if model.is_mla:
        # the MLA layer body has no multi-LoRA sites yet; refusing to
        # load keeps selection an explicit error instead of a silent
        # base-model response
        if discover_adapters(adapters_dir):
            logger.warning("per-request adapters are not supported on MLA "
                           "models yet; adapters in %s ignored", adapters_dir)
        return {}, {}
    found = discover_adapters(adapters_dir)
    loaded = []
    for name, path in found.items():
        try:
            adapter, cfg, base = load_adapter(path)
        except Exception:
            logger.exception("skipping unreadable adapter %s", name)
            _count("unreadable")
            continue
        if base and base_model and base != base_model:
            if not allow_base_mismatch:
                logger.warning(
                    "refusing adapter %s: targets base %s, serving %s "
                    "(pass --adapter-allow-base-mismatch to serve it "
                    "anyway)", name, base, base_model)
                _count("base_mismatch")
                continue
            logger.warning("adapter %s targets base %s, serving %s "
                           "(allowed by --adapter-allow-base-mismatch)",
                           name, base, base_model)
        loaded.append((name, adapter, cfg))
    if not loaded:
        return {}, {}

    rmax = max(cfg.r for _, _, cfg in loaded)
    n = len(loaded)
    serve_lora: dict = {}
    for g in model.groups:
        specs = model._layer_specs(g.moe, g.kind)
        # MoE groups still have dense ATTENTION projections — their
        # q/k/v/o adapters apply; only the expert MLP targets are
        # per-request-unsupported (the moe path has no LoRA sites)
        targets = (("q", "k", "v", "o") if g.moe
                   else ("q", "k", "v", "o", "gate", "up", "down"))
        group_buf: dict = {}
        for t in targets:
            if t not in specs:
                continue
            in_dim, out_dim = specs[t][0]
            key_a = f"{g.name}/{t}_lora_a"
            key_b = f"{g.name}/{t}_lora_b"
            if not any(key_a in ad for _, ad, _ in loaded):
                continue
            A = np.zeros((g.count, n + 1, in_dim, rmax), np.float32)
            B = np.zeros((g.count, n + 1, rmax, out_dim), np.float32)
            for i, (name, ad, cfg) in enumerate(loaded):
                if key_a not in ad:
                    continue
                a = np.asarray(ad[key_a], np.float32)     # [L, in, r]
                b = np.asarray(ad[key_b], np.float32)     # [L, r, out]
                A[:, i + 1, :, :a.shape[-1]] = a
                B[:, i + 1, :b.shape[1], :] = b * cfg.scaling
            group_buf[f"{t}_a"] = jnp.asarray(A, model.dtype)
            group_buf[f"{t}_b"] = jnp.asarray(B, model.dtype)
        if group_buf:
            serve_lora[g.name] = group_buf
    if not serve_lora:
        # no routable targets at all: report nothing loadable so the
        # caller falls back to merge semantics instead of serving
        # phantom adapter names
        logger.warning("adapters in %s carry no per-request-servable "
                       "targets", adapters_dir)
        return {}, {}
    name_to_index = {name: i + 1 for i, (name, _, _) in enumerate(loaded)}
    logger.info("loaded %d adapters for per-request serving: %s (rmax=%d)",
                n, list(name_to_index), rmax)
    return serve_lora, name_to_index


def apply_adapters_to_params(model, params, adapters_dir: str) -> dict:
    """Load every adapter in the dir and merge into the base weights.
    Multiple adapters merge additively (strength folded at tune time)."""
    from kaito_tpu.tuning.lora import (
        LoraConfig,
        apply_adapter,
        load_adapter,
        merge_lora,
    )

    for name, path in discover_adapters(adapters_dir).items():
        try:
            adapter, cfg, base = load_adapter(path)
        except Exception:
            logger.exception("skipping unreadable adapter %s", name)
            continue
        logger.info("loading adapter %s (base %s, r=%d)", name, base, cfg.r)
        params = apply_adapter(params, adapter)
        model.lora_scaling = cfg.scaling
        params = merge_lora(model, params)
    return params

"""LoRA: low-rank adapter parameters over the stacked layer trees.

The JAX/TPU counterpart of the reference's PEFT usage
(``presets/workspace/tuning/text-generation/cli.py`` ExtLoraConfig +
``fine_tuning.py`` get_peft_model): adapter factors live as extra keys
in the layer stacks (``q_lora_a``/``q_lora_b`` ...), the model applies
them at the projection sites inside the layer scan (engine/nn.py
lora_delta), and only these keys train — the base stays frozen (and may
be int8 for QLoRA).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from kaito_tpu.engine.model import TransformerLM

DEFAULT_TARGETS = ("q", "k", "v", "o")
ALL_TARGETS = ("q", "k", "v", "o", "gate", "up", "down")


@dataclass
class LoraConfig:
    r: int = 8
    alpha: int = 16
    targets: tuple[str, ...] = DEFAULT_TARGETS
    dropout: float = 0.0     # applied by the trainer on the lora path

    @property
    def scaling(self) -> float:
        return self.alpha / self.r


def add_lora_params(model: TransformerLM, params: dict, cfg: LoraConfig,
                    key: jax.Array) -> dict:
    """Return params with lora factors added to each layer stack.
    A ~ N(0, 1/r) on the input side, B = 0 (delta starts at zero)."""
    out = dict(params)
    for g in model.groups:
        stack = dict(params[g.name])
        specs = model._layer_specs(g.moe, g.kind)
        for t in cfg.targets:
            if t not in specs:
                continue
            in_dim, out_dim = specs[t][0]
            ka = jax.random.fold_in(key, hash((g.name, t)) % 2**31)
            stack[f"{t}_lora_a"] = (
                jax.random.normal(ka, (g.count, in_dim, cfg.r), model.dtype)
                / np.sqrt(cfg.r))
            stack[f"{t}_lora_b"] = jnp.zeros((g.count, cfg.r, out_dim), model.dtype)
        out[g.name] = stack
    model.lora_scaling = cfg.scaling
    return out


def is_lora_path(path) -> bool:
    return any("lora" in str(getattr(p, "key", p)) for p in path)


def lora_mask(params: dict) -> dict:
    """Pytree of bools: True for trainable (lora) leaves — feeds
    optax.masked so the base stays frozen."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: is_lora_path(path), params)


def extract_adapter(params: dict) -> dict:
    """Only the lora leaves (the artifact we ship)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out: dict = {}
    for path, leaf in flat:
        keys = [str(getattr(p, "key", p)) for p in path]
        if any("lora" in k for k in keys):
            out["/".join(keys)] = np.asarray(leaf)
    return out


def apply_adapter(params: dict, adapter: dict) -> dict:
    """Insert saved lora leaves back into a param tree."""
    out = jax.tree.map(lambda x: x, params)  # fresh containers, shared leaves
    for flat_key, value in adapter.items():
        keys = flat_key.split("/")
        node = out
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = jnp.asarray(value)
    return out


def merge_lora(model: TransformerLM, params: dict) -> dict:
    """Fold deltas into the base weights for serving without lora
    compute: W' = W + scaling * A @ B. Removes the lora keys."""
    scaling = model.lora_scaling
    out = dict(params)
    for g in model.groups:
        stack = dict(out[g.name])
        for t in ALL_TARGETS:
            a = stack.pop(f"{t}_lora_a", None)
            b = stack.pop(f"{t}_lora_b", None)
            if a is None or b is None or t not in stack:
                continue
            base = stack[t]
            delta = jnp.einsum("lir,lro->lio", a, b) * scaling
            if isinstance(base, dict):  # quantized base: dequant + merge
                w = base["q8"].astype(delta.dtype) * base["scale"][..., None, :]
                stack[t] = w + delta
            else:
                stack[t] = base + delta
        out[g.name] = stack
    return out


# -- adapter artifact io ----------------------------------------------------

ADAPTER_WEIGHTS = "adapter.msgpack"
ADAPTER_CONFIG = "adapter_config.json"


def save_adapter(path: str, params: dict, cfg: LoraConfig, base_model: str):
    from flax import serialization

    os.makedirs(path, exist_ok=True)
    adapter = extract_adapter(params)
    with open(os.path.join(path, ADAPTER_WEIGHTS), "wb") as f:
        f.write(serialization.to_bytes(adapter))
    with open(os.path.join(path, ADAPTER_CONFIG), "w") as f:
        json.dump({"base_model": base_model, "r": cfg.r, "alpha": cfg.alpha,
                   "targets": list(cfg.targets), "format": "kaito-tpu-lora-v1"},
                  f, indent=2)


def load_adapter(path: str) -> tuple[dict, LoraConfig, str]:
    from flax import serialization

    with open(os.path.join(path, ADAPTER_CONFIG)) as f:
        meta = json.load(f)
    with open(os.path.join(path, ADAPTER_WEIGHTS), "rb") as f:
        adapter = serialization.msgpack_restore(f.read())
    cfg = LoraConfig(r=meta["r"], alpha=meta["alpha"],
                     targets=tuple(meta["targets"]))
    return adapter, cfg, meta.get("base_model", "")

"""Neural-net building blocks for the config-driven transformer.

Functional JAX (no module framework): parameters are plain pytrees so
the engine controls placement/donation precisely and trees map 1:1 onto
logical sharding axes (kaito_tpu.parallel.sharding).  Compute runs in
the params' dtype (bf16 on TPU) with fp32 norms/softmax, which is what
the MXU wants.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from kaito_tpu.models.metadata import ModelArch


def linear(x: jax.Array, w) -> jax.Array:
    """Matmul accepting either a plain weight or a QTensor dict —
    int8 ``{"q8": int8[in,out], "scale": f32[out]}`` or packed int4
    ``{"q4": int8[in/2,out], "scale": f32[G,out]}`` (engine/quant.py).
    QTensors route through ops/quant_matmul.quant_linear: the fused
    Pallas dequant kernel for decode-shaped calls on TPU (the HBM read
    is the quantized bytes by construction), pure-JAX dequant-into-dot
    everywhere else — the QLoRA memory model either way.
    """
    from kaito_tpu.engine.quant import is_qtensor

    if is_qtensor(w):
        from kaito_tpu.engine.ops.quant_matmul import quant_linear

        return quant_linear(x, w)
    return x @ w


def lora_delta(x: jax.Array, p: dict, name: str, scaling: float) -> jax.Array:
    """Low-rank update ``(x @ A) @ B * (alpha/r)`` when the layer stack
    carries lora factors for ``name`` (keys set by kaito_tpu.tuning.lora)."""
    a = p.get(f"{name}_lora_a")
    if a is None:
        return 0.0
    b = p[f"{name}_lora_b"]
    return ((x @ a) @ b) * scaling


def multi_lora_delta(x: jax.Array, lora: Optional[dict], name: str,
                     ids: Optional[jax.Array]):
    """Per-request batched LoRA: each row of the batch applies ITS OWN
    adapter's low-rank update (adapter 0 is the all-zeros base).

    The serving counterpart of the reference's per-request vLLM
    LoRARequest routing (inference_api.py:417-498).  x: [B, T, E];
    lora[f"{name}_a"]: [n_adapters, E, r] (per-layer slice of the scan
    stack); ids: [B] int32.  Scaling is folded into B at load time.
    """
    if lora is None or ids is None:
        return 0.0
    a = lora.get(f"{name}_a")
    if a is None:
        return 0.0
    b = lora[f"{name}_b"]
    ax = jnp.einsum("bte,ber->btr", x, a[ids])
    return jnp.einsum("btr,bro->bto", ax, b[ids])


def rms_norm(x: jax.Array, scale: jax.Array, eps: float, offset: bool) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    w = scale.astype(jnp.float32)
    if offset:
        w = 1.0 + w
    return (y * w).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: Optional[jax.Array], eps: float) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


def apply_norm(x: jax.Array, params: dict, arch: ModelArch) -> jax.Array:
    if arch.norm_type == "layernorm":
        return layer_norm(x, params["scale"], params.get("bias"), arch.rms_norm_eps)
    return rms_norm(x, params["scale"], arch.rms_norm_eps, arch.norm_offset)


# ---------------------------------------------------------------------------
# Rotary position embedding (with llama3 / linear / yarn-style scaling)
# ---------------------------------------------------------------------------

def _yarn_find_correction_dim(num_rotations: float, dim: int, base: float,
                              max_pos: float) -> float:
    return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))
            ) / (2 * math.log(base))


def yarn_get_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN attention-magnitude correction (0.1·m·ln(s)+1)."""
    if scale <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def rope_frequencies(arch: ModelArch) -> jax.Array:
    """Per-pair inverse frequencies, with rope_scaling applied
    (exact llama3 / yarn NTK-by-parts / longrope per-dim factors)."""
    rot_dim = int(arch.head_dim * arch.partial_rotary_factor)
    rot_dim -= rot_dim % 2
    exponent = jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim
    inv_freq = 1.0 / (arch.rope_theta ** exponent)

    scaling = arch.rope_scaling or {}
    rope_type = str(scaling.get("rope_type", scaling.get("type", ""))).lower()
    if rope_type == "linear":
        inv_freq = inv_freq / float(scaling.get("factor", 1.0))
    elif rope_type == "llama3":
        # Llama-3.1 frequency-dependent scaling: low-frequency components
        # are stretched by `factor`, high-frequency kept, mid smoothed.
        factor = float(scaling.get("factor", 8.0))
        low = float(scaling.get("low_freq_factor", 1.0))
        high = float(scaling.get("high_freq_factor", 4.0))
        old_len = float(scaling.get("original_max_position_embeddings", 8192))
        wavelen = 2.0 * math.pi / inv_freq
        low_wl = old_len / low
        high_wl = old_len / high
        smooth = (old_len / wavelen - low) / (high - low)
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = jnp.where(
            wavelen > low_wl,
            inv_freq / factor,
            jnp.where(wavelen < high_wl, inv_freq,
                      (1 - smooth) * inv_freq / factor + smooth * inv_freq),
        )
        inv_freq = scaled
    elif rope_type == "yarn":
        # exact NTK-by-parts: high-frequency pairs keep the base table
        # (extrapolation), low-frequency pairs interpolate by `factor`,
        # with a linear ramp between the beta_fast/beta_slow correction
        # dims (the deepseek / HF YarnRotaryEmbedding recipe)
        factor = float(scaling.get("factor", 1.0))
        orig = float(scaling.get("original_max_position_embeddings",
                                 arch.max_position_embeddings))
        beta_fast = float(scaling.get("beta_fast", 32.0))
        beta_slow = float(scaling.get("beta_slow", 1.0))
        low = math.floor(_yarn_find_correction_dim(
            beta_fast, rot_dim, arch.rope_theta, orig))
        high = math.ceil(_yarn_find_correction_dim(
            beta_slow, rot_dim, arch.rope_theta, orig))
        low, high = max(low, 0), min(high, rot_dim - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip(
            (jnp.arange(rot_dim // 2, dtype=jnp.float32) - low)
            / (high - low), 0.0, 1.0)
        extrap_mask = 1.0 - ramp
        inv_freq = (inv_freq / factor) * (1.0 - extrap_mask) \
            + inv_freq * extrap_mask
    elif rope_type in ("longrope", "su"):
        # phi-3 family: per-dim rescale factors, long vs short chosen
        # by whether the model runs past its original trained length
        orig = float(scaling.get("original_max_position_embeddings",
                                 arch.max_position_embeddings))
        use_long = arch.max_position_embeddings > orig
        factors = scaling.get("long_factor" if use_long else "short_factor")
        if factors is not None:
            f = jnp.asarray(factors, jnp.float32)[: rot_dim // 2]
            inv_freq = inv_freq / f
        else:
            inv_freq = inv_freq / float(scaling.get("factor", 1.0))
    return inv_freq


def longrope_tables(arch: ModelArch):
    """Per-position longrope state for archs carrying factor lists:
    ``(short_inv_freq, long_inv_freq, orig_len, short_mscale,
    long_mscale)``; None otherwise.

    The serving engine switches tables PER POSITION (positions past the
    original trained length use long factors) — the vLLM
    Phi3LongRoPE cache semantics, which HF's per-forward seq-len switch
    approximates; a batch mixing short and long sequences gets each
    row's correct table.
    """
    scaling = arch.rope_scaling or {}
    rope_type = str(scaling.get("rope_type", scaling.get("type", ""))).lower()
    if rope_type not in ("longrope", "su") or "long_factor" not in scaling:
        return None
    rot_dim = int(arch.head_dim * arch.partial_rotary_factor)
    rot_dim -= rot_dim % 2
    exponent = jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim
    base = 1.0 / (arch.rope_theta ** exponent)
    half = rot_dim // 2
    short = base / jnp.asarray(scaling.get("short_factor"),
                               jnp.float32)[:half]
    long = base / jnp.asarray(scaling.get("long_factor"),
                              jnp.float32)[:half]
    orig = float(scaling.get("original_max_position_embeddings",
                             arch.max_position_embeddings))
    s = arch.max_position_embeddings / orig
    default_m = (math.sqrt(1.0 + math.log(s) / math.log(orig))
                 if s > 1.0 else 1.0)
    short_m = float(scaling.get("short_mscale") or default_m)
    long_m = float(scaling.get("long_mscale") or default_m)
    return short, long, orig, short_m, long_m


def rope_attention_factor(arch: ModelArch) -> float:
    """Magnitude correction multiplying the ROTATED dims' cos/sin (the
    HF attention_scaling contract): yarn's mscale (or the
    mscale/mscale_all_dim ratio when both are set — deepseek style,
    where the all-dim part moves into the softmax scale instead), and
    longrope's sqrt(1 + ln(s)/ln(orig))."""
    scaling = arch.rope_scaling or {}
    rope_type = str(scaling.get("rope_type", scaling.get("type", ""))).lower()
    if scaling.get("attention_factor") is not None:
        return float(scaling["attention_factor"])
    if rope_type == "yarn":
        factor = float(scaling.get("factor", 1.0))
        mscale = float(scaling.get("mscale", 1.0))
        mad = scaling.get("mscale_all_dim")
        if mad is not None:
            return yarn_get_mscale(factor, mscale) \
                / yarn_get_mscale(factor, float(mad))
        return yarn_get_mscale(factor, mscale)
    if rope_type in ("longrope", "su"):
        orig = float(scaling.get("original_max_position_embeddings",
                                 arch.max_position_embeddings))
        s = arch.max_position_embeddings / orig
        if s <= 1.0:
            return 1.0
        return math.sqrt(1.0 + math.log(s) / math.log(orig))
    return 1.0


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array,
               head_dim: int, mscale=1.0) -> jax.Array:
    """Rotate the first ``2*len(inv_freq)`` dims of each head.

    x: [..., seq, heads, head_dim]; positions: [..., seq].  ``mscale``
    multiplies the rotated output (HF's attention_scaling on cos/sin —
    yarn/longrope magnitude correction); pass-through dims unscaled.
    ``inv_freq`` may be per-position ([..., seq, half] — the longrope
    short/long switch) or a plain [half] table.
    """
    rot = 2 * inv_freq.shape[-1]
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # [..., seq, rot/2]
    cos = jnp.cos(angles)[..., :, None, :] * mscale
    sin = jnp.sin(angles)[..., :, None, :] * mscale
    x_rot = x[..., :rot].astype(jnp.float32)
    x_pass = x[..., rot:]
    x1, x2 = jnp.split(x_rot, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


def activation(x: jax.Array, name: str) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name in ("gelu",):
        return jax.nn.gelu(x, approximate=False)
    if name in ("gelu_tanh", "gelu_new"):
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {name!r}")


def mlp(x: jax.Array, p: dict, arch: ModelArch, lora_scaling: float = 0.0,
        serve_lora: Optional[dict] = None,
        lora_ids: Optional[jax.Array] = None,
        overlap=None, pf_down: Optional[dict] = None) -> jax.Array:
    """Gated (SwiGLU/GeGLU) or classic 2-matrix MLP.

    ``overlap`` is the engine's (mesh, axis) comm-overlap handle
    (docs/multichip.md): when set, the row-parallel DOWN projection —
    the one whose output all-reduce sits on the TP decode critical
    path — routes through the pipelined ring instead of the implicit
    GSPMD collective, with ``pf_down`` (the next layer's quantized
    down slab) riding the same call as the layer-ahead prefetch, and
    the COLUMN-parallel gate/up projections route through the
    pipelined all-gather+matmul ring (plain 2-D weights only).  The
    LoRA deltas stay on the plain path: they are rank-r rescues whose
    collectives are noise next to the main projection's.
    """
    def _col(w):
        """Column-parallel projection: ring when overlapped+eligible,
        plain linear (implicit GSPMD collectives) otherwise."""
        if overlap is not None:
            from kaito_tpu.engine.ops.overlap_collectives import (
                ag_matmul_eligible, all_gather_matmul)

            mesh, axis = overlap
            if ag_matmul_eligible(x, w, int(mesh.shape[axis])):
                return all_gather_matmul(x, w, mesh, axis_name=axis)
        return linear(x, w)

    # falcon-h1: (gate pre-activation, down) multipliers
    gate_m, down_m = arch.mlp_multipliers or (None, None)
    if arch.gated_mlp:
        gate = _col(p["gate"]) + lora_delta(x, p, "gate", lora_scaling) \
            + multi_lora_delta(x, serve_lora, "gate", lora_ids)
        if gate_m is not None:
            gate = gate * jnp.asarray(gate_m, gate.dtype)
        gate = activation(gate, arch.hidden_act)
        up = _col(p["up"]) + lora_delta(x, p, "up", lora_scaling) \
            + multi_lora_delta(x, serve_lora, "up", lora_ids)
        h = gate * up
    else:
        h = _col(p["up"]) + lora_delta(x, p, "up", lora_scaling) \
            + multi_lora_delta(x, serve_lora, "up", lora_ids)
        if "up_bias" in p:
            h = h + p["up_bias"]
        h = activation(h, arch.hidden_act)
    if overlap is not None:
        from kaito_tpu.engine.ops.overlap_collectives import overlap_linear

        mesh, axis = overlap
        down = overlap_linear(h, p["down"], mesh, axis_name=axis,
                              prefetch=pf_down)
    else:
        down = linear(h, p["down"])
    out = down + lora_delta(h, p, "down", lora_scaling) \
        + multi_lora_delta(h, serve_lora, "down", lora_ids)
    if "down_bias" in p:
        out = out + p["down_bias"]
    if down_m is not None:
        out = out * jnp.asarray(down_m, out.dtype)
    return out


def moe_mlp(x: jax.Array, p: dict, arch: ModelArch) -> jax.Array:
    """Token-choice MoE with dense expert compute.

    x: [T, E].  Routing picks top-k experts per token; compute is done
    as dense einsums over all experts with a routing-weight mask —
    static shapes, MXU-friendly, exact (at the cost of FLOPs
    proportional to expert count; a Pallas grouped-matmul replaces this
    on the perf milestone).
    """
    T, E = x.shape
    X = arch.num_experts
    k = arch.num_experts_per_tok
    logits = (x.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # [T, X]
    weights, idx = jax.lax.top_k(logits, k)                             # [T, k]
    weights = jax.nn.softmax(weights, axis=-1)
    # scatter top-k weights back to a dense [T, X] routing matrix
    route = jnp.zeros((T, X), jnp.float32)
    route = route.at[jnp.arange(T)[:, None], idx].set(weights)
    # dense expert compute: h[x] = act(x @ gate_x) * (x @ up_x) @ down_x
    def expert_dot(spec, lhs, w):
        """einsum accepting a plain [X, in, out] stack or a QTensor:
        int8 {"q8", "scale": [X, out]} keeps the fused form (dequant
        fuses into the dot; the per-expert scale rides the output's
        [x, out] dims); int4's per-GROUP scales can't fold post-dot
        across groups, so the expert stack dequants to lhs.dtype first
        (elementwise — XLA fuses it into the einsum's RHS read)."""
        from kaito_tpu.engine.quant import (dequant_weight, is_qtensor,
                                            qtensor_kind)

        if is_qtensor(w):
            if qtensor_kind(w) == "int4":
                return jnp.einsum(spec, lhs, dequant_weight(w, lhs.dtype))
            return jnp.einsum(spec, lhs, w["q8"].astype(lhs.dtype)) \
                * w["scale"].astype(lhs.dtype)
        return jnp.einsum(spec, lhs, w)

    gate = expert_dot("te,xei->txi", x, p["experts_gate"])
    up = expert_dot("te,xei->txi", x, p["experts_up"])
    h = activation(gate, arch.hidden_act) * up
    out = expert_dot("txi,xie->txe", h, p["experts_down"])
    y = jnp.einsum("txe,tx->te", out.astype(jnp.float32), route).astype(x.dtype)
    if "shared_gate" in p:
        shared = {"gate": p["shared_gate"], "up": p["shared_up"], "down": p["shared_down"]}
        y = y + mlp(x, shared, arch)
    return y


def moe_mlp_ragged(x: jax.Array, p: dict, arch: ModelArch) -> jax.Array:
    """Token-choice MoE via grouped (ragged) matmuls.

    Tokens sort by assigned expert and each expert runs one matmul over
    its contiguous group (``lax.ragged_dot`` — XLA's grouped-GEMM,
    megablox-style on TPU).  FLOPs scale with top_k instead of the
    expert count, unlike the dense fallback in :func:`moe_mlp`.
    Serving-path implementation; training keeps the dense form.
    """
    T, E = x.shape
    X = arch.num_experts
    k = arch.num_experts_per_tok
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    weights, idx = jax.lax.top_k(logits, k)            # [T, k]
    weights = jax.nn.softmax(weights, axis=-1)

    flat_expert = idx.reshape(-1)                      # [T*k]
    order = jnp.argsort(flat_expert)                   # stable
    token_of = order // k                              # originating token
    x_sorted = x[token_of]                             # [T*k, E]
    group_sizes = jnp.bincount(flat_expert, length=X)
    expert_of_row = flat_expert[order]                 # [T*k]

    def ragged(lhs, w):
        """ragged_dot accepting a plain stack or a QTensor: int8's
        convert fuses into the grouped GEMM's RHS load and each row's
        output scales by its expert's per-out-channel scale; int4
        dequants the stack first (per-group scales don't fold post-dot
        across groups — same trade as expert_dot in moe_mlp)."""
        from kaito_tpu.engine.quant import (dequant_weight, is_qtensor,
                                            qtensor_kind)

        if is_qtensor(w):
            if qtensor_kind(w) == "int4":
                return jax.lax.ragged_dot(
                    lhs, dequant_weight(w, lhs.dtype), group_sizes,
                    preferred_element_type=jnp.float32)
            out = jax.lax.ragged_dot(lhs, w["q8"].astype(lhs.dtype),
                                     group_sizes,
                                     preferred_element_type=jnp.float32)
            return out * w["scale"][expert_of_row].astype(out.dtype)
        return jax.lax.ragged_dot(lhs, w, group_sizes,
                                  preferred_element_type=jnp.float32)

    gate = ragged(x_sorted, p["experts_gate"])
    up = ragged(x_sorted, p["experts_up"])
    h = (activation(gate, arch.hidden_act) * up).astype(x.dtype)
    out_sorted = ragged(h, p["experts_down"])

    w_sorted = weights.reshape(-1)[order]
    y = jnp.zeros((T, E), jnp.float32).at[token_of].add(
        out_sorted * w_sorted[:, None])
    y = y.astype(x.dtype)
    if "shared_gate" in p:
        shared = {"gate": p["shared_gate"], "up": p["shared_up"],
                  "down": p["shared_down"]}
        y = y + mlp(x, shared, arch)
    return y


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap

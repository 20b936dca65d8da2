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
               head_dim: int, mscale=1.0, interleave: bool = False
               ) -> jax.Array:
    """Rotate the first ``2*len(inv_freq)`` dims of each head: pair
    ``i`` is dims ``(i, i + half)`` (rotate-half), or with
    ``interleave`` dims ``(2i, 2i + 1)``, each where it lies (the
    deepseek-v3 family's published pairing, ``rope_interleave``).

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
    if interleave:
        x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(x_rot.shape)
    else:
        x1, x2 = jnp.split(x_rot, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
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
    if arch.expert_shards != 1:
        raise NotImplementedError("the dense expert path holds every "
                                  "expert; a share of them is served by "
                                  "moe_mlp_ragged")
    logits = (x.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # [T, X]
    idx, weights = route_tokens(logits, arch, p.get("router_bias"))           # [T, k]
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


def route_tokens(logits: jax.Array, arch: ModelArch,
          bias: Optional[jax.Array] = None):
    """The router's choice, for every scoring this repo serves:
    ``logits`` [T, X] float32 -> (idx [T, k] int32, weights [T, k]
    float32).  Scores are the softmax or the sigmoid of the logits over
    ALL experts; the k experts with the largest score are chosen, with
    the correction ``bias`` (one float an expert) added to choose and
    never to weigh; the chosen scores are normalized to sum to one and
    scaled by ``routed_scaling_factor``.  (Softmax scores normalized
    over the chosen k are the softmax over the chosen logits: what
    mixtral and deepseek-v2 publish.)"""
    if arch.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choose = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(choose, arch.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if arch.routed_scaling_factor != 1.0:
        weights = weights * arch.routed_scaling_factor
    return idx, weights


def _grouped_matmul(lhs: jax.Array, w, group_sizes: jax.Array,
                    expert_of_row: jax.Array, kernel: bool,
                    layer=None) -> jax.Array:
    """``lhs[rows of group g] @ w[g]`` for each group, float32 out.
    Rows past ``sum(group_sizes)`` belong to no group: what they hold
    on return is undefined, and the caller masks them.  ``kernel``:
    the Pallas grouped matmul (an expert with no row is not visited
    and its matrix is not read); otherwise, and for quantized stacks,
    XLA's ``ragged_dot``.  With ``layer``, ``w`` is the whole stack of
    a layer kind, [layers, experts, in, out], and ``layer`` the index
    into it: the kernel then reads its tiles straight out of the stack
    (every other layer's experts are groups of no rows), where a slice
    handed to a custom call would be a copy of the layer's matrices."""
    from kaito_tpu.engine.quant import (dequant_weight, is_qtensor,
                                        qtensor_kind)

    if layer is not None:
        if kernel and not is_qtensor(w):
            from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

            n_layers, held = w.shape[:2]
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n_layers * held,), jnp.int32),
                group_sizes.astype(jnp.int32), (layer * held,))
            return gmm(lhs, w.reshape((n_layers * held,) + w.shape[2:]),
                       sizes, preferred_element_type=jnp.float32,
                       tiling=_gmm_tile(lhs.shape[0], *w.shape[-2:],
                                        lhs.dtype.itemsize))
        w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, layer, 0, keepdims=False), w)
    if is_qtensor(w):
        # int8's convert fuses into the grouped GEMM's RHS load and each
        # row's output scales by its expert's per-out-channel scale;
        # int4 dequants the stack first (per-group scales don't fold
        # post-dot across groups — same trade as expert_dot in moe_mlp)
        if qtensor_kind(w) == "int4":
            return jax.lax.ragged_dot(
                lhs, dequant_weight(w, lhs.dtype), group_sizes,
                preferred_element_type=jnp.float32)
        out = jax.lax.ragged_dot(lhs, w["q8"].astype(lhs.dtype),
                                 group_sizes,
                                 preferred_element_type=jnp.float32)
        return out * w["scale"][expert_of_row].astype(out.dtype)
    if kernel:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        return gmm(lhs, w, group_sizes.astype(jnp.int32),
                   preferred_element_type=jnp.float32,
                   tiling=_gmm_tile(lhs.shape[0], *w.shape[-2:],
                                        lhs.dtype.itemsize))
    return jax.lax.ragged_dot(lhs, w, group_sizes,
                              preferred_element_type=jnp.float32)


_GMM_TILE_M = 128
# What one set of the grouped-matmul kernel's tiles may hold of VMEM
# (``_gmm_vmem_bytes``).  A Mosaic call gets 16 MiB on a v5e unless it
# asks for more, and megablox's ``gmm`` cannot ask: the budget leaves a
# quarter of that to what the compiler keeps beside the tiles.
_GMM_VMEM_BUDGET = 12 << 20


def _gmm_rows(rows: int) -> int:
    """The rows the grouped-matmul kernel is handed for ``rows`` sorted
    pairs: a whole number of its row tiles (the kernel refuses any
    other count), 128 rows a tile, 16 for a handful of pairs."""
    tile = _GMM_TILE_M if rows >= _GMM_TILE_M else 16
    return -(-rows // tile) * tile


def _gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """VMEM one tile set of the kernel needs: the pipeline keeps two
    buffers each of the lhs [tm, tk], rhs [tk, tn] (``itemsize`` bytes
    an element) and float32 out [tm, tn] tiles, the next copied while
    this one multiplies, beside the float32 accumulator."""
    return (2 * (itemsize * (tm * tk + tk * tn) + 4 * tm * tn)
            + 4 * tm * tn)


def _gmm_tile(rows: int, k: int, n: int, itemsize: int = 2) -> tuple:
    """The kernel's (m, k, n) tiles for ``rows`` x [k, n] experts of
    ``itemsize`` bytes an element, from those numbers alone (PERF.md
    section 6, PR 45: the table these rules are read off).

    m: 128, or all of fewer rows.  k and n: the whole dimension or a
    multiple of 128 that divides it (a tile that does not is multiplied
    whole and its overhang masked: half of 1,792's fourth 512), under
    ``_GMM_VMEM_BUDGET``.  K stays whole where an n-tile no narrower
    than before fits beside it: with one k-tile an expert's [K, tn]
    stays in VMEM over all the row tiles of its group, where a split K
    copies it again for every row tile, and the accumulator is written
    once.  A K too long for that is cut to its largest divisor up to
    2,048.  Then n is the widest divisor that fits: the fewer tiles an
    expert's matrix is read in, the fewer grid steps pay their fixed
    cost, and a prefill's rows are read once an n-tile.

    What every call had before, ``(min(K, 2048), min(N, 512))``, stays
    in two cases: where no divisor of N is as wide as that, and for a
    call of at most two row tiles whose K and N the 2 MiB tile divides
    (no group there spans row tiles enough to use a resident [K, tn]
    twice, a copy of 2 MiB hides a grid step's fixed cost, and every
    wider tile measured slower: MiMo's 4,096 x 2,048 at 256 rows)."""
    tm = min(rows, _GMM_TILE_M)
    before = (min(k, 2048), min(n, 512))
    if rows <= 2 * _GMM_TILE_M and k % 2048 == 0 and n % 512 == 0:
        return (tm,) + before

    def divisors(x):
        """``x`` and the multiples of 128 that divide it, widest first."""
        return [x] + [t for t in range((x - 1) // 128 * 128, 127, -128)
                      if x % t == 0]

    def widest(tk):
        return next((tn for tn in divisors(n) if _gmm_vmem_bytes(
            tm, tk, tn, itemsize) <= _GMM_VMEM_BUDGET), 0)

    tk = k if widest(k) >= before[1] else next(
        (t for t in divisors(k) if t <= 2048), before[0])
    tn = widest(tk)
    return (tm, tk, tn) if tn >= before[1] else (tm,) + before


def _pass_rows(arch: ModelArch, tokens: int) -> int:
    """Sorted pairs one pass of the expert layer computes: every pair
    when the layer is whole, twice the even share of a layer that
    ``expert_shards`` chips share."""
    pairs = tokens * arch.num_experts_per_tok
    if arch.expert_shards == 1:
        return pairs
    return min(pairs, max(256, 2 * pairs // arch.expert_shards))


def expert_tiles(arch: ModelArch, tokens: int, itemsize: int) -> dict:
    """The tiles ``moe_mlp_ragged(kernel=True)`` runs a step of
    ``tokens`` tokens with over experts of ``itemsize`` bytes an
    element, ``{"KxN": [m, k, n]}`` for the gate and up matrices and
    for the down one: what ``/health`` lists."""
    rows = _gmm_rows(_pass_rows(arch, tokens))
    E, inter = arch.hidden_size, arch.moe_intermediate_size
    return {f"{k}x{n}": list(_gmm_tile(rows, k, n, itemsize))
            for k, n in ((E, inter), (inter, E))}


def _combine_slots(y: jax.Array, out: jax.Array, rel: jax.Array,
                   cap: int) -> jax.Array:
    """``y`` [T, E] float32 with every token's pairs added: slot ``j``
    of token ``t`` is row ``rel[t, j]`` of ``out`` [rows, E] where that
    lies in [0, cap) and nothing otherwise; summed in float32, a token's
    k pairs one after the other ([T, E] a time)."""
    for j in range(rel.shape[1]):
        r = rel[:, j]
        y = y + jnp.where(((r >= 0) & (r < cap))[:, None],
                          out[jnp.clip(r, 0, out.shape[0] - 1)], 0.0)
    return y


def _combine_held(y: jax.Array, out: jax.Array, rows: jax.Array,
                  live: jax.Array, k: int, fresh) -> jax.Array:
    """What ``_combine_slots`` gives, moving only the rows that hold a
    pair: row ``n`` of ``out`` [rows, E] float32 holds pair ``rows[n]``
    (token ``rows[n] // k``) where ``live[n]`` and nothing otherwise.
    The live pairs sort into token order, a token's in the order of its
    slots, and the Pallas kernel adds each to its token's row of ``y``
    [T, E] in that order (``ops/moe_combine.py``, a whole number of its
    token tiles): the same float32 sums as the loop over all ``k``
    slots, less its exact zeros.  ``fresh`` (bool scalar): ``y`` is all
    zeros."""
    from kaito_tpu.engine.ops.moe_combine import moe_combine_pallas

    T = y.shape[0]
    pair, pos = jax.lax.sort(
        (jnp.where(live, rows, T * k),
         jnp.arange(out.shape[0], dtype=jnp.int32)), num_keys=1)
    return moe_combine_pallas(y, out, pair // k, pos, fresh)


def moe_mlp_ragged(x: jax.Array, p: dict, arch: ModelArch, *,
                   valid: Optional[jax.Array] = None, kernel: bool = False,
                   with_stats: bool = False, layer=None):
    """Token-choice MoE via grouped matmuls over the experts HELD here.

    x: [T, E].  The router scores all ``arch.num_experts``; the
    parameters hold the ``arch.experts_held`` experts of this chip's
    share (all of them when ``expert_shards`` is 1).  The (token,
    expert) pairs whose expert is held sort by expert to the front and
    each held expert runs one matmul over its contiguous group
    (``_grouped_matmul``); a pair whose expert lives on another chip
    adds nothing here, and a token none of whose experts is held gets a
    zero.  The sorted pairs are computed ``cap`` rows a pass, as many
    passes as the held pairs need: every pair when the layer is whole,
    twice the even share of a layer that ``expert_shards`` chips share
    (one pass unless routing sends this share more than twice its
    due), so no pair is dropped whatever the routing.  ``valid`` [T]
    bool leaves a row's pairs out (a slot that decodes nothing, a
    prompt's padding).  FLOPs scale with the pairs held, not the expert
    count.  Decode and prefill use this function.

    A pass's rows go back to their tokens summed in float32, a token's
    pairs in the order of its ``k`` slots: ``k`` gathers of [T, E]
    (``_combine_slots``).  Where a pass holds fewer pairs than were
    routed (``cap < pairs``: a share at prefill widths) and ``kernel``
    is set, only the rows that hold a pair move (``_combine_held``, the
    Pallas kernel ``ops/moe_combine.py``: ``moe_combine`` in a trace,
    and ``pallas`` under ``moe_combine`` on ``/health``, ``xla``
    otherwise).  The sums are the same sums in the same order: the
    terms left out are exact zeros.  A whole layer and a share's decode
    widths (``cap == pairs``) hold every routed pair, and the gathers
    stand.

    ``layer``: the expert stacks of ``p`` are a layer kind's whole
    stacks and this the layer's index into them (``_grouped_matmul``).

    ``with_stats``: also returns int32 [4]: held experts (one call
    each), held experts that got a pair, pairs held, pairs routed.
    """
    T, E = x.shape
    k = arch.num_experts_per_tok
    held = arch.experts_held
    lo = arch.expert_shard * held
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    idx, weights = route_tokens(logits, arch, p.get("router_bias"))

    local = idx - lo                                   # [T, k]
    here = (local >= 0) & (local < held)
    if valid is not None:
        here &= valid[:, None]
    key = jnp.where(here, local, held).reshape(-1)     # held = not here
    order = jnp.argsort(key)                           # stable
    group_sizes = jnp.bincount(key, length=held + 1)[:held]
    n_here = jnp.sum(group_sizes)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    flat_w = weights.reshape(-1)
    pairs = T * k
    cap = _pass_rows(arch, T)
    n_rows = _gmm_rows(cap) if kernel else cap
    compact = False
    if kernel and cap < pairs:
        from kaito_tpu.engine.ops.moe_combine import token_tile

        compact = T % token_tile(T) == 0
    if not compact:
        # each pair's place in the sorted order
        place = jnp.zeros((pairs,), jnp.int32).at[order].set(
            jnp.arange(pairs, dtype=jnp.int32))

    def one_pass(i, y):
        """Sorted pairs [i*cap, (i+1)*cap) through the held experts,
        added to ``y`` [T, E] float32 at their tokens."""
        at = i * cap
        slot = jnp.arange(n_rows, dtype=jnp.int32)
        live = (slot < cap) & (at + slot < n_here)
        rows = order[jnp.minimum(at + slot, pairs - 1)]
        sizes = jnp.clip(ends, at, at + cap) - jnp.clip(starts, at, at + cap)
        x_sorted = x[rows // k]                        # [n_rows, E]
        expert_of_row = jnp.minimum(key[rows], held - 1)

        def grouped(lhs, w):
            return _grouped_matmul(lhs, w, sizes, expert_of_row, kernel,
                                   layer)

        with jax.named_scope("moe_experts"):
            gate = grouped(x_sorted, p["experts_gate"])
            up = grouped(x_sorted, p["experts_up"])
            h = jnp.where(live[:, None], activation(gate, arch.hidden_act)
                          * up, 0.0).astype(x.dtype)
            out = grouped(h, p["experts_down"])
        out = jnp.where(live[:, None], out * flat_w[rows][:, None], 0.0)
        if compact:
            return _combine_held(y, out, rows, live, k, fresh=i == 0)
        return _combine_slots(y, out, (place - at).reshape(T, k), cap)

    y = jnp.zeros((T, E), jnp.float32)
    if cap == pairs:
        y = one_pass(0, y)
    else:
        y = jax.lax.fori_loop(0, -(-n_here // cap), one_pass, y)
    y = y.astype(x.dtype)
    if "shared_gate" in p:
        shared = {"gate": p["shared_gate"], "up": p["shared_up"],
                  "down": p["shared_down"]}
        y = y + mlp(x, shared, arch)
    if not with_stats:
        return y
    routed = (T if valid is None else jnp.sum(valid)) * k
    stats = jnp.stack([jnp.int32(held), jnp.sum(group_sizes > 0),
                       n_here, routed]).astype(jnp.int32)
    return y, stats


def short_conv(v: jax.Array, carried: jax.Array, taps: jax.Array):
    """The causal depthwise convolution of a gated short-convolution
    layer (lfm2), in every form it is served in: ``v`` [B, T, C] the
    chunk's inputs, ``carried`` [B, K-1, C] the K-1 inputs before the
    chunk, oldest first (zeros at a sequence's start: a fresh prompt;
    the row of the state pool: a later chunk, and a decode step, which
    is a chunk of one), ``taps`` [K, C] with ``taps[k]`` the weight of
    the input k tokens back.  Returns (``c`` [B, T, C] float32 with
    ``c[t] = sum_k taps[k] * v[t-k]``, ``seen`` [B, K-1+T, C]: the
    carried inputs and then the chunk's, what ``short_conv_carry``
    takes the next state from)."""
    with jax.named_scope("short_conv"):
        K, T = taps.shape[0], v.shape[1]
        seen = jnp.concatenate([carried.astype(v.dtype), v], axis=1)
        w = taps.astype(jnp.float32)
        c = sum(w[k] * seen[:, K - 1 - k:K - 1 - k + T].astype(jnp.float32)
                for k in range(K))
    return c, seen


def short_conv_carry(seen: jax.Array, true_lens: jax.Array,
                     taps: int) -> jax.Array:
    """What a sequence carries past a chunk of ``true_lens`` [B] valid
    tokens: the last ``taps - 1`` inputs it has seen, [B, taps-1, C]
    (``seen`` as ``short_conv`` returns it; a chunk of no token leaves
    what was carried)."""
    with jax.named_scope("short_conv"):
        at = true_lens.astype(jnp.int32)[:, None] + jnp.arange(
            taps - 1, dtype=jnp.int32)[None, :]
        return jnp.take_along_axis(seen, at[..., None], axis=1)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap

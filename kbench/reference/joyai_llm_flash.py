"""Plain reference of JoyAI-LLM-Flash's language model (``model_type``
``joyai_llm_flash``; its keys are the DeepSeek-V3 family's, key for
key): a decoder with latent attention in every layer, a dense FFN in
the first ``first_k_dense_replace`` layers and, after them, a
sigmoid-routed layer of many small experts with a shared one.

The forward pass as the catalog row's ``config`` (the model's public
``config.json``) gives it, in straightforward ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``.  No kernels, no
cache, no pages, no batching: one sequence, masks built from positions,
and the EXPANDED form of latent attention only (per-head keys and
values from the latent; it never absorbs a projection into the query).
It reads only the HF-keyed ``config`` and a parameter tree and shares no
code with the program.  With ``h`` the hidden size (2,048):

- ``num_hidden_layers`` (40) pre-norm blocks: ``u = x +
  Attn(RMSNorm(x))``, ``y = u + FFN_l(RMSNorm(u))``, eps
  ``rms_norm_eps`` (1e-6); a final RMSNorm; an untied head of
  ``vocab_size`` (129,280) rows; no bias anywhere.
- LATENT ATTENTION, every layer, ``num_attention_heads`` (32) heads:
  ``c_q = RMSNorm(x W_qa)`` (``q_lora_rank`` 1,536; null: ``q = x
  W_q``, implemented); ``q = c_q W_qb``, each head ``[q_nope 128 |
  q_rope 64]`` (``qk_nope_head_dim`` | ``qk_rope_head_dim``); ``[c_kv |
  k_r] = x W_kva`` (``kv_lora_rank`` 512 | 64); ``c_kv <-
  RMSNorm(c_kv)``; ``k_nope,h = c_kv W_kvb_k,h`` (128 a head), ``v_h =
  c_kv W_kvb_v,h`` (``v_head_dim`` 128; the tree stores ``W_kvb`` in
  these two parts); rotary embedding on ``q_rope`` of every head and on
  the one ``k_r`` all heads share, theta ``rope_theta`` (32,000,000)
  over the 64 dims, INTERLEAVED pairs ``(2i, 2i+1)``
  (``rope_interleave`` true) [+], no scaling (``rope_scaling`` null);
  score ``(q_nope . k_nope + q_rope . k_r) / sqrt(128 + 64)``, causal
  softmax; ``o = concat_h(sum_j p_j v_h,j) W_o`` (32 x 128 -> h).
  ``head_dim`` (64) is the family's name for the rotary width and
  ``num_key_value_heads`` (32) is not used by a latent layer [+].
- Layers ``[0, first_k_dense_replace)``: a dense gated SiLU MLP of
  width ``intermediate_size`` (7,168).  The others (``moe_layer_freq``
  1): ``s = sigmoid(x W_r)`` over all the router's outputs, in float32;
  the ``num_experts_per_tok`` (8) with the largest ``s + b``
  (``topk_method`` ``noaux_tc``: ``b`` chooses and never weighs;
  ``n_group`` 1, ``topk_group`` 1: no group limit); ``g =
  routed_scaling_factor (2.5) x s[idx] / sum s[idx]``
  (``norm_topk_prob``); ``y = sum_k g_k E_k(x) + S(x)`` with ``E_k`` and
  the shared expert ``S`` gated SiLU MLPs of width
  ``moe_intermediate_size`` (768; ``S`` of ``n_shared_experts`` times
  that, added once).
- THE CHIP'S SHARE.  ``n_routed_experts`` counts the experts HELD here
  and ``expert_shards`` the chips that share each layer by experts
  (this repo's keys; published: 256 experts, one holder), so the router
  has ``n_routed_experts * expert_shards`` outputs and the held experts
  are ``[expert_shard * held, (expert_shard + 1) * held)``.  The sum
  above runs over the chosen experts that are held; what the absent
  ones would add is left out, here as in the program, and that partial
  result (with the whole shared expert) goes on to the next layer.
- Left out: the multi-token-prediction layer
  (``num_nextn_predict_layers`` 1), no part of the next-token forward
  pass.  ``ep_size`` says how the checkpoint was trained and changes no
  equation.

Lines marked [+] rest on the DeepSeek-V3 family's public modelling
code, whose keys these are, and not on a key alone; the configuration's
file lists them under ``assumed``.

Parameter tree (the layout of the program's ``init_params``, which is
data generation): ``embed [V', h]``, ``final_norm [h]``, ``lm_head
[V', h]``, and two stacks in layer order on the first axis, ``dense``
(the first ``first_k_dense_replace`` layers) and ``moe`` (the rest):
``attn_norm``, ``q_a [n, h, 1536]``, ``q_a_norm``, ``q_b [n, 1536,
H*192]`` (or ``q [n, h, H*192]``), ``kv_a [n, h, 576]``, ``kv_a_norm
[n, 512]``, ``kv_b_k [n, 512, H*128]``, ``kv_b_v [n, 512, H*128]``, ``o
[n, H*128, h]``, ``mlp_norm``, then ``gate``/``up``/``down`` or
``router [n, h, X]``, ``router_bias [n, X]``, ``experts_gate [n, held,
h, 768]``, ``experts_up``, ``experts_down [n, held, 768, h]``,
``shared_gate [n, h, 768]``, ``shared_up``, ``shared_down``; ``y = x @
W``.  ``V'`` is the vocabulary padded up by the program; rows past
``vocab_size`` are no tokens.

Departures, for memory only: weights are kept in the type they are
served in and one layer at a time is upcast to float32; attention runs
over query blocks, one at a time; a held expert is computed for every
position and weighted by zero where it was not chosen, one expert at a
time; the head is computed for the positions asked for, in blocks over
the vocabulary, with a running log-sum-exp.  None changes the
mathematics.
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 256
VOCAB_BLOCK = 16384
# what ``forward`` accepts for ``perturb``
PERTURBATIONS = ("rope_split_half", "k_rope_unrotated", "scale_by_nope_dim",
                 "no_kv_norm", "softmax_router", "no_correction_bias",
                 "no_routed_scale", "shared_expert_dropped",
                 "experts_dropped", "one_expert_dropped",
                 "last_layer_dropped", "weights_fp8")


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta, interleave):
    """x: [T, heads, D], every dim rotates: pair ``i`` is dims ``(2i,
    2i+1)`` (``interleave``) or ``(i, i + D/2)``, by the angle
    ``position x theta^(-2i/D)``."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _check(config: dict) -> None:
    if config.get("model_type") != "joyai_llm_flash":
        raise ValueError("this reference implements model_type "
                         "joyai_llm_flash only")
    if config.get("rope_scaling"):
        raise ValueError("the reference implements plain rotary embedding "
                         "only; rope_scaling must be null")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference implements the SiLU-gated FFN only")
    for key in ("attention_bias", "tie_word_embeddings"):
        if config.get(key):
            raise ValueError(f"the reference does not implement {key}")
    if not config.get("kv_lora_rank"):
        raise ValueError("the reference implements latent attention only: "
                         "kv_lora_rank must be set")
    if config.get("scoring_func") != "sigmoid" \
            or config.get("topk_method") != "noaux_tc" \
            or not config.get("norm_topk_prob", True):
        raise ValueError("the reference implements the sigmoid router with "
                         "a correction bias and normalized weights only")
    if (config.get("n_group") or 1) != 1 or (config.get("topk_group") or 1) != 1:
        raise ValueError("the reference implements no group-limited routing")
    if (config.get("moe_layer_freq") or 1) != 1:
        raise ValueError("the reference implements an expert layer in every "
                         "layer past first_k_dense_replace only")


def layer_names(config: dict) -> list:
    """(stack, index in the stack) of every layer, in layer order."""
    k = int(config.get("first_k_dense_replace") or 0)
    L = int(config["num_hidden_layers"])
    return [("dense", l) if l < k else ("moe", l - k) for l in range(L)]


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


def _make_layer(config: dict, moe: bool, perturb: str):
    """One block, dense or expert, jitted."""
    H = int(config["num_attention_heads"])
    dn = int(config["qk_nope_head_dim"])
    dr = int(config["qk_rope_head_dim"])
    dl = int(config["kv_lora_rank"])
    dv = int(config["v_head_dim"])
    theta = float(config["rope_theta"])
    interleave = bool(config.get("rope_interleave", False))
    if perturb == "rope_split_half":
        interleave = not interleave
    denom = float(dn if perturb == "scale_by_nope_dim" else dn + dr)
    eps = float(config.get("rms_norm_eps", 1e-6))
    top_k = int(config["num_experts_per_tok"])
    held = int(config["n_routed_experts"])
    lo = int(config.get("expert_shard", 0)) * held
    route_scale = float(config.get("routed_scaling_factor") or 1.0)
    if perturb == "no_routed_scale":
        route_scale = 1.0

    def attention(u, p):
        T = u.shape[0]
        pos = jnp.arange(T)
        if "q_a" in p:
            q = _rms_norm(u @ p["q_a"], p["q_a_norm"], eps) @ p["q_b"]
        else:
            q = u @ p["q"]
        q = q.reshape(T, H, dn + dr)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, theta,
                                            interleave)
        kv = u @ p["kv_a"]                                    # [T, dl+dr]
        c_kv = kv[:, :dl]
        if perturb != "no_kv_norm":
            c_kv = _rms_norm(c_kv, p["kv_a_norm"], eps)
        k_r = kv[:, None, dl:]                                # [T, 1, dr]
        if perturb != "k_rope_unrotated":
            k_r = _rope(k_r, pos, theta, interleave)
        # the expanded form: a key and a value a head and position
        k_nope = (c_kv @ p["kv_b_k"]).reshape(T, H, dn)
        v = (c_kv @ p["kv_b_v"]).reshape(T, H, dv)

        def block(args):
            # one block of queries against every key, one block at a
            # time (jax.lax.map): the scores of 32 heads over 4,608
            # positions are 150 MB a block
            qn, qr, i = args                    # [Q, H, dn], [Q, H, dr], [Q]
            sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                  + jnp.einsum("qhd,kd->hqk", qr, k_r[:, 0])) \
                / jnp.sqrt(denom)
            seen = pos[None, :] <= i[:, None]
            sc = jnp.where(seen[None], sc, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

        n = T // Q_BLOCK
        outs = jax.lax.map(block, (q_nope.reshape(n, Q_BLOCK, H, dn),
                                   q_rope.reshape(n, Q_BLOCK, H, dr),
                                   pos.reshape(n, Q_BLOCK)))
        return outs.reshape(T, H * dv) @ p["o"]

    def experts(x, p):
        logits = x @ p["router"]                             # [T, X]
        if perturb == "softmax_router":
            s = jax.nn.softmax(logits, axis=-1)
        else:
            s = jax.nn.sigmoid(logits)
        choose = s if perturb == "no_correction_bias" \
            else s + p["router_bias"]
        _, idx = jax.lax.top_k(choose, top_k)                # [T, k]
        g = jnp.take_along_axis(s, idx, axis=-1)
        g = g / jnp.sum(g, axis=-1, keepdims=True) * route_scale
        y = jnp.zeros_like(x)
        if "shared_gate" in p and perturb != "shared_expert_dropped":
            # the shared expert sees every token, once
            y = (jax.nn.silu(x @ p["shared_gate"])
                 * (x @ p["shared_up"])) @ p["shared_down"]
        if perturb == "experts_dropped":
            return y
        first = 1 if perturb == "one_expert_dropped" else 0

        def one(e, y):
            # the weight of held expert e at each position: its g where
            # it was chosen, zero where it was not; one expert at a time
            w = jnp.sum(jnp.where(idx == lo + e, g, 0.0), axis=-1)
            gate, up, down = (
                jax.lax.dynamic_index_in_dim(p[name], e, 0, keepdims=False)
                .astype(jnp.float32)
                for name in ("experts_gate", "experts_up", "experts_down"))
            out = (jax.nn.silu(x @ gate) * (x @ up)) @ down
            return y + w[:, None] * out

        return jax.lax.fori_loop(first, held, one, y)

    @jax.jit
    def layer(x, p):
        with jax.default_matmul_precision("highest"):
            # the expert stacks are upcast one expert at a time
            p = {k: v if k.startswith("experts_") else v.astype(jnp.float32)
                 for k, v in p.items()}
            x = x + attention(_rms_norm(x, p["attn_norm"], eps), p)
            v = _rms_norm(x, p["mlp_norm"], eps)
            if moe:
                return x + experts(v, p)
            return x + (jax.nn.silu(v @ p["gate"]) * (v @ p["up"])) @ p["down"]

    return layer


@jax.jit
def _head_block(h, block, targets, lo):
    """Logits of the rows ``block`` for the positions ``h``: their
    log-sum-exp, their maximum, and the logit of each position's target
    id if it lies in this block (else -inf)."""
    with jax.default_matmul_precision("highest"):
        logits = h @ block.astype(jnp.float32).T               # [P, B]
    rows = block.shape[0]
    hit = (targets >= lo) & (targets < lo + rows)
    picked = jnp.take_along_axis(
        logits, jnp.clip(targets - lo, 0, rows - 1)[:, None], axis=1)[:, 0]
    return (jax.nn.logsumexp(logits, axis=-1), jnp.max(logits, axis=-1),
            jnp.where(hit, picked, -jnp.inf))


def forward(config: dict, params: dict, tokens, start: int, *,
            put=lambda x: x, perturb: str = ""):
    """Teacher-forced log-probabilities of one sequence.

    For every position ``p`` in ``[start, T-1)`` returns the log-softmax
    of ``tokens[p+1]`` given ``tokens[:p+1]`` (``target``) and the
    largest log-softmax at ``p`` (``top``); ``top`` also covers the last
    position ``T-1``, whose ``target`` is NaN.  ``put`` moves one
    layer's (or one vocabulary block's) weights to where the compute
    runs.  ``perturb`` names a deliberately cruder computation, used to
    show what the tolerance catches: ``rope_split_half`` (rotate-half
    pairs in place of interleaved ones), ``k_rope_unrotated`` (the
    shared key part not rotated), ``scale_by_nope_dim`` (scores over
    sqrt(128) in place of sqrt(192)), ``no_kv_norm`` (the latent not
    normed), ``softmax_router`` (softmax scores in place of sigmoid
    ones), ``no_correction_bias`` (experts chosen by the scores alone),
    ``no_routed_scale`` (weights not scaled by
    ``routed_scaling_factor``), ``shared_expert_dropped``,
    ``experts_dropped`` (the held experts give zero),
    ``one_expert_dropped`` (the first held expert gives zero),
    ``last_layer_dropped``, and ``weights_fp8`` (every layer's matrices
    rounded to float8 e4m3: the nearest precision below the bfloat16
    they are served in).
    """
    if perturb and perturb not in PERTURBATIONS:
        raise ValueError(f"no perturbation {perturb!r}")
    _check(config)
    T = len(tokens)
    # padded at the end to whole query blocks, so that few lengths
    # compile; attention is causal, so no real position sees the padding
    tokens = jnp.asarray(list(tokens) + [0] * (-T % Q_BLOCK), jnp.int32)
    V = int(config["vocab_size"])
    eps = float(config.get("rms_norm_eps", 1e-6))
    layers = {}
    x = _embed(put(params["embed"]), tokens)
    names = layer_names(config)
    if perturb == "last_layer_dropped":
        names = names[:-1]
    for name, i in names:
        moe = name == "moe"
        if moe not in layers:
            layers[moe] = _make_layer(config, moe, perturb)
        p = put({k: v[i] for k, v in params[name].items()})
        if perturb == "weights_fp8":
            # rounded outside the jitted layer, one array at a time, so
            # that no compiler keeps the excess precision
            p = {k: v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                 if v.ndim >= 2 else v for k, v in p.items()}
        x = layers[moe](x, p)
    tokens = tokens[:T]
    h = _rms_norm(x[start:T], put(params["final_norm"]).astype(jnp.float32),
                  eps)
    head = params["lm_head"]
    targets = jnp.concatenate([tokens[start + 1:], jnp.zeros((1,), jnp.int32)])
    lse = jnp.full((T - start,), -jnp.inf)
    top = jnp.full((T - start,), -jnp.inf)
    tgt = jnp.full((T - start,), -jnp.inf)
    for lo in range(0, V, VOCAB_BLOCK):
        block = put(head[lo:min(lo + VOCAB_BLOCK, V)])
        b_lse, b_top, b_tgt = _head_block(h, block, targets, lo)
        lse = jnp.logaddexp(lse, b_lse)
        top = jnp.maximum(top, b_top)
        tgt = jnp.maximum(tgt, b_tgt)
    target = (tgt - lse).at[-1].set(jnp.nan)
    return {"target": target, "top": top - lse}

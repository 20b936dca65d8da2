"""Plain reference of MiMo-V2.5's language model (``model_type``
``mimo_v2``): a decoder whose layers are of two attention kinds, full
and window, each with its own head counts, and whose feed-forward is
dense in layer 0 and a sigmoid-routed expert layer everywhere else.

The forward pass as the catalog row's ``config`` (the model's public
``config.json``) gives it, in straightforward ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``.  No kernels, no
cache, no pages, no batching: one sequence, masks built from positions.
It reads only the HF-keyed ``config`` and a parameter tree and shares no
code with the program.  With ``h`` the hidden size (4,096):

- Pre-norm blocks: ``u = x + Attn_l(RMSNorm(x))``,
  ``y = u + FFN_l(RMSNorm(u))``, eps ``layernorm_epsilon``; a final
  RMSNorm; an untied head of ``vocab_size`` rows.
- ``hybrid_layer_pattern[l]`` 0: a FULL layer, ``num_attention_heads``
  (64) query heads, ``num_key_value_heads`` (4) KV heads, q/k head size
  ``head_dim`` (192), v head size ``v_head_dim`` (128), rope theta
  ``rope_theta``, causal over the whole prefix, no sink
  (``add_full_attention_sink_bias`` false).  1: a WINDOW layer, the
  ``swa_*`` keys (64 query heads, 8 KV heads, 192 / 128), theta
  ``swa_rope_theta``, causal over the last ``sliding_window`` (128)
  positions (``0 <= i - j < 128``), and with
  ``add_swa_attention_sink_bias`` a learnable sink bias ``s_h`` a query
  head: ``o_i = sum_j exp(a_ij - m) v_j / (exp(s_h - m) + sum_j
  exp(a_ij - m))``: a column that takes probability and carries no
  value. [+]
- Both kinds: scores ``q.k / sqrt(head_dim)``; rotary embedding on the
  first ``int(head_dim * partial_rotary_factor)`` = 64 dims of each q
  and k head, rotate-half within those 64, the other 128 unrotated [+];
  values multiplied by ``attention_value_scale`` (0.707) before
  attention [+]; no biases; ``o_proj`` from heads x v head size.
  ``attention_projection_layout`` ``fused_qkv`` is how the checkpoint
  stores q, k and v and changes no equation [+];
  ``attention_chunk_size`` is not used by a causal text forward
  pass [+].
- ``moe_layer_freq[l]`` 0: a dense SwiGLU FFN of width
  ``intermediate_size``.  1: an EXPERT layer: ``s = sigmoid(W_r x)``
  over all the router's outputs, in float32; the
  ``num_experts_per_tok`` (8) experts with the largest ``s + b`` are
  chosen (``b`` the correction bias of ``topk_method`` ``noaux_tc``,
  which chooses and never weighs; ``n_group`` 1 and ``topk_group`` 1:
  no group limit); weights ``g = s[idx] / sum s[idx]``
  (``norm_topk_prob``), times ``routed_scaling_factor`` (null = 1);
  ``y = sum_k g_k W_down,k (silu(W_gate,k x) * W_up,k x)``; no shared
  expert.
- THE CHIP'S SHARE.  ``n_routed_experts`` counts the experts HELD here
  and ``expert_shards`` the chips that share each layer by experts
  (this repo's keys; published: 256 experts, one holder), so the router
  has ``n_routed_experts * expert_shards`` outputs and the held experts
  are ``[expert_shard * held, (expert_shard + 1) * held)``.  The sum
  above runs over the chosen experts that are held; what the absent
  ones would add is left out, here as in the program, and that partial
  result goes on to the next layer.
- Left out: the 3 multi-token-prediction layers, the vision and audio
  towers (the catalog's ``config`` is the language model's).

Lines marked [+] rest on the catalog's ``described_as`` or on the
family's public modelling code and not on a key alone; the
configuration's file lists them under ``assumed``.

Parameter tree (the layout of the program's ``init_params``, which is
data generation): ``embed [V', h]``, ``final_norm [h]``, ``lm_head
[V', h]``, and one stack a layer kind, named
``<full|window>_<dense|moe>``, holding that kind's layers in layer
order on the first axis: ``attn_norm``, ``q [n, h, H*192]``, ``k [n,
h, Hkv*192]``, ``v [n, h, Hkv*128]``, ``o [n, H*128, h]``, ``sink [n,
H]`` (window kinds), ``mlp_norm``, then ``gate``/``up``/``down`` or
``router [n, h, X]``, ``router_bias [n, X]``, ``experts_gate [n, held,
h, 2048]``, ``experts_up``, ``experts_down [n, held, 2048, h]``; ``y =
x @ W``.  ``V'`` is the vocabulary padded up by the program; rows past
``vocab_size`` are no tokens.

Departures, for memory only: weights are kept in the type they are
served in and one layer at a time is upcast to float32; attention runs
over query blocks, one at a time; a held expert is computed for every
position and weighted by zero where it was not chosen, one expert at a
time; the head is computed for the
positions asked for, in blocks over the vocabulary, with a running
log-sum-exp.  None changes the mathematics.
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 256
VOCAB_BLOCK = 16384
# what ``forward`` accepts for ``perturb``
PERTURBATIONS = ("no_sink", "window_as_full", "theta_swapped",
                 "no_value_scale", "no_correction_bias", "softmax_router",
                 "experts_dropped", "one_expert_dropped",
                 "last_layer_dropped", "weights_fp8")


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta, rot):
    """x: [T, heads, D]; the first ``rot`` dims of each head rotate
    (rotate-half within them), the rest pass."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                                / rot))
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _check(config: dict) -> None:
    if config.get("model_type") != "mimo_v2":
        raise ValueError("this reference implements model_type mimo_v2 only")
    scaling = config.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default":
        raise ValueError("the reference implements plain rotary embedding "
                         "only; rope_scaling must be default")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference implements the SiLU-gated FFN only")
    for key in ("attention_bias", "tie_word_embeddings", "n_shared_experts",
                "add_full_attention_sink_bias", "hybrid_block_size"):
        if config.get(key):
            raise ValueError(f"the reference does not implement {key}")
    if config.get("scoring_func") != "sigmoid" \
            or config.get("topk_method") != "noaux_tc" \
            or not config.get("norm_topk_prob", True):
        raise ValueError("the reference implements the sigmoid router with "
                         "a correction bias and normalized weights only")
    if (config.get("n_group") or 1) != 1 or (config.get("topk_group") or 1) != 1:
        raise ValueError("the reference implements no group-limited routing")
    L = config["num_hidden_layers"]
    if len(config["hybrid_layer_pattern"]) != L \
            or len(config["moe_layer_freq"]) != L:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq must name "
                         "every layer")


def layer_names(config: dict) -> list:
    """(stack, index in the stack) of every layer, in layer order: the
    program stacks the layers of one attention kind and one FFN kind."""
    seen: dict = {}
    out = []
    for win, moe in zip(config["hybrid_layer_pattern"],
                        config["moe_layer_freq"]):
        name = ("window" if win else "full") + ("_moe" if moe else "_dense")
        out.append((name, seen.get(name, 0)))
        seen[name] = seen.get(name, 0) + 1
    return out


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


def _make_layer(config: dict, window_kind: bool, moe: bool, perturb: str):
    """One block of the given kinds, jitted."""
    pre = "swa_" if window_kind else ""
    H = config[pre + "num_attention_heads"]
    Hkv = config[pre + "num_key_value_heads"]
    D = config[pre + "head_dim"]
    Dv = config[pre + "v_head_dim"]
    theta_full = float(config["rope_theta"])
    theta_win = float(config["swa_rope_theta"])
    if perturb == "theta_swapped":
        theta_full, theta_win = theta_win, theta_full
    theta = theta_win if window_kind else theta_full
    rot = int(D * float(config.get("partial_rotary_factor", 1.0)))
    rot -= rot % 2
    window = int(config["sliding_window"]) if window_kind else None
    if perturb == "window_as_full":
        window = None
    sink_on = window_kind and bool(config.get("add_swa_attention_sink_bias"))
    if perturb == "no_sink":
        sink_on = False
    value_scale = float(config.get("attention_value_scale") or 1.0)
    if perturb == "no_value_scale":
        value_scale = 1.0
    eps = float(config.get("layernorm_epsilon", 1e-5))
    top_k = int(config["num_experts_per_tok"])
    held = int(config["n_routed_experts"])
    lo = int(config.get("expert_shard", 0)) * held
    route_scale = float(config.get("routed_scaling_factor") or 1.0)

    def attention(u, p):
        T = u.shape[0]
        pos = jnp.arange(T)
        q = _rope((u @ p["q"]).reshape(T, H, D), pos, theta, rot)
        k = _rope((u @ p["k"]).reshape(T, Hkv, D), pos, theta, rot)
        v = (u @ p["v"]).reshape(T, Hkv, Dv) * value_scale
        # query head h reads key/value head h // (H // Hkv)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        def block(args):
            # one block of queries against every key, one block at a
            # time (jax.lax.map): the scores of 64 heads over 4,608
            # positions are 300 MB a block
            qb, i = args                       # [Q, H, D], [Q]
            sc = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(float(D))
            seen = pos[None, :] <= i[:, None]
            if window is not None:
                seen &= i[:, None] - pos[None, :] < window
            sc = jnp.where(seen[None], sc, -jnp.inf)
            if sink_on:
                col = jnp.broadcast_to(p["sink"][:, None, None],
                                       sc.shape[:2] + (1,))
                w = jax.nn.softmax(jnp.concatenate([sc, col], axis=-1),
                                   axis=-1)[..., :-1]
            else:
                w = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("hqk,khd->qhd", w, v)

        outs = jax.lax.map(block, (q.reshape(T // Q_BLOCK, Q_BLOCK, H, D),
                                   pos.reshape(T // Q_BLOCK, Q_BLOCK)))
        attn = outs.reshape(T, H * Dv)
        return attn @ p["o"]

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

        y = jax.lax.fori_loop(first, held, one, y)
        return y

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
    show what the tolerance catches: ``no_sink`` (window layers without
    their sink column), ``window_as_full`` (window layers that see the
    whole prefix), ``theta_swapped`` (the two kinds' rope thetas
    exchanged), ``no_value_scale``, ``no_correction_bias`` (experts
    chosen by the scores alone), ``softmax_router`` (softmax scores in
    place of sigmoid ones), ``experts_dropped`` (the held experts give
    zero), ``one_expert_dropped`` (the first held expert gives zero),
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
    eps = float(config.get("layernorm_epsilon", 1e-5))
    layers = {}
    x = _embed(put(params["embed"]), tokens)
    names = layer_names(config)
    if perturb == "last_layer_dropped":
        names = names[:-1]
    for l, (name, i) in enumerate(names):
        kinds = (bool(config["hybrid_layer_pattern"][l]),
                 bool(config["moe_layer_freq"][l]))
        if kinds not in layers:
            layers[kinds] = _make_layer(config, *kinds, perturb)
        p = put({k: v[i] for k, v in params[name].items()})
        if perturb == "weights_fp8":
            # rounded outside the jitted layer, one array at a time, so
            # that no compiler keeps the excess precision
            p = {k: v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                 if v.ndim >= 2 else v for k, v in p.items()}
        x = layers[kinds](x, p)
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

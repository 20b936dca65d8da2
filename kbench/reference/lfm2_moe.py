"""Plain reference of LFM2-8B-A1B's language model (``model_type``
``lfm2_moe``; the dense ``lfm2`` is the same with no expert layer): a
decoder most of whose layers mix tokens by a gated short convolution
and a few by grouped-query attention, with a dense feed-forward in the
first ``num_dense_layers`` layers and a sigmoid-routed expert layer in
the rest.

The forward pass as the catalog row's ``config`` (the model's public
``config.json``) gives it, in straightforward ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``.  No kernels, no
cache, no state, no pages, no batching: one sequence, whole.  It reads
only the HF-keyed ``config`` and a parameter tree and shares no code
with the program.  With ``h`` the hidden size (2,048):

- Pre-norm blocks, RMSNorm with a plain gain, eps ``norm_eps``:
  ``u = x + Op_l(RMSNorm(x))``, ``y = u + FFN_l(RMSNorm(u))``; a final
  RMSNorm; the head TIED to the embedding [+] (the row has no
  ``tie_word_embeddings``; the family ties); no bias anywhere
  (``conv_bias`` false).
- ``layer_types[l]`` ``conv``: a GATED SHORT CONVOLUTION.
  ``[B | C | u] = x W_in`` (h -> 3h); ``v = B * u``;
  ``c_t = sum_{k=0..K-1} w_k * v_{t-k}``, a causal depthwise
  convolution over time with ``conv_L_cache`` (3) taps a channel, zeros
  before the sequence's start, no activation [+];
  ``Op(x) = (C * c) W_out`` (h -> h).
- ``layer_types[l]`` ``full_attention``: GQA, ``num_attention_heads``
  (32) query and ``num_key_value_heads`` (8) KV heads of
  ``hidden_size / num_attention_heads`` = 64 [+]; an RMSNorm over each
  query and each key head, one 64-wide gain for all query heads and one
  for all key heads, BEFORE the rotary embedding [+]; rotary embedding
  over all 64 dims, split-half pairs (i, i + 32), theta ``rope_theta``,
  no scaling; causal softmax at ``1/sqrt(64)``; ``W_o`` h -> h.
- FFN, layers below ``num_dense_layers``: ``W_2 (silu(W_1 x) * W_3 x)``
  of width ``intermediate_size`` (7,168).  From there on an EXPERT
  layer: ``s = sigmoid(x W_r)`` over ``num_experts`` (32) in float32;
  the ``num_experts_per_tok`` (4) with the largest ``s + b`` are chosen
  (``use_expert_bias``: ``b`` chooses and never weighs); ``g = s[idx] /
  (sum s[idx] + 1e-6)`` [+] (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``y = sum_k g_k E_k(x)``, each ``E_k`` a
  gated SiLU FFN of width ``moe_intermediate_size`` (1,792); no shared
  expert.  Every expert is held: the sum runs over all four.

Lines marked [+] rest on the family's public modelling code
(``Lfm2Moe*`` in ``transformers``) and not on a key alone; the
configuration's file lists them under ``assumed``.

Parameter tree (the layout of the program's ``init_params``, which is
data generation): ``embed [V', h]``, ``final_norm [h]`` (``lm_head [V',
h]`` only where ``tie_word_embeddings`` is false), and one stack a
layer kind, named ``<full|conv>_<dense|moe>``, holding that kind's
layers in layer order on the first axis.  ``conv_*``: ``attn_norm``,
``conv_in [n, h, 3h]``, ``conv_w [n, K, h]`` (``conv_w[k]`` weighs the
input ``k`` tokens back), ``conv_out [n, h, h]``; ``full_*``:
``attn_norm``, ``q [n, h, H*64]``, ``k``, ``v [n, h, Hkv*64]``, ``o [n,
H*64, h]``, ``q_norm``, ``k_norm [n, 64]``; both: ``mlp_norm``, then
``gate``/``up``/``down`` or ``router [n, h, X]``, ``router_bias [n,
X]``, ``experts_gate [n, X, h, 1792]``, ``experts_up``,
``experts_down [n, X, 1792, h]``; ``y = x @ W``.  ``V'`` is the
vocabulary padded up by the program; rows past ``vocab_size`` are no
tokens.

Departures, for memory only: weights are kept in the type they are
served in and one layer at a time is upcast to float32; attention runs
over query blocks, one at a time; an expert is computed for every
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
PERTURBATIONS = ("weights_fp8", "conv_state_dropped", "conv_gate_dropped",
                 "no_qk_norm", "rope_interleaved", "softmax_router",
                 "no_expert_bias", "one_expert_dropped", "experts_dropped",
                 "last_layer_dropped")


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta, interleaved):
    """x: [T, heads, D]; every dim rotates, in pairs (i, i + D/2)
    (``interleaved``: (2i, 2i+1), the perturbation)."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _check(config: dict) -> None:
    if config.get("model_type") not in ("lfm2_moe", "lfm2"):
        raise ValueError("this reference implements model_type lfm2_moe "
                         "(and the dense lfm2) only")
    if config.get("conv_bias"):
        raise ValueError("the reference does not implement conv_bias")
    scaling = config.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default":
        raise ValueError("the reference implements plain rotary embedding "
                         "only; rope_scaling must be default")
    types = config.get("layer_types") or []
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    for t in types:
        if t not in ("conv", "full_attention"):
            raise ValueError(f"the reference does not implement a "
                             f"{t!r} layer")
    if config["model_type"] == "lfm2":
        if config.get("block_auto_adjust_ff_dim"):
            raise ValueError("the reference takes the dense FFN's width "
                             "from the parameters' shapes only where "
                             "block_auto_adjust_ff_dim is false")
        return
    if not config.get("norm_topk_prob", True) \
            or not config.get("use_expert_bias", True):
        raise ValueError("the reference implements the sigmoid router with "
                         "an expert bias and normalized weights only")


def _is_moe(config: dict, l: int) -> bool:
    return config["model_type"] == "lfm2_moe" \
        and l >= int(config.get("num_dense_layers", 0))


def layer_names(config: dict) -> list:
    """(stack, index in the stack) of every layer, in layer order: the
    program stacks the layers of one mixer and one FFN kind."""
    seen: dict = {}
    out = []
    for l, t in enumerate(config["layer_types"]):
        name = ("conv" if t == "conv" else "full") \
            + ("_moe" if _is_moe(config, l) else "_dense")
        out.append((name, seen.get(name, 0)))
        seen[name] = seen.get(name, 0) + 1
    return out


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


def _make_layer(config: dict, conv: bool, moe: bool, perturb: str):
    """One block of the given kinds, jitted."""
    H = int(config["num_attention_heads"])
    Hkv = int(config["num_key_value_heads"])
    D = int(config.get("head_dim") or config["hidden_size"] // H)
    theta = float(config["rope_theta"])
    eps = float(config.get("norm_eps", 1e-5))
    taps = int(config.get("conv_L_cache", 3))
    top_k = int(config.get("num_experts_per_tok", 0))
    experts_n = int(config.get("num_experts", 0))
    route_scale = float(config.get("routed_scaling_factor") or 1.0)

    def short_conv(u, p):
        T = u.shape[0]
        gate_b, gate_c, x = jnp.split(u @ p["conv_in"], 3, axis=-1)
        v = gate_b * x
        # c_t = sum_k w_k v_{t-k}, zeros before the sequence's start
        held = 1 if perturb == "conv_state_dropped" else taps
        back = jnp.pad(v, ((taps - 1, 0), (0, 0)))
        c = sum(p["conv_w"][k] * back[taps - 1 - k:taps - 1 - k + T]
                for k in range(held))
        if perturb != "conv_gate_dropped":
            c = gate_c * c
        return c @ p["conv_out"]

    def attention(u, p):
        T = u.shape[0]
        pos = jnp.arange(T)
        q = (u @ p["q"]).reshape(T, H, D)
        k = (u @ p["k"]).reshape(T, Hkv, D)
        v = (u @ p["v"]).reshape(T, Hkv, D)
        if perturb != "no_qk_norm":
            q = _rms_norm(q, p["q_norm"], eps)
            k = _rms_norm(k, p["k_norm"], eps)
        q = _rope(q, pos, theta, perturb == "rope_interleaved")
        k = _rope(k, pos, theta, perturb == "rope_interleaved")
        # query head h reads key/value head h // (H // Hkv)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)

        def block(args):
            # one block of queries against every key, one at a time
            qb, i = args                       # [Q, H, D], [Q]
            sc = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(float(D))
            sc = jnp.where((pos[None, :] <= i[:, None])[None], sc, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

        outs = jax.lax.map(block, (q.reshape(T // Q_BLOCK, Q_BLOCK, H, D),
                                   pos.reshape(T // Q_BLOCK, Q_BLOCK)))
        return outs.reshape(T, H * D) @ p["o"]

    def experts(x, p):
        logits = x @ p["router"]                             # [T, X]
        if perturb == "softmax_router":
            s = jax.nn.softmax(logits, axis=-1)
        else:
            s = jax.nn.sigmoid(logits)
        choose = s if perturb == "no_expert_bias" else s + p["router_bias"]
        _, idx = jax.lax.top_k(choose, top_k)                # [T, k]
        g = jnp.take_along_axis(s, idx, axis=-1)
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6) * route_scale
        if perturb == "experts_dropped":
            # the last of the chosen four gives nothing
            g = g.at[:, -1].set(0.0)
        first = 1 if perturb == "one_expert_dropped" else 0

        def one(e, y):
            # the weight of expert e at each position: its g where it
            # was chosen, zero where it was not; one expert at a time
            w = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)
            gate, up, down = (
                jax.lax.dynamic_index_in_dim(p[name], e, 0, keepdims=False)
                .astype(jnp.float32)
                for name in ("experts_gate", "experts_up", "experts_down"))
            out = (jax.nn.silu(x @ gate) * (x @ up)) @ down
            return y + w[:, None] * out

        return jax.lax.fori_loop(first, experts_n, one, jnp.zeros_like(x))

    @jax.jit
    def layer(x, p):
        with jax.default_matmul_precision("highest"):
            # the expert stacks are upcast one expert at a time
            p = {k: v if k.startswith("experts_") else v.astype(jnp.float32)
                 for k, v in p.items()}
            u = _rms_norm(x, p["attn_norm"], eps)
            x = x + (short_conv(u, p) if conv else attention(u, p))
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
    show what the tolerance catches: ``weights_fp8`` (every layer's
    matrices rounded to float8 e4m3: the nearest precision below the
    bfloat16 they are served in), ``conv_state_dropped`` (a conv layer
    sees its newest input alone: the taps one and two tokens back give
    nothing, which is a served path that drops or zeroes the carried
    state), ``conv_gate_dropped`` (``C`` taken as ones), ``no_qk_norm``,
    ``rope_interleaved`` (rotary pairs (2i, 2i+1)), ``softmax_router``
    (softmax scores in place of sigmoid ones), ``no_expert_bias``
    (experts chosen by the scores alone), ``one_expert_dropped`` (expert
    0 gives zero), ``experts_dropped`` (the last of each token's chosen
    four gives zero), and ``last_layer_dropped``.
    """
    if perturb and perturb not in PERTURBATIONS:
        raise ValueError(f"no perturbation {perturb!r}")
    _check(config)
    T = len(tokens)
    # padded at the end to whole query blocks, so that few lengths
    # compile; every layer is causal, so no real position sees the padding
    tokens = jnp.asarray(list(tokens) + [0] * (-T % Q_BLOCK), jnp.int32)
    V = int(config["vocab_size"])
    eps = float(config.get("norm_eps", 1e-5))
    layers = {}
    x = _embed(put(params["embed"]), tokens)
    names = layer_names(config)
    if perturb == "last_layer_dropped":
        names = names[:-1]
    for l, (name, i) in enumerate(names):
        kinds = (config["layer_types"][l] == "conv", _is_moe(config, l))
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
    head = params["embed"] if config.get("tie_word_embeddings", True) \
        else params["lm_head"]
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

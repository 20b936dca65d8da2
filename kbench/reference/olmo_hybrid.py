"""Plain reference of Olmo-Hybrid-7B's language model (``model_type``
``olmo_hybrid``): a dense decoder three quarters of whose layers mix
tokens by a gated delta rule (a linear-attention layer whose state is a
matrix a head, corrected towards each new value along its key) and a
quarter by multi-head attention with no rotary embedding.

The forward pass as the catalog row's ``config`` (the model's public
``config.json``) gives it, in straightforward ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``.  No kernels, no
cache, no state pool, no pages, no batching, no chunked form of the
recurrence: one sequence, whole, the delta rule token by token (a
``lax.scan`` over positions).  It reads only the HF-keyed ``config``
and a parameter tree and shares no code with the program.  With ``h``
the hidden size (3,840), RMSNorm eps ``rms_norm_eps`` (1e-6), no bias
anywhere (``attention_bias`` false), embedding and head untied:

- The block, both kinds [+]: the family's reordered norm (OLMo 2 and
  3): ``u = x + RMSNorm(Op_l(x))``, ``y = u + RMSNorm(MLP(u))``; the
  operator and the MLP read the residual stream as it is, each has one
  norm on its OUTPUT (an h-wide gain); a final RMSNorm before the head.
  MLP: ``W_down(silu(W_gate x) * W_up x)``, width ``intermediate_size``
  (11,008).
- ``layer_types[l]`` ``full_attention``: MHA, ``num_attention_heads``
  (30) query and ``num_key_value_heads`` (30) KV heads of ``hidden_size
  / num_attention_heads`` = 128 [+]; ``q = RMSNorm(x W_q)``, ``k =
  RMSNorm(x W_k)``: ONE norm over the whole 3,840-wide projection,
  before the split into heads [+] (statistics over all heads' lanes, a
  gain a lane); NO rotary embedding (``rope_parameters.rope_theta`` is
  null, read as it stands [+]: positions reach attention through the
  recurrent layers below it); causal softmax at ``1/sqrt(128)``;
  ``W_o`` h -> h.
- ``layer_types[l]`` ``linear_attention``: a GATED DELTA RULE (Gated
  DeltaNet; the ``linear_*`` keys are the flash-linear-attention
  layer's).  ``H`` = ``linear_num_value_heads`` (30) heads (as many key
  heads), keys ``dk`` = ``linear_key_head_dim`` (96), values ``dv`` =
  ``linear_value_head_dim`` (192).  ``q~ = x W_q`` (-> H dk), ``k~ = x
  W_k`` (-> H dk), ``v~ = x W_v`` (-> H dv); each through a causal
  depthwise convolution of ``linear_conv_kernel_dim`` (4) taps a
  channel, zeros before the sequence's start, no bias [+], then SiLU
  [+].  A head: ``q = q~ / sqrt(sum q~^2 + 1e-6) * dk^-1/2``, ``k = k~ /
  sqrt(sum k~^2 + 1e-6)`` [+].  Gates a token and head, from ``x``:
  ``beta = sigmoid(x W_b)``, times 2 where ``linear_allow_neg_eigval``
  (the transition's eigenvalue along ``k`` is ``1 - beta`` in (-1, 1));
  ``g = -exp(A_log) * softplus(x W_a + dt_bias)``, ``alpha = exp(g)``.
  The state ``S`` is a ``dk x dv`` matrix a head, zero at the start::

      S'  = alpha_t S_{t-1}        u_t = beta_t (v_t - S'^T k_t)
      S_t = S' + k_t u_t^T         o_t = S_t^T q_t

  Output: ``RMSNorm_dv(o_t) * silu(x W_g)`` a head (one ``dv``-wide gain
  shared by the heads [+], ``W_g`` h -> H dv), then ``W_o`` H dv -> h.

Lines marked [+] rest on the family's public modelling code (OLMo 2/3
and the flash-linear-attention ``GatedDeltaNet`` layer) and not on a
key alone; the configuration's file lists them under ``assumed``.

Parameter tree (the layout of the program's ``init_params``, which is
data generation): ``embed [V', h]``, ``lm_head [V', h]``, ``final_norm
[h]``, and one stack a layer kind, ``gdn_dense`` and ``full_dense``,
holding that kind's layers in layer order on the first axis.  Both:
``attn_norm [n, h]`` (the norm on the operator's output), ``mlp_norm``
(on the MLP's), ``gate``/``up [n, h, I]``, ``down [n, I, h]``.
``gdn_dense``: ``gdn_in [n, h, H(2dk+dv) + H dv]``, columns ``[W_q | W_k
| W_v | W_g]``; ``gdn_gates [n, h, 2H]``, columns ``[W_a | W_b]``;
``gdn_conv_w [n, K, H(2dk+dv)]`` over
the channels ``[q | k | v]``, ``gdn_conv_w[K-1]`` on the newest input;
``gdn_a_log``, ``gdn_dt_bias [n, H]``; ``gdn_norm [n, dv]``; ``gdn_out
[n, H dv, h]``.  ``full_dense``: ``q``, ``k``, ``v [n, h, H 128]``, ``o
[n, H 128, h]``, ``q_norm``, ``k_norm [n, H 128]``.  ``y = x @ W``.
``V'`` is the vocabulary padded up by the program; rows past
``vocab_size`` are no tokens.

Departures, for memory only: weights are kept in the type they are
served in and one layer at a time is upcast to float32; attention runs
over query blocks, one at a time; the head is computed for the
positions asked for, in blocks over the vocabulary, with a running
log-sum-exp.  None changes the mathematics.
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 256
VOCAB_BLOCK = 16384
# what ``forward`` accepts for ``perturb``
PERTURBATIONS = ("weights_fp8", "delta_term_dropped", "decay_dropped",
                 "beta_not_doubled", "conv_state_dropped", "no_l2_norm",
                 "out_gate_dropped", "no_qk_norm", "qk_norm_per_head",
                 "rope_added", "pre_norm", "last_layer_dropped")


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    """x: [T, heads, D]; every dim rotates, in pairs (i, i + D/2) (the
    perturbation ``rope_added`` only: the model has none)."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _check(config: dict) -> None:
    if config.get("model_type") != "olmo_hybrid":
        raise ValueError("this reference implements model_type olmo_hybrid "
                         "only")
    if config.get("attention_bias"):
        raise ValueError("the reference does not implement attention_bias")
    rope = config.get("rope_parameters") or {}
    if rope.get("rope_theta", config.get("rope_theta")) is not None:
        raise ValueError("the reference implements attention with no rotary "
                         "embedding only; rope_theta must be null")
    types = config.get("layer_types") or []
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    for t in types:
        if t not in ("linear_attention", "full_attention"):
            raise ValueError(f"the reference does not implement a "
                             f"{t!r} layer")
    if int(config.get("linear_num_key_heads",
                      config["linear_num_value_heads"])) \
            != int(config["linear_num_value_heads"]):
        raise ValueError("the reference implements as many key heads as "
                         "value heads only")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference implements the SiLU MLP only")


def layer_names(config: dict) -> list:
    """(stack, index in the stack) of every layer, in layer order: the
    program stacks the layers of one mixer kind."""
    seen: dict = {}
    out = []
    for t in config["layer_types"]:
        name = "gdn_dense" if t == "linear_attention" else "full_dense"
        out.append((name, seen.get(name, 0)))
        seen[name] = seen.get(name, 0) + 1
    return out


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


def _make_layer(config: dict, linear: bool, perturb: str):
    """One block of the given kind, jitted."""
    H = int(config["num_attention_heads"])
    Hkv = int(config["num_key_value_heads"])
    D = int(config.get("head_dim") or config["hidden_size"] // H)
    eps = float(config.get("rms_norm_eps", 1e-6))
    Hl = int(config["linear_num_value_heads"])
    dk = int(config["linear_key_head_dim"])
    dv = int(config["linear_value_head_dim"])
    taps = int(config.get("linear_conv_kernel_dim", 4))
    beta_scale = 2.0 if config.get("linear_allow_neg_eigval") \
        and perturb != "beta_not_doubled" else 1.0

    def delta_rule(x, p):
        T = x.shape[0]
        C = Hl * (2 * dk + dv)
        proj = x @ p["gdn_in"]
        qkv, z = proj[:, :C], proj[:, C:]
        gates = x @ p["gdn_gates"]
        a_in, b_in = gates[:, :Hl], gates[:, Hl:]
        # c_t = sum_j w_{K-1-j} x_{t-j}, zeros before the sequence's start
        held = 1 if perturb == "conv_state_dropped" else taps
        back = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
        conv = sum(p["gdn_conv_w"][taps - 1 - j]
                   * back[taps - 1 - j:taps - 1 - j + T] for j in range(held))
        conv = jax.nn.silu(conv)
        q = conv[:, :Hl * dk].reshape(T, Hl, dk)
        k = conv[:, Hl * dk:2 * Hl * dk].reshape(T, Hl, dk)
        v = conv[:, 2 * Hl * dk:].reshape(T, Hl, dv)
        if perturb != "no_l2_norm":
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        q = q * dk ** -0.5
        beta = beta_scale * jax.nn.sigmoid(b_in)                 # [T, Hl]
        g = -jnp.exp(p["gdn_a_log"]) * jax.nn.softplus(a_in
                                                       + p["gdn_dt_bias"])
        alpha = jnp.ones_like(g) if perturb == "decay_dropped" else jnp.exp(g)

        def token(S, inp):
            # S: [Hl, dk, dv], one token
            q_t, k_t, v_t, a_t, b_t = inp
            S = a_t[:, None, None] * S
            if perturb == "delta_term_dropped":
                u = b_t[:, None] * v_t
            else:
                u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
            S = S + k_t[:, :, None] * u[:, None, :]
            return S, jnp.einsum("hkv,hk->hv", S, q_t)

        _, o = jax.lax.scan(token, jnp.zeros((Hl, dk, dv), jnp.float32),
                            (q, k, v, alpha, beta))              # [T, Hl, dv]
        o = _rms_norm(o, p["gdn_norm"], eps)
        if perturb != "out_gate_dropped":
            o = o * jax.nn.silu(z.reshape(T, Hl, dv))
        return o.reshape(T, Hl * dv) @ p["gdn_out"]

    def attention(x, p):
        T = x.shape[0]
        pos = jnp.arange(T)
        q, k = x @ p["q"], x @ p["k"]
        if perturb == "qk_norm_per_head":
            # statistics over a head's 128 lanes in place of all 3,840
            q = (_rms_norm(q.reshape(T, H, D), 1.0, eps).reshape(T, H * D)
                 * p["q_norm"])
            k = (_rms_norm(k.reshape(T, Hkv, D), 1.0, eps).reshape(T, Hkv * D)
                 * p["k_norm"])
        elif perturb != "no_qk_norm":
            q = _rms_norm(q, p["q_norm"], eps)
            k = _rms_norm(k, p["k_norm"], eps)
        q = q.reshape(T, H, D)
        k = k.reshape(T, Hkv, D)
        v = (x @ p["v"]).reshape(T, Hkv, D)
        if perturb == "rope_added":
            q, k = _rope(q, pos, 10000.0), _rope(k, pos, 10000.0)
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

    def mlp(x, p):
        return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]

    @jax.jit
    def layer(x, p):
        with jax.default_matmul_precision("highest"):
            p = {k: v.astype(jnp.float32) for k, v in p.items()}
            op = delta_rule if linear else attention
            if perturb == "pre_norm":
                # the norms in front of the operators, as most families
                u = x + op(_rms_norm(x, p["attn_norm"], eps), p)
                return u + mlp(_rms_norm(u, p["mlp_norm"], eps), p)
            u = x + _rms_norm(op(x, p), p["attn_norm"], eps)
            return u + _rms_norm(mlp(u, p), p["mlp_norm"], eps)

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
    bfloat16 they are served in), ``delta_term_dropped`` (``u_t = beta_t
    v_t``: plain gated linear attention, the state never reads itself
    back), ``decay_dropped`` (``alpha`` = 1), ``beta_not_doubled``,
    ``conv_state_dropped`` (a convolution sees its newest input alone:
    the three older taps give nothing, which is a served path that
    drops or zeroes the carried tail), ``no_l2_norm``,
    ``out_gate_dropped`` (``silu(x W_g)`` taken as ones), ``no_qk_norm``,
    ``qk_norm_per_head`` (the QK norm's statistics a head and not over
    the whole projection), ``rope_added`` (theta 10,000 on the attention
    layers: shows that "no rotary" is checked), ``pre_norm`` (the norms
    in front of the operators: shows that the reordered norm is
    checked), and ``last_layer_dropped``.
    """
    if perturb and perturb not in PERTURBATIONS:
        raise ValueError(f"no perturbation {perturb!r}")
    _check(config)
    T = len(tokens)
    # padded at the end to whole query blocks, so that few lengths
    # compile; every layer is causal, so no real position sees the padding
    tokens = jnp.asarray(list(tokens) + [0] * (-T % Q_BLOCK), jnp.int32)
    V = int(config["vocab_size"])
    eps = float(config.get("rms_norm_eps", 1e-6))
    layers = {}
    x = _embed(put(params["embed"]), tokens)
    names = layer_names(config)
    if perturb == "last_layer_dropped":
        names = names[:-1]
    for l, (name, i) in enumerate(names):
        linear = config["layer_types"][l] == "linear_attention"
        if linear not in layers:
            layers[linear] = _make_layer(config, linear, perturb)
        p = put({k: v[i] for k, v in params[name].items()})
        if perturb == "weights_fp8":
            # rounded outside the jitted layer, one array at a time, so
            # that no compiler keeps the excess precision
            p = {k: v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                 if v.ndim >= 2 else v for k, v in p.items()}
        x = layers[linear](x, p)
    tokens = tokens[:T]
    h = _rms_norm(x[start:T], put(params["final_norm"]).astype(jnp.float32),
                  eps)
    head = params["embed"] if config.get("tie_word_embeddings", False) \
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

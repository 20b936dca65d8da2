"""Plain reference of Falcon-H1: a decoder whose every block runs
grouped-query attention and a Mamba-2 state-space mixer side by side.

The forward pass as ``tiiuae/Falcon-H1-34B-Instruct``'s ``config.json``
(``model_type`` ``falcon_h1``) and the published model code give it, in
straightforward ``jax.numpy`` and float32, with
``jax.default_matmul_precision("highest")``.  No kernels, no cache, no
batching, no chunked scan: one sequence, every position attends to the
whole prefix, and the mixer's recurrence runs token by token.  It reads
only the HF-keyed ``config`` and a parameter tree and shares no code
with the program.  With ``h`` the hidden size:

- ``x = E[token] * embedding_multiplier``; the head is
  ``(RMSNorm(x_L) @ W_head^T) * lm_head_multiplier``, untied.
- Block: ``u = RMSNorm(x)``;
  ``x <- x + attention_out_multiplier * Attn(u * attention_in_multiplier)
  + ssm_out_multiplier * SSM(u)``; ``v = RMSNorm(x)``; ``x <- x + MLP(v)``.
- Attention: GQA, no biases, full rotary embedding (rotate-half),
  ``k = W_k(.) * key_multiplier``, scale ``1/sqrt(head_dim)``, causal.
- MLP: ``down(silu(gate(v) * mlp_multipliers[0]) * up(v)) *
  mlp_multipliers[1]``.
- SSM: ``p = W_in(u * ssm_in_multiplier)`` times a vector that is
  ``ssm_multipliers[0..4]`` over ``[z | x | B | C | dt]``;
  ``xBC = silu(conv1d([x|B|C]))``, depthwise, causal, with bias; per
  head ``i`` of group ``g``: ``dt = softplus(dt_i + dt_bias_i)``,
  ``a = exp(-exp(A_log_i) dt)``, ``H <- a H + dt x_i (outer) B_g``,
  ``y_i = H C_g + D_i x_i``; then ``y <- y * silu(z)``, RMSNorm over
  each group's channels times a weight, ``out = W_out y``.

Parameter tree (the layout of the program's ``init_params``, which is
data generation): ``embed [V', h]``, ``final_norm [h]``, ``lm_head
[V', h]``, and ``dense`` with every layer stacked on the first axis:
``attn_norm``, ``q``, ``k``, ``v``, ``o``, ``mlp_norm``, ``gate``,
``up``, ``down`` as the dense decoders', and the mixer's ``ssm_in [L, h,
2*d_ssm + 2*G*N + H]``, ``ssm_conv [L, K, d_ssm + 2*G*N]`` (row ``K-1``
on the current input), ``ssm_conv_bias``, ``ssm_dt_bias [L, H]``,
``ssm_a_log [L, H]``, ``ssm_d [L, H]``, ``ssm_norm [L, d_ssm]``,
``ssm_out [L, d_ssm, h]``; ``y = x @ W``.  ``V'`` is the vocabulary
padded up by the program; rows past ``vocab_size`` are no tokens.

Departures, for memory only: weights are kept in the type they are
served in and one layer at a time is upcast to float32; attention runs
over query blocks; the head is computed for the positions asked for, in
blocks over the vocabulary, with a running log-sum-exp.  None changes
the mathematics.
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 512
VOCAB_BLOCK = 16384
# what ``forward`` accepts for ``perturb``
PERTURBATIONS = ("drop_ssm_branch", "drop_attention_branch",
                 "drop_last_layer", "state_fp8", "no_conv", "weights_fp8")


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    """x: [T, heads, D]; the whole head rotates (rotate-half form)."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _check(config: dict) -> None:
    if config.get("model_type") != "falcon_h1":
        raise ValueError("this reference implements model_type falcon_h1 only")
    if config.get("rope_scaling"):
        raise ValueError("the reference implements plain rotary embedding "
                         "only; rope_scaling must be null")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference implements the SiLU-gated MLP only")
    if config.get("attn_layer_indices") is not None:
        raise ValueError("the reference runs attention in every block; "
                         "attn_layer_indices must be null")
    if config.get("mamba_norm_before_gate") or not config.get(
            "mamba_rms_norm", True):
        raise ValueError("the reference implements the gated RMSNorm after "
                         "the gate only")
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias",
                "projectors_bias", "tie_word_embeddings"):
        if config.get(key):
            raise ValueError(f"the reference does not implement {key}")
    if not config.get("mamba_conv_bias", True):
        raise ValueError("the reference implements the convolution with "
                         "its bias only")


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


def _make_layer(config: dict, perturb: str):
    _check(config)
    H = config["num_attention_heads"]
    Hkv = config.get("num_key_value_heads") or H
    D = config.get("head_dim") or config["hidden_size"] // H
    eps = float(config.get("rms_norm_eps", 1e-5))
    theta = float(config.get("rope_theta", 10000.0))
    Hm = config["mamba_n_heads"]
    d_ssm = config.get("mamba_d_ssm") or \
        config.get("mamba_expand", 2) * config["hidden_size"]
    Pm = config.get("mamba_d_head") or d_ssm // Hm
    G = config.get("mamba_n_groups", 1)
    N = config["mamba_d_state"]
    K = config.get("mamba_d_conv", 4)
    m_attn_in = float(config.get("attention_in_multiplier", 1.0))
    m_attn_out = float(config.get("attention_out_multiplier", 1.0))
    m_key = float(config.get("key_multiplier", 1.0))
    m_ssm_in = float(config.get("ssm_in_multiplier", 1.0))
    m_ssm_out = float(config.get("ssm_out_multiplier", 1.0))
    m_ssm = [float(x) for x in config.get("ssm_multipliers", [1.0] * 5)]
    m_gate, m_down = (float(x) for x in
                      config.get("mlp_multipliers", [1.0, 1.0]))
    mup = jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in zip(
        (d_ssm, d_ssm, G * N, G * N, Hm), m_ssm)])

    def attention(u, p):
        T = u.shape[0]
        pos = jnp.arange(T)
        a = u * m_attn_in
        q = _rope((a @ p["q"]).reshape(T, H, D), pos, theta)
        k = _rope(((a @ p["k"]) * m_key).reshape(T, Hkv, D), pos, theta)
        v = (a @ p["v"]).reshape(T, Hkv, D)
        # query head h reads key/value head h // (H // Hkv)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        outs = []
        for s in range(0, T, Q_BLOCK):
            qb = q[s:s + Q_BLOCK]
            sc = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(float(D))
            causal = (pos[None, :] <= pos[s:s + Q_BLOCK, None])[None]
            w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", w, v))
        attn = jnp.concatenate(outs, axis=0).reshape(T, H * D)
        return (attn @ p["o"]) * m_attn_out

    def mixer(u, p):
        T = u.shape[0]
        proj = ((u * m_ssm_in) @ p["ssm_in"]) * mup
        z = proj[:, :d_ssm]
        xbc = proj[:, d_ssm:2 * d_ssm + 2 * G * N]
        dt = jax.nn.softplus(proj[:, 2 * d_ssm + 2 * G * N:]
                             + p["ssm_dt_bias"])                 # [T, Hm]
        if perturb != "no_conv":
            # depthwise causal convolution: row K-1 on the current input
            padded = jnp.concatenate(
                [jnp.zeros((K - 1, xbc.shape[1]), jnp.float32), xbc])
            xbc = p["ssm_conv_bias"] + sum(
                p["ssm_conv"][k] * padded[k:k + T] for k in range(K))
        xbc = jax.nn.silu(xbc)
        x = xbc[:, :d_ssm].reshape(T, Hm, Pm)
        B = xbc[:, d_ssm:d_ssm + G * N].reshape(T, G, N)
        C = xbc[:, d_ssm + G * N:].reshape(T, G, N)
        # head i reads group i // (Hm // G)
        B = jnp.repeat(B, Hm // G, axis=1)
        C = jnp.repeat(C, Hm // G, axis=1)
        a = jnp.exp(-jnp.exp(p["ssm_a_log"])[None, :] * dt)      # [T, Hm]

        def step(h, inp):
            a_t, dt_t, x_t, b_t, c_t = inp
            h = a_t[:, None, None] * h \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            if perturb == "state_fp8":
                # float8 e5m2, the 8-bit type whose range holds an
                # unscaled state.  reduce_precision, not a pair of
                # casts: the TPU compiler may keep the excess precision
                # of a round trip through a narrower type, and then
                # nothing is rounded
                h = jax.lax.reduce_precision(h, exponent_bits=5,
                                             mantissa_bits=2)
            return h, jnp.einsum("hpn,hn->hp", h, c_t)

        _, y = jax.lax.scan(step, jnp.zeros((Hm, Pm, N), jnp.float32),
                            (a, dt, x, B, C))
        y = (y + p["ssm_d"][None, :, None] * x).reshape(T, d_ssm)
        y = y * jax.nn.silu(z)
        yg = y.reshape(T, G, d_ssm // G)
        yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), axis=-1,
                                         keepdims=True) + eps)
        y = yg.reshape(T, d_ssm) * p["ssm_norm"]
        return (y @ p["ssm_out"]) * m_ssm_out

    @jax.jit
    def layer(x, p):
        with jax.default_matmul_precision("highest"):
            p = {k: v.astype(jnp.float32) for k, v in p.items()}
            u = _rms_norm(x, p["attn_norm"], eps)
            if perturb != "drop_attention_branch":
                x = x + attention(u, p)
            if perturb != "drop_ssm_branch":
                x = x + mixer(u, p)
            v = _rms_norm(x, p["mlp_norm"], eps)
            return x + ((jax.nn.silu((v @ p["gate"]) * m_gate)
                         * (v @ p["up"])) @ p["down"]) * m_down

    return layer


@jax.jit
def _head_block(h, block, targets, lo, scale):
    """Logits of the rows ``block`` for the positions ``h``: their
    log-sum-exp, their maximum, and the logit of each position's target
    id if it lies in this block (else -inf)."""
    with jax.default_matmul_precision("highest"):
        logits = (h @ block.astype(jnp.float32).T) * scale     # [P, B]
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
    show what the tolerance catches: ``drop_ssm_branch`` and
    ``drop_attention_branch`` (a block without one of its two mixers),
    ``drop_last_layer``, ``state_fp8`` (the recurrent state rounded to
    float8 e5m2 after every token: the nearest precision below the
    bfloat16 it is served in), ``no_conv`` (the mixer without its
    convolution)
    and ``weights_fp8`` (every layer's matrices rounded to float8 e4m3:
    the nearest precision below the bfloat16 they are served in).
    """
    if perturb and perturb not in PERTURBATIONS:
        raise ValueError(f"no perturbation {perturb!r}")
    T = len(tokens)
    # padded at the end to whole query blocks, so that few lengths
    # compile; attention and the recurrence are causal, so no real
    # position sees the padding
    tokens = jnp.asarray(list(tokens) + [0] * (-T % Q_BLOCK), jnp.int32)
    V = int(config["vocab_size"])
    eps = float(config.get("rms_norm_eps", 1e-5))
    layer = _make_layer(config, perturb)
    x = _embed(put(params["embed"]), tokens) \
        * float(config.get("embedding_multiplier", 1.0))
    stack = params["dense"]
    L = int(stack["q"].shape[0])
    if perturb == "drop_last_layer":
        L -= 1
    for i in range(L):
        p = put({k: v[i] for k, v in stack.items()})
        if perturb == "weights_fp8":
            # rounded outside the jitted layer, one array at a time, so
            # that no compiler keeps the excess precision
            p = {k: v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                 if v.ndim == 2 and not k.startswith("ssm_conv") else v
                 for k, v in p.items()}
        x = layer(x, p)
    tokens = tokens[:T]
    h = _rms_norm(x[start:T], put(params["final_norm"]).astype(jnp.float32), eps)
    head = params["lm_head"]
    scale = float(config.get("lm_head_multiplier", 1.0))
    targets = jnp.concatenate([tokens[start + 1:], jnp.zeros((1,), jnp.int32)])
    lse = jnp.full((T - start,), -jnp.inf)
    top = jnp.full((T - start,), -jnp.inf)
    tgt = jnp.full((T - start,), -jnp.inf)
    for lo in range(0, V, VOCAB_BLOCK):
        block = put(head[lo:min(lo + VOCAB_BLOCK, V)])
        b_lse, b_top, b_tgt = _head_block(h, block, targets, lo, scale)
        lse = jnp.logaddexp(lse, b_lse)
        top = jnp.maximum(top, b_top)
        tgt = jnp.maximum(tgt, b_tgt)
    target = (tgt - lse).at[-1].set(jnp.nan)
    return {"target": target, "top": top - lse}

"""Plain reference of a dense decoder-only transformer.

The forward pass as the published descriptions give it — token
embedding, pre-norm blocks of RMSNorm, grouped-query causal attention
with rotary position embedding on the first ``partial_rotary_factor``
of each head (rotate-half form), a gated SiLU MLP, a final RMSNorm and
a tied or untied output head — in straightforward ``jax.numpy`` and
float32, with ``jax.default_matmul_precision("highest")``.  No
kernels, no cache, no batching: one sequence, every position attends
to the whole prefix.  It reads only the HF-keyed ``config`` and a
parameter tree and shares no code with the program.

Parameter tree (the layout of the program's ``init_params``, which is
data generation): ``embed [V', E]``, ``final_norm [E]``, optionally
``lm_head [V', E]``, and ``dense`` with every layer stacked on the
first axis: ``attn_norm [L, E]``, ``q [L, E, H*D]``, ``k``/``v``
``[L, E, Hkv*D]``, ``o [L, H*D, E]``, ``mlp_norm [L, E]``, ``gate``/
``up`` ``[L, E, I]``, ``down [L, I, E]``; ``y = x @ W``.  ``V'`` is the
vocabulary padded up by the program; rows past ``vocab_size`` are no
tokens and are left out of the softmax.

Departures, for memory only: weights are kept in the type they are
served in (bf16) and one layer at a time is upcast to float32;
attention runs over query blocks; the head is computed for the
positions asked for, in blocks over the vocabulary, with a running
log-sum-exp.  None changes the mathematics.
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 512
VOCAB_BLOCK = 16384
# what ``forward`` accepts for ``perturb``
PERTURBATIONS = ("drop_last_layer", "head_int8", "weights_fp8")


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta, rot):
    """x: [T, heads, D]; rotate the first ``rot`` dims of each head."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def _dims(config: dict):
    E = config["hidden_size"]
    H = config["num_attention_heads"]
    Hkv = config.get("num_key_value_heads") or H
    D = config.get("head_dim") or E // H
    rot = int(D * config.get("partial_rotary_factor", 1.0))
    return E, H, Hkv, D, rot - rot % 2


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


def _make_layer(config: dict):
    _, H, Hkv, D, rot = _dims(config)
    eps = float(config.get("rms_norm_eps", 1e-5))
    theta = float(config.get("rope_theta", 10000.0))
    if config.get("rope_scaling"):
        raise ValueError("the reference implements plain rotary embedding "
                         "only; rope_scaling must be null")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference implements the SiLU-gated MLP only")

    @jax.jit
    def layer(x, p):
        with jax.default_matmul_precision("highest"):
            p = {k: v.astype(jnp.float32) for k, v in p.items()}
            T = x.shape[0]
            pos = jnp.arange(T)
            h = _rms_norm(x, p["attn_norm"], eps)
            q = _rope((h @ p["q"]).reshape(T, H, D), pos, theta, rot)
            k = _rope((h @ p["k"]).reshape(T, Hkv, D), pos, theta, rot)
            v = (h @ p["v"]).reshape(T, Hkv, D)
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
            x = x + attn @ p["o"]
            h = _rms_norm(x, p["mlp_norm"], eps)
            return x + (jax.nn.silu(h @ p["gate"]) * (h @ p["up"])) @ p["down"]

    return layer


@jax.jit
def _head_block(h, block, targets, lo):
    """Logits of the rows ``block`` for the positions ``h``: their
    log-sum-exp, their maximum, and the logit of each position's target
    id if it lies in this block (else -inf)."""
    with jax.default_matmul_precision("highest"):
        logits = h @ block.astype(jnp.float32).T          # [P, B]
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
    runs, for trees that are spread over devices.  ``perturb`` names a
    deliberately cruder computation, used to show what the tolerance
    catches: ``drop_last_layer``, ``head_int8`` (the head's rows rounded
    to 8-bit integers) or ``weights_fp8`` (every layer's matrices
    rounded to float8 e4m3).
    """
    T = len(tokens)
    # padded at the end to whole query blocks, so that few lengths
    # compile; attention is causal, so no real position sees the padding
    tokens = jnp.asarray(list(tokens) + [0] * (-T % Q_BLOCK), jnp.int32)
    V = int(config["vocab_size"])
    eps = float(config.get("rms_norm_eps", 1e-5))
    layer = _make_layer(config)
    x = _embed(put(params["embed"]), tokens)
    stack = params["dense"]
    L = int(stack["q"].shape[0])
    if perturb == "drop_last_layer":
        L -= 1
    for i in range(L):
        p = put({k: v[i] for k, v in stack.items()})
        if perturb == "weights_fp8":
            p = {k: v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                 if v.ndim == 2 else v for k, v in p.items()}
        x = layer(x, p)
    tokens = tokens[:T]
    h = _rms_norm(x[start:T], put(params["final_norm"]).astype(jnp.float32), eps)
    head = params["embed"] if config.get("tie_word_embeddings") \
        else params["lm_head"]
    targets = jnp.concatenate([tokens[start + 1:], jnp.zeros((1,), jnp.int32)])
    lse = jnp.full((T - start,), -jnp.inf)
    top = jnp.full((T - start,), -jnp.inf)
    tgt = jnp.full((T - start,), -jnp.inf)
    for lo in range(0, V, VOCAB_BLOCK):
        block = put(head[lo:min(lo + VOCAB_BLOCK, V)])
        if perturb == "head_int8":
            scale = jnp.max(jnp.abs(block.astype(jnp.float32)),
                            axis=1, keepdims=True) / 127.0
            block = (jnp.round(block.astype(jnp.float32) / scale)
                     * scale).astype(jnp.bfloat16)
        b_lse, b_top, b_tgt = _head_block(h, block, targets, lo)
        lse = jnp.logaddexp(lse, b_lse)
        top = jnp.maximum(top, b_top)
        tgt = jnp.maximum(tgt, b_tgt)
    target = (tgt - lse).at[-1].set(jnp.nan)
    return {"target": target, "top": top - lse}

"""Grouped-matmul MoE path vs the dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine import nn
from kaito_tpu.engine.kv_cache import create_kv_cache
from kaito_tpu.engine.model import TransformerLM
from kaito_tpu.models.autogen import arch_from_hf_config

MOE_CFG = {
    "architectures": ["MixtralForCausalLM"], "model_type": "mixtral",
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "num_local_experts": 8,
    "num_experts_per_tok": 2, "max_position_embeddings": 256,
}


def _arch():
    return arch_from_hf_config(MOE_CFG)


def test_ragged_moe_matches_dense():
    arch = _arch()
    model = TransformerLM(arch, dtype=jnp.float32)
    p = model.init_params(jax.random.PRNGKey(0))["moe"]
    layer_p = {k: v[0] for k, v in p.items()}
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(13, arch.hidden_size), jnp.float32)
    dense = nn.moe_mlp(x, layer_p, arch)
    ragged = nn.moe_mlp_ragged(x, layer_p, arch)
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_ragged_moe_with_shared_experts():
    cfg = dict(MOE_CFG, model_type="deepseek_v3",
               architectures=["DeepseekV3ForCausalLM"],
               n_routed_experts=4, num_experts_per_tok=2,
               n_shared_experts=1, moe_intermediate_size=32,
               first_k_dense_replace=0,
               kv_lora_rank=16, qk_rope_head_dim=8, qk_nope_head_dim=8,
               v_head_dim=8)
    arch = arch_from_hf_config(cfg)
    model = TransformerLM(arch, dtype=jnp.float32)
    p = model.init_params(jax.random.PRNGKey(1))["moe"]
    layer_p = {k: v[0] for k, v in p.items()}
    x = jnp.asarray(np.random.RandomState(1).randn(7, arch.hidden_size),
                    jnp.float32)
    np.testing.assert_allclose(
        np.asarray(nn.moe_mlp_ragged(x, layer_p, arch)),
        np.asarray(nn.moe_mlp(x, layer_p, arch)), rtol=2e-5, atol=2e-5)


def test_model_prefill_decode_with_ragged_moe():
    arch = _arch()
    model = TransformerLM(arch, dtype=jnp.float32)
    model.moe_impl = "ragged"
    params = model.init_params(jax.random.PRNGKey(0))
    cache = create_kv_cache(arch, 32, 16, jnp.float32)
    pt = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    toks = jnp.asarray(np.random.RandomState(2).randint(0, 512, (1, 9)),
                       jnp.int32)
    _, full, _ = model.prefill(params, cache, toks,
                               jnp.asarray([9], jnp.int32), pt)

    dense_model = TransformerLM(arch, dtype=jnp.float32)  # dense path
    cache2 = create_kv_cache(arch, 32, 16, jnp.float32)
    _, ref, _ = dense_model.prefill(params, cache2, toks,
                                    jnp.asarray([9], jnp.int32), pt)
    np.testing.assert_allclose(np.asarray(full), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def _crafted_routing(tokens, top_k, experts, held, seed):
    """Distinct experts a token, drawn evenly; token 5 chooses held
    experts only and tokens 128..255 (a whole tile of the kernel's)
    none, where the layer is shared."""
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.permutation(experts)[:top_k] for _ in range(tokens)])
    if held < experts:
        idx[5] = np.arange(top_k)
        away = held + np.stack([rng.permutation(experts - held)[:top_k]
                                for _ in range(128)])
        idx[128:256] = away[:max(0, min(tokens, 256) - 128)]
    weights = rng.random((tokens, top_k)) + 0.1
    return (jnp.asarray(idx, jnp.int32),
            jnp.asarray(weights / weights.sum(1, keepdims=True), jnp.float32))


def _loop_of_every_slot(y, out, rows, live, k, fresh):
    """The un-sort where no kernel runs: every one of a token's ``k``
    slots gathers a row of ``out``."""
    pairs = y.shape[0] * k
    n_rows = out.shape[0]
    rel = jnp.full((pairs,), n_rows, jnp.int32).at[
        jnp.where(live, rows, pairs)].set(
            jnp.arange(n_rows, dtype=jnp.int32), mode="drop")
    return nn._combine_slots(y, out, rel.reshape(-1, k), n_rows)


@pytest.mark.parametrize("tokens,top_k,shards,n_valid,compact", [
    (4096, 8, 16, 2489, True),   # the longest prefill bucket, padded
    (2048, 8, 16, None, True),
    (404, 8, 16, 300, False),    # no whole number of the kernel's tiles
    (32, 8, 16, None, False),    # a share's decode width: every pair a pass
    (64, 2, 1, 50, False),       # a whole layer
])
def test_compact_unsort_equals_the_loop_bit_for_bit(monkeypatch, tokens,
                                                    top_k, shards, n_valid,
                                                    compact):
    """Where a pass holds fewer pairs than were routed only the rows
    that hold a pair go back to their tokens (the kernel, interpreted),
    and the float32 sums are the loop's over all k slots bit for bit: a
    mask that ends mid-tile, a token all of whose pairs are held, a tile
    of tokens with none.  Where a pass holds every pair, or the tokens
    are no whole number of tiles, the loop itself still runs."""
    from jax.experimental.pallas import tpu as pltpu

    from kaito_tpu.models.metadata import ModelArch

    held = 16
    arch = ModelArch(
        vocab_size=64, hidden_size=128, num_layers=1, num_heads=2,
        num_kv_heads=2, head_dim=16, intermediate_size=64,
        num_experts=held * shards, num_experts_per_tok=top_k,
        moe_intermediate_size=128, expert_shards=shards)
    rng = np.random.default_rng(tokens + shards)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32) / 8

    p = {"router": draw(128, held * shards),
         "experts_gate": draw(held, 128, 128),
         "experts_up": draw(held, 128, 128),
         "experts_down": draw(held, 128, 128)}
    x = draw(tokens, 128) * 8
    valid = None if n_valid is None else jnp.arange(tokens) < n_valid
    routing = _crafted_routing(tokens, top_k, held * shards, held, tokens)
    monkeypatch.setattr(nn, "route_tokens", lambda *a, **kw: routing)
    calls = []
    compacted = nn._combine_held

    def counted(*a, **kw):
        calls.append(1)
        return compacted(*a, **kw)

    def layer():
        # one program: an interpreted kernel's callbacks deadlock
        # against operations dispatched one by one behind it
        with pltpu.force_tpu_interpret_mode():
            return np.asarray(jax.jit(lambda x, p: nn.moe_mlp_ragged(
                x, p, arch, valid=valid, kernel=True))(x, p))

    monkeypatch.setattr(nn, "_combine_held", counted)
    got = layer()
    assert bool(calls) == compact
    monkeypatch.setattr(nn, "_combine_held", _loop_of_every_slot)
    want = layer()
    assert np.array_equal(got, want)
    assert np.abs(want[5]).max() > 1e-3
    if shards > 1 and tokens >= 256:
        assert not want[128:256].any()
    if n_valid is not None:
        assert not want[n_valid:].any() and want[n_valid - 1].any()


def _divides_or_whole(tile, dim):
    return tile == dim or (tile % 128 == 0 and dim % tile == 0)


@pytest.mark.parametrize("rows", [16, 128, 256, 16384])
@pytest.mark.parametrize("k,n,want", [
    (2048, 1792, (2048, 896)),      # lfm2: two n-tiles, none half empty
    (1792, 2048, (1792, 1024)),
    (2048, 768, (2048, 768)),       # joyai: an expert's matrix one tile
    (768, 2048, (768, 2048)),
    (4096, 2048, (4096, 512)),      # mimo's prefill: K whole, the rhs
    (2048, 4096, (2048, 1024)),     # stays put over a group's row tiles
    (4096, 14336, (4096, 512)),     # mixtral
    (14336, 4096, (2048, 1024)),    # a K no tile holds whole is split
    (2048, 1400, (2048, 512)),      # no multiple of 128 divides 1,400:
    (2048, 1408, (2048, 512)),      # nor one as wide as before 1,408
    (96, 64, (96, 64)),             # narrower than a tile: whole
])
def test_grouped_kernel_tiles_follow_the_shapes(rows, k, n, want):
    """The grouped matmul's tiles for the widths served and in the
    presets: k and n divide their dimension (or are what every call
    had before, where no multiple of 128 does), m is the rows or 128,
    and a tile set stays under the VMEM budget written beside it."""
    tm, tk, tn = nn._gmm_tile(rows, k, n)
    assert tm == min(rows, 128) and rows % tm == 0
    before = (min(k, 2048), min(n, 512))
    assert (tk, tn) == before or (
        _divides_or_whole(tk, k) and _divides_or_whole(tn, n)
        and tn >= before[1])
    assert nn._gmm_vmem_bytes(tm, tk, tn, 2) <= nn._GMM_VMEM_BUDGET < 16 << 20
    # a call of at most two row tiles keeps the 2 MiB tile where it
    # divides both dimensions (what the chip measured fastest there)
    if rows <= 256 and k % 2048 == 0 and n % 512 == 0:
        assert (tk, tn) == before
    elif rows >= 128:           # fewer rows leave room for a wider n
        assert (tk, tn) == want


@pytest.mark.parametrize("hidden,inter,tiles", [
    (256, 384, {"256x384": [64, 256, 384], "384x256": [64, 384, 256]}),
    (384, 256, {"384x256": [64, 384, 256], "256x384": [64, 256, 384]}),
])
def test_grouped_kernel_at_a_width_no_power_of_two(monkeypatch, hidden,
                                                   inter, tiles):
    """The kernel interpreted at tiles of 384, three times 128, against
    XLA's ragged dot through the expert layer, and the tiles it ran
    with are the ones ``expert_tiles`` names for ``/health``."""
    import importlib

    from jax.experimental.pallas import tpu as pltpu

    from kaito_tpu.models.metadata import ModelArch

    arch = ModelArch(
        vocab_size=64, hidden_size=hidden, num_layers=1, num_heads=2,
        num_kv_heads=2, head_dim=16, intermediate_size=64, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=inter)
    rng = np.random.default_rng(hidden)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32) / 8

    p = {"router": draw(hidden, 8) * 8,
         "experts_gate": draw(2, 8, hidden, inter),
         "experts_up": draw(2, 8, hidden, inter),
         "experts_down": draw(2, 8, inter, hidden)}
    x = draw(32, hidden) * 8
    assert nn.expert_tiles(arch, 32, 4) == tiles
    ran = {}
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    kernel = megablox.gmm

    def recorded(lhs, rhs, *a, tiling, **kw):
        ran["%dx%d" % rhs.shape[-2:]] = list(tiling)
        return kernel(lhs, rhs, *a, tiling=tiling, **kw)

    monkeypatch.setattr(megablox, "gmm", recorded)
    want = nn.moe_mlp_ragged(x, p, arch, layer=jnp.int32(1))
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(lambda x, p, at: nn.moe_mlp_ragged(
            x, p, arch, kernel=True, layer=at))(x, p, jnp.int32(1))
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    assert np.abs(np.asarray(want)).max() > 1e-2
    assert ran == tiles


@pytest.mark.parametrize("kernel,quantization,listed", [
    (True, None, True),
    (False, None, False),       # a CPU, a mesh: XLA's ragged dot
    (True, "int8", False),      # quantized stacks take it too
])
def test_engine_lists_the_tiles_it_runs(kernel, quantization, listed):
    """What ``/health`` gets under ``moe_tiles``: the tiles of a decode
    step of every slot and of the longest prefill chunk, by the
    experts' [K, N]; nothing where the kernel does not run."""
    from types import SimpleNamespace

    from kaito_tpu.engine.engine import InferenceEngine
    from kaito_tpu.models.metadata import ModelArch

    arch = ModelArch(
        vocab_size=64, hidden_size=2048, num_layers=1, num_heads=2,
        num_kv_heads=2, head_dim=16, intermediate_size=64, num_experts=32,
        num_experts_per_tok=4, moe_intermediate_size=1792)
    eng = SimpleNamespace(
        model=SimpleNamespace(moe_combine="xla", moe_kernel=kernel),
        cfg=SimpleNamespace(quantization=quantization, max_num_seqs=32,
                            max_prefill_tokens=4096, page_size=64,
                            max_model_len=8192),
        md=SimpleNamespace(arch=arch), dtype=jnp.dtype(jnp.bfloat16),
        _bucket=lambda n: n)
    tiles = InferenceEngine._expert_tiles(eng)
    if not listed:
        assert tiles is None
        return
    one = {"2048x1792": [128, 2048, 896], "1792x2048": [128, 1792, 1024]}
    assert tiles == {"decode": one, "prefill": one}

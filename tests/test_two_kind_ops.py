"""The attention kernels with what a second attention kind asks of them
(docs/kv-cache.md, "Two kinds of page"): a sink bias a head, values
narrower than keys, both head layouts, a window that starts mid-page
and pages wholly behind it that the table no longer names.  Interpret
mode against engine/attention.py's JAX paths; Mosaic's own verdict at
the published widths on a described v5e; and the expert layer's
grouped-matmul kernel against XLA's ragged dot."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from kaito_tpu.engine import attention as A
from kaito_tpu.engine.ops.decode_attention import (
    paged_decode_attention_pallas)
from kaito_tpu.engine.ops.flash_prefill import flash_prefill_attention
from tests.helpers.flash_geometries import SERVED

BIG = 1 << 30


def _t(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


# (query heads, KV heads, window, sink): the full kind's layout, the
# window kind's, and the window kind's at the full kind's KV heads
LAYOUTS = [(8, 2, BIG, False), (8, 4, 24, True), (8, 2, 24, True),
           (8, 4, BIG, True)]


@pytest.mark.parametrize("flat", [False, True])
@pytest.mark.parametrize("H,Hkv,window,sink_on", LAYOUTS)
def test_decode_kernel_against_the_jax_path(H, Hkv, window, sink_on, flat):
    """Rows of 70, 0 and 41 tokens over pages of 16, keys of 48 and
    values of 32: a window of 24 starts mid-page (70 - 24 = 46), and the
    pages wholly behind it are the null page in the kernel's table."""
    rng = np.random.default_rng(0)
    B, D, Dv, ps, P, pmax = 3, 48, 32, 16, 40, 8
    q, ck, cv = _t(rng, B, H, D), _t(rng, 2, P, ps, Hkv, D), \
        _t(rng, 2, P, ps, Hkv, Dv)
    table = rng.permutation(np.arange(1, P))[:B * pmax].reshape(B, pmax)
    lens = np.asarray([70, 0, 41], np.int32)
    freed = table.copy()
    for b in range(B):
        freed[b, :max(int(lens[b]) - window, 0) // ps] = 0
    sink = _t(rng, H) if sink_on else None
    want = A.paged_decode_attention(
        q, ck, cv, jnp.asarray(table, jnp.int32), jnp.asarray(lens),
        scale=0.2, sliding_window=window, layer=jnp.int32(1), sink=sink)
    kv_heads = None
    if flat:
        # token-flat pools, [layers, pages, page_size * heads, dim], as
        # a model with two kinds of page stores them
        ck, cv = ck.reshape(2, P, ps * Hkv, D), cv.reshape(2, P, ps * Hkv, Dv)
        kv_heads = Hkv
    with pltpu.force_tpu_interpret_mode():
        got = paged_decode_attention_pallas(
            q, ck, cv, jnp.asarray(freed, jnp.int32), jnp.asarray(lens),
            jnp.int32(window), scale=0.2, layer=jnp.int32(1), sink=sink,
            kv_heads=kv_heads)
    assert got.shape == (B, H, Dv)
    want = np.where((lens > 0)[:, None, None], want, 0.0)
    assert np.abs(np.asarray(got) - want).max() < 2e-5
    # the JAX path too reads nothing of a freed page
    again = A.paged_decode_attention(
        q, ck, cv, jnp.asarray(freed, jnp.int32), jnp.asarray(lens),
        scale=0.2, sliding_window=window, layer=jnp.int32(1), sink=sink,
        kv_heads=kv_heads)
    assert np.abs(np.where((lens > 0)[:, None, None], again, 0.0)
                  - want).max() < 2e-5


@pytest.mark.parametrize("H,Hkv,window,sink_on", LAYOUTS)
def test_flash_kernel_against_the_jax_path(H, Hkv, window, sink_on):
    rng = np.random.default_rng(1)
    B, T, D, Dv = 2, 64, 48, 32
    q, k, v = _t(rng, B, T, H, D), _t(rng, B, T, Hkv, D), \
        _t(rng, B, T, Hkv, Dv)
    true_len = jnp.asarray([64, 37], jnp.int32)
    sink = _t(rng, H) if sink_on else None
    want = A.prefill_attention(q, k, v, scale=0.2, sliding_window=window,
                               true_len=true_len, sink=sink)
    with pltpu.force_tpu_interpret_mode():
        got = flash_prefill_attention(q, k, v, true_len, jnp.int32(window),
                                      scale=0.2, block_q=16, block_k=16,
                                      sink=sink)
    assert got.shape == (B, T, H, Dv)
    live = (np.arange(T)[None, :] < np.asarray(true_len)[:, None])
    diff = np.where(live[:, :, None, None], np.asarray(got - want), 0.0)
    assert np.abs(diff).max() < 2e-5
    # the interpreter's output buffer starts as NaNs: the second row's
    # query block past its 37 tokens (48..63) was written, as zeros
    assert np.isfinite(np.asarray(got)).all()
    assert not np.asarray(got)[1, 48:].any()


def test_a_sink_takes_probability_and_carries_no_value():
    """One key, value v: with a sink of the key's score the output is
    v / 2, and with none it is v."""
    q = jnp.ones((1, 1, 1, 4)) * 0.5
    k = jnp.ones((1, 1, 1, 4))
    v = jnp.full((1, 1, 1, 4), 3.0)
    score = float((q * k).sum())
    plain = A.prefill_attention(q, k, v, scale=1.0)
    sunk = A.prefill_attention(q, k, v, scale=1.0,
                               sink=jnp.asarray([score]))
    np.testing.assert_allclose(np.asarray(plain), 3.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sunk), 1.5, rtol=1e-6)


def test_context_attention_across_freed_pages_with_a_sink():
    """A chunk of 20 queries from position 50 over a window of 24: the
    pages wholly behind position 27 may be the null page."""
    rng = np.random.default_rng(2)
    H, Hkv, D, Dv, ps, P, pmax = 8, 4, 48, 32, 16, 30, 6
    ck, cv = _t(rng, P, ps, Hkv, D), _t(rng, P, ps, Hkv, Dv)
    q = _t(rng, 1, 20, H, D)
    table = np.arange(1, pmax + 1)[None].astype(np.int32)
    freed = table.copy()
    freed[0, :(50 - 24 + 1) // ps] = 0
    sink = _t(rng, H)
    kw = dict(scale=0.2, sliding_window=24, sink=sink)
    start, n = jnp.asarray([50], jnp.int32), jnp.asarray([20], jnp.int32)
    want = A.paged_context_attention(q, ck, cv, jnp.asarray(table), start,
                                     n, **kw)
    got = A.paged_context_attention(q, ck, cv, jnp.asarray(freed), start,
                                    n, **kw)
    assert got.shape == (1, 20, H, Dv)
    assert np.abs(np.asarray(got - want)).max() < 1e-6


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("layers,Hkv,sink_on", [(2, 4, False), (5, 8, True)])
def test_kernels_compile_for_v5e_at_the_published_widths(one_chip, layers,
                                                         Hkv, sink_on):
    """MiMo-V2.5's two kinds at the cell's 32 rows: 64 query heads, keys
    stored at 256 lanes (192 padded: Mosaic copies whole 128-lane tiles
    and refuses a 192-wide slice), values of 128, pages of 64."""
    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, H, D, Dv, ps, P = 32, 64, 256, 128, 64, 600
    sink = (sd((H,), jnp.float32),) if sink_on else ()

    def decode(q, ck, cv, pt, ln, win, li, *s, kv_heads=Hkv):
        return paged_decode_attention_pallas(
            q, ck, cv, pt, ln, win, scale=0.07, layer=li,
            sink=s[0] if s else None, kv_heads=kv_heads)

    rest = (sd((B, 80), jnp.int32), sd((B,), jnp.int32), sd((), jnp.int32),
            sd((), jnp.int32), *sink)
    # the pools token-flat, as a model with two kinds of page stores
    # them: the kernel reads them as they lie
    compiled = jax.jit(decode).lower(
        sd((B, H, D)), sd((layers, P, ps * Hkv, D)),
        sd((layers, P, ps * Hkv, Dv)), *rest).compile()
    assert "attention" in compiled.as_text()
    key_pool = layers * P * ps * Hkv * D * 2
    assert compiled.memory_analysis().temp_size_in_bytes < key_pool // 8
    if Hkv == 4:
        # what the flat form is for: four KV heads of two lane tiles in
        # [.., page_size, heads, dim] merge into the kernel's rows only
        # by a copy of the whole key pool, every layer of every step
        copied = jax.jit(partial(decode, kv_heads=None)).lower(
            sd((B, H, D)), sd((layers, P, ps, Hkv, D)),
            sd((layers, P, ps, Hkv, Dv)), *rest).compile()
        assert copied.memory_analysis().temp_size_in_bytes >= key_pool

    _compile_flash(sd, 4096, H, Hkv, D, Dv, sink_on)


def test_decode_kernel_compiles_for_v5e_over_lane_packed_heads(one_chip):
    """LFM2-8B-A1B's attention layers at the cell's 32 rows: 8 KV heads
    of 64 lie two to a 128-lane row of the token-flat pools ([3, P, 64
    x 4, 128]), which the kernel reads as 4 KV heads of 128 under
    queries laid into their own head's lanes (attention.
    lane_pack_queries); one head a row of 64 lanes is what Mosaic
    refuses (a 64-lane slice of a 128-lane tile)."""
    from kaito_tpu.engine import attention as A

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, H, Hkv, D, ps, P, pack = 32, 32, 8, 64, 64, 2600, 2

    def decode(q, ck, cv, pt, ln, win, li, rows):
        out = paged_decode_attention_pallas(
            A.lane_pack_queries(q, Hkv, pack), ck, cv, pt, ln, win,
            scale=0.125, layer=li, kv_heads=rows)
        return A.lane_unpack_outputs(out, Hkv, pack)

    rest = (sd((B, 80), jnp.int32), sd((B,), jnp.int32), sd((), jnp.int32),
            sd((), jnp.int32))
    pool = sd((3, P, ps * Hkv // pack, pack * D))
    compiled = jax.jit(partial(decode, rows=Hkv // pack)).lower(
        sd((B, H, D)), pool, pool, *rest).compile()
    assert "attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 3 * P * ps * Hkv * D * 2 // 8
    plain = sd((3, P, ps * Hkv, D))
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(lambda q, ck, cv, *r: paged_decode_attention_pallas(
            q, ck, cv, *r[:3], scale=0.125, layer=r[3],
            kv_heads=Hkv)).lower(sd((B, H, D)), plain, plain,
                                 *rest).compile()


def test_decode_kernel_compiles_for_v5e_at_a_group_of_one(one_chip):
    """Olmo-Hybrid-7B's attention layers at the cell's 32 rows: 30 KV
    heads of 128 under 30 query heads, pools [2, P, 64 x 30, 128]; the
    query block is [30, 128] and a page's score panel [30, 1920]."""
    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, H, D, ps, P = 32, 30, 128, 64, 2600
    pool = sd((2, P, ps * H, D))
    compiled = jax.jit(
        lambda q, ck, cv, pt, ln, win, li: paged_decode_attention_pallas(
            q, ck, cv, pt, ln, win, scale=D ** -0.5, layer=li,
            kv_heads=H)).lower(
        sd((B, H, D)), pool, pool, sd((B, 80), jnp.int32),
        sd((B,), jnp.int32), sd((), jnp.int32), sd((), jnp.int32)).compile()
    assert "attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2 * P * ps * H * D * 2 // 8


def test_delta_rule_kernel_compiles_for_v5e_at_the_published_widths(one_chip):
    """Olmo-Hybrid-7B's state pool at the cell's 32 slots: six layers'
    rows of [96, 30 x 192] bfloat16 (45 whole lane tiles, the heads in
    pairs of three), aliased in and out: no temporary of a layer's
    rows, and the kernel is in the program under the name the
    roofline's reader looks for."""
    from kaito_tpu.engine.ops.gdn import gdn_state_update

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, H, dk, dv = 32, 30, 96, 192
    compiled = jax.jit(gdn_state_update, donate_argnums=(0,)).lower(
        sd((6, S, dk, H * dv), jnp.bfloat16), sd((), jnp.int32),
        sd((S,), jnp.int32), sd((1,), jnp.int32), sd((S, H, dk)),
        sd((S, H, dk)), sd((S, H, dv)), sd((S, H)), sd((S, H))).compile()
    assert "gdn_state_update" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < S * dk * H * dv * 2


def _compile_flash(sd, T, H, Hkv, D, Dv, sink_on):
    def flash(q, k, v, tl, win, *s):
        return flash_prefill_attention(q, k, v, tl, win, scale=0.07,
                                       sink=s[0] if s else None)

    sink = (sd((H,), jnp.float32),) if sink_on else ()
    compiled = jax.jit(flash).lower(
        sd((1, T, H, D)), sd((1, T, Hkv, D)), sd((1, T, Hkv, Dv)),
        sd((1,), jnp.int32), sd((), jnp.int32), *sink).compile()
    # under the name the trace's reduction finds it by
    assert "%attention" in compiled.as_text()


@pytest.mark.parametrize("H,Hkv,D,Dv,sink_on", SERVED.values(), ids=SERVED)
def test_flash_tile_compiles_for_v5e_at_every_bucket(one_chip, H, Hkv, D, Dv,
                                                     sink_on):
    """The tile the wrapper picks from its operands (G 3, 5, 16 and 8;
    chunks of 128 to 4,096) is one Mosaic takes: the VMEM arithmetic of
    ``_vmem_need`` is held against the compiler's own verdict, which
    interpret mode cannot give."""
    from kaito_tpu.engine.config import EngineConfig

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for T in EngineConfig.prefill_buckets:
        _compile_flash(sd, T, H, Hkv, D, Dv, sink_on)


@pytest.mark.parametrize("rows", [32, 24, 4096])
def test_expert_layer_compiles_for_v5e_and_names_its_kernel(one_chip, rows):
    """The held share at the published widths, decode's 32 rows (24:
    no multiple of the kernel's row tile) and a prefill chunk's 4,096
    (passes of an eighth of the pairs): the grouped matmul is in the
    program under the name the roofline's reader looks for, and reads
    the stack as it lies (no temporary of a layer's matrices); the
    chunk's un-sort is there as ``moe_combine``."""
    from kaito_tpu.engine import nn
    from kaito_tpu.models.metadata import ModelArch

    arch = ModelArch(
        vocab_size=64, hidden_size=4096, num_layers=1, num_heads=2,
        num_kv_heads=2, head_dim=16, intermediate_size=64, num_experts=256,
        num_experts_per_tok=8, moe_intermediate_size=2048,
        router_scoring="sigmoid", router_bias=True, expert_shards=16)

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"router": sd((4096, 256)), "router_bias": sd((256,)),
         "experts_gate": sd((5, 16, 4096, 2048)),
         "experts_up": sd((5, 16, 4096, 2048)),
         "experts_down": sd((5, 16, 2048, 4096))}

    def layer(x, p, valid, at):
        return nn.moe_mlp_ragged(x, p, arch, valid=valid, kernel=True,
                                 with_stats=True, layer=at)

    compiled = jax.jit(layer).lower(
        sd((rows, 4096)), p, sd((rows,), jnp.bool_),
        sd((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "%gmm" in text
    # a chunk's pass holds an eighth of the routed pairs and its rows go
    # back to their tokens through the compact un-sort's kernel; a
    # decode step's pass holds every pair, and the loop stands
    assert ("%moe_combine" in text) == (rows == 4096)
    one_layer = 3 * 16 * 4096 * 2048 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer // (
        8 if rows <= 32 else 2)


@pytest.mark.parametrize("k,n,held", [
    (2048, 1792, 32), (1792, 2048, 32),     # lfm2-8b-a1b: tiles of 896
    (2048, 768, 16), (768, 2048, 16),       # joyai-llm-flash: one tile
    (4096, 14336, 2), (14336, 4096, 2),     # mixtral: K whole, K split
])
@pytest.mark.parametrize("rows", [128, 4096])
def test_grouped_kernel_tiles_compile_for_v5e(one_chip, k, n, held, rows):
    """The tiles ``nn._gmm_tile`` picks at the widths served and in the
    presets are tiles Mosaic takes within the VMEM a call gets: the
    budget's arithmetic held against the compiler's own verdict."""
    from kaito_tpu.engine import nn

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(lhs, w, sizes, expert_of_row, at):
        return nn._grouped_matmul(lhs, w, sizes, expert_of_row, True, at)

    text = jax.jit(call).lower(
        sd((rows, k)), sd((2, held, k, n)), sd((held,), jnp.int32),
        sd((rows,), jnp.int32), sd((), jnp.int32)).compile().as_text()
    assert "%gmm" in text


def test_grouped_kernel_equals_the_ragged_dot():
    """The Pallas grouped matmul in interpret mode against XLA's ragged
    dot, through the expert layer: the whole stack by index, an expert
    with no row, rows that no held expert takes."""
    from dataclasses import replace

    from kaito_tpu.engine import nn
    from kaito_tpu.models.metadata import ModelArch

    arch = ModelArch(
        vocab_size=64, hidden_size=128, num_layers=1, num_heads=2,
        num_kv_heads=2, head_dim=16, intermediate_size=64, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=128,
        router_scoring="sigmoid", router_bias=True, expert_shards=4,
        expert_shard=1)
    rng = np.random.default_rng(3)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32) / 8

    p = {"router": draw(128, 16) * 8, "router_bias": draw(16),
         "experts_gate": draw(3, 4, 128, 128),
         "experts_up": draw(3, 4, 128, 128),
         "experts_down": draw(3, 4, 128, 128)}
    # expert 5 (the second of this share) is never chosen
    p["router_bias"] = p["router_bias"].at[5].set(-10.0)
    x = draw(64, 128) * 8
    valid = jnp.asarray(rng.random(64) < 0.8)
    want, stats = nn.moe_mlp_ragged(x, p, arch, valid=valid, with_stats=True,
                                    layer=jnp.int32(2))
    # (one program: an interpreted kernel's callbacks deadlock against
    # operations dispatched one by one behind it)
    with pltpu.force_tpu_interpret_mode():
        got, stats_k = jax.jit(lambda x, p, at: nn.moe_mlp_ragged(
            x, p, arch, valid=valid, kernel=True, with_stats=True,
            layer=at))(x, p, jnp.int32(2))
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    assert np.asarray(stats).tolist() == np.asarray(stats_k).tolist()
    calls, touched, here, routed = np.asarray(stats).tolist()
    assert calls == 4 and touched == 3 and routed == int(valid.sum()) * 4
    assert not np.asarray(got)[~np.asarray(valid)].any()
    # by a plain slice of the stack it is the same layer
    sliced = {k: (v[2] if k.startswith("experts") else v)
              for k, v in p.items()}
    plain = nn.moe_mlp_ragged(x, sliced, replace(arch), valid=valid)
    assert np.abs(np.asarray(plain - want)).max() < 1e-6


@pytest.mark.parametrize("tokens,top_k,shards", [
    (24, 8, 1),     # 192 pairs: more than a row tile and no multiple of it
    (96, 2, 1),     # 192 again, the other way round
    (3, 2, 1),      # 6 pairs: fewer than a sublane tile
    (1, 8, 1),
    (37, 4, 1),     # 148 pairs
    (24, 8, 4),     # a share: 192 pairs, all of them in one pass
])
def test_grouped_kernel_takes_any_number_of_pairs(tokens, top_k, shards):
    """The kernel wants a whole number of row tiles; the expert layer
    pads the sorted pairs to one (rows of no group, masked), so every
    batch depth and prefill bucket of every expert model runs: 24 slots
    of 8 experts a token, 96 of 2, a lone row."""
    from kaito_tpu.engine import nn
    from kaito_tpu.models.metadata import ModelArch

    arch = ModelArch(
        vocab_size=64, hidden_size=128, num_layers=1, num_heads=2,
        num_kv_heads=2, head_dim=16, intermediate_size=64, num_experts=16,
        num_experts_per_tok=top_k, moe_intermediate_size=128,
        expert_shards=shards, expert_shard=shards - 1)
    rng = np.random.default_rng(tokens * top_k + shards)
    held = 16 // shards

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32) / 8

    p = {"router": draw(128, 16) * 8, "experts_gate": draw(held, 128, 128),
         "experts_up": draw(held, 128, 128),
         "experts_down": draw(held, 128, 128)}
    x = draw(tokens, 128) * 8
    want = nn.moe_mlp_ragged(x, p, arch)
    with pltpu.force_tpu_interpret_mode():      # one program, as above
        got = jax.jit(lambda x, p: nn.moe_mlp_ragged(
            x, p, arch, kernel=True))(x, p)
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    assert np.abs(np.asarray(want)).max() > 1e-3


@pytest.mark.parametrize("tokens", [512, 640])
@pytest.mark.parametrize("kernel", [False, True])
def test_a_share_that_gets_most_pairs_drops_none(kernel, tokens):
    """A share's pass computes twice its even share of the pairs; when
    routing sends it more (here every token chooses held experts), the
    passes go on until every held pair is computed: the shares still
    add up to the whole layer.  Three passes or more, each through the
    compact un-sort (its kernel's ring of row copies starts cold in
    every pass, and from the second on ``y`` is read, not zeros)."""
    from dataclasses import replace

    from kaito_tpu.engine import nn
    from kaito_tpu.models.metadata import ModelArch

    whole = ModelArch(
        vocab_size=64, hidden_size=128, num_layers=1, num_heads=2,
        num_kv_heads=2, head_dim=16, intermediate_size=64, num_experts=32,
        num_experts_per_tok=4, moe_intermediate_size=128,
        router_scoring="sigmoid", router_bias=True)
    rng = np.random.default_rng(11)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32) / 8

    p = {"router": draw(128, 32) * 8, "router_bias": jnp.zeros((32,)),
         "experts_gate": draw(32, 128, 128), "experts_up": draw(32, 128, 128),
         "experts_down": draw(32, 128, 128)}
    # the bias sends three of every token's four pairs to experts 8..11
    p["router_bias"] = p["router_bias"].at[8:11].set(5.0)
    x = draw(tokens, 128) * 8
    uncut = nn.moe_mlp_ragged(x, p, whole)
    share = replace(whole, expert_shards=8, expert_shard=2)   # experts 8..11
    held = {k: (v[8:12] if k.startswith("experts") else v)
            for k, v in p.items()}
    rest = replace(whole, expert_shards=1)
    others = dict(p)
    for name in ("experts_gate", "experts_up", "experts_down"):
        others[name] = p[name].at[8:12].set(0.0)
    def layer(x, held):
        return nn.moe_mlp_ragged(x, held, share, kernel=kernel,
                                 with_stats=True)

    if kernel:
        with pltpu.force_tpu_interpret_mode():  # one program, as above
            y, stats = jax.jit(layer)(x, held)
    else:
        y, stats = layer(x, held)
    calls, touched, here, routed = np.asarray(stats).tolist()
    # 2,048 pairs, 512 a pass (2,560 and 640): this share holds three
    # quarters of them
    pairs, cap = tokens * 4, tokens
    assert routed == pairs and here >= 3 * pairs // 4 > 2 * pairs // 8
    assert -(-here // cap) >= 3
    total = y + nn.moe_mlp_ragged(x, others, rest)
    assert np.abs(np.asarray(total - uncut)).max() < 1e-4


@pytest.mark.parametrize("tokens,n_valid,fresh", [
    (512, 300, True),     # the mask ends inside the third tile of 128
    (512, None, False),   # a later pass: y is read, not zeros
    (96, 50, True),       # one tile of fewer than 128 tokens
    (4096, 2489, True),   # the longest bucket: 32 tiles
])
def test_combine_kernel_equals_the_loop(tokens, n_valid, fresh):
    """The compact un-sort's kernel in interpret mode against the loop
    over all eight slots, bit for bit, on the same rows: a token that
    holds all eight of its pairs, a tile of tokens that hold none (and
    so a ring of row copies that runs across it), rows of padding,
    fewer pairs in a tile than the ring has slots."""
    from kaito_tpu.engine import nn

    k, held, experts, E = 8, 16, 256, 256
    rng = np.random.default_rng(tokens)
    idx = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    idx[5] = np.arange(k)
    if tokens >= 256:
        idx[128:256] += held * (idx[128:256] < held)
    here = idx < held
    if n_valid is not None:
        here[n_valid:] = False
    order = np.argsort(np.where(here, idx, held).reshape(-1), kind="stable")
    place = np.empty(tokens * k, np.int32)
    place[order] = np.arange(tokens * k)
    cap = max(256, tokens)
    live = np.arange(cap) < here.sum()
    assert live.sum() < cap
    out = jnp.asarray(rng.standard_normal((cap, E)) * live[:, None],
                      jnp.float32)
    y = (jnp.zeros((tokens, E), jnp.float32) if fresh
         else _t(rng, tokens, E))
    want = np.asarray(nn._combine_slots(
        y, out, jnp.asarray(place.reshape(tokens, k)), cap))
    with pltpu.force_tpu_interpret_mode():      # one program, as above
        got = np.asarray(jax.jit(lambda y, out, rows, live: nn._combine_held(
            y, out, rows, live, k, jnp.bool_(fresh)))(
                y, out, jnp.asarray(order[:cap], jnp.int32),
                jnp.asarray(live)))
    assert np.array_equal(got, want)
    assert not np.array_equal(want, np.asarray(y))
    if tokens >= 256:
        assert np.array_equal(want[128:256], np.asarray(y)[128:256])


@pytest.mark.parametrize("stacked", [False, True])
def test_token_flat_writes_equal_the_five_dimensional_ones(stacked):
    """A token's [heads, dim] rows land at ``offset * heads`` of its
    page: prefill chunks (one starting mid-page, padding to the null
    page) and decode tokens (an inactive row to the null page), against
    the same writes into [.., page_size, heads, dim]."""
    from kaito_tpu.engine.kv_cache import (write_decode_tokens,
                                           write_prefill_tokens)

    rng = np.random.default_rng(5)
    L, P, ps, Hkv, D = 3, 12, 8, 4, 16
    pool = _t(rng, L, P, ps, Hkv, D) if stacked else _t(rng, P, ps, Hkv, D)
    layer = jnp.int32(1) if stacked else None
    flat = pool.reshape(pool.shape[:-3] + (ps * Hkv, D))
    table = jnp.asarray([[3, 5, 7, 0], [2, 9, 4, 11]], jnp.int32)
    new = _t(rng, 2, 13, Hkv, D)
    args = (table, jnp.asarray([0, 5], jnp.int32),
            jnp.asarray([13, 9], jnp.int32), ps)
    want = write_prefill_tokens(pool, new, *args, layer=layer)
    got = write_prefill_tokens(flat, new, *args, layer=layer)
    assert got.shape == flat.shape
    # (rows of the null page aside: padding lands there in any order)
    keep = np.ones(P, bool)
    keep[0] = False
    same = np.asarray(got).reshape(want.shape) == np.asarray(want)
    assert same[..., keep, :, :, :].all()
    assert not (np.asarray(want) == np.asarray(pool))[..., keep, :, :, :].all()
    one = _t(rng, 2, Hkv, D)
    args = (table, jnp.asarray([13, 30], jnp.int32), ps,
            jnp.asarray([True, False]))
    want = write_decode_tokens(pool, one, *args, layer=layer)
    got = write_decode_tokens(flat, one, *args, layer=layer)
    assert (np.asarray(got).reshape(want.shape) == np.asarray(want)).all()

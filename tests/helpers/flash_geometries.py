"""The attention geometries the benchmark's configurations send
through flash prefill, shared by the tile arithmetic's test
(tests/test_flash_prefill.py) and the test that hands the same tiles to
Mosaic (tests/test_two_kind_ops.py).  bf16 on the chip."""

# name: (query heads, KV heads, key lanes, value lanes, sink)
SERVED = {
    "phi-4-mini": (24, 8, 128, 128, False),
    "falcon-h1": (20, 4, 128, 128, False),
    # keys of 192 stored at 256 lanes
    "mimo-v2.5-full": (64, 4, 256, 128, False),
    "mimo-v2.5-window": (64, 8, 256, 128, True),
    # heads of 64: half a lane tile (a fresh chunk's q, k and v go to
    # the kernel as they are; the pools pair the heads up)
    "lfm2-8b-a1b": (32, 8, 64, 64, False),
    # as many KV heads as query heads: a group of one
    "olmo-hybrid-7b": (30, 30, 128, 128, False),
}

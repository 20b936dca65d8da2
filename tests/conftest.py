"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; all sharding/mesh tests run
on ``xla_force_host_platform_device_count=8`` CPU devices, per the
repo's test strategy (SURVEY.md §4's "fake topology backend" gap in the
reference).  Must run before the first ``import jax``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# Modules dominated by XLA compiles / engine loops (measured with
# --durations on a 1-core box; everything here costs >5 s per test).
# `make unit-test-fast` deselects them: the fast tier covers the
# operator/controller/RAG/API surface in well under a minute.
_SLOW_MODULES = {
    "test_async_dispatch", "test_chip_smoke",
    "test_chunked_prefill", "test_cp_serve", "test_decode_run_ahead",
    "test_dp_router", "test_dp_serve",
    "test_e2e_sim", "test_engine_core", "test_engine_model",
    "test_engine_tp", "test_engine_tp_features", "test_flash_prefill",
    "test_host_offload", "test_kind_e2e", "test_mla", "test_moe_ragged",
    "test_multihost",
    "test_pallas_model_path", "test_pallas_ops", "test_parallel_families",
    "test_pd_disaggregation", "test_pipeline_parallel", "test_pp_serve",
    "test_prefix_caching", "test_quant", "test_real_checkpoint",
    "test_ring_attention",
    "test_scheduler", "test_serve_with_adapter", "test_server",
    "test_streaming", "test_train_step", "test_trainer_mesh",
    "test_tuning", "test_weights", "test_parsers",
}


def pytest_collection_modifyitems(config, items):
    matched = set()
    collected = set()
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]   # pkg-proof
        collected.add(mod)
        if mod in _SLOW_MODULES:
            matched.add(mod)
            item.add_marker(pytest.mark.slow)
    # drift guard: on a full collection, every _SLOW_MODULES entry must
    # still name a real module (a rename would otherwise silently move
    # its tests into the fast tier); partial runs match a subset
    if len(collected) > len(_SLOW_MODULES):
        missing = _SLOW_MODULES - matched
        assert not missing, f"_SLOW_MODULES entries match no tests: {missing}"


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices()
    # >= 2 proves the forced virtual mesh is live; the default CI run
    # gets 8, `make overlap` runs its TP=2 smoke under an explicit 4
    assert len(devices) >= 2
    return devices

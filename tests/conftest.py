"""Test config: force an 8-device virtual CPU platform so every test —
including mesh/sharding/collective tests — runs without TPU hardware
(the role of the reference's fake_cpu_device / Gloo CPU process groups,
SURVEY.md §4).

The suite never runs on an accelerator: the platform is pinned to the CPU
here, before any array is built, whatever JAX_PLATFORMS says. The chip is
reached only by ``python chip_smoke.py`` through the builder's tool.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# XLA:CPU's fast matmul path is bf16-like; tests check f32 numerics
jax.config.update("jax_default_matmul_precision", "highest")
assert jax.default_backend() == "cpu", jax.default_backend()
assert len(jax.devices()) == 8, jax.devices()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu

    paddle_tpu.seed(1234)
    yield

# Persistent XLA compilation cache: the suite compiles hundreds of graphs
# (every model family x train/eval); caching them on disk makes re-runs
# dramatically faster without changing what gets exercised. Placed by the
# package's one helper: JAX_COMPILATION_CACHE_DIR if set, else
# <checkout>/.jax_cache.
from paddle_tpu.utils.build_cache import enable_compile_cache  # noqa: E402

enable_compile_cache(min_compile_time_secs=0.5)

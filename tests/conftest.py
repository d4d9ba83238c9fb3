"""Test harness: fake 8-device CPU mesh (SURVEY.md section 4).

Distributed-without-a-cluster via `--xla_force_host_platform_device_count=8`,
the standard JAX trick for exercising shard_map/psum collectives in CI with
no TPU. The platform is pinned through jax.config before any backend is
initialized, so the suite runs on the CPU whatever the machine holds."""

import os
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# The suite's compile cache (utils/compile_cache.py) lives OUTSIDE the
# checkout: one tier-1 run writes ~40 MB / 600 files, and the chip tool
# copies the checkout whole. JAX reads the variable at import, the package
# then uses that directory as it stands, and drill subprocesses inherit it.
# A machine (CI) that sets the variable itself keeps its own directory.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), f"rlr_fl_test_cache_{os.getuid()}"))

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# exact f32 matmuls for parity tests (TPU-style bf16 accumulation otherwise)
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture
def own_cache_root(monkeypatch):
    """For tests that count cold vs warm compiles in a cache root of their
    own (`compile_cache_dir=tmp_path`): drop the suite-wide variable, which
    takes precedence over the flag, and hand XLA's cache back afterwards."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
        compile_cache)
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV)
    suite_dir = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", suite_dir)
    compile_cache._reset_jax_cache_state()

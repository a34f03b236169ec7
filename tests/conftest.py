"""Test harness: force JAX onto 8 virtual CPU devices before jax imports.

Multi-chip hardware is unavailable in CI; every mesh/pipeline test runs on a
virtual 8-device CPU topology (SURVEY.md §4 test plan item (c)).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite runs on virtual CPU devices
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Persistent XLA compilation cache: the suite is compile-dominated;
# caching compiled executables across runs cuts repeat wall-clock by
# ~1/3 (a cold run still compiles everything once).  The suite keeps its
# entries (CPU programs for eight virtual devices) in a sub-directory of
# the checkout's cache so they stay apart from what the entry points
# write; an outer JAX_COMPILATION_CACHE_DIR wins, as everywhere
# (split_learning_tpu/platform.py).
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(os.path.dirname(__file__), "..",
                                   ".jax_cache", "tests"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
os.environ.setdefault("JAX_PERSISTENT_CACHE_ENABLE_XLA_CACHES", "all")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    import jax
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


def dense_attention(q, k, v, causal=False):
    """Reference full-softmax attention oracle shared by the flash /
    ring / ulysses parity tests ((B, S, H, D) layout, fp32 compute)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(d)
    if causal:
        n = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None, None],
                      s, -jnp.inf)
    p = jax.nn.softmax(s)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def qkv_batch(key, b=2, s=32, h=8, d=8):
    import jax
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, (b, s, h, d)) for k in ks)


def bench_reference(name: str):
    """A configuration's plain reference (``benchmarks/configs/<name>.py``)
    as a module: the oracle of the tests that tie the program to it."""
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

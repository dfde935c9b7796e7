"""Test configuration.

Multi-chip sharding is tested on a virtual 8-device CPU mesh (the analog of
the reference testing Spark code on a `local[*]` master,
`core/.../workflow/BaseTest.scala:28-141`): JAX must see the flags before
first initialization, hence the env mutation at import time.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Hermetic serve state: without this, any PredictionServer.stop() in a
# test persists its dispatch EWMAs + observed batch-size histogram to
# the default ~/.pio_store/serving/, and LATER tests (or later runs)
# restore that foreign history — narrowed warm buckets then recompile
# mid-test and trip the zero-recompile gates. Tests that exercise the
# persistence itself monkeypatch PIO_DISPATCH_STATE to a tmp path.
os.environ["PIO_DISPATCH_STATE"] = "off"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Authoritative even when something imported jax before the env
# mutation above, as long as no backend has been initialized yet.
jax.config.update("jax_platforms", "cpu")

# The program places a persistent compile cache (utils.device); the
# suite keeps compiling from nothing, as it always has, so that one
# run's executables never decide what the next run's tests see.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _bounded_jax_cache():
    """Clear jax's compiled-executable caches between test modules.

    The full suite compiles many hundreds of XLA:CPU programs in one
    process; with caches never dropped, late-suite compilations have
    been observed to segfault inside backend_compile (flaky, ~test 440,
    always mid-LLVM-compile — an upstream runtime fragility, not a
    repo bug: the same programs compile fine in fresh processes).
    Bounding the accumulated executable state keeps the suite's memory
    profile flat and has eliminated the crash in practice; the cost is
    per-module recompiles the modules already pay on first use."""
    yield
    jax.clear_caches()


@pytest.fixture()
def mem_registry():
    """A fresh all-in-memory storage registry, installed as process default."""
    from predictionio_tpu.data.storage import StorageRegistry, set_default

    reg = StorageRegistry({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "MEM",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    set_default(reg)
    yield reg
    set_default(None)

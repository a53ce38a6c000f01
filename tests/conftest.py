"""Test harness: run everything on an 8-device virtual CPU mesh.

Mirrors the reference's in-process distributed harness idea
(``tests/unit/common.py DistributedTest``: world_size-N workers on one host, no
real cluster) — on JAX this is one process with
``--xla_force_host_platform_device_count=8`` so shardings/collectives compile
and execute exactly as they would across 8 real chips.
"""

import os

# Must be set before jax is imported anywhere.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
# These programs are compiled far more often than they run, and no case reads
# what LLVM's optimisation passes decide: without them twelve compile-bound
# files' cases summed to 1 763 s where they had summed to 2 271 (PR 59).
os.environ["XLA_FLAGS"] = _flags + " --xla_backend_optimization_level=0"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

# ---- which tests run ------------------------------------------------------
# Every test runs in tier-1. `slow` is set by a decorator in the test's own
# file alone, with its reason beside it and a line in CHANGES.md (25 at most).
# When the run needs time, buy it from the dearest cases (`--durations=15`).


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: set by hand in the test's own file, with the reason;"
        " tier-1 runs -m 'not slow'")


# Tests are hermetic: nothing an earlier run left in <checkout>/.xla_cache is
# read back, and nothing is written there (the engine turns the persistent
# cache on by default — utils/compile_cache.py).
jax.config.update("jax_enable_compilation_cache", False)
jax.config.update("jax_threefry_partitionable", True)


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    yield
    from deepspeed_tpu.comm import mesh as mesh_mod

    mesh_mod._global_mesh = None


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Every compiled CPU program holds memory mappings of its own, and a
    worker that ran a few compile-heavy files in a row (``--dist
    loadfile``) reached the kernel's limit (``vm.max_map_count`` 65 530):
    the next compile's ``mmap`` failed and XLA segfaulted (PR 57: after
    ``test_mixed_step.py`` and ``test_solar_open2.py``, 65 284 mappings in
    ``test_head_rows.py``). A file's programs go when the file is done."""
    yield
    import gc

    jax.clear_caches()
    gc.collect()


@pytest.fixture
def one_device():
    """The process's mesh on a one-chip host, not this directory's eight
    virtual devices: what says a program is one device's where the trace
    has no mesh context (``MoELayer.grouped``; an engine built under it
    with ``tp_size`` 1 keeps it)."""
    from deepspeed_tpu.comm import mesh as mesh_mod

    return mesh_mod.init_mesh({"data": 1}, devices=jax.devices()[:1])


@pytest.fixture
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs

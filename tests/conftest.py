"""Test environment: two tiers.

Default tier (what tier-1 runs, no chip needed): JAX is pinned to the
CPU (``JAX_PLATFORMS=cpu`` / the config pin below) with 8 virtual
devices, so multi-chip sharding is exercised without hardware; Pallas
kernels run in interpret mode.  Tests marked ``@pytest.mark.tpu`` are
*skipped* (visibly) in this tier.  ``tests/test_chip_compile.py``
compiles the main-path kernels for a described v5e here — that catches
what the TPU compiler refuses, not what a chip computes.

Compiled tier (``UIGC_TEST_TPU=1 python -m pytest tests/ -q`` on the
machine with the chip, through the chip tool): the CPU pin is lifted,
only ``tpu``-marked tests run, and they compile the Pallas kernels for
real and check verdicts against the numpy oracle on the chip.  The
process holds the chip, so no test of this tier may start a child that
needs it.  It shares the persistent compile cache with
``chip_smoke.py`` (utils/platform.enable_compile_cache).
"""

import os

#: Compiled-on-TPU tier requested?
TPU_MODE = os.environ.get("UIGC_TEST_TPU", "") not in ("", "0")

_flags = os.environ.get("XLA_FLAGS", "")
if not TPU_MODE and "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

if TPU_MODE:
    from uigc_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
else:
    jax.config.update("jax_platforms", "cpu")


import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: compiled-on-TPU parity tier (run with UIGC_TEST_TPU=1 on a "
        "machine with a real chip; skipped in the default CPU tier)",
    )
    config.addinivalue_line(
        "markers",
        "slow: long randomized runs (chaos long-haul, determinism "
        "replays); excluded from the tier-1 gate via -m 'not slow'",
    )


def pytest_collection_modifyitems(config, items):
    if TPU_MODE:
        from uigc_tpu.utils.platform import is_tpu_platform

        if not is_tpu_platform(jax.devices()[0].platform):
            # An explicit opt-in with no chip must fail, not all-skip to
            # green — the tier's whole purpose is catching compile breaks.
            pytest.exit(
                "UIGC_TEST_TPU=1 but no TPU device is visible "
                f"(platform={jax.devices()[0].platform!r})",
                returncode=2,
            )
        skip_cpu = pytest.mark.skip(
            reason="UIGC_TEST_TPU=1: only the compiled-TPU tier runs"
        )
        for item in items:
            if "tpu" not in item.keywords:
                item.add_marker(skip_cpu)
    else:
        skip_tpu = pytest.mark.skip(
            reason="needs a real TPU: run UIGC_TEST_TPU=1 python -m pytest tests/"
        )
        for item in items:
            if "tpu" in item.keywords:
                item.add_marker(skip_tpu)


from uigc_tpu import native as _native  # noqa: E402

#: True when the C++ data plane could be built and loaded.
NATIVE_AVAILABLE = _native.is_available()

#: Shared parametrize value for the native shadow-graph backend: skips
#: (visibly) instead of silently dropping coverage when g++ is missing.
NATIVE_BACKEND = pytest.param(
    "native",
    marks=pytest.mark.skipif(not NATIVE_AVAILABLE, reason="no C++ toolchain"),
)

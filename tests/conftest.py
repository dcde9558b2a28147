"""Suite-wide configuration.

``REPRO_STRICT_DEPRECATIONS=1`` turns any ``DeprecationWarning`` *raised
from inside* ``repro.*`` modules into an error (the module field of a
warnings filter matches the warning's caller, so test files may still
exercise the deprecated ``slab_*`` shims directly — only ``src/`` callers
fail). CI runs the suite once in this mode so no internal module silently
keeps calling the deprecated tuple-threading API.
"""

import os

import pytest

# every program XLA compiles for the CPU keeps memory mappings of its code;
# a worker that has compiled a few thousand programs reaches the kernel's
# per-process limit (vm.max_map_count, 65,530 by default) and the next
# compile segfaults. Past this many mappings the fixture below drops JAX's
# compiled-program caches (only recompiles are lost). One test may add
# about 50,000 by itself (test_dispatch.py's set-algebra property compiles
# anew for each drawn set size), so a test must start well below the limit.
_MAPPINGS_BUDGET = 10_000


def pytest_configure(config):
    if os.environ.get("REPRO_STRICT_DEPRECATIONS"):
        config.addinivalue_line(
            "filterwarnings", r"error::DeprecationWarning:repro\.")


def _mappings() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:            # no procfs: nothing to measure
        return 0


@pytest.fixture(autouse=True)
def _shed_compiled_programs():
    yield
    if _mappings() > _MAPPINGS_BUDGET:
        import jax
        jax.clear_caches()

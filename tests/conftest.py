"""Test harness config: run JAX on a simulated 8-device CPU mesh.

Tests run on the CPU: multi-chip hardware is not available in CI, and
sharding correctness is tested on virtual CPU devices per SURVEY.md
section 4's closing note. The platform and the device count are pinned
through the environment, before jax is imported, so every child process
a test starts inherits the same pin.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import importlib.util  # noqa: E402

import pytest  # noqa: E402

# -- requires_pyelftools: differential ELF/DWARF comparisons -----------------
# A handful of tests cross-check the in-repo ELF/DWARF parsers against
# pyelftools; an environment without pyelftools cannot run the
# comparison at all. That is an ENVIRONMENT property, not a code
# failure, so those report as skips. The affected tests all carry
# "pyelftools" in their names.
HAVE_PYELFTOOLS = importlib.util.find_spec("elftools") is not None

requires_pyelftools = pytest.mark.skipif(
    not HAVE_PYELFTOOLS,
    reason="pyelftools is not installed (differential ELF/DWARF "
           "comparisons need it)")

_PYELFTOOLS_NAME_FRAGMENT = "pyelftools"


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.get_closest_marker("requires_pyelftools") is not None \
                or _PYELFTOOLS_NAME_FRAGMENT in item.name:
            item.add_marker(requires_pyelftools)

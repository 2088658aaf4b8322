"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

The sandbox that runs the suite has no accelerator, and the machine with
the chip gives it to one process at a time, so tests never take it:
shardings are validated on a virtual CPU mesh, and what only the chip's
compiler can say is asked of it without a chip (tests/
test_chip_compile.py).  The chip itself is exercised by chip_smoke.py.

The platform is set twice: the env var before any jax import, and
jax.config.update after it, which also holds where something imported
jax before this file ran.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

try:
    import jax  # noqa: E402  (after the env setup above, by design)
except ImportError:                          # no jax: the non-jax majority
    jax = None                               # of the suite still runs
else:
    jax.config.update("jax_platforms", "cpu")
    assert jax.devices()[0].platform == "cpu", (
        "tests must run on the virtual CPU mesh, not the real chip; got "
        f"{jax.devices()[0]}")
    # persistent XLA compilation cache, by the one rule every entry
    # point follows (ouroboros_tpu/compile_cache.py): the sharded-verify
    # kernels take minutes to compile cold, which would eat the tier-1
    # timeout budget on every run instead of only the first
    from ouroboros_tpu.compile_cache import cache_dir
    cache_dir()

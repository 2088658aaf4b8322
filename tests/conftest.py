"""Test configuration: force JAX onto a virtual 8-device CPU mesh, and
start the files that compile for minutes before the rest.

The sandbox that runs the suite has no accelerator, and the machine with
the chip gives it to one process at a time, so tests never take it:
shardings are validated on a virtual CPU mesh, and what only the chip's
compiler can say is asked of it without a chip (tests/
test_chip_compile.py).  The chip itself is exercised by chip_smoke.py.

The platform is set twice: the env var before any jax import, and
jax.config.update after it, which also holds where something imported
jax before this file ran.

Order: a file is one worker's under `--dist loadfile`, so a run is as
long as whatever starts last.  Files that hold a `device` test come
first (`device_files_first`), those that compile for minutes first of
all (`COMPILES_FOR_MINUTES`), and the thousand sub-second tests fill
the workers behind them.
"""
import os
import sys

import pytest

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
    # point follows (ouroboros_tpu/compile_cache.py): the window
    # programs take minutes to compile cold, which would eat the tier-1
    # timeout budget on every run instead of only the first, and a file
    # that starts later loads what an earlier one compiled
    from ouroboros_tpu.compile_cache import cache_dir
    cache_dir()


# The `device` files that take minutes on an empty compile cache, in the
# order they start, with the seconds each took in one cold run of the
# driver's command (six workers on 8 cores, 733 s in all, PR 43).  The
# workers take the first six at second 0: five files whose composites
# no other file builds, the longest first, and a short one.  The last
# four start as those end.  Three of them build nothing of their own
# and load from the persistent cache what the files before them
# compiled, if they start behind them and not beside them:
# `test_ed_tiles` the tile programs and the flat bucket of four other
# files (567 s beside them, 462 s behind `test_fresh_keys`; since PR 46
# `test_fresh_keys` builds both tile programs itself, 152 -> 390 s, and
# `test_ed_tiles` behind it takes 342 s),
# `test_longchain` `test_hardfork_sync`'s four programs (432 s beside
# it, 187 s behind it), and so does `test_delegrush` (PR 45: the same
# composite; 181-212 s on a cache that held it).  `test_mesh_batch` is
# the shortest of those that build their own.
# tests/test_suite_order.py fails on a name that is no `device` file
# under tests/.
COMPILES_FOR_MINUTES = (
    ("test_mixedfill.py", 606),
    ("test_sharded_replay.py", 536),
    ("test_served_replay.py", 534),
    ("test_hardfork_sync.py", 369),
    ("test_chip_compile.py", 345),
    ("test_fresh_keys.py", 390),
    ("test_ed_tiles.py", 462),
    ("test_mesh_batch.py", 364),
    ("test_longchain.py", 187),
    ("test_delegrush.py", 212),
)


# Holds a spinning thread's CPU seconds against the wall clock, which
# is true while the workers are not all compiling: it starts before
# they do, and is over in seconds.
READS_THE_CPU_CLOCK = ("test_observe.py",)


def device_files_first(items: list) -> list:
    """`items` with every file that holds a `device` test before every
    file that holds none, `COMPILES_FOR_MINUTES` first among those in
    its own order, and `READS_THE_CPU_CLOCK` before them all; files
    otherwise in the order they came, and each file's items as they
    were."""
    marked = {it.path for it in items if it.get_closest_marker("device")}
    rank = {name: i for i, (name, _secs) in enumerate(COMPILES_FOR_MINUTES)}
    return sorted(items, key=lambda it: (
        it.path.name not in READS_THE_CPU_CLOCK,
        it.path not in marked,
        rank.get(it.path.name, len(rank))))


def _xdist_is_here(config) -> bool:
    return hasattr(config.option, "loadscopereorder")


@pytest.hookimpl(trylast=True)     # after `-m` has deselected
def pytest_collection_modifyitems(config, items):
    # the same in every xdist worker, which compares the collections;
    # one process alone (`-p no:xdist`) gains nothing from an order, and
    # cut by a time limit it would count the long files only
    if _xdist_is_here(config):
        items[:] = device_files_first(items)


def pytest_configure(config):
    # pytest-xdist (3.8.0 here) hands the files of `--dist loadfile` out
    # by number of tests, descending, unless told not to: the files that
    # compile for minutes hold few tests and would start last, beside
    # idle workers.  Without xdist (`-p no:xdist`) there is no such
    # option and nothing to do.
    if _xdist_is_here(config):
        config.option.loadscopereorder = False

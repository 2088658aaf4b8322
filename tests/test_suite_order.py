"""The order the suite starts its files in (tests/conftest.py): files
that hold a `device` test first, the ones that compile for minutes
first among them.  No device work here: the sort is run on stand-in
items, the tuple is held against the files under tests/, and the live
session is asked whether the hook and the xdist setting took."""
import pathlib
from types import SimpleNamespace

import pytest

import conftest as suite

TESTS = pathlib.Path(__file__).parent
LONG = [name for name, _secs in suite.COMPILES_FOR_MINUTES]


class _Item:
    def __init__(self, file: str, n: int, device: bool = False):
        self.path = TESTS / file
        self.n = n
        self.device = device

    def get_closest_marker(self, name: str):
        return object() if name == "device" and self.device else None

    def __repr__(self):
        return f"{self.path.name}::{self.n}"


CLOCK = suite.READS_THE_CPU_CLOCK[0]


def _interleaved() -> list:
    """Two plain files, a file with ONE marked test among plain ones,
    the tuple's last and first file and the file that reads the CPU
    clock, their items dealt in turn."""
    files = [("test_a.py", [False] * 3),
             ("test_one_marked.py", [False, True, False]),
             (LONG[-1], [True] * 3),
             ("test_z.py", [False] * 3),
             (CLOCK, [False] * 3),
             (LONG[0], [True] * 3)]
    return [_Item(file, n, marks[n]) for n in range(3)
            for file, marks in files]


@pytest.fixture(scope="module")
def ordered():
    return suite.device_files_first(_interleaved())


def _files(items) -> list:
    return list(dict.fromkeys(it.path.name for it in items))


def test_every_item_of_a_file_with_a_device_test_comes_first(ordered):
    assert _files(ordered[3:12]) == [LONG[0], LONG[-1],
                                     "test_one_marked.py"]
    assert not any(it.device for it in ordered[12:])


def test_the_file_that_reads_the_cpu_clock_starts_before_the_load(ordered):
    assert _files(ordered[:3]) == [CLOCK]
    assert all((TESTS / name).is_file()
               for name in suite.READS_THE_CPU_CLOCK)


def test_the_tuples_files_lead_in_the_tuples_order(ordered):
    assert _files(ordered)[1:3] == [LONG[0], LONG[-1]]
    every = suite.device_files_first(
        [_Item(name, 0, True) for name in reversed(LONG)]
        + [_Item("test_b.py", 0, True)])
    assert _files(every) == LONG + ["test_b.py"]


def test_files_outside_the_tuple_keep_the_order_they_came_in(ordered):
    assert _files(ordered)[3:] == ["test_one_marked.py", "test_a.py",
                                   "test_z.py"]


def test_the_order_within_each_file_is_untouched(ordered):
    assert len(ordered) == 18
    for file in _files(ordered):
        assert [it.n for it in ordered if it.path.name == file] == [0, 1, 2]


@pytest.mark.parametrize("name", LONG)
def test_a_name_in_the_tuple_is_a_device_file_under_tests(name):
    """A file renamed, deleted or no longer marked fails here, not by
    starting last again."""
    assert "pytest.mark.device" in (TESTS / name).read_text()


def test_the_tuple_names_each_file_once_and_only_minutes():
    assert min(secs for _name, secs in suite.COMPILES_FOR_MINUTES) > 120
    assert len(set(LONG)) == len(LONG)


@pytest.mark.parametrize("has_option", [False, True])
def test_configure_turns_off_only_an_option_that_exists(has_option):
    option = SimpleNamespace(**({"loadscopereorder": True}
                                if has_option else {}))
    suite.pytest_configure(SimpleNamespace(option=option))
    assert vars(option) == ({"loadscopereorder": False}
                            if has_option else {})


def test_this_session_runs_in_that_order(request):
    """The hook is registered under the name pytest calls, and xdist, if
    it is here, keeps the collection's order; without it (`-p
    no:xdist`) the collection is left as it came."""
    items = request.session.items
    if not hasattr(request.config.option, "loadscopereorder"):
        pytest.skip("no xdist: one process, no order to keep")
    assert items == suite.device_files_first(items)
    assert request.config.option.loadscopereorder is False


@pytest.mark.parametrize("has_option", [False, True])
def test_the_hook_sorts_only_where_xdist_is(has_option):
    option = SimpleNamespace(**({"loadscopereorder": False}
                                if has_option else {}))
    items = _interleaved()
    came = list(items)
    suite.pytest_collection_modifyitems(SimpleNamespace(option=option),
                                        items)
    assert items == (suite.device_files_first(came) if has_option
                     else came)

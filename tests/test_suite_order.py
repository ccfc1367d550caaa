"""The order in which a run hands out its files (``tests/conftest.py``)."""

import os

import conftest


def test_worlds_first_then_files_by_count_each_in_its_own_order():
    ids = [
        "tests/test_b.py::test_1",
        "tests/test_elastic_spmd_e2e.py::test_world",
        "tests/test_c.py::test_z",
        "tests/test_a.py::test_2[x::y]",
        "tests/test_c.py::test_a",
        "tests/test_goodput_e2e.py::test_world",
        "tests/test_a.py::test_1",
        "tests/test_c.py::test_m",
        "tests/test_one.py::test_only",
        "tests/test_b.py::test_0",
    ]
    rank = conftest.file_order(ids)
    assert sorted(rank, key=rank.get) == [
        "tests/test_goodput_e2e.py",       # WORLDS_FIRST, in its order
        "tests/test_elastic_spmd_e2e.py",
        "tests/test_c.py",                 # three tests
        "tests/test_a.py",                 # two, ties by name
        "tests/test_b.py",
        "tests/test_one.py",               # one test and no world: last
    ]
    # what the hook does with it: a stable sort keyed by file only
    ordered = sorted(ids, key=lambda n: rank[n.split("::", 1)[0]])
    assert ordered == [
        "tests/test_goodput_e2e.py::test_world",
        "tests/test_elastic_spmd_e2e.py::test_world",
        "tests/test_c.py::test_z",
        "tests/test_c.py::test_a",
        "tests/test_c.py::test_m",
        "tests/test_a.py::test_2[x::y]",
        "tests/test_a.py::test_1",
        "tests/test_b.py::test_1",
        "tests/test_b.py::test_0",
        "tests/test_one.py::test_only",
    ]
    # a worker that collected them in another order ranks them alike
    assert conftest.file_order(ids[::-1]) == rank


def test_every_world_named_first_is_a_file_of_the_suite():
    here = os.path.dirname(os.path.abspath(__file__))
    assert len(set(conftest.WORLDS_FIRST)) == len(conftest.WORLDS_FIRST)
    for name in conftest.WORLDS_FIRST:
        assert os.path.isfile(os.path.join(here, name)), name

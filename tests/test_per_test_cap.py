"""What ``tests/conftest.py`` does to a run besides its fixtures: the per-test
cap (``_PER_TEST_TIMEOUT_S``), which works without ``pytest-timeout`` (the
armed body itself), and the order the files are collected in, read from the
recorded costs of the last whole run (``tests/junit_costs.txt``)."""

import time

import pytest

from conftest import capped, longest_first, recorded_order


def test_a_body_over_its_cap_fails_with_the_caps_message(capfd):
    began = time.monotonic()
    with pytest.raises(pytest.fail.Exception, match="per-test cap of 0.2 s"):
        with capped(0.2):
            time.sleep(30)  # what a test stuck on a back-off does
    assert time.monotonic() - began < 10
    assert "Current thread" in capfd.readouterr().err  # the stacks
    with capped(0.2):  # a body inside its cap leaves no timer behind
        pass
    time.sleep(0.3)


# -- the order the files are collected in (``conftest.longest_first``) ---------
def test_a_file_the_record_does_not_name_is_collected_first(tmp_path):
    record = tmp_path / "junit_costs.txt"
    record.write_text(
        "     700 cpu-s, 3 files\n"
        "   400.0     9  chipbench_tests/test_long.py\n"
        "   250.0    70  test_middling.py\n"
        "    50.0     5  test_short.py\n")
    recorded = recorded_order(str(record))
    assert recorded == [
        "chipbench_tests/test_long.py", "test_middling.py", "test_short.py"]
    collected = ["test_a_new_one.py", "test_middling.py", "test_short.py",
                 "chipbench_tests/test_long.py", "test_z_another_new_one.py"]
    assert longest_first(collected, recorded) == [
        "test_a_new_one.py", "test_z_another_new_one.py",
        "chipbench_tests/test_long.py", "test_middling.py", "test_short.py"]


@pytest.mark.parametrize("record", ["missing", "unreadable"])
def test_no_record_means_collection_order(tmp_path, record):
    path = tmp_path / "junit_costs.txt"
    if record == "unreadable":
        path.write_bytes(b"\xff\xfe\x00 not text \xff")
    assert recorded_order(str(path)) is None
    collected = ["test_b.py", "test_a.py", "chipbench_tests/test_c.py"]
    assert longest_first(collected, None) == collected


def test_only_a_whole_run_refreshes_the_record(tmp_path):
    """``tests/junit_costs.py`` writes the record where the junit file leaves
    out no file the record names and the tree still has."""
    from junit_costs import left_out

    record = tmp_path / "junit_costs.txt"
    assert left_out(record, {"test_a.py": (1.0, 1)}) == []  # no record yet
    record.write_text(
        "     300 cpu-s, 2 files\n"
        "   200.0     9  test_a.py\n"
        "   100.0     5  test_merged_away.py\n")
    (tmp_path / "test_a.py").touch()
    assert left_out(record, {"test_b.py": (1.0, 1)}) == ["test_a.py"]
    assert left_out(record, {"test_a.py": (1.0, 1), "test_b.py": (1.0, 1)}) == []


def test_the_committed_record_names_files_that_exist():
    """``tests/junit_costs.txt`` as committed: read as ``conftest`` reads it,
    every file it names is there (a renamed file would run first, which is
    safe, and its old line would be dead weight)."""
    import os

    recorded = recorded_order()
    assert recorded, "tests/junit_costs.txt is missing or names no file"
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    assert [name for name in recorded
            if not os.path.isfile(os.path.join(tests_dir, name))] == []

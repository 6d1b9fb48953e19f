"""The per-test cap of ``tests/conftest.py`` (``_PER_TEST_TIMEOUT_S``), which
works without ``pytest-timeout``: the armed body itself."""

import time

import pytest

from conftest import capped


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

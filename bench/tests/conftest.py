"""Fixtures of the benchmark's own tests (helpers in `benchtest.py`)."""
import pytest

import benchtest


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    import harness
    return harness.Bench(benchtest.tiny_copy(tmp_path_factory.mktemp("tiny")))


@pytest.fixture(scope="module")
def runner():
    return benchtest.load_run()

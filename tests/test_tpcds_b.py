"""The other half of the TPC-DS suite (test_tpcds.py): a module of its own,
so that `--dist loadfile` runs the two halves on two workers."""

import pytest

from test_tpcds import (NAMES, _bound_xla_within_module,  # noqa: F401
                        check_query, suites)


@pytest.mark.parametrize("name", NAMES[1::2])
def test_query_matches_cpu_oracle(name, suites):
    check_query(name, suites)

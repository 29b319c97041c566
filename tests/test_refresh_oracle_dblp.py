"""Refresh against a fresh extract and the numpy oracle: the DBLP model
(co-role joins; its JS-MV view maintained in place).

The cases and their checks are in ``refresh_oracle.py``.
"""
import pytest

from refresh_oracle import KINDS, check_refresh

# compile-heavy: CI deselects these with -m "not slow"
pytestmark = pytest.mark.slow


@pytest.mark.parametrize("kind", KINDS)
def test_refresh_matches_a_fresh_extract_and_the_oracle(kind):
    check_refresh("dblp", kind)

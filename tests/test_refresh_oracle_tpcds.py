"""Refresh against a fresh extract and the numpy oracle: the TPC-DS
models (fraud on the store channel, the combined model).

The cases and their checks are in ``refresh_oracle.py``.
"""
import pytest

from refresh_oracle import KINDS, check_refresh


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name", ["fraud_store", "combined"])
def test_refresh_matches_a_fresh_extract_and_the_oracle(name, kind):
    check_refresh(name, kind)

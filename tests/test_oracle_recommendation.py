"""Extraction against the numpy oracle: the recommendation model on the
store and catalog channels, whose Co-pur co-role joins through hot items
are the largest outputs (the web channel's cases are in ``test_oracle.py``).

The cases and their checks are in ``extraction_oracle.py``.
"""
import pytest

from extraction_oracle import METHODS, check_extraction


@pytest.mark.parametrize("compiled", [True, False],
                         ids=["compiled", "eager"])
@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("name", ["recommendation_store",
                                  "recommendation_catalog"])
def test_extraction_matches_the_numpy_oracle(name, method, compiled):
    check_extraction(name, method, compiled)

"""Extraction against the numpy oracle: the fraud model on each channel,
the recommendation model on the web channel, the combined model, DBLP and
IMDB.

The cases and their checks are in ``extraction_oracle.py``.
"""
import pytest

from extraction_oracle import METHODS, check_extraction


@pytest.mark.parametrize("compiled", [True, False],
                         ids=["compiled", "eager"])
@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("name", ["fraud_store", "fraud_catalog", "fraud_web",
                                  "recommendation_web", "combined", "dblp",
                                  "imdb"])
def test_extraction_matches_the_numpy_oracle(name, method, compiled):
    check_extraction(name, method, compiled)

"""The paper's seven headline results, reproduced on the 10-app bank.

One engine builds the whole bank; each claim of ``repro.paper.CLAIMS``
is one case, held to the threshold the claim fixes. A change that moves
the reproduction's results away from the paper fails here.
"""

import pytest

from repro.experiments import ExperimentEngine
from repro.paper import CLAIMS, FIGURES


@pytest.fixture(scope="module")
def engine():
    return ExperimentEngine()


@pytest.fixture(scope="module")
def figures(engine):
    """Each figure a claim reads, computed once for the module."""
    return {name: FIGURES[name](engine)
            for name in dict.fromkeys(c.figure for c in CLAIMS)}


@pytest.mark.parametrize("claim", CLAIMS, ids=lambda c: c.name)
def test_paper_claim_holds(claim, figures):
    r = figures[claim.figure]
    assert claim.holds(r), f"{claim.reads(r)} (paper: {claim.paper})"

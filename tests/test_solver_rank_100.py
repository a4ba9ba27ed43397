"""The Pallas solver's three entries at rank 100, the rank of the cell
`netflix-als-f100.retrain`: `test_cholesky_bits.py`'s comparison to the bit
with the full-tile body and `test_solver_ranks.py`'s with numpy float64, on
the same shapes, so that this process compiles each entry's interpreted
program once for both.  A file a rank (the other is
`test_solver_rank_128.py`): the three cases to the bit take the CPU compiler
three to five minutes, and the test runner hands out whole files, those of few
tests last."""

import pytest

from test_cholesky_bits import (  # noqa: F401  (the fixture is used by name)
    ENTRIES, assert_x_to_the_bit, full_tile_solver)
from test_solver_ranks import assert_matches_numpy


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("k", [100])
def test_trailing_block_elimination_is_the_full_tile_to_the_bit(
        rng, full_tile_solver, k, entry):
    assert_x_to_the_bit(rng, full_tile_solver, k, entry)


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("k", [100])
def test_ranks_above_64_match_numpy(rng, k, entry):
    assert_matches_numpy(rng, k, entry)

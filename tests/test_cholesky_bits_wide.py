"""`test_cholesky_bits.py`'s comparison at the cell `netflix-als-f100.retrain`'s
rank and at the top of the solver's range: a file of its own, because these six
cases take the CPU compiler as long as that file's twelve."""

import pytest

from test_cholesky_bits import (  # noqa: F401  (the fixture is used by name)
    ENTRIES, assert_x_to_the_bit, full_tile_solver)


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("k", [100, 128])
def test_trailing_block_elimination_is_the_full_tile_to_the_bit(
        rng, full_tile_solver, k, entry):
    assert_x_to_the_bit(rng, full_tile_solver, k, entry)

"""Traffic is deterministic in --seed, and every seed gets the same
amount of work."""
import benchpaths  # noqa: F401
import numpy as np
import pytest

from benchlib import traffic as gen

SEEDS = (0, 7, 2**31 + 11, 2**40 + 3, -5)


@pytest.mark.parametrize("seed", SEEDS)
def test_prompts_are_deterministic_in_the_seed(seed):
    p = gen.prompt(seed, 3, 5, 1024, 50304)
    assert p.shape == (1, 1024) and p.dtype == np.int32
    assert np.array_equal(p, gen.prompt(seed, 3, 5, 1024, 50304))
    assert p.min() >= 0 and p.max() < 50304


def test_prompts_and_pools():
    p = gen.prompt(2**31 + 1, 3, 5, 1024, 50304)
    assert not np.array_equal(p, gen.prompt(2**31 + 1, 3, 6, 1024, 50304))
    assert not np.array_equal(p, gen.prompt(2**31 + 1, 4, 5, 1024, 50304))
    assert not np.array_equal(p, gen.prompt(2**31 + 2, 3, 5, 1024, 50304))


def test_generators_take_any_whole_number():
    for keys in [(0,), (-1, 2), (2**70, 3), (2**31 + 5, 20, 7, 1)]:
        a = gen.rng(*keys).integers(0, 1 << 30, 4)
        assert np.array_equal(a, gen.rng(*keys).integers(0, 1 << 30, 4))

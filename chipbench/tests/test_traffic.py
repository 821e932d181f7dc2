"""The generator is a pure function of the seed."""
import numpy as np

from chipbench import generate


def test_designs_same_seed_same_rows():
    a = generate.designs(generate.rng_for(2**31 + 7, 10, 0), 74, 500)
    b = generate.designs(generate.rng_for(2**31 + 7, 10, 0), 74, 500)
    c = generate.designs(generate.rng_for(2**31 + 8, 10, 0), 74, 500)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])


def test_designs_are_the_programs_sample_mixed():
    from repro.core.dse.samplers import sample_mixed
    for seed in (0, 5, 2**33 + 1):
        ours = generate.designs(np.random.default_rng(seed), 155, 300)
        theirs = sample_mixed(np.random.default_rng(seed), 155, 300)
        assert all(np.array_equal(x, np.asarray(y))
                   for x, y in zip(ours, theirs.to_numpy()))

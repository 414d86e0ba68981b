"""The training feed: the same (seed, index) gives the same example, two
indices differ."""
import numpy as np

import traffic_gen


def test_train_examples_differ_and_repeat():
    shape = {"crop": 8, "msa_rows": 2, "atoms_per_residue": 3}
    a = traffic_gen.train_batch(shape, 2**31 + 3, 0)
    b = traffic_gen.train_batch(shape, 2**31 + 3, 1)
    again = traffic_gen.train_batch(shape, 2**31 + 3, 0)
    assert a["seq"].shape == (1, 8) and a["coords"].shape == (1, 8, 14, 3)
    assert a["msa"].shape == (1, 2, 8) and a["mask"].all() and a["msa_mask"].all()
    assert not np.array_equal(a["msa"], b["msa"])
    assert not np.array_equal(a["coords"], b["coords"])
    assert all(np.array_equal(a[k], again[k]) for k in a)
    other = traffic_gen.train_batch(shape, 4, 0)
    assert not np.array_equal(a["seq"], other["seq"])

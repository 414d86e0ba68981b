"""The arithmetic of a training cell's comparison."""
import compare


def test_leaf_gaps_floor_small_leaves_at_the_median():
    ref = [1.0, 0.1, 0.001, 0.0]      # median of the used leaves: 0.1
    prog = [1.1, 0.1, 0.002, 0.0]
    gaps = compare.leaf_gaps(prog, ref)
    assert abs(gaps[0] - 0.1) < 1e-12
    assert gaps[1] == 0.0
    assert abs(gaps[2] - 0.01) < 1e-12   # 0.001 against the median 0.1, not itself
    assert gaps[3] == 0.0                # unused on both sides


def test_a_leaf_the_program_fills_where_the_reference_has_none_is_infinite():
    assert compare.leaf_gaps([0.5], [0.0]) == [float("inf")]


def test_larger_half_and_moved_leaves():
    ref = [4.0, 2.0, 1.0, 1e-5, 0.0]  # used leaves 4, 2, 1, 1e-5: median 1.5
    assert compare.larger_half(ref) == [True, True, False, False, False]
    assert compare.moved_leaves(ref) == [True, True, True, False, False]
    gap, where = compare.worst_leaf_gap([4.0, 2.2, 3.0, 0.0, 0.0], ref,
                                        compare.larger_half(ref))
    assert where == 1 and abs(gap - 0.1) < 1e-12

"""The work count against hand counts, and against the program's own
interaction lists on a random cloud."""
import numpy as np
import pytest

from bench.reference import worktree


def _blob(center, n, seed):
    rng = np.random.default_rng(seed)
    return np.asarray(center) + rng.uniform(-0.5, 0.5, (n, 3))


def test_two_leaves_by_hand():
    # Two leaves of 20 far apart: each batch takes its own leaf directly
    # (20 x 20) and the other as a cluster of (1+1)^3 = 8 points.
    x = np.concatenate([_blob([0, 0, 0], 20, 1), _blob([10, 0, 0], 20, 2)])
    w = worktree.count_work(x, x, theta=0.5, degree=1, leaf_size=20,
                            batch_size=20)
    assert (w.approx_pairs, w.direct_pairs, w.clusters) == (2, 2, 2)
    assert w.approx_evals == 2 * 20 * 8
    assert w.direct_evals == 2 * 20 * 20
    assert w.modified_charge_terms == 2 * 20 * 8
    assert w.approx_bytes == 4 * 4 * 8 * 2
    assert w.direct_bytes == 4 * 4 * 40
    assert w.target_bytes == 4 * 4 * 40
    assert w.modified_charge_bytes == 4 * 2 * (4 * 20 + 8)


def test_small_cluster_goes_direct_leaf_by_leaf():
    # The far group holds 40 particles in two leaves of 20; at degree 3
    # ((3+1)^3 = 64 >= 40) it fails the MAC on size only, so each near
    # batch takes its two leaves directly, and the far batches see the
    # near leaf (20 < 64) directly too. No approximation at all.
    far = np.concatenate([_blob([10, 0, 0], 20, 3),
                          _blob([10, 3, 0], 20, 4)])
    x = np.concatenate([_blob([0, 0, 0], 20, 5), far])
    w = worktree.count_work(x, x, theta=0.5, degree=3, leaf_size=20,
                            batch_size=20)
    assert w.approx_pairs == 0 and w.clusters == 0
    assert w.direct_pairs == 9
    assert w.direct_evals == 9 * 20 * 20


@pytest.mark.parametrize("theta,degree,leaf", [(0.8, 4, 256), (0.5, 2, 64)])
def test_counts_match_the_program_lists(theta, degree, leaf):
    from repro.core.interaction import build_interaction_lists
    from repro.core.tree import build_batches, build_tree

    x = np.random.default_rng(0).uniform(-1, 1, (6000, 3))
    tree = build_tree(x, leaf)
    batches = build_batches(x, leaf)
    lists = build_interaction_lists(tree, batches, theta, degree)
    k3 = (degree + 1) ** 3
    w = worktree.count_work(x, x, theta=theta, degree=degree,
                            leaf_size=leaf, batch_size=leaf)
    assert w.approx_pairs == int(lists.approx_counts.sum())
    assert w.direct_pairs == int(lists.direct_counts.sum())
    assert w.approx_evals == int((lists.approx_counts
                                  * batches.count).sum()) * k3
    leaf_count = tree.count[tree.leaf_ids]
    direct = lists.direct
    src = np.where(direct >= 0, leaf_count[np.maximum(direct, 0)], 0)
    assert w.direct_evals == int((src.sum(1) * batches.count).sum())


def test_flops_per_evaluation():
    w = worktree.Work(10, 20, 30, 0, 0, 0, 0, 0, 0, 0)
    assert worktree.flops(w, "coulomb", 2) == 11 * 30 + (4 + 1.0) * 30

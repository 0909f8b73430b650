"""Tree-pair comparison helpers shared by the identity fuzz suites.

`assert_trees_match_mod_ties` is the tie-proving comparator the streamed
and cross-platform identity contracts route through; because a false
NEGATIVE here would silently void those contracts, the comparator has its
own adversarial suite (tests/test_tie_comparator.py) proving it rejects
real divergences — flipped splits at non-boundary gains, perturbed
leaves, split/leaf flips away from the min_split_gain floor, swapped
children, and root-cause floods.
"""

import numpy as np


def assert_trees_match_mod_ties(full, streamed, min_split_gain,
                                leaf_rtol=1e-3, leaf_atol=2e-5,
                                leaf_contrib_atol=1e-3,
                                cascade_gain_atol=2e-3,
                                cascade_leaf_scale=5.0,
                                max_root_causes=None):
    """Bitwise tree equality, except provable f32-order boundary ties.

    Streamed training accumulates per-chunk histogram partials on host;
    the in-memory path sums once on device. The summation TREES differ,
    so where a decision's competing quantities land within ~1 bfloat16
    ULP of each other the rounded comparison can legitimately go either
    way — the same seam as cross-platform (MXU order) and cross-process
    (gloo order), measured by the round-4 fuzz campaigns at ~1 root-cause
    node per 160k (seed 197: candidate gains 0.00102997 vs 0.00102234).

    The checkable contract, enforced per tree by walking the heap from
    the root and PRUNING each divergent subtree:
      - every node whose ancestors all matched must either match
        bitwise in its decision (feature, threshold_bin, is_leaf; leaf
        values to float tolerance, gains to bf16 tolerance), or be a
        PROVABLE tie: competing gains within 2 bf16 ULPs (cross-feature
        or cross-bin flip), or a gain within 2 ULPs of min_split_gain
        (split-vs-leaf flip at the floor);
      - descendants of a flipped decision legitimately diverge and are
        excluded (different rows reach them);
      - root causes stay rare (they are measured to be). The default
        rarity cap is calibrated for the fuzz suites' scales;
        million-row witnesses pass explicit `max_root_causes`
        (boundary-tie incidence grows with row count — the config-3
        witness on the earlier host, round 5, measured the rates).

    Leaf values pass when EITHER bound holds: the relative/absolute
    allclose (leaf_rtol/leaf_atol), or a pred-CONTRIBUTION bound
    lr * |dv| <= leaf_contrib_atol. The second models legitimate drift
    cascade, found by the round-5 sampling campaign (case 1063): with
    reg_lambda=0 a near-empty leaf carries |value| ~ 1/min_child_weight
    (~1600 there), so an in-contract RELATIVE drift of 2e-4 is ~0.33
    absolute; times lr*sigmoid' it shifts the next round's gradients
    and moves downstream leaves by absolute amounts that blow past any
    fixed RELATIVE tolerance exactly where |v| is small (measured:
    3.5e-3 on a 0.79 leaf — 4.4e-3 relative, but only 3.5e-4 of pred
    contribution). What propagates — and what a real leaf-aggregation
    bug inflates — is lr * |dv|; the adversarial suite's perturbations
    (lr * 0.1 = 1e-2) stay firmly rejected.

    Gains get the cascade treatment too (round-5 campaign case 10030):
    once a root cause is ACCEPTED in round r0, every later round trains
    on legitimately-diverged predictions (the flipped node routes real
    rows differently), so matched decisions there carry small ABSOLUTE
    gain drift that the relative bf16 window rejects exactly where
    gains are small (measured: |dg| = 1.5e-4 on a 0.004 gain, 3.9%
    relative, trees 0-6 bit-identical and the tree-7 flip a proven
    tie). Post-root-cause rounds therefore accept EITHER the relative
    TIE or |dg| <= cascade_gain_atol (2e-3 — 13x the measured cascade,
    25x under the adversarial suite's 5e-2 corruption, which also has
    no root cause and so never activates the allowance). Rounds at or
    before the first root cause keep the strict window.

    The LEAF bounds scale by cascade_leaf_scale (5x) in post-root-cause
    rounds for the same reason: different real rows flow through later
    trees once a flip is accepted, and case 10030's tree-8 leaves
    measured dv=5.6e-3 on |v|=3.85 — relative 1.47e-3 and contribution
    1.69e-3, each ~1.5x past the tight bounds. At 5x, the adversarial
    leaf perturbation (relative 5e-2, contribution 1e-2) stays
    rejected with >= 2x margin — and scoped to cascade rounds only."""
    TIE = 2 ** -6                     # 2 bf16 ULPs, relative
    T, N = full.feature.shape
    n_root_causes = 0
    first_rc_round = None
    trees_per_round = (full.n_classes if full.loss == "softmax" else 1)
    for t in range(T):
        cascade = (first_rc_round is not None
                   and t // trees_per_round > first_rc_round)

        def gain_ok(ga, gb):
            d = abs(ga - gb)
            return (d <= TIE * max(abs(ga), abs(gb), 1e-12)
                    or (cascade and d <= cascade_gain_atol))

        queue = [0]
        while queue:
            s_ = queue.pop()
            fa, fb = int(full.feature[t, s_]), int(streamed.feature[t, s_])
            ba = int(full.threshold_bin[t, s_])
            bb = int(streamed.threshold_bin[t, s_])
            la = bool(full.is_leaf[t, s_])
            lb = bool(streamed.is_leaf[t, s_])
            ga = float(full.split_gain[t, s_])
            gb = float(streamed.split_gain[t, s_])
            if (fa, ba, la) == (fb, bb, lb):
                va = float(full.leaf_value[t, s_])
                vb = float(streamed.leaf_value[t, s_])
                dv = abs(va - vb)
                ls = cascade_leaf_scale if cascade else 1.0
                assert (dv <= ls * (leaf_atol + leaf_rtol * abs(vb))
                        or dv * full.learning_rate
                        <= ls * leaf_contrib_atol), \
                    ("leaf value", t, s_, va, vb)
                assert gain_ok(ga, gb), (t, s_, ga, gb)
                if not la and 2 * s_ + 2 < N:
                    queue += [2 * s_ + 1, 2 * s_ + 2]
                continue
            # Divergent decision with matching ancestors: a root cause.
            n_root_causes += 1
            if first_rc_round is None:
                first_rc_round = t // trees_per_round
            if la != lb:
                # split-vs-leaf flip: the split side's gain must sit at
                # the min_split_gain floor (leaves record gain 0).
                g_split = gb if la else ga
                assert (abs(g_split - min_split_gain) <= TIE * max(
                            g_split, min_split_gain)
                        or (cascade and abs(g_split - min_split_gain)
                            <= cascade_gain_atol)), \
                    (t, s_, g_split, min_split_gain)
            else:
                # both split, different (feature, bin): candidate tie.
                assert gain_ok(ga, gb), (t, s_, ga, gb)
            # Subtree excluded: different rows flow below a flipped node.
    cap = (max(1, T * N // 500) if max_root_causes is None
           else max_root_causes)
    assert n_root_causes <= cap, (n_root_causes, cap, T, N)


def assert_prefix_identity_mod_ties(ens_a, ens_b, min_split_gain,
                                    leaf_rtol=1e-3, leaf_atol=1e-5,
                                    max_root_causes=4):
    """The at-scale cross-partition identity contract (ONE home — the
    config-3 witness of the earlier host, round 5, asserted it at 1M
    rows over 4 partitions; its reduced-size suite twin asserts the
    SAME thing):

      - every tree BEFORE the first structural divergence is bitwise
        identical in its decisions AND carries equivalent leaf values
        (f32 psum-order drift only — a leaf-aggregation bug that
        preserves structure must not hide behind the structural test);
      - the first divergent tree's root causes are PROVABLE
        bf16-boundary ties (assert_trees_match_mod_ties, per-tree);
      - later trees legitimately cascade (they train on the residuals
        the tied choice changed) and are NOT asserted here — callers
        add a quality-equivalence check (e.g. holdout AUC).

    Returns (bitwise_prefix_tree_count, first_divergent_tree_or_None).
    """
    import dataclasses

    def one_tree(e, t):
        return dataclasses.replace(
            e, feature=e.feature[t:t + 1],
            threshold_bin=e.threshold_bin[t:t + 1],
            threshold_raw=e.threshold_raw[t:t + 1],
            is_leaf=e.is_leaf[t:t + 1],
            leaf_value=e.leaf_value[t:t + 1],
            split_gain=e.split_gain[t:t + 1],
            default_left=(None if e.default_left is None
                          else e.default_left[t:t + 1]))

    same = [
        bool(np.array_equal(ens_a.feature[t], ens_b.feature[t])
             and np.array_equal(ens_a.threshold_bin[t],
                                ens_b.threshold_bin[t])
             and np.array_equal(ens_a.is_leaf[t], ens_b.is_leaf[t]))
        for t in range(ens_a.n_trees)
    ]
    first = same.index(False) if False in same else None
    prefix_n = first if first is not None else ens_a.n_trees
    for t in range(prefix_n):
        np.testing.assert_allclose(
            ens_a.leaf_value[t], ens_b.leaf_value[t],
            rtol=leaf_rtol, atol=leaf_atol,
            err_msg=f"prefix tree {t} leaves")
    if first is not None:
        assert_trees_match_mod_ties(
            one_tree(ens_a, first), one_tree(ens_b, first),
            min_split_gain, leaf_rtol=leaf_rtol, leaf_atol=leaf_atol,
            max_root_causes=max_root_causes)
    return prefix_n, first

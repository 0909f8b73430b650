"""SplitGain: scan histogram bins, score splits, argmax per node.

Layer L3 kernel #2 (SURVEY.md §2 "SplitGain"): cumulative-sum scan over the
bin axis, XGBoost-style gain formula, argmax over the flattened (feature, bin)
axis. NumPy twin: reference/numpy_trainer.best_splits — tie-break semantics
(first occurrence in flattened order) deliberately match jnp.argmax so every
backend picks identical splits.

This is tiny (histograms are [N, F, B, 2] ~ KBs-MBs) — pure XLA vector code,
fused by the compiler; never a bottleneck next to the histogram build.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ddt_tpu.telemetry.annotations import op_scope
from ddt_tpu.telemetry.costmodel import costed


@op_scope("cat_vec")
def cat_feature_vec(cat_features, n_features: int) -> "jax.Array | None":
    """bool [n_features] mask of one-vs-rest (categorical) columns, or
    None when there are none — the single home of the cat_features →
    vector convention (grow routing, streamed traversal, device eval all
    read this)."""
    if not cat_features:
        return None
    return jnp.zeros(n_features, bool).at[
        jnp.asarray(cat_features, jnp.int32)].set(True)


def node_totals(hist: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(G, H) per node: sums over bins of feature 0 (any feature sums the
    same rows). float32 [n_nodes] each."""
    return hist[:, 0, :, 0].sum(axis=1), hist[:, 0, :, 1].sum(axis=1)


@op_scope("gain")
def best_splits_impl(
    hist: jax.Array,            # float32 [n_nodes, F, B, 2]
    reg_lambda: float,
    min_child_weight: float,
    feature_mask: jax.Array | None = None,   # bool [F]; False = excluded
    missing_bin: bool = False,
    cat_mask: jax.Array | None = None,       # bool [F]; True = categorical
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-node best split: (gain [n], feature [n] i32, bin [n] i32,
    default_left [n] bool).

    gain = 0.5 * (GL^2/(HL+l) + GR^2/(HR+l) - G^2/(H+l)); split at bin b
    sends bins <= b left; last bin invalid (empty right child); children must
    carry >= min_child_weight hessian mass. Invalid positions score -inf.
    feature_mask implements colsample_bytree: masked features never win.

    missing_bin=True (cfg.missing_policy="learn"): bin B-1 holds NaN rows;
    both default directions are scored per (feature, bin) and the argmax
    runs over the flattened (direction, feature, bin) axis with the RIGHT
    block first — zero-missing nodes tie exactly and deterministically pick
    default_left=False.

    cat_mask marks categorical features (cfg.cat_features): one-vs-rest
    candidates ("bin == k goes left", every bin valid, one-hot gain)
    replace the ordinal cumsum gains on those features; under missing_bin
    they compete in the RIGHT block only. Semantics identical to the NumPy
    twin (reference/numpy_trainer.best_splits); keep in sync.
    """
    n_nodes, F, B, _ = hist.shape
    GL = jnp.cumsum(hist[..., 0], axis=2)           # [n, F, B]
    HL = jnp.cumsum(hist[..., 1], axis=2)
    # PER-FEATURE totals: feature f's own cumsum tail, so degenerate
    # candidates (all mass on one side) get an EXACTLY-zero complement
    # rather than cross-feature f32 noise near min_child_weight. Keep in
    # sync with numpy_trainer.best_splits and native/split_gain.cpp.
    G = GL[:, :, B - 1:B]                           # [n, F, 1]
    H = HL[:, :, B - 1:B]

    def gain_of(GLd, HLd):
        GR = G - GLd
        HR = H - HLd
        parent = jnp.square(G) / (H + reg_lambda)
        gain = 0.5 * (
            jnp.square(GLd) / (HLd + reg_lambda)
            + jnp.square(GR) / (HR + reg_lambda)
            - parent
        )
        valid = (HLd >= min_child_weight) & (HR >= min_child_weight)
        valid = valid & ~jnp.isnan(gain)            # 0/0 when reg_lambda == 0
        if feature_mask is not None:
            valid = valid & feature_mask[None, :, None]
        return gain, valid

    # Deterministic split selection: round gains to bfloat16 before argmax.
    # Gains within float noise of each other (different cumsum algorithms,
    # psum accumulation order across partitions, NumPy-vs-XLA rounding)
    # collapse to EXACT ties, broken by the shared first-flattened-index rule
    # — so every backend and every partition count picks identical splits.
    # Selecting among candidates within bf16 resolution (~0.4%) of the max is
    # immaterial to model quality; decision stability across devices is not.
    #
    # Determinism boundary: bf16 rounding absorbs noise RELATIVE to the
    # gain's magnitude — it collapses near-ties AMONG candidates, but it
    # cannot protect the split/no-split DECISION when a signal-free
    # node's best gain is itself f32 cancellation noise (~1e-8): with
    # min_split_gain=0 that noise's sign decides leaf-vs-split and
    # legitimately differs across summation orders (any reg_lambda).
    # reg_lambda=0 with min_child_weight=0 additionally lets near-empty
    # children amplify the noise unboundedly (0/0 vs x/0 can even differ
    # NaN-vs-inf across backends). Cross-backend bit-identity therefore
    # holds when decisions sit above the noise floor: min_split_gain >=
    # ~1e-3 (and min_child_weight >= ~1e-3 when reg_lambda = 0) — the
    # domain tests/test_config_fuzz.py randomizes over. Well-separated
    # real-signal configs (the default-parameter test suites) satisfy
    # this without any explicit floor.
    #
    # Cross-PLATFORM boundary (measured on the earlier host, round 3):
    # all of the above holds WITHIN a platform. Real-v5e
    # vs CPU training additionally differs by f32 summation ORDER (MXU
    # systolic accumulation vs sequential loops), which flips decisions
    # on EXACT near-ties that straddle a bf16 quantization boundary —
    # ~2-4 nodes per 155 at depth 4, unaffected by min_split_gain or
    # f32 matmul inputs (ordering is not a dtype). Model quality is
    # equivalent (held-out AUC within 0.004 both directions over 20
    # trees); reproducibility ACROSS platforms is per-platform, not
    # bitwise.
    #
    # Cross-PROCESS boundary (round 3, tests/test_multiprocess.py): a
    # multi-process mesh (gloo/real-pod collectives) may sum the
    # histogram allreduce in a different order than the single-
    # controller compilation of the same mesh shape. Measured effect:
    # tree STRUCTURE stays bit-identical (bf16 gain rounding absorbs
    # the ULPs), leaf VALUES agree to float tolerance (rtol ~2e-4)
    # rather than bitwise. The bit-identity contract is therefore:
    # bitwise within one controller at any partition count; structure-
    # identical + leaf-tolerant across controllers/processes.
    #
    # Chunked-ACCUMULATION boundary (round 4, fuzz campaign 2: seed
    # 197, the one divergence in 210 random streaming cases): streamed training sums per-chunk
    # histogram partials on host, a different f32 summation tree than
    # the in-memory single device sum. When a node's two best candidate
    # gains land within ~1 bf16 ULP of each other (measured: 0.00102997
    # vs 0.00102234 at the min_split_gain floor, reg_lambda=0), the
    # rounded argmax can legitimately pick either — ~1 root-cause node
    # per 160k across the campaigns. Streamed == in-memory is therefore
    # bitwise EXCEPT provable bf16-boundary candidate ties (the fuzz's
    # _assert_trees_match_mod_ties states the checkable contract); the
    # many fixed-seed streaming suites remain bitwise in practice.
    def overlay_cat(gain, valid):
        """Replace cat features' ordinal gains with one-vs-rest gains
        (left child = exactly bin k => GL_k is the per-bin sum itself)."""
        if cat_mask is None:
            return gain, valid
        gc, vc = gain_of(hist[..., 0], hist[..., 1])
        m = cat_mask[None, :, None]
        return jnp.where(m, gc, gain), jnp.where(m, vc, valid)

    if not missing_bin:
        gain, valid = gain_of(GL, HL)
        valid = valid & (jnp.arange(B) < B - 1)[None, None, :]
        gain, valid = overlay_cat(gain, valid)
        gain = jnp.where(valid, gain, -jnp.inf).astype(jnp.bfloat16)
        flat = gain.reshape(n_nodes, F * B)
        best = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(
            flat, best[:, None], axis=1)[:, 0].astype(jnp.float32)
        return (
            best_gain,
            (best // B).astype(jnp.int32),
            (best % B).astype(jnp.int32),
            jnp.zeros(n_nodes, bool),
        )

    miss_g = hist[:, :, B - 1:B, 0]                 # [n, F, 1]
    miss_h = hist[:, :, B - 1:B, 1]
    gain_r, valid_r = gain_of(GL, HL)               # missing stays RIGHT
    gain_l, valid_l = gain_of(GL + miss_g, HL + miss_h)   # missing LEFT
    not_nan_bin = (jnp.arange(B) < B - 1)[None, None, :]
    valid_r = valid_r & not_nan_bin
    # t = B-2 under LEFT puts every row left (empty right child): invalid
    # regardless of the min_child_weight knob.
    valid_l = valid_l & (jnp.arange(B) < B - 2)[None, None, :]
    gain_r, valid_r = overlay_cat(gain_r, valid_r)
    if cat_mask is not None:
        valid_l = valid_l & ~cat_mask[None, :, None]   # cat: RIGHT only
    g16 = jnp.concatenate(
        [jnp.where(valid_r, gain_r, -jnp.inf),
         jnp.where(valid_l, gain_l, -jnp.inf)], axis=1,
    ).astype(jnp.bfloat16)                          # [n, 2F, B]: RIGHT first
    flat = g16.reshape(n_nodes, 2 * F * B)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(
        flat, best[:, None], axis=1)[:, 0].astype(jnp.float32)
    fb = best % (F * B)
    return (
        best_gain,
        (fb // B).astype(jnp.int32),
        (fb % B).astype(jnp.int32),
        best >= F * B,
    )


#: The standalone jit entry (granular backend surface + host callers).
#: `best_splits_impl` above is the raw traced body: the fused level round
#: (ops/grow.py) calls it DIRECTLY so gain scoring inlines into the same
#: XLA program as the histogram build and row routing — no nested pjit
#: boundary between hist output and the gain epilogue.
best_splits = costed("gain", phase="gain")(
    functools.partial(
        jax.jit,
        static_argnames=("reg_lambda", "min_child_weight", "missing_bin"),
    )(best_splits_impl))

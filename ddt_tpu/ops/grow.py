"""Whole-tree growth as one traced XLA program (the L5 level loop, on device).

SURVEY.md §3's per-level stack — build_histograms -> [psum] -> best_splits ->
apply splits -> partition_rows — realised TPU-first: the depth loop is
UNROLLED inside one jitted function (static shapes per level: level d has
2^d nodes), so growing a tree is a single device dispatch with zero host
round-trips. The reference crosses the host<->device boundary per kernel call;
on TPU that would serialise ~6 dispatches x 100 trees of latency, so we fuse.

Each level is one FUSED ROUND (`ddt:fused_round`): the VMEM-streaming
histogram kernel, the optional sibling-SUBTRACTION assembly
(level_histograms — levels >= 1 build only left children and recover
right children as parent - left, halving kernel work and allreduce
payload; arXiv:1812.08295's pipelined on-chip hist->gain architecture is
the blueprint), the gain epilogue (split.best_splits_impl inlined into
the same program — no nested pjit boundary), and row routing — with no
intermediate state landing in HBM between stages beyond the level's own
[2^d, F, B, 2] histogram.

Distribution (SURVEY.md §1 L2): pass `axis_name` when tracing under
jax.shard_map over a row-sharded mesh — the histogram (and final-leaf
aggregate) get a `jax.lax.psum` over ICI, which is the TPU-native realisation
of the reference's "cross-partition histogram allreduce over the FPGA network
fabric" [BASELINE]. Everything else is replicated math on tiny arrays, so all
shards deterministically grow identical trees.

Row routing keeps a dense per-row heap node-id vector ("partition_rows" as a
jnp.where update — SURVEY.md §2 "Node partitioner": no data movement, static
shapes; rows frozen at early leaves are masked out of histograms by the
node_index = -1 sentinel).

Feature parallelism (SURVEY.md §2 "Parallelism strategies": the optional
`features` mesh axis, the TP-analog for histogram GBDT): pass
`feature_axis_name` when Xb is COLUMN-sharded over a second mesh axis. Each
shard histograms only its own features (splitting the hot loop's F dimension
across chips), local per-node best splits are combined with an `all_gather`
of the (gain, feature, bin) triples — tiny: [n_shards, n_level] — and row
routing recovers the winning feature's values via a masked `psum` over the
feature axis (exactly one shard owns each winning column, all others
contribute zero). Tie-break stays bit-identical to single-device: within a
shard argmax picks the first flattened (feature, bin); across shards the
first shard wins ties, which IS global first-feature order.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ddt_tpu.ops import grad as grad_ops
from ddt_tpu.ops import histogram as H
from ddt_tpu.ops import split as S
from ddt_tpu.parallel import comms
from ddt_tpu.telemetry.annotations import traced_scope
from ddt_tpu.utils import device

# Perfetto alignment (docs/OBSERVABILITY.md): the traced_scope blocks
# below name the lowered XLA ops `ddt:fused_round` (one whole level's
# hist -> subtract -> gain -> route group) with `ddt:hist` /
# `ddt:allreduce` / `ddt:hist:subtract` / `ddt:gain` / `ddt:route` /
# `ddt:leaf` nested inside, so a profiler capture's device timeline
# carries the same phase names as the host PhaseTimer spans. Zero
# runtime cost — named scopes are HLO metadata, not ops.


def resolve_hist_subtraction(flag: str, platform: str | None = None,
                             integer_hists: bool = False) -> bool:
    """cfg.hist_subtraction ('auto'|'on'|'off') -> bool for this platform.

    'auto' enables the sibling-subtraction trick only on a real TPU chip:
    it changes right-child bin sums by float-rounding ULPs (parent - left
    vs a direct sum), which is invisible to model quality and absorbed by
    the bf16 gain rounding in almost every decision, but would break the
    streamed == in-memory BITWISE contracts the CPU fixed-seed suites
    assert (ops/split.py's determinism-boundary notes). Off-chip runs and
    oracles therefore default off; tests opt in with 'on'.

    `integer_hists=True` (the quantized-gradient path, cfg.grad_dtype):
    parent - left is EXACT in the int32 domain — the f32-ULP caveat that
    forced the platform gate does not exist there — so 'auto' resolves
    ON everywhere: half the kernel work and half the collective payload
    per level >= 1, with the streamed == in-memory contracts intact
    ('off' still forces it off)."""
    if flag == "on":
        return True
    if flag == "off":
        return False
    if flag != "auto":
        raise ValueError(
            f"hist_subtraction must be auto|on|off, got {flag!r}")
    if integer_hists:
        return True
    if platform is None:
        platform = device.platform()
    return platform == "tpu"


def _slab_widths(F: int, slabs: int, row_shards: int) -> list[int]:
    """Feature-slab widths for the slab-pipelined build+reduce loop.

    Slab boundaries align to the row-shard count (each non-final slab's
    width is a multiple of `row_shards`) so that under reduce_scatter
    every padded local column id lands >= F — the one-line validity test
    the gain mask relies on (`col < F`). Returns [F] when pipelining is
    off or the shape is too narrow to split."""
    if slabs <= 1:
        return [F]
    fc = -(-F // (slabs * row_shards)) * row_shards
    if fc <= 0 or fc >= F:
        return [F]
    return [min(fc, F - i) for i in range(0, F, fc)]


def level_histograms(
    Xb: jax.Array,
    g: jax.Array,
    h: jax.Array,
    node_index: jax.Array,      # int32 [R] level-local, -1 = frozen
    n_level: int,
    n_bins: int,
    *,
    hist_impl: str = "auto",
    row_chunk: int = 32_768,
    input_dtype=jnp.bfloat16,
    allreduce=lambda x: x,
    comms_slabs: int = 1,
    row_shards: int = 1,
    parent_hist: jax.Array | None = None,   # [n_level//2, F(_loc), B, 2],
    #   the PREVIOUS level's post-collective histograms (the local slab
    #   under reduce_scatter — the carry and the reduce share a layout)
    parent_split: jax.Array | None = None,  # bool [n_level//2]: which
    #   parents actually split (children of leaves must read zero mass)
) -> jax.Array:
    """One level's [n_level, F, B, 2] histograms (post-collective; the
    merged F/row_shards slab under reduce_scatter), with the classic GBDT
    sibling-SUBTRACTION trick when parent state is given: only LEFT
    children are built from rows (half the kernel work AND half the
    collective payload), and each right child is recovered as
    parent - left. Children of non-split parents are gated to exactly
    zero — without the gate a frozen parent's phantom right child would
    inherit the full parent mass and could "win" a split no training row
    can reach (a predict-time divergence, since predict-time rows CAN
    reach it).

    `allreduce` is the histogram collective (comms.hist_reduce bound by
    the caller: psum or reduce-scatter, optionally compressed). With
    `comms_slabs` > 1 the build+collective is SLAB-PIPELINED: the
    feature axis splits into row-shard-aligned slabs (_slab_widths), and
    slab k+1's histogram kernels are dispatched before slab k's
    collective completes — inside one traced program, XLA's async
    collectives then hide the wire latency behind VPU work. f32/bf16
    collectives are elementwise reductions, so the phasing is
    bit-identical to the monolithic form by construction; int32_fixed
    derives its fixed-point scale per collective, so each slab
    quantizes on its own (tighter) grid — deterministic and inside the
    same error bound, but not bitwise vs slabs=1.

    Exactness: left-child sums are BITWISE identical to a direct full
    build (a node's rows accumulate in the same tile order; absent rows
    contribute exact +0.0 terms either way). Right-child sums differ
    from a direct build by f32 rounding ULPs — the documented seam
    behind cfg.hist_subtraction's platform gating."""
    F = Xb.shape[1]
    widths = _slab_widths(F, comms_slabs, row_shards)

    def build_reduced(ni, n_nodes):
        """Per-slab histogram build, each slab's collective issued as
        soon as its build is traced (the overlap phasing)."""
        outs = []
        lo = 0
        for w in widths:
            with traced_scope("hist"):
                hs = H.build_histograms(
                    Xb[:, lo:lo + w] if len(widths) > 1 else Xb,
                    g, h, ni, n_nodes, n_bins,
                    impl=hist_impl, row_chunk=row_chunk,
                    input_dtype=input_dtype,
                )
            with traced_scope("allreduce"):
                outs.append(allreduce(hs))
            lo += w
        if len(outs) == 1:
            return outs[0]
        return jnp.concatenate(outs, axis=1)

    if parent_hist is None or n_level < 2:
        return build_reduced(node_index, n_level)
    half = n_level // 2
    # Rows sitting in LEFT children (even level-local index) keyed by
    # parent slot; everyone else (right children, frozen) masks out.
    # HALF a full level's collective payload.
    is_left = (node_index >= 0) & (node_index % 2 == 0)
    li = jnp.where(is_left, node_index // 2, -1).astype(jnp.int32)
    hist_left = build_reduced(li, half)
    with traced_scope("hist:subtract"):
        gate = parent_split.reshape(half, 1, 1, 1)
        # Dtype-generic zero: on the quantized path the carry and the
        # left build are int32 and the subtraction is EXACT (integer
        # adds commute) — the f32-ULP right-child caveat vanishes.
        hist_right = jnp.where(gate, parent_hist - hist_left,
                               jnp.zeros((), hist_left.dtype))
        # Interleave [half, {left,right}, F, B, 2] -> level order
        # (left child = 2p, right child = 2p + 1).
        hist = jnp.stack([hist_left, hist_right], axis=1)
        return hist.reshape((n_level,) + hist_left.shape[1:])


class TreeArrays(NamedTuple):
    """One grown tree in SoA heap layout + per-row leaf assignment."""

    feature: jax.Array        # int32 [n_nodes_total], -1 on leaves
    threshold_bin: jax.Array  # int32 [n_nodes_total]
    is_leaf: jax.Array        # bool  [n_nodes_total]
    leaf_value: jax.Array     # float32 [n_nodes_total]
    split_gain: jax.Array     # float32 [n_nodes_total], 0 on leaves
    default_left: jax.Array   # bool  [n_nodes_total] NaN-row direction
    leaf_of_row: jax.Array    # int32 [R] heap slot where each row landed


def grow_tree(
    Xb: jax.Array,            # uint8 [R, F] (the local shard when distributed)
    g: jax.Array,             # float32 [R]
    h: jax.Array,             # float32 [R]
    *,
    max_depth: int,
    n_bins: int,
    reg_lambda: float,
    min_child_weight: float,
    min_split_gain: float,
    hist_impl: str = "auto",
    row_chunk: int = 32_768,
    input_dtype=jnp.bfloat16,
    axis_name: "str | tuple[str, ...] | None" = None,   # row-shard axes;
    #   a ("hosts", "rows") tuple for pod meshes — psum reduces over all of
    #   them (XLA phases ICI before DCN for a (hosts, rows, ...) mesh).
    feature_axis_name: str | None = None,
    feature_mask: jax.Array | None = None,   # bool [F global]; colsample
    missing_bin: bool = False,   # cfg.missing_policy="learn": bin n_bins-1
    #   holds NaN rows; splits learn a default direction for them.
    cat_features: tuple = (),    # GLOBAL feature indices with one-vs-rest
    #   ("bin == k goes left") categorical splits (cfg.cat_features).
    hist_subtraction: bool = False,  # sibling-subtraction trick: levels
    #   >= 1 build only LEFT-child histograms and derive right children as
    #   parent - left (see level_histograms / resolve_hist_subtraction —
    #   backends resolve cfg.hist_subtraction before tracing).
    split_comms: str = "allreduce",  # RESOLVED collective for split
    #   finding ("allreduce" | "reduce_scatter" — backends resolve
    #   cfg.split_comms via comms.resolve_split_comms): reduce_scatter
    #   hands each row shard one merged F/P feature slab, split finding
    #   runs on the slab, and the tiny per-shard winner tuples are
    #   combined by GLOBAL flattened candidate index
    #   (comms.combine_shard_winners) — same trees, O(F·B/P) payload.
    #   COMPOSES with feature_axis_name (the 2D rows x features mesh):
    #   the scatter runs over the row axes WITHIN this shard's F/Pf
    #   column slab (per-device slab F/(Pr·Pf)) and ONE winner combine
    #   gathers over both axes — trees stay structure-identical to
    #   single-device at any (Pr, Pf).
    hist_comms_dtype: str = "f32",   # wire dtype of the histogram
    #   collective (comms.hist_reduce): f32 | bf16 | int32_fixed.
    comms_slabs: int = 1,            # RESOLVED slab-pipelining factor
    #   (comms.resolve_comms_slabs): the level's build+collective splits
    #   into this many feature slabs so slab k+1's kernels overlap slab
    #   k's wire time. 1 = monolithic; f32/bf16 phasing is bit-identical
    #   either way (int32_fixed: see level_histograms).
    grad_dtype: str = "f32",         # cfg.grad_dtype: "int8"/"int16"
    #   quantizes g/h ONCE per tree onto a shared power-of-two grid
    #   (ops/grad.quantize_gradients — per-output-dim scale from psum'd
    #   |g|,|h| stats, seeded stochastic rounding) and runs the whole
    #   level loop in the integer domain: int32 histograms, exact
    #   sibling subtraction, bit-stable integer merges, ONE dequantize
    #   per level just before the gain epilogue.
    quant_tree_id=None,              # traced int32 ABSOLUTE tree index
    #   (round * n_classes + class) — the stochastic-rounding key's
    #   per-tree component; None = 0 (single-shot callers).
    quant_seed: int = 0,             # cfg.seed (static rounding key part)
) -> TreeArrays:
    """Grow one complete-heap tree. Trace under jit (and shard_map if
    axis_name is set). Matches reference/numpy_trainer.grow_tree decisions.

    With feature_axis_name, Xb is the [R_loc, F_loc] column shard and the
    returned tree's feature indices are GLOBAL (shard offset applied);
    feature_mask is indexed globally and sliced to the local columns."""
    R, F = Xb.shape
    # Routing packs (feat << 12 | bin << 3 | cat << 2 | default_left << 1
    # | split) into int32 — enforce the field bounds at trace time so a
    # future wider-bin or huge-F config fails loudly instead of silently
    # corrupting row routing.
    assert n_bins <= 512, f"routing pack needs n_bins <= 512, got {n_bins}"
    # The packed feats are GLOBAL indices under feature sharding (shard
    # offset applied below), so the bound must cover shards x local width,
    # not just the local F. axis_size is static at trace time.
    F_global = F if feature_axis_name is None else (
        F * jax.lax.axis_size(feature_axis_name))
    assert F_global < 2 ** 19, \
        f"routing pack needs global F < 2^19, got {F_global}"
    N = 2 ** (max_depth + 1) - 1

    feature = jnp.full((N,), -1, jnp.int32)
    threshold_bin = jnp.zeros((N,), jnp.int32)
    is_leaf = jnp.zeros((N,), bool)
    leaf_value = jnp.zeros((N,), jnp.float32)
    split_gain = jnp.zeros((N,), jnp.float32)
    default_left = jnp.zeros((N,), bool)

    node_id = jnp.zeros((R,), jnp.int32)   # heap slot per row
    frozen = jnp.zeros((R,), bool)

    # Split-finding comms (parallel/comms.py; docs/PERF.md "Histogram
    # comms"): `allreduce` is the exact psum for the small aggregates
    # (node totals, leaf sums, routing values); the HISTOGRAM collective
    # is hist_collective — psum or reduce_scatter over the row axes,
    # optionally compressed on the wire.
    rs = split_comms == "reduce_scatter" and axis_name is not None
    P_row = comms.axis_size(axis_name)

    def allreduce(x):
        return comms.psum(x, axis_name)

    def hist_collective(hs):
        if rs:
            hs = comms.pad_to_multiple(hs, 1, P_row)
        return comms.hist_reduce(
            hs, axis_name,
            mode="reduce_scatter" if rs else "allreduce",
            comms_dtype=hist_comms_dtype, scatter_dim=1)

    # Quantized gradients (cfg.grad_dtype; docs/PERF.md "Quantized
    # gradients"): ONE in-trace quantization per tree — per-output-dim
    # scales from psum'd/pmax'd |g|,|h| stats (ops/grad.quant_scale),
    # then seeded stochastic rounding keyed by (seed, tree, GLOBAL row
    # id) so chaos retries, resharding and resumes replay identical
    # bits. Every consumer below (histograms, node totals, leaf sums)
    # accumulates the INTEGER q's and dequantizes exactly once after
    # its merge.
    quant = grad_dtype != "f32"
    gscale = hscale = scale2 = None
    if quant:
        tid = quant_tree_id if quant_tree_id is not None else jnp.int32(0)
        g, h, gscale, hscale = grad_ops.quantize_gradients(
            g, h, grad_dtype=grad_dtype, tree_id=tid, seed=quant_seed,
            local_offset=comms.flat_axis_index(axis_name) * R,
            allreduce=allreduce,
            allmax=lambda x: comms.pmax(x, axis_name),
            n_rows_global=R * comms.axis_size(axis_name))
        scale2 = jnp.stack([gscale, hscale])      # [..., 2] dequant vector

    # Local->global column map of this shard's reduce-scattered slab:
    # slab s of width w contributes wp/P_row contiguous columns per
    # shard (wp = w padded to the shard count); slab boundaries align to
    # P_row (_slab_widths), so every padded local column id lands >= F
    # and `col < F` is the validity test. None when not scattering.
    col_ids = None
    if rs:
        idx = comms.flat_axis_index(axis_name)
        parts, lo = [], 0
        for w in _slab_widths(F, comms_slabs, P_row):
            b = (-(-w // P_row) * P_row) // P_row
            parts.append(lo + idx * b + jnp.arange(b, dtype=jnp.int32))
            lo += w
        col_ids = (jnp.concatenate(parts) if len(parts) > 1
                   else parts[0]).astype(jnp.int32)

    cat_vec_g = S.cat_feature_vec(cat_features, F_global)  # bool [F_global]
    cat_vec = cat_vec_g                    # this shard's columns

    if feature_axis_name is not None:
        f_shard = jax.lax.axis_index(feature_axis_name)
        f_lo = f_shard * F                 # global index of local column 0
        if feature_mask is not None:
            feature_mask = jax.lax.dynamic_slice_in_dim(
                feature_mask, f_lo, F)     # this shard's columns
        if cat_vec_g is not None:
            cat_vec = jax.lax.dynamic_slice_in_dim(cat_vec_g, f_lo, F)

    # Sibling-subtraction carry: the previous level's post-allreduce
    # histograms + its split decisions (level_histograms gates phantom
    # children of frozen parents on these). None keeps every level a
    # direct build — the bit-exact baseline path.
    prev_hist = None
    prev_split = None

    for depth in range(max_depth):         # unrolled: static 2^d nodes/level
        offset = (1 << depth) - 1
        n_level = 1 << depth
        node_index = jnp.where(frozen, -1, node_id - offset).astype(jnp.int32)
        # One FUSED level round: hist -> [psum] -> (subtract) -> gain ->
        # route, a single traced group with no host boundary and no HBM
        # round-trip of intermediate state between stages (the gain
        # epilogue consumes best_splits_impl directly — no nested pjit).
        with traced_scope("fused_round"):
            hist = level_histograms(
                Xb, g, h, node_index, n_level, n_bins,
                hist_impl=hist_impl, row_chunk=row_chunk,
                input_dtype=input_dtype, allreduce=hist_collective,
                comms_slabs=comms_slabs, row_shards=P_row,
                parent_hist=prev_hist, parent_split=prev_split,
            )
            if feature_axis_name is None and not rs:
                G, Hh = S.node_totals(hist)
                if quant:
                    # Integer bin sums, dequantized ONCE — exact.
                    G = G.astype(jnp.float32) * gscale
                    Hh = Hh.astype(jnp.float32) * hscale
            else:
                # Node totals from the row vectors, not the histogram:
                # local histograms hold different COLUMNS per shard, so
                # their bin sums agree only up to float add order — this
                # form is bit-identical (and provably feature-axis-
                # invariant) on every shard. On the quantized path the
                # segment sums run int32 (exact under ANY order, so the
                # histogram form would agree too — this one stays for
                # symmetry with the f32 path).
                act = node_index >= 0
                seg = jnp.clip(node_index, 0, n_level - 1)
                if quant:
                    zq = jnp.zeros((), g.dtype)
                    G = allreduce(jax.ops.segment_sum(
                        jnp.where(act, g, zq).astype(jnp.int32), seg,
                        num_segments=n_level)).astype(jnp.float32) * gscale
                    Hh = allreduce(jax.ops.segment_sum(
                        jnp.where(act, h, zq).astype(jnp.int32), seg,
                        num_segments=n_level)).astype(jnp.float32) * hscale
                else:
                    G = allreduce(jax.ops.segment_sum(
                        jnp.where(act, g, 0.0), seg, num_segments=n_level))
                    Hh = allreduce(jax.ops.segment_sum(
                        jnp.where(act, h, 0.0), seg, num_segments=n_level))
            with traced_scope("gain"):
                # The ONE dequantize per level (quantized path): the
                # int32 histogram — post-collective, post-subtraction —
                # becomes f32 only here, feeding the gain epilogue; the
                # sibling-subtraction carry below keeps the INTEGER
                # form so next level's parent - left stays exact.
                hist_q = hist
                if quant:
                    hist = hist.astype(jnp.float32) * scale2
                if rs:
                    # Slab-local split finding: masks gather down to this
                    # shard's columns (padded ids >= F are invalid), the
                    # slab argmax runs locally, winners map back to
                    # GLOBAL feature ids via col_ids (+ the feature-shard
                    # offset on a 2D mesh), and the tiny per-shard tuples
                    # combine by global flattened candidate index —
                    # exactly the single-device argmax's pick
                    # (comms.combine_shard_winners). With a feature axis
                    # the combine gathers over BOTH axes in one pass:
                    # every (row, feature) shard owns a disjoint global
                    # column set, so the layout-independent tie-break key
                    # needs no per-axis staging.
                    valid_loc = col_ids < F
                    cid = jnp.minimum(col_ids, F - 1)
                    fm_loc = valid_loc if feature_mask is None else (
                        jnp.take(feature_mask, cid) & valid_loc)
                    cm_loc = None if cat_vec is None else (
                        jnp.take(cat_vec, cid) & valid_loc)
                    gains, feats, bins, dls = S.best_splits_impl(
                        hist, reg_lambda, min_child_weight, fm_loc,
                        missing_bin=missing_bin, cat_mask=cm_loc)
                    feats = jnp.take(col_ids, feats)
                    if feature_axis_name is None:
                        combine_axes, nf = axis_name, F
                    else:
                        feats = feats + f_lo
                        row_t = (axis_name if isinstance(axis_name, tuple)
                                 else (axis_name,))
                        combine_axes = row_t + (feature_axis_name,)
                        nf = F_global
                    gains, feats, bins, dls = comms.combine_shard_winners(
                        gains, feats, bins, dls, combine_axes,
                        n_features=nf, n_bins=n_bins,
                        missing_bin=missing_bin)
                else:
                    gains, feats, bins, dls = S.best_splits_impl(
                        hist, reg_lambda, min_child_weight, feature_mask,
                        missing_bin=missing_bin, cat_mask=cat_vec)
                    if feature_axis_name is not None:
                        # Combine per-shard winners: all_gather the
                        # (gain, feat, bin, direction) tuples (tiny) and
                        # pick by global flattened candidate index — the
                        # global first-(direction, feature, bin)
                        # tie-break rule (comms.combine_shard_winners).
                        feats = feats + f_lo
                        gains, feats, bins, dls = \
                            comms.combine_shard_winners(
                                gains, feats, bins, dls, feature_axis_name,
                                n_features=F_global, n_bins=n_bins,
                                missing_bin=missing_bin)
            # Guarded like the final level and the streamed twin: an EMPTY
            # node at reg_lambda=0 would otherwise store -0/0 = NaN as its
            # leaf value, which a predict-time row (different data) can
            # reach.
            value = jnp.where(Hh > 0, -G / (Hh + reg_lambda), 0.0)

            do_split = (
                (gains > min_split_gain) & jnp.isfinite(gains) & (Hh > 0)
            )
            sl = slice(offset, offset + n_level)
            feature = feature.at[sl].set(jnp.where(do_split, feats, -1))
            threshold_bin = threshold_bin.at[sl].set(
                jnp.where(do_split, bins, 0))
            is_leaf = is_leaf.at[sl].set(~do_split)
            leaf_value = leaf_value.at[sl].set(
                jnp.where(do_split, 0.0, value))
            split_gain = split_gain.at[sl].set(
                jnp.where(do_split, gains.astype(jnp.float32), 0.0))
            default_left = default_left.at[sl].set(do_split & dls)

            # Route rows through the new splits (dense node-id update).
            # All per-row lookups are one-hot compare+reduce instead of
            # gathers: TPU gathers (even from a 32-entry table) each cost
            # ~10-20 ms at 1M rows, while the [R, n_level] masked
            # reductions are a few ms total — and integer one-hot sums
            # are EXACT, so routing is bit-identical to the gather
            # formulation. The five per-node tables (feature, bin,
            # cat-ness, direction, do_split) are packed into ONE int32 so
            # a single masked reduction covers them:
            # feat<<12 | bin<<3 | cat<<2 | default_left<<1 | split.
            with traced_scope("route"):
                idx_c = jnp.clip(node_id - offset, 0, n_level - 1)
                noh = (idx_c[:, None]
                       == jnp.arange(n_level, dtype=jnp.int32)[None, :])
                if cat_vec_g is not None:
                    # Per-NODE cat-ness of the winning (global) feature.
                    # An n_level-sized gather from the replicated
                    # [F_global] table is fine — the gathers this file
                    # avoids are [R]-sized ones.
                    cat_n = jnp.take(cat_vec_g, feats, axis=0)
                else:
                    cat_n = jnp.zeros(n_level, bool)
                table = ((feats << 12) | (bins << 3)
                         | (cat_n.astype(jnp.int32) << 2)
                         | (dls.astype(jnp.int32) << 1)
                         | do_split.astype(jnp.int32))
                packed_r = jnp.sum(jnp.where(noh, table[None, :], 0),
                                   axis=1)
                split_here = (packed_r & 1).astype(bool) & ~frozen
                dl_r = ((packed_r >> 1) & 1).astype(bool)
                cat_r = ((packed_r >> 2) & 1).astype(bool)
                feat_r = packed_r >> 12
                bin_r = (packed_r >> 3) & 0x1FF
                if feature_axis_name is None:
                    foh = (
                        jax.lax.broadcasted_iota(jnp.int32, (1, F), 1)
                        == feat_r[:, None]
                    )
                    fv = jnp.sum(jnp.where(foh, Xb.astype(jnp.int32), 0),
                                 axis=1)
                else:
                    # Winning columns live on exactly one feature shard:
                    # lanes only match on the owner (out-of-range local
                    # index matches nothing), everyone else contributes
                    # zero; psum broadcasts.
                    loc = feat_r - f_lo
                    foh = (
                        jax.lax.broadcasted_iota(jnp.int32, (1, F), 1)
                        == loc[:, None]
                    )
                    fv = comms.psum(
                        jnp.sum(jnp.where(foh, Xb.astype(jnp.int32), 0),
                                axis=1),
                        feature_axis_name,
                    )
                go_right = fv > bin_r
                if cat_features:
                    # Categorical one-vs-rest: the matched category goes
                    # LEFT.
                    go_right = jnp.where(cat_r, fv != bin_r, go_right)
                if missing_bin:
                    # NaN rows occupy the reserved top bin and follow the
                    # node's learned default direction.
                    go_right = jnp.where(fv == n_bins - 1, ~dl_r, go_right)
                go_right = go_right.astype(jnp.int32)
                node_id = jnp.where(split_here,
                                    2 * node_id + 1 + go_right, node_id)
                frozen = frozen | ~split_here

        # Carry for the next level's sibling subtraction (the integer
        # form on the quantized path — subtraction must stay exact).
        if hist_subtraction:
            prev_hist = hist_q if quant else hist
            prev_split = do_split

    # Final level: leaf values from per-terminal-node (G, H) aggregates
    # via the shared one-hot contraction (grad_ops.leaf_gh_sums — the
    # one home; rationale and numerics notes live on it). On the
    # quantized path the contraction is an exact int32 sum, the psum an
    # exact integer merge, and the dequantize happens once after it —
    # leaf (G, H) are bitwise shard- and order-invariant where the f32
    # form differed from the CPU twin by ULPs.
    with traced_scope("leaf"):
        offset = (1 << max_depth) - 1
        n_last = 1 << max_depth
        active = ~frozen
        idx = jnp.clip(node_id - offset, 0, n_last - 1)
        GH = grad_ops.leaf_gh_sums(idx, active, g, h, n_last)
        if quant:
            Gl = allreduce(GH[:, 0]).astype(jnp.float32) * gscale
            Hl = allreduce(GH[:, 1]).astype(jnp.float32) * hscale
        else:
            Gl = allreduce(GH[:, 0])
            Hl = allreduce(GH[:, 1])
        vals = jnp.where(Hl > 0, -Gl / (Hl + reg_lambda), 0.0)
        sl = slice(offset, offset + n_last)
        is_leaf = is_leaf.at[sl].set(True)
        leaf_value = leaf_value.at[sl].set(vals.astype(jnp.float32))

    return TreeArrays(feature, threshold_bin, is_leaf, leaf_value,
                      split_gain, default_left, node_id)


def tree_predict_delta(tree: TreeArrays, learning_rate: float) -> jax.Array:
    """Per-row raw-score increment from a freshly grown tree: lr * leaf value
    at the slot each row landed in (leaf_of_row). Keeps residuals fresh
    without re-traversing (SURVEY.md §3 hot loop #2 avoided during training).
    """
    return learning_rate * tree.leaf_value[tree.leaf_of_row]

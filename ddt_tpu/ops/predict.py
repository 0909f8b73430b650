"""Batch ensemble prediction: depth-unrolled compare+select on XLA.

Layer L3/L6 (SURVEY.md §3 "predict"): the reference's `TreeEnsemble.predict`
batch-scoring path. The north star calls this "gather+compare" [BASELINE] —
but on TPU a literal per-(tree,row) `take_along_axis` traversal lowers to
scalar-loop gathers (measured ~10 M lookups/s on a v5e: 28 s for 200k rows x
100 trees, and the 10M x 1000 config killed the chip). So the gathers are
re-expressed as one-hot compare+reduce, which vectorises on the VPU and is
EXACT (integer sums select a single matching lane):

1. Leaf-chain pushdown (`_effective_arrays`): descendants of a leaf inherit
   its value/slot; leaves themselves get feature=-1, thr=+inf so every row
   walks all the way to the bottom level (always-left below a leaf). This
   removes the frozen-node case, so at level d a row's node is exactly its
   d-bit relative index — all lookups stay inside the level's 2^d-wide slice.
2. Per level: node-relative one-hot [T, R, 2^d] selects (feature, thr) from
   the level slice; a feature one-hot [T, R, F] selects the row's bin value
   (feature=-1 matches no lane -> fv=0 < thr=+inf -> go left). All
   compare+select+reduce chains fuse — nothing [T, R, *]-shaped reaches HBM.
3. Bottom level: one-hot select of the (pushed-down) leaf value per row.

Doubly chunked via lax.scan — trees in chunks of `tree_chunk`, rows in chunks
of `row_chunk` — so the working set stays bounded for the 10M-row x
1000-tree inference config [BASELINE] (a flat [1000, 10M] int32 node state
alone would be 40 GB).

Since the inference-overhaul PR the module exposes THREE related entries:

- `predict_raw` — the original raw-arrays contract (pushdown computed
  in-trace); kept for tests and host callers.
- `predict_raw_effective` — the same scoring core fed PRE-pushed-down,
  pre-padded arrays (models/tree.CompiledEnsemble builds them ONCE per
  model on host; backends keep them device-resident across calls).
- the Pallas fast path (`ops/predict_pallas.py`) — dispatched from either
  entry via `use_pallas` (None = auto: binned data on a real TPU whose
  depth, features and classes fit the kernel's VMEM budget, at any tree
  count; the one-hot path is the fallback).

What a backend runs is built through `layout_entry` below: ONE table of
layouts (`LAYOUTS`), each owned by its kernel module, whose entry makes
the plan, the host tables and the jitted entry of THIS module with the
model's static arguments bound, kernel or twin among them: chosen on the
host, once a model, and handed to the trace as a bool. An entry that a
direct caller gives None asks its layout's rule itself, once.

A FOURTH entry serves the other ensemble layout, the NODE LIST
(models/tree.NodeListEnsemble: leaf-wise trees too deep and sparse for a
heap): `predict_raw_effective_paths`, the PATH-MATRIX form (Hummingbird's
GEMM strategy, OSDI 2020). Per tree, with W lanes of nodes and of leaves:

    v[r, n] = bin[r, feature[n]]            = X[rows, F] @ sel[F, W]
    s[r, n] = +1 if v[r, n] > thr[n] else -1
              (with learned NaN directions: +1 if thr[n] < v[r, n] < up[n],
              up[n] the NaN bin where node n sends NaN left, else +BIG: the
              NaN bin lies above every threshold, so the plain compare is
              the default-RIGHT route already)
    m[r, l] = sum_n s[r, n] P[n, l]         P = +1 / -1 / 0: leaf l in node
                                            n's right / left subtree / not
    score[r] += sum_l where(m[r, l] == len[l], leaf_value[l], 0)

A CATEGORY-SET node (LightGBM's categorical split: the row goes LEFT iff its
bin is in the node's set; models/tree.CompiledNodeList, CATEGORY SETS) is a
column of `sel` like any: the rows are widened by the ONE-HOT of every
category column's bin, K rows a column, the node's column of `sel` is
multi-hot over its set's K rows, so v[r, n] = 1 where the bin is in the set
and 0 elsewhere, thr[n] = 0, and row n of P is negated (+1: in the set,
left). Stage `predict:catset` where XLA makes the one-hot (the jax.numpy
form); the kernel makes it in VMEM.

`m[r, l] == len[l]` (the nodes on leaf l's path) for the ONE leaf whose
every path node sends the row its way. Bins, +-1 and P are exact in
bfloat16 and every sum an integer below 2^8: exact with float32
accumulation in any order; leaf values stay float32. The plain jax.numpy
form below is the fallback and what a CPU runs; the Pallas kernel is
`ops/predict_paths.py`, dispatched by the same rule.

The chain. A tree of more lanes than one path matrix should hold, or one
whose leaves are VECTORS (an averaged forest), is cut into SUB-TREES of at
most 256 lanes (models/tree.cut_subtrees), each an entry of the same three
tables whose "leaves" are its EXITS: real leaves, and links to the sub-tree
that holds the child. A sub-tree is one connected piece of its tree or
(PR 53) SEVERAL, glued into one binary tree by copies of their lowest
common ancestors, which ask their nodes' questions again and hang no exit: a
row that reaches a piece answered those nodes on its way, so the glued
tree's walk ends in the exit the tree's own walk takes, and a row that
reaches none of them is not active there. Per sub-tree k of a tree, parents
first (every piece of k hangs on an earlier sub-tree of the tree),
with e_k[r, x] = (m_k[r, x] == len_k[x]) as above (the exit the row would
take from k's root),

    a_root[r] = 1,   a_j[r] = a_k[r] e_k[r, link k -> j]
    score[r, :] += a_k[r] (e_k[r, real leaves] @ V_k)       V_k [W, C]

so a row's activity follows its one chain of sub-trees down and the one
real leaf it ends in adds its vector. One more matmul a sub-tree does both:
e_k @ [V_k | L_k], L_k[x, j - k - 1] = 1 where exit x links to sub-tree j:
the activity is a row of A lanes, lane 0 this sub-tree's and lane i that of
the sub-tree i further on, shifted down a lane a sub-tree and reset to lane
0 at a tree's first. V_k holds a float32 value as its three bfloat16 pieces
in lanes of their own (models/tree.split_bfloat16: exact), e and a are 0/1,
one exit a row: the class lanes' sums are float32 sums of exact products.
Where the 3 C lanes of pieces and a tree's chain fit ONE tile of 128 lanes
(models/tree.exit_table_lanes: THE RULE) the table is that one tile, L_k
right behind V_k's pieces, the sums and the activity are 128 lanes each
that take the same a_k (e_k @ table), and "lane 0" above is lane 3 C.
The forest's answer is the sum over its trees divided by their number.
Where a sub-tree is numbered as two halves of 128 lanes that share their
spine (models/tree.cut_subtrees, `halved`: every exit's path in the exit's
own half, the second half holding copies of the nodes above its first), P_k
is block-diagonal, the table holds its two diagonal blocks alone and m_k is
two products of half the size, side by side.

A FIFTH entry serves the third layout, the OBLIVIOUS ensemble
(models/tree.ObliviousEnsemble: CatBoost's symmetric trees, D splits and
2^D leaf values a tree): `predict_raw_effective_oblivious`. The trees go in
groups of 128, a tree a lane; per group

    v_d[r, j] = bin[r, split_feature[j, d]]   = X[rows, F] @ sel_d[F, 128]
    idx[r, j] = sum_d (v_d[r, j] > thr_d[j]) << d     the first split is
                                                      the LOW bit
    score[r] += sum_j leaf[idx[r, j], j]

with no sum that crosses lanes before the last. VECTOR LEAVES (C values a
leaf, the library's `MultiClass`): the same index against class c's leaf
rows for each c, margins [R, C] = bias[c] + scale x the class's sum, and
with `link` "softmax" their softmax on the device (stage `predict:link`,
the node list's). The jax.numpy form below is
the fallback and what a CPU runs; the Pallas kernel is
`ops/predict_oblivious.py`, dispatched by the same rule.
"""

from __future__ import annotations

import functools
import importlib
import typing

import jax
import jax.numpy as jnp

from ddt_tpu.telemetry.annotations import op_scope, traced_scope
from ddt_tpu.telemetry.costmodel import costed
from ddt_tpu.utils import device

_DEFAULT_ROW_CHUNK = 65_536


def _effective_arrays(feature, thr, is_leaf, leaf_value, max_depth):
    """Push leaves down the heap: returns (eff_feat, eff_thr, eff_val,
    eff_slot) where every node below a leaf inherits the leaf's value and
    original slot, leaf/inherited nodes carry feature=-1 and thr=+BIG.

    All ops are on tiny [T, N] arrays (N = 2^(D+1)-1); the per-level parent
    indexing uses STATIC index vectors, which XLA lowers to cheap slices.

    `leaf_value=None` skips the value chain entirely (eff_val comes back
    None) — `traverse` only needs slots, and the old throwaway
    `jnp.zeros`-shaped value array bought nothing but flops.
    """
    T, N = feature.shape
    big = (
        jnp.asarray(jnp.inf, thr.dtype)
        if jnp.issubdtype(thr.dtype, jnp.floating)
        else jnp.asarray(2 ** 30, thr.dtype)
    )
    dead = is_leaf
    eff_feat = jnp.where(dead, -1, feature)
    eff_thr = jnp.where(dead, big, thr)
    eff_val = leaf_value
    eff_slot = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (T, N))
    chained = is_leaf
    for d in range(1, max_depth + 1):
        lo, hi = (1 << d) - 1, (1 << (d + 1)) - 1
        par = (jnp.arange(lo, hi) - 1) // 2            # static indices
        pch = chained[:, par]                          # parent leaf/chained
        eff_feat = eff_feat.at[:, lo:hi].set(
            jnp.where(pch, -1, eff_feat[:, lo:hi]))
        eff_thr = eff_thr.at[:, lo:hi].set(
            jnp.where(pch, big, eff_thr[:, lo:hi]))
        if eff_val is not None:
            eff_val = eff_val.at[:, lo:hi].set(
                jnp.where(pch, eff_val[:, par], eff_val[:, lo:hi]))
        eff_slot = eff_slot.at[:, lo:hi].set(
            jnp.where(pch, eff_slot[:, par], eff_slot[:, lo:hi]))
        chained = chained.at[:, lo:hi].set(pch | is_leaf[:, lo:hi])
    return eff_feat, eff_thr, eff_val, eff_slot


def _select_level(k, table):
    """table[t, k[t, r]] for a level-local table [T, w] — one-hot
    compare+reduce (exact: k matches exactly one lane)."""
    w = table.shape[1]
    noh = k[:, :, None] == jnp.arange(w, dtype=jnp.int32)[None, None, :]
    zero = jnp.zeros((), table.dtype)
    return jnp.sum(jnp.where(noh, table[:, None, :], zero), axis=-1)


def _descend(eff_feat, eff_thr, Xc, max_depth, dl=None,
             missing_bin_value=-1, cat_node=None):
    """Relative node index at the bottom level: int32 [T, R].

    Per-level formulation: one-hot select of the row's (feature, thr) from
    the level slice, then a feature one-hot select of the bin value. Used
    for float (raw-threshold) data; the binned fast path is _descend_comp.

    `dl` ([T, N] bool) enables missing-value routing: rows whose selected
    value is missing — bin == missing_bin_value for integer data, NaN for
    float data — follow the node's learned default direction. Pushed-down
    leaf nodes select fv = 0 (feature=-1 matches no lane), which is neither
    the reserved bin nor NaN, so they stay on the always-left path.

    `cat_node` ([T, N] bool) marks categorical one-vs-rest nodes: the
    matched bin goes LEFT (fv != thr goes right). Gated on eff_feat >= 0
    so pushed-down leaf nodes (thr = +BIG, fv = 0) stay always-left.
    """
    Tc = eff_feat.shape[0]
    R, F = Xc.shape
    binned = jnp.issubdtype(Xc.dtype, jnp.integer)
    k = jnp.zeros((Tc, R), jnp.int32)
    f_iota = jnp.arange(F, dtype=jnp.int32)[None, None, :]
    for d in range(max_depth):
        lo, w = (1 << d) - 1, 1 << d
        feat_r = _select_level(k, eff_feat[:, lo:lo + w])         # [T, R]
        thr_r = _select_level(k, eff_thr[:, lo:lo + w])
        foh = feat_r[:, :, None] == f_iota                        # [T, R, F]
        fv = jnp.sum(
            jnp.where(foh, Xc[None, :, :], jnp.zeros((), Xc.dtype)), axis=-1
        )
        go = fv > thr_r
        if cat_node is not None:
            cat_r = _select_level(
                k, cat_node[:, lo:lo + w].astype(jnp.int32)).astype(bool)
            go = jnp.where(cat_r & (feat_r >= 0), fv != thr_r, go)
        if dl is not None:
            miss = (fv == missing_bin_value) if binned else jnp.isnan(fv)
            dl_r = _select_level(
                k, dl[:, lo:lo + w].astype(jnp.int32)).astype(bool)
            go = jnp.where(miss, ~dl_r, go)
        k = 2 * k + go.astype(jnp.int32)
    return k


def _descend_comp(eff_feat, eff_thr, Xc, max_depth, dl=None,
                  missing_bin_value=-1, cat_node=None):
    """Binned fast path: relative node index at the bottom level, [R, T].

    Precomputes the comparison bit of EVERY internal node for every row in
    one MXU matmul — colval[(t,n), r] = Xc[r, feat[t,n]] via the feature
    one-hot (exact: bin values <= 255 are exact in bf16, and the one-hot
    contraction selects a single element) — then descends by selecting the
    path node's bit per level (2 VPU ops/level vs ~3+(F/2^d)·3 for the
    per-level selects). Returns k ROW-MAJOR [R, T] (the caller's vals/class
    accumulation contracts over T)."""
    Tc, N = eff_feat.shape
    R, F = Xc.shape
    n_int = (1 << max_depth) - 1          # internal nodes
    foh = (
        eff_feat[:, :n_int, None]
        == jnp.arange(F, dtype=jnp.int32)[None, None, :]
    ).astype(jnp.bfloat16)                # [T, Nint, F]; feat=-1 -> zero row
    colval = jax.lax.dot_general(
        Xc.astype(jnp.bfloat16), foh.reshape(Tc * n_int, F),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.bfloat16,   # bins <= 255: exact in bf16
    ).reshape(R, Tc, n_int)               # [R, T, Nint] exact bin values
    comp = colval > eff_thr[None, :, :n_int].astype(jnp.bfloat16)
    if cat_node is not None:
        # One-vs-rest nodes: the matched bin (exact in bf16) goes left.
        # Gate on eff_feat >= 0 so pushed-down leaves stay always-left.
        cat_eff = cat_node[:, :n_int] & (eff_feat[:, :n_int] >= 0)
        comp = jnp.where(
            cat_eff[None, :, :],
            colval != eff_thr[None, :, :n_int].astype(jnp.bfloat16), comp)
    if dl is not None:
        # Missing rows (the reserved bin, exact in bf16) follow the node's
        # learned direction; pushed-down leaves have colval=0, never the
        # reserved bin.
        miss = colval == jnp.bfloat16(missing_bin_value)
        comp = jnp.where(miss, ~dl[None, :, :n_int], comp)
    k = jnp.zeros((R, Tc), jnp.int32)
    for d in range(max_depth):
        lo, w = (1 << d) - 1, 1 << d
        noh = k[:, :, None] == jnp.arange(w, dtype=jnp.int32)[None, None, :]
        go = jnp.any(noh & comp[:, :, lo:lo + w], axis=-1)
        k = 2 * k + go.astype(jnp.int32)
    return k


@functools.partial(jax.jit, static_argnames=("max_depth",))
def traverse(
    feature: jax.Array,        # int32 [T, N]
    thr: jax.Array,            # [T, N] int32 bins or float32 raw thresholds
    is_leaf: jax.Array,        # bool  [T, N]
    Xc: jax.Array,             # [R, F] int32 (binned) or float32 (raw)
    max_depth: int,
) -> jax.Array:
    """Leaf slot per (tree, row): int32 [T, R] (the ORIGINAL heap slot the
    row lands in, as with explicit frozen-node traversal).

    Routed through the shared effective-arrays helper with leaf_value=None
    — no throwaway value array is allocated or pushed down; persistent
    cross-call reuse of the pushdown lives one level up
    (models/tree.CompiledEnsemble + the backend cache)."""
    eff_feat, eff_thr, _, eff_slot = _effective_arrays(
        feature, thr, is_leaf, None, max_depth)
    k = _descend(eff_feat, eff_thr, Xc, max_depth)
    lo = (1 << max_depth) - 1
    return _select_level(k, eff_slot[:, lo:])


def resolve_use_pallas(use_pallas, binned: bool, fits) -> bool:
    """What is common to every layout's kernel-or-twin rule
    (`kernel_serves` of its kernel module). None = auto: the Pallas kernel
    is taken when the data is binned, a real TPU backs the computation and
    `fits()` says so: a zero-argument callable the layout HANDS over, its
    own module's budget predicate (`predict_pallas_fits`,
    `predict_paths_fits`, `predict_oblivious_fits`), asked on the auto path
    alone. Explicit True demands the kernel (binned data required: raises
    otherwise; off-TPU it runs in interpret mode, the test contract; past
    the budget the kernel's dispatcher refuses by name); explicit False
    always takes the jax.numpy form."""
    if use_pallas is None:
        return bool(binned and device.platform() == "tpu" and fits())
    if use_pallas and not binned:
        raise ValueError(
            "use_pallas=True requires binned (integer) data; the Pallas "
            "traversal kernel has no raw-threshold form — use the one-hot "
            "path for float features")
    return bool(use_pallas)


# cfg.predict_impl as a rule's `use_pallas` (None = auto). "lut" / "lut4"
# are the heap layout's quantized tiers: their fallback, and what the other
# layouts serve them by, is the f32 auto value.
USE_PALLAS = {"auto": None, "pallas": True, "onehot": False,
              "lut": None, "lut4": None}


class ScoringProgram(typing.NamedTuple):
    """What a layout's entry makes of one compiled model for a backend
    (`layout_entry`): all that lies between `ens.compile()` and the chunk
    loop and knows the layout."""

    plan: typing.Any       # how the tables meet the kernel; the protocol of
    #   TablePlan / PathPlan / ObliviousPlan: `blocks` (0: the jax.numpy
    #   form scores), `step_rows(rows)`, `table_bytes`, `span_counts()` (the
    #   `ddt:predict:ensemble` span's, in order), `root_counts()`
    tables: tuple          # the host tables, packed, in upload order
    fn: typing.Callable    # fn(*device tables, rows[, entry=]): `entry` with
    #   the model's static arguments bound, kernel-or-twin among them (bool)
    entry: typing.Any      # the jitted entry of this module, whose name is
    #   the program's (None: a quantized tier, no stage map)
    columns: int           # of the answer: 1 = [rows], else [rows, classes]
    classes: int
    tier: str = "f32"      # what serves: or the heap's "lut" / "lut4"
    fill: typing.Callable = iter   # tables -> what goes up, each made as it
    #   is asked for (a padded copy is gone when its transfer is)


class Layout(typing.NamedTuple):
    module: str        # the kernel module that owns it: the entry
    #   `scoring_program`, the rule `kernel_serves`, `PHASES_COUNTS`
    links: tuple = ()  # the losses whose link function its program takes
    #   on the device (`predict_raw(..., link=True)`, stage `predict:link`)


# The ONE table of scoring layouts, by the `layout` a model and its
# compiled form name (models/tree.py). A layout is a row here, its kernel
# module and its model class: backend, rule and CLI know none by name.
LAYOUTS = {
    "heap": Layout("ddt_tpu.ops.predict_pallas"),
    "node_list": Layout("ddt_tpu.ops.predict_paths", ("softmax",)),
    "oblivious": Layout("ddt_tpu.ops.predict_oblivious", ("softmax",)),
}


def layout_entry(layout: str) -> typing.Callable:
    """The ONE lookup: `scoring_program(ce, n_features, row_dtype,
    predict_impl, link) -> ScoringProgram` of the kernel module that owns
    `layout` (imported here): the program that scores rows of `n_features`
    columns of `row_dtype` with the compiled model `ce`. It resolves kernel
    or twin on the host, once a model (`predict_impl`: cfg.predict_impl),
    and binds the answer; `link`: the program ends in the model's link
    function (`Layout.links`)."""
    return importlib.import_module(LAYOUTS[layout].module).scoring_program


def phases_counts() -> tuple:
    """The plan counts `cli predict` repeats in `phases_ms`: every
    registered layout's `PHASES_COUNTS`, the heap's first."""
    return tuple(dict.fromkeys(
        k for lay in LAYOUTS.values()
        for k in importlib.import_module(lay.module).PHASES_COUNTS))


def _predict_effective(
    eff_feat, eff_thr, bot_val, cls_oh, Xc, *,
    max_depth: int, learning_rate, base, n_classes: int,
    tree_chunk: int, row_chunk: int | None,
    missing_bin_value: int, eff_dl=None, eff_cat=None,
    use_pallas=None,
):
    """Scoring core on PRE-pushed-down, tree-padded arrays.

    eff_feat/eff_thr [Tpad, N], bot_val [Tpad, 2^D] (bottom level of the
    pushed-down values), cls_oh [Tpad, C] (round-major class one-hot;
    padded trees carry value 0 so their class column gains exactly 0.0).
    eff_dl/eff_cat are the pushdown-aligned routing masks or None. The
    doubly chunked scan is unchanged from the original predict_raw body —
    the pushdown just moved out (models/tree.CompiledEnsemble computes it
    once per model on host; predict_raw still computes it in-trace)."""
    binned = bool(jnp.issubdtype(Xc.dtype, jnp.integer))
    R, F = Xc.shape
    C = n_classes
    if R == 0:
        out = jnp.full((0, C), base, jnp.float32)
        return out[:, 0] if C == 1 else out
    Tpad = eff_feat.shape[0]
    if use_pallas is not False:     # (the one-hot form imports no kernel)
        from ddt_tpu.ops import predict_pallas

        if predict_pallas.kernel_serves(
                use_pallas, binned, max_depth, F, C,
                (eff_dl is not None) + (eff_cat is not None)):
            return predict_pallas.predict_effective_pallas(
                eff_feat, eff_thr, bot_val, cls_oh, Xc,
                max_depth=max_depth, learning_rate=learning_rate, base=base,
                n_classes=C, tree_chunk=tree_chunk,
                missing_bin_value=missing_bin_value,
                eff_dl=eff_dl, eff_cat=eff_cat,
            )
    if binned:
        # The kernel above takes the uint8 chunk as it is; the one-hot
        # form compares int32 bins.
        with traced_scope("predict:widen"):
            Xc = Xc.astype(jnp.int32)
    if row_chunk is None:
        # The binned comparison-matrix descent materialises
        # [Rc, chunk, Nint] bits; default to a smaller row chunk there to
        # bound it. Round-5 interleaved sweep (docs/PERF.md): the
        # row_chunk axis is flat within ~4% over 4k-16k while
        # tree_chunk=64 dominates — (64, 8192) sits on the plateau.
        # None is the only "use default" value — an explicit row_chunk,
        # including 65536, is always honored.
        row_chunk = 8_192 if binned else _DEFAULT_ROW_CHUNK
    n_tc = Tpad // tree_chunk
    use_missing = eff_dl is not None
    use_cat = eff_cat is not None
    with traced_scope("predict:tables"):
        featp = eff_feat.reshape(n_tc, tree_chunk, -1)
        thrp = eff_thr.reshape(n_tc, tree_chunk, -1)
        if use_missing:
            dlp = eff_dl.reshape(n_tc, tree_chunk, -1)
        if use_cat:
            catp = eff_cat.reshape(n_tc, tree_chunk, -1)
        valp = bot_val.reshape(n_tc, tree_chunk, -1)  # bottom level only
        cls_ohp = cls_oh.reshape(n_tc, tree_chunk, C)

    row_chunk = min(row_chunk, R)
    n_rc = -(-R // row_chunk)
    rpad = n_rc * row_chunk - R
    with traced_scope("predict:widen"):
        Xp = jnp.pad(Xc, ((0, rpad), (0, 0))).reshape(n_rc, row_chunk, F)

    def row_body(_, xrc):
        def tree_body(acc, args):
            f, t, v, coh = args[:4]
            rest = list(args[4:])
            dlc = rest.pop(0) if use_missing else None
            catc = rest.pop(0) if use_cat else None
            with traced_scope("predict:traverse"):
                if binned:
                    k = _descend_comp(f, t, xrc, max_depth, dl=dlc,
                                      missing_bin_value=missing_bin_value,
                                      cat_node=catc)
                else:
                    k = _descend(f, t, xrc, max_depth, dl=dlc,
                                 missing_bin_value=missing_bin_value,
                                 cat_node=catc)
            with traced_scope("predict:accumulate"):
                if binned:
                    W = v.shape[1]                               # [Rc, chunk]
                    noh = (
                        k[:, :, None]
                        == jnp.arange(W, dtype=jnp.int32)[None, None, :]
                    )
                    vals = jnp.sum(
                        jnp.where(noh, v[None, :, :], 0.0), axis=-1
                    )                                            # [Rc, chunk]
                    contract = (((1,), (0,)), ((), ()))
                else:
                    vals = _select_level(k, v)                   # [chunk, Rc]
                    contract = (((0,), (0,)), ((), ()))
                # Scatter chunk sums into classes: one_hot [chunk, C]
                # matmul.
                acc = acc + jax.lax.dot_general(
                    vals, coh, contract,
                    preferred_element_type=jnp.float32,
                    # Exact: one operand is a 0/1 one-hot, so HIGHEST costs
                    # little and keeps predictions bit-stable across
                    # platforms.
                    precision=jax.lax.Precision.HIGHEST,
                )                                                # [Rc, C]
            return acc, None

        acc0 = jnp.zeros((row_chunk, C), jnp.float32)
        xs = [featp, thrp, valp, cls_ohp]
        if use_missing:
            xs.append(dlp)
        if use_cat:
            xs.append(catp)
        acc, _ = jax.lax.scan(tree_body, acc0, tuple(xs))
        return None, acc

    # The two scans' own bookkeeping is the traversal's; the class dot
    # inside them names itself (the innermost scope is an instruction's
    # stage: telemetry.annotations.device_stages).
    with traced_scope("predict:traverse"):
        _, accs = jax.lax.scan(row_body, None, Xp)           # [n_rc, Rc, C]
    with traced_scope("predict:accumulate"):
        out = base + learning_rate * accs.reshape(n_rc * row_chunk, C)[:R]
        return out[:, 0] if C == 1 else out


@costed("predict", phase="predict")
@functools.partial(
    jax.jit,
    static_argnames=("max_depth", "n_classes", "tree_chunk", "row_chunk",
                     "missing_bin_value", "use_pallas"),
)
@op_scope("predict")
def predict_raw_effective(
    eff_feat: jax.Array,       # [Tpad, N] pushed-down features
    eff_thr: jax.Array,        # [Tpad, N] pushed-down thresholds
    bot_val: jax.Array,        # float32 [Tpad, 2^D] bottom-level values
    cls_oh: jax.Array,         # float32 [Tpad, C] class one-hot
    Xc: jax.Array,             # [R, F]
    max_depth: int,
    learning_rate: float,
    base: float,
    n_classes: int = 1,
    tree_chunk: int = 64,
    row_chunk: int | None = None,
    eff_dl: jax.Array | None = None,
    missing_bin_value: int = -1,
    eff_cat: jax.Array | None = None,
    use_pallas: bool | None = None,
) -> jax.Array:
    """predict_raw on a CompiledEnsemble's precomputed arrays — no
    pushdown, no padding, no class-one-hot construction in-trace. The
    backend keeps these arrays device-resident across calls (a rebuild
    and re-upload is the span `ddt:predict:ensemble`, 18 ms for 1000
    trees on the v5e: PERF.md). Tpad must be a multiple of tree_chunk
    (CompiledEnsemble.build guarantees it)."""
    return _predict_effective(
        eff_feat, eff_thr, bot_val, cls_oh, Xc,
        max_depth=max_depth, learning_rate=learning_rate, base=base,
        n_classes=n_classes, tree_chunk=tree_chunk, row_chunk=row_chunk,
        missing_bin_value=missing_bin_value, eff_dl=eff_dl,
        eff_cat=eff_cat, use_pallas=use_pallas,
    )


@costed("predict", phase="predict")
@functools.partial(
    jax.jit,
    static_argnames=("max_depth", "n_classes", "tree_chunk", "row_chunk",
                     "missing_bin_value", "use_pallas"),
)
@op_scope("predict")
def predict_raw(
    feature: jax.Array,        # int32 [T, N]
    thr: jax.Array,            # [T, N]
    is_leaf: jax.Array,        # bool [T, N]
    leaf_value: jax.Array,     # float32 [T, N]
    Xc: jax.Array,             # [R, F]
    max_depth: int,
    learning_rate: float,
    base: float,
    n_classes: int = 1,        # 1 = scalar output; C = softmax round-major
    tree_chunk: int = 64,
    row_chunk: int | None = None,
    default_left: jax.Array | None = None,   # bool [T, N]; None = no
    #   missing-value handling (models trained without the reserved bin)
    missing_bin_value: int = -1,             # reserved NaN bin id (binned
    #   data); raw float data detects NaN directly
    cat_node: jax.Array | None = None,       # bool [T, N]; one-vs-rest
    #   split nodes ("bin == thr goes left", cfg.cat_features). For raw
    #   float data the caller must put the BIN id in thr for these nodes
    #   (categorical columns carry bin ids in both representations).
    use_pallas: bool | None = None,          # None = auto (binned data on
    #   a real TPU at a VMEM-fitting shape); the one-hot path is the
    #   fallback. ops/predict_pallas.py documents the kernel.
) -> jax.Array:
    """Raw margin scores: [R] (n_classes==1) or [R, C].

    Doubly lax.scan-chunked (rows outer, trees inner); per-chunk leaf values
    are accumulated into the per-class output (round-major tree->class
    interleave for softmax, matching reference/numpy_trainer.fit).
    """
    T = feature.shape[0]               # on device where casts are free
    C = n_classes
    n_tc = -(-T // tree_chunk)
    tpad = n_tc * tree_chunk - T

    def pad_t(a, fill=0):
        return jnp.pad(a, ((0, tpad), (0, 0)), constant_values=fill)

    # Padded trees are all-leaf at the root with value 0 -> contribute 0.
    ef, et, ev, _ = _effective_arrays(
        pad_t(feature, -1), pad_t(thr), pad_t(is_leaf, True),
        pad_t(leaf_value), max_depth,
    )
    lo = (1 << max_depth) - 1
    # Class of tree t is t % C (round-major interleave).
    cls = jnp.arange(n_tc * tree_chunk, dtype=jnp.int32) % C
    cls_oh = jax.nn.one_hot(cls, C, dtype=jnp.float32)   # [Tpad, C]
    return _predict_effective(
        ef, et, ev[:, lo:], cls_oh, Xc,
        max_depth=max_depth, learning_rate=learning_rate, base=base,
        n_classes=C, tree_chunk=tree_chunk, row_chunk=row_chunk,
        missing_bin_value=missing_bin_value,
        eff_dl=pad_t(default_left) if default_left is not None else None,
        eff_cat=pad_t(cat_node) if cat_node is not None else None,
        use_pallas=use_pallas,
    )


# Trees and rows a step of the jax.numpy path form takes: its three
# [trees, rows, W] float32 intermediates are 67 MB each at 256 lanes.
_PATHS_TREE_CHUNK, _PATHS_ROW_CHUNK = 8, 8_192


def _predict_paths(sel, planes, paths, Xc, *, learning_rate, base,
                   missing_routes: bool = False, leaves=None, chain=None,
                   mean: bool = False, cat=None, sets=None):
    """The path-matrix form (module docstring) in plain jax.numpy: trees in
    chunks of _PATHS_TREE_CHUNK, rows in chunks of _PATHS_ROW_CHUNK, so the
    [trees, rows, W] intermediates stay bounded. The operands are
    widened to float32 (XLA's CPU backend has no bf16 x bf16 = f32 dot);
    every value is one bfloat16 holds, so a TPU's default one-pass matmul
    of them is exact too. `missing_routes`: planes' row 3 is read.
    `leaves` and `chain`: the SUB-TREE form, `_predict_chain`. `cat`:
    (cat_expand, cat_bins), the model carries CATEGORY SETS: `sel`'s K rows
    beside the ordinal ones are the one-hot's, made here a chunk of rows,
    the ordinal ones behind the first `sets.ordinal_at` blocks of them
    (`sets`: predict_paths.CatSets; its spans are the kernel's to skip by,
    every product here is whole)."""
    if chain is not None:
        return _predict_chain(sel, planes, paths, leaves, Xc, chain=chain,
                              learning_rate=learning_rate, base=base,
                              missing_routes=missing_routes, mean=mean)
    T, Fk, W = sel.shape
    Fp = cat[0].shape[1] if cat else Fk
    R, F = Xc.shape
    tree_chunk = _PATHS_TREE_CHUNK
    n_tc = -(-T // tree_chunk)
    # trees that fill the last chunk: no node, no leaf (len -1: no match)
    t_fill = ((0, n_tc * tree_chunk - T), (0, 0), (0, 0))
    with traced_scope("predict:tables"):
        selp = jnp.pad(sel.astype(jnp.float32), t_fill).reshape(
            n_tc, tree_chunk, Fk, W)
        planesp = jnp.pad(planes, t_fill, constant_values=-1.0).reshape(
            n_tc, tree_chunk, 8, W)
        pathsp = jnp.pad(paths.astype(jnp.float32), t_fill).reshape(
            n_tc, tree_chunk, W, W)
    row_chunk = min(_PATHS_ROW_CHUNK, R)
    n_rc = -(-R // row_chunk)
    with traced_scope("predict:widen"):
        Xp = jnp.pad(Xc.astype(jnp.float32),
                     ((0, n_rc * row_chunk - R), (0, Fp - F))
                     ).reshape(n_rc, row_chunk, Fp)

    def row_body(_, xrc):
        if cat:
            with traced_scope("predict:catset"):
                expand, bins = cat
                hot = [jnp.where(jnp.dot(xrc, expand[b].astype(jnp.float32))
                                 == bins[b, 0:1, :], 1.0, 0.0)
                       for b in range(expand.shape[0])]
                # (no ordinal K rows where every node asks a set)
                at = sets.ordinal_at if sets else 0
                xrc = jnp.concatenate(
                    hot[:at] + [xrc] * (Fk > 128 * len(hot)) + hot[at:],
                    axis=1)

        def tree_body(acc, args):
            a, pl_, p = args
            with traced_scope("predict:traverse"):
                v = jnp.einsum("rf,tfn->trn", xrc, a,
                               preferred_element_type=jnp.float32)
                right = v > pl_[:, None, 0, :]
                if missing_routes:
                    right &= v < pl_[:, None, 3, :]
                s = jnp.where(right, 1.0, -1.0)
                m = jnp.einsum("trn,tnl->trl", s, p,
                               preferred_element_type=jnp.float32)
            with traced_scope("predict:accumulate"):
                hit = m == pl_[:, None, 1, :]
                acc = acc + jnp.sum(
                    jnp.where(hit, pl_[:, None, 2, :], 0.0), axis=(0, 2))
            return acc, None

        acc, _ = jax.lax.scan(tree_body, jnp.zeros((row_chunk,), jnp.float32),
                              (selp, planesp, pathsp))
        return None, acc

    with traced_scope("predict:traverse"):
        _, accs = jax.lax.scan(row_body, None, Xp)
    with traced_scope("predict:accumulate"):
        return base + learning_rate * accs.reshape(n_rc * row_chunk)[:R]


def _predict_chain(sel, planes, paths, leaves, Xc, *, chain, learning_rate,
                   base, missing_routes: bool, mean: bool):
    """The SUB-TREE form (module docstring, "The chain") in plain
    jax.numpy: one sub-tree a step of the scan, in the table's order (a
    tree's sub-trees in a row, parents first), rows in chunks of
    _PATHS_ROW_CHUNK; the carry is the kernel's, the class lanes' sums and
    the activity lanes. Same tables, same equations, another order of the
    float32 adds."""
    from ddt_tpu.ops.predict_paths import fold_leaf_pieces

    S, Fp, W = sel.shape
    R, F = Xc.shape
    cl, al, hand = chain.class_lanes, chain.act_lanes, chain.at_hand
    row_chunk = min(_PATHS_ROW_CHUNK, R)
    n_rc = -(-R // row_chunk)
    with traced_scope("predict:widen"):
        Xp = jnp.pad(Xc.astype(jnp.float32),
                     ((0, n_rc * row_chunk - R), (0, Fp - F))
                     ).reshape(n_rc, row_chunk, Fp)
    with traced_scope("predict:tables"):
        first = (jnp.arange(al) == hand).astype(jnp.float32)[None, :]

    def row_body(_, xrc):
        def subtree_body(carry, args):
            acc, act = carry
            a_sel, pl_, p, lv = args
            with traced_scope("predict:traverse"):
                v = jnp.dot(xrc, a_sel.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
                right = v > pl_[None, 0, :]
                if missing_routes:
                    right &= v < pl_[None, 3, :]
                s, p = jnp.where(right, 1.0, -1.0), p.astype(jnp.float32)
                if chain.halved:    # the diagonal blocks, side by side
                    h = W // 2
                    m = jnp.concatenate(
                        [jnp.dot(s[:, j * h:(j + 1) * h],
                                 p[:, j * h:(j + 1) * h],
                                 preferred_element_type=jnp.float32)
                         for j in range(2)], axis=1)
                else:
                    m = jnp.dot(s, p, preferred_element_type=jnp.float32)
                e = jnp.where(m == pl_[None, 1, :], 1.0, 0.0)
                y = jnp.dot(e, lv.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
            with traced_scope("predict:accumulate"):
                act = jnp.where(pl_[4, 0] > 0.0, first, act)
                a = act[:, hand:hand + 1]
                if chain.shared:    # ONE tile: the links behind the pieces
                    ay = a * y
                    acc = acc + ay
                    act = jnp.roll(act, -1, axis=1) + ay
                else:
                    acc = acc + a * y[:, :cl]
                    act = jnp.roll(act, -1, axis=1) + a * y[:, cl:]
            return (acc, act), None

        (acc, _), _ = jax.lax.scan(
            subtree_body, (jnp.zeros((row_chunk, cl), jnp.float32),
                           jnp.zeros((row_chunk, al), jnp.float32)),
            (sel, planes, paths, leaves))
        return None, acc

    with traced_scope("predict:traverse"):
        _, accs = jax.lax.scan(row_body, None, Xp)
    with traced_scope("predict:accumulate"):
        acc = accs.reshape(n_rc * row_chunk, cl)[:R]
    return fold_leaf_pieces(acc, chain, learning_rate, base, mean)


@costed("predict", phase="predict")
@functools.partial(
    jax.jit,
    static_argnames=("learning_rate", "base", "use_pallas",
                     "missing_routes", "n_trees", "leaf_columns", "mean",
                     "select_spans", "link", "cat_ordinal_at"),
)  # (cat_expand / cat_bins: arrays, their shapes say the rest)
@op_scope("predict")
def predict_raw_effective_paths(
    sel: jax.Array,            # bf16 [T, Fp, W] feature one-hot of the nodes
    #   (or, with its planes, predict_paths.pack_select's where the kernel
    #   serves and its select answers two nodes a lane)
    planes: jax.Array,         # f32 [T, 8, W] rows: thr, path length, value, up
    paths: jax.Array,          # bf16 [T, W, W] signed path matrix
    Xc: jax.Array,             # [R, F] integer bins
    learning_rate: float,
    base: float,
    use_pallas: bool | None = None,
    missing_routes: bool = False,
    leaves: jax.Array | None = None,   # bf16 [S, W, E]: the exits
    n_trees: int = 0,
    leaf_columns: int = 1,
    mean: bool = False,
    select_spans: tuple = (),
    link: str = "none",
    cat_expand: jax.Array | None = None,   # bf16 [B, Fp, 128]: category
    cat_bins: jax.Array | None = None,     # f32 [B, 8, 128]     sets
    cat_ordinal_at: int = 0,
) -> jax.Array:
    """Raw margins [R] of a node-list ensemble from its compiled tables
    (models/tree.CompiledNodeList): the path-matrix form, by the Pallas
    kernel (ops/predict_paths.py) where its rule, `kernel_serves`, says so
    (`use_pallas` a bool: decided already, nothing is asked) and by
    `_predict_paths` otherwise. Binned rows only, which the kernel takes at
    the width they come in (uint8 from api.predict: nothing is widened in
    XLA). `missing_routes`: the model carries learned NaN directions
    (`CompiledNodeList.missing_bin_value` >= 0); without them the program
    is the one-compare program. With `leaves` the tables are the SUB-TREE
    form's (one entry a sub-tree of the `n_trees` trees, `leaf_columns`
    values a leaf) and the answer of vector leaves (`mean`) is the mean
    over the trees, float32 [R, leaf_columns]; of scalar leaves with
    `leaf_columns` C > 1 (softmax's round-major trees) the margins
    [R, C], and with `link` "softmax" their softmax, the class
    probabilities, taken here on the device (stage `predict:link`);
    `select_spans` (`CompiledNodeList.select_spans`) the K-blocks of the
    select each lane tile of a sub-tree (of an uncut tree with category
    sets) reads, which the kernel alone asks for. `cat_expand` and
    `cat_bins`: the model carries CATEGORY SETS (`CompiledNodeList`: the
    uncut form alone), `sel` the one-hot blocks' K rows with the ordinal
    ones behind the first `cat_ordinal_at` of them."""
    if not jnp.issubdtype(Xc.dtype, jnp.integer):
        raise ValueError("the path-matrix form scores binned (integer) rows")
    from ddt_tpu.ops import predict_paths

    chain = None
    if leaves is not None:
        chain = predict_paths.chain_of(n_trees, leaf_columns, leaves.shape[2],
                                       select_spans, paths.shape)
    if link not in ("none", "softmax") or (
            link == "softmax" and (mean or leaf_columns < 2)):
        raise ValueError(f"link {link!r} of {leaf_columns} leaf columns"
                         + " of an averaged forest" * mean)
    if Xc.shape[0] == 0:
        wide = mean or leaf_columns > 1
        return jnp.full((0, leaf_columns) if wide else (0,),
                        0.0 if mean else base, jnp.float32)
    form = dict(learning_rate=learning_rate, base=base,
                missing_routes=missing_routes, leaves=leaves, chain=chain,
                mean=mean)
    if cat_expand is not None:
        if chain is not None:
            raise ValueError("category sets in the sub-tree form")
        form["cat"] = (cat_expand, cat_bins)
        form["sets"] = predict_paths.CatSets(
            cat_expand.shape[0], sel.shape[1], select_spans, cat_ordinal_at)
    if predict_paths.kernel_serves(use_pallas, planes.shape[2], Xc.shape[1],
                                   Xc.dtype, chain, form.get("sets")):
        out = predict_paths.predict_paths_pallas(sel, planes, paths, Xc,
                                                 **form)
    else:
        out = _predict_paths(sel, planes, paths, Xc, **form)
    return _linked(out, link)


def _linked(margins: jax.Array, link: str) -> jax.Array:
    """The answer of a scoring program that ends in `link`: "softmax" the
    class probabilities of margins [R, C], taken here on the device (stage
    `predict:link`); "none" the margins."""
    if link == "softmax":
        with traced_scope("predict:link"):
            return jax.nn.softmax(margins, axis=1)
    return margins


# Rows a step of the jax.numpy oblivious form takes at most (the float32
# copy of its rows is 64 MB at 2000 columns), and the elements of its
# [rows, 2^D, 128] leaf one-hot a step may hold (4,096 rows at depth 6).
_OBLIVIOUS_ROW_CHUNK, _OBLIVIOUS_ONEHOT = 8_192, 1 << 25


def _predict_oblivious(sel, thr, leaf, Xc, *, scale, bias):
    """The oblivious form (module docstring) in plain jax.numpy: a group of
    128 trees a step of the scan, rows in chunks of _OBLIVIOUS_ROW_CHUNK.
    The operands are widened to float32 (XLA's CPU backend has no bf16 x
    bf16 = f32 dot); every value is one bfloat16 holds, so a TPU's default
    one-pass matmul of them is exact too."""
    G, D, Fp, W = sel.shape
    R, F = Xc.shape
    C = leaf.shape[1] >> D              # the columns of a leaf
    row_chunk = min(R, _OBLIVIOUS_ROW_CHUNK,
                    max(512, _OBLIVIOUS_ONEHOT // (W << D)))
    n_rc = -(-R // row_chunk)
    with traced_scope("predict:widen"):
        Xp = jnp.pad(Xc.astype(jnp.float32),
                     ((0, n_rc * row_chunk - R), (0, Fp - F))
                     ).reshape(n_rc, row_chunk, Fp)

    def row_body(_, xrc):
        def group_body(acc, args):
            a, t, lv = args
            with traced_scope("predict:traverse"):
                v = jnp.einsum("rf,dfj->drj", xrc, a.astype(jnp.float32),
                               preferred_element_type=jnp.float32)
                idx = jnp.zeros((row_chunk, W), jnp.int32)
                for d in range(D):
                    idx |= (v[d] > t[d:d + 1]).astype(jnp.int32) << d
            with traced_scope("predict:accumulate"):
                leaves = jnp.arange(1 << D, dtype=jnp.int32)
                hit = idx[:, None, :] == leaves[None, :, None]  # [Rc, 2^D, W]
                if C == 1:
                    return acc + jnp.sum(jnp.where(hit, lv[None], 0.0),
                                         axis=(1, 2)), None
                # a class at a time against the ONE index: [C, Rc]
                return acc + jnp.stack([
                    jnp.sum(jnp.where(hit, lv[None, c << D:(c + 1) << D],
                                      0.0), axis=(1, 2))
                    for c in range(C)]), None

        acc, _ = jax.lax.scan(
            group_body,
            jnp.zeros((row_chunk,) if C == 1 else (C, row_chunk),
                      jnp.float32),
            (sel, thr, leaf))
        return None, acc

    with traced_scope("predict:traverse"):
        _, accs = jax.lax.scan(row_body, None, Xp)
    with traced_scope("predict:accumulate"):
        if C == 1:
            return bias + scale * accs.reshape(n_rc * row_chunk)[:R]
        accs = accs.transpose(1, 0, 2).reshape(C, n_rc * row_chunk)[:, :R]
        return (jnp.asarray(bias, jnp.float32)[:, None] + scale * accs).T


@costed("predict", phase="predict")
@functools.partial(jax.jit, static_argnames=("scale", "bias", "use_pallas",
                                             "link"))
@op_scope("predict")
def predict_raw_effective_oblivious(
    sel: jax.Array,            # bf16 [G, D, Fp, 128] split d's feature one-hot
    thr: jax.Array,            # f32 [G, Dp, 128] split d's bin
    leaf: jax.Array,           # f32 [G, C 2^D, 128] leaf values, a tree a lane
    Xc: jax.Array,             # [R, F] integer bins
    scale: float,
    bias: "float | tuple",
    use_pallas: bool | None = None,
    link: str = "none",
) -> jax.Array:
    """Raw margins [R] of an oblivious ensemble from its compiled tables
    (models/tree.CompiledOblivious): by the Pallas kernel
    (ops/predict_oblivious.py) where its rule, `kernel_serves`, says so
    (`use_pallas` a bool: decided already, nothing is asked) and by
    `_predict_oblivious` otherwise. Binned rows only, which the kernel
    takes at the width they come in (uint8 from api.predict: nothing is
    widened in XLA). Of VECTOR LEAVES (`leaf` C 2^D rows a group, `bias` a
    tuple of C) the margins [R, C], and with `link` "softmax" their
    softmax, the class probabilities, taken here on the device (stage
    `predict:link`)."""
    if not jnp.issubdtype(Xc.dtype, jnp.integer):
        raise ValueError("the oblivious form scores binned (integer) rows")
    C = leaf.shape[1] >> sel.shape[1]
    if link not in ("none", "softmax") or (link == "softmax" and C < 2) or (
            C > 1 and len(bias) != C):
        raise ValueError(f"link {link!r} and a bias {bias!r} of {C} leaf "
                         "column(s)")
    if Xc.shape[0] == 0:
        return jnp.full((0,), bias, jnp.float32) if C == 1 else jnp.zeros(
            (0, C), jnp.float32)
    from ddt_tpu.ops import predict_oblivious

    if predict_oblivious.kernel_serves(use_pallas, sel.shape[1], Xc.shape[1],
                                       Xc.dtype, C):
        out = predict_oblivious.predict_oblivious_pallas(
            sel, thr, leaf, Xc, scale=scale, bias=bias)
    else:
        out = _predict_oblivious(sel, thr, leaf, Xc, scale=scale, bias=bias)
    return _linked(out, link)


def predict_proba(raw: jax.Array, loss: str) -> jax.Array:
    if loss == "logloss":
        return jax.nn.sigmoid(raw)
    if loss == "softmax":
        return jax.nn.softmax(raw, axis=1)
    return raw

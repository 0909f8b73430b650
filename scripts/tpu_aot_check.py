#!/usr/bin/env python
"""Compile-only check: does the installed compiler accept the TPU programs?

No chip is needed. jax + libtpu compile for a DESCRIBED v5e
(jax.experimental.topologies) from a CPU host, so a kernel the Mosaic
compiler refuses — a block shape, an accumulator width, a vector-layout
cast — is found here, for free, before chip time is spent on it. Run it
before each use of the chip:

    JAX_PLATFORMS=cpu python scripts/tpu_aot_check.py            # everything
    JAX_PLATFORMS=cpu python scripts/tpu_aot_check.py --only hist
    JAX_PLATFORMS=cpu python scripts/tpu_aot_check.py --only bosch --digest \
        [--tree <another checkout>]

`--digest` prints, for every kernel case and whole program, the SHA-1 of
its optimized HLO (source metadata and the kernels' serialized bodies cut)
and of its Mosaic modules without debug locations: two checkouts that
print the same pair run the same program (`--tree`: take `ddt_tpu` from
that checkout; the case tables stay this script's).

A compile is not a run: it says nothing of results, of memory at run time
or of speed. chip_smoke.py is the run.

What is compiled:

- every Pallas kernel, at the smoke (Higgs 1M x 28) and Covertype
  (200k x 54, 7 classes) shapes: `kernel_cases()` — the SAME table
  tests/test_tpu_lowering.py lowers with jax.export in tier-1 (its
  `default` cases), so the two checks cannot drift apart;
- the whole fused-rounds program TPUDevice builds
  (`TPUDevice._build_rounds_fn`) on one device, on a rows=4 mesh and on a
  2x2 (rows x features) mesh over the four described chips, and the
  scoring program (`TPUDevice._predict_entry`) of every cell of
  BENCHMARK.json: heap ensembles, node lists (the path-matrix form, its
  chained sub-trees with and without the link, category sets) and
  oblivious ensembles.

Exit 0 iff every default-dispatch case compiled; opt-in kernels
(grad_dtype=int8|int16, predict_impl=lut|lut4) are reported and do not
decide the exit code.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import os
import re
import sys
import time
import typing

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOPOLOGY = "v5e:2x2"

HIGGS = dict(rows=1_000_000, features=28)
COVERTYPE = dict(rows=200_000, features=54, classes=7)
# The CTR model's scoring chunk (benchmark config criteo-ctr-100t-d6).
CRITEO = dict(rows=2_000_000, features=39)
# LightGBM's Bosch model's scoring chunk (benchmark config
# bosch-lgbm-500t-255l): 968 columns, TPUDevice.predict_chunk_rows of them.
BOSCH = dict(chunk_rows=262_144, features=968)
# LightGBM's Allstate claims model (benchmark config
# allstate-lgbm-500t-255l-cat): 32 columns, 16 of them categorical, with the
# ids their sets may name (the two vehicle-model columns at the 254 that
# max_bin=255 keeps); the 16 others ordinal.
ALLSTATE = dict(features=32, categories=(
    (3, 75), (4, 254), (5, 254), (6, 10), (7, 3), (8, 6), (9, 3), (10, 3),
    (11, 6), (12, 4), (13, 4), (14, 2), (15, 3), (16, 11), (17, 11),
    (27, 15)))
# Eight columns ALL categorical (LightGBM's Expo model's shape): a model
# whose every node asks a set has no ordinal K row.
ALL_SETS = dict(features=8, categories=(
    (0, 12), (1, 31), (2, 7), (3, 24), (4, 26), (5, 254), (6, 254), (7, 10)))
# scikit-learn's MNIST forest's scoring chunk (benchmark config
# mnist-rf-100t-full): 784 pixel columns, TPUDevice.predict_chunk_rows.
FOREST = dict(chunk_rows=299_593, features=784)
# XGBoost's deep Covertype model's call (benchmark config
# covtype-xgb-softprob-d16): the set's own rows, one chunk.
XGB = dict(rows=581_012, features=54)
# CatBoost's Epsilon model's scoring chunk (benchmark config
# epsilon-catboost-8000t-d6): 2000 dense columns, 8000 trees of depth 6.
EPSILON = dict(chunk_rows=131_072, features=2000, n_trees=8000, depth=6)


class KernelCase(typing.NamedTuple):
    """One kernel at one shape: `build()` -> (traceable fn, [(shape, dtype)])."""

    name: str
    default: bool          # on the default dispatch (decides the verdict)
    build: typing.Callable


def _hist_case(rows, features, n_nodes, n_bins, grad_dtype="float32"):
    def build():
        import jax.numpy as jnp

        from ddt_tpu.ops.hist_pallas import build_histograms_pallas

        def fn(Xb, g, h, ni):
            return build_histograms_pallas(Xb, g, h, ni, n_nodes, n_bins)

        gd = jnp.dtype(grad_dtype)
        return fn, [((rows, features), jnp.uint8), ((rows,), gd),
                    ((rows,), gd), ((rows,), jnp.int32)]

    return build


def _random_ensemble(n_trees, depth, features, n_classes, missing, cat):
    """A full-depth random ensemble (seeded) as a models/tree.TreeEnsemble:
    the scoring kernels take the COMPILED (pushed-down) layout, so the
    cases go through the same ens.compile() the backend uses."""
    import numpy as np

    from ddt_tpu.models.tree import empty_ensemble

    rng = np.random.default_rng(7)
    loss = "softmax" if n_classes > 1 else "logloss"
    ens = empty_ensemble(
        n_trees, depth, features, 0.1, 0.0, loss, max(n_classes, 2),
        missing_bin=missing, n_bins=255,
        cat_features=(0, 1) if cat else ())
    n_int = (1 << depth) - 1
    ens.feature[:, :n_int] = rng.integers(0, features, (n_trees, n_int))
    ens.threshold_bin[:, :n_int] = rng.integers(0, 254, (n_trees, n_int))
    ens.is_leaf[:, n_int:] = True
    ens.leaf_value[:, n_int:] = rng.standard_normal(
        (n_trees, n_int + 1)).astype(np.float32)
    if missing:
        ens.default_left[:, :n_int] = rng.random((n_trees, n_int)) < 0.5
    return ens


def _predict_case(rows, features, n_trees, depth, n_classes=1,
                  missing=False, cat=False, tier="f32"):
    def build():
        import jax.numpy as jnp

        from ddt_tpu.ops import predict_lut, predict_pallas

        ens = _random_ensemble(n_trees, depth, features, n_classes,
                               missing, cat)
        ce = ens.compile(tree_chunk=64)
        if tier == "f32":
            ops = ce.arrays()
            use_missing = ce.eff_dl is not None
            use_cat = ce.eff_cat is not None

            def fn(ef, et, bv, coh, *rest):
                *opt, Xc = rest
                opt = list(opt)
                dl = opt.pop(0) if use_missing else None
                cn = opt.pop(0) if use_cat else None
                return predict_pallas.predict_effective_pallas(
                    ef, et, bv, coh, Xc,
                    max_depth=ce.max_depth, learning_rate=ce.learning_rate,
                    base=ce.base_score, n_classes=ce.n_classes_out,
                    tree_chunk=ce.tree_chunk,
                    missing_bin_value=ce.missing_bin_value,
                    eff_dl=dl, eff_cat=cn)
        elif tier in ("lut", "lut_int8leaf"):
            tables = ce.quantize(
                leaf_dtype="int8" if tier == "lut_int8leaf" else "float16")
            ops = predict_lut.lut_device_operands(tables)
            static = dict(
                max_depth=tables.max_depth,
                learning_rate=tables.learning_rate,
                base=tables.base_score, n_classes=tables.n_classes_out,
                tree_chunk=tables.tree_chunk,
                n_trees_padded=tables.n_trees_padded,
                missing_bin_value=tables.missing_bin_value,
                use_missing=tables.eff_dl is not None,
                use_cat=tables.eff_cat is not None,
                use_scale=tables.leaf_scale is not None)

            def fn(*args):
                *o, Xc = args
                return predict_lut.predict_effective_lut_ops(
                    tuple(o), Xc, **static)
        else:
            packed = ce.quantize(leaf_dtype="int4").pack_int4()
            ops = packed.ops
            static = packed.static_kwargs()

            def fn(*args):
                *o, Xc = args
                return predict_lut.predict_effective_lut4_ops(
                    tuple(o), Xc, **static)

        shapes = [(a.shape, a.dtype) for a in ops]
        shapes.append(((rows, features), jnp.uint8))
        return fn, shapes

    return build


def _random_node_list(n_trees, n_leaves, features, missing=False,
                      categories=(), leaf_columns=0, n_classes=1):
    """A random leaf-wise ensemble (seeded) as a models/tree
    NodeListEnsemble; `missing`: with learned NaN directions;
    `categories`: (column, cardinality) pairs that ask category sets;
    `leaf_columns`: an averaged forest of vector leaves (`n_leaves` may be
    a range each tree draws from); `n_classes` > 1: softmax's round-major
    trees."""
    import numpy as np

    from ddt_tpu.models.tree import random_node_list

    meta = dict(learning_rate=0.1, base_score=0.0, loss="logloss")
    if leaf_columns:
        meta = dict(leaf_columns=leaf_columns)
    elif n_classes > 1:
        meta.update(loss="softmax", n_classes=n_classes)
    return random_node_list(np.random.default_rng(7), n_trees, n_leaves,
                            features, missing=missing, **meta,
                            **({"categories": categories} if categories
                               else {}))


def _paths_case(rows, features, n_trees, n_leaves, missing=False,
                categories=(), cat_spans=(), cat_ordinal_at=0):
    """The path-matrix kernel (ops/predict_paths.py) over a node list's
    compiled tables as a backend hands them over (the select packed on the
    host where it answers two nodes a lane: up to 64 columns, 256 lanes
    and more), the rows as api.predict does (uint8). `categories`:
    (column, cardinality) pairs whose nodes ask CATEGORY SETS (the one-hot
    K-blocks; one node a lane, the select as the model compiles it).
    `cat_spans` and `cat_ordinal_at`: the K-blocks of that select each lane
    tile reads and where its ordinal rows lie, as a model's build found
    them (`CompiledNodeList.select_spans`, `.cat_ordinal_at`: shape facts
    of the program; the tables here are a random model's and only their
    shapes are read); (): every tile reads every block."""
    def build():
        import jax.numpy as jnp
        import numpy as np

        from ddt_tpu.ops import predict_paths

        ce = _random_node_list(n_trees, n_leaves, features, missing,
                               categories).compile()
        tables = ce.arrays()
        # (`--tree` may name a checkout from before the packed select)
        per_lane = getattr(predict_paths, "select_nodes_per_lane", None)
        if not categories and per_lane and per_lane(features, ce.lanes) == 2:
            tables = (*predict_paths.pack_select(ce.sel, ce.planes, features,
                                                 xp=np), ce.paths)

        # (`--tree` may name a checkout from before the sets' spans: it
        # compiles the dense program)
        spans = cat_spans if categories and "spans" in \
            predict_paths.CatSets._fields else ()

        def fn(sel, planes, paths, *rest):
            *cat, Xc = rest
            return predict_paths.predict_paths_pallas(
                sel, planes, paths, Xc,
                learning_rate=ce.learning_rate, base=ce.base_score,
                missing_routes=missing, interpret=False,
                **({"cat": tuple(cat)} if cat else {}),
                **({"sets": predict_paths.CatSets(
                    len(cat[0]), sel.shape[1], spans, cat_ordinal_at)}
                   if spans else {}))

        shapes = [(a.shape, a.dtype) for a in tables]
        shapes.append(((rows, features), jnp.uint8))
        return fn, shapes

    return build


def _forest_case(rows, features, n_trees, n_subtrees, classes, lanes=256,
                 most_subtrees=0, mean=True, select_spans=(),
                 packed=False, halved=False):
    """The SUB-TREE form of the path-matrix kernel (ops/predict_paths.py:
    the chain and the class dot) over the compiled tables' SHAPES
    (models/tree.CompiledNodeList: 1.28 GB at the MNIST forest's), the rows
    as api.predict does (uint8). `select_spans`: the K-blocks of the select
    each lane tile reads, as the model's build found them (`CompiledNodeList.
    select_spans`); (): every block. `most_subtrees`: the sub-trees of the
    model's largest tree (0: every tree has its share), which with the
    classes decides the width of the exits' table, as the build does
    (models/tree.exit_table_lanes: ONE lane tile where the pieces and the
    chain fit it). `mean` False: scalar leaves, the margins [rows, classes]
    of softmax's round-major trees. `packed`: the select as a backend hands
    it over where it answers two nodes a lane (`pack_select`'s shape: up to
    64 columns), and not as the model compiles it. `halved`: the path table
    of sub-trees numbered as two halves that share their spine, the two
    diagonal blocks alone (`models/tree.cut_subtrees`: what
    `choose_select_spans` takes for a model of one K-block)."""
    def build():
        import jax.numpy as jnp
        import numpy as np

        from ddt_tpu.ops import predict_paths
        try:
            from ddt_tpu.models.tree import exit_table_lanes
        except ImportError:
            # (`--tree` names a checkout from before the one tile of exits)
            def exit_table_lanes(classes, n_subtrees):
                return (-(-3 * classes // 128) * 128
                        + -(-(int(n_subtrees.max()) + 1) // 128) * 128), 0

        most = most_subtrees or -(-n_subtrees // n_trees)
        path_rows = lanes // 2 if halved else lanes
        # (`--tree` may name a checkout from before the halves)
        chain = predict_paths.chain_of(
            n_trees, classes, exit_table_lanes(classes, np.array([most]))[0],
            select_spans, *([(n_subtrees, path_rows, lanes)] * halved))

        def fn(sel, planes, paths, leaves, Xc):
            return predict_paths.predict_paths_pallas(
                sel, planes, paths, Xc, learning_rate=1.0, base=0.0,
                interpret=False, leaves=leaves, chain=chain, mean=mean)

        sel = predict_paths._select_shape(lanes, features, 2 if packed else 1)
        return fn, [
            ((n_subtrees, *sel), jnp.bfloat16),
            ((n_subtrees, 8, lanes), jnp.float32),
            ((n_subtrees, path_rows, lanes), jnp.bfloat16),
            ((n_subtrees, lanes, chain.exit_lanes), jnp.bfloat16),
            ((rows, features), jnp.uint8)]

    return build


def _oblivious_case(rows, features, n_trees, depth, classes=1):
    """The oblivious form (ops/predict_oblivious.py) over the compiled
    tables' SHAPES (models/tree.CompiledOblivious: 197 MB at the Epsilon
    model's), the rows as api.predict does (uint8). `classes` C > 1:
    vector leaves, the C-fold resolve."""
    def build():
        import jax.numpy as jnp

        from ddt_tpu.ops import predict_oblivious

        groups = -(-n_trees // predict_oblivious.GROUP)
        bias = 0.25 if classes == 1 else (0.25,) * classes

        def fn(sel, thr, leaf, Xc):
            return predict_oblivious.predict_oblivious_pallas(
                sel, thr, leaf, Xc, scale=0.5, bias=bias, interpret=False)

        return fn, [
            ((groups, depth, -(-features // 16) * 16, 128), jnp.bfloat16),
            ((groups, -(-depth // 8) * 8, 128), jnp.float32),
            ((groups, classes << depth, 128), jnp.float32),
            ((rows, features), jnp.uint8)]

    return build


def kernel_cases() -> list:
    """Every Pallas kernel the system has, at the shapes the repo names.
    `default` marks the default dispatch on a TPU (f32 gradients, the f32
    traversal kernel); the rest are the opt-in kernels."""
    H, C = HIGGS, COVERTYPE
    hr, hf = H["rows"], H["features"]
    cr, cf, cc = C["rows"], C["features"], C["classes"]
    return [
        # Histogram: row-major at 255 bins, transposed at <= 128, root
        # level (N=1) and the deepest level of a depth-6 tree (N=32).
        KernelCase("hist/higgs/255bins/N=1", True,
                   _hist_case(hr, hf, 1, 255)),
        KernelCase("hist/higgs/255bins/N=32", True,
                   _hist_case(hr, hf, 32, 255)),
        KernelCase("hist/higgs/64bins/N=1", True,
                   _hist_case(hr, hf, 1, 64)),
        KernelCase("hist/higgs/64bins/N=32", True,
                   _hist_case(hr, hf, 32, 64)),
        # Covertype's deepest level (depth 8): 2N = 256 matmul columns,
        # feature-chunked to fit VMEM.
        KernelCase("hist/covertype/255bins/N=128", True,
                   _hist_case(cr, cf, 128, 255)),
        # Traversal: with and without the missing / categorical
        # operands, one output and seven. The kernel takes the trees in
        # groups of 128 whatever their count, so its layouts are: whole
        # groups (1000 pads to 1024), a last group filled up with trees
        # that score 0 (50 -> 64 -> 128; 210 -> 256; 150 -> 192 -> 256),
        # each with and without the row-per-plane dl and cat tables.
        KernelCase("predict/higgs/1000x6", True,
                   _predict_case(hr, hf, 1000, 6)),
        KernelCase("predict/50x4/missing+cat", True,
                   _predict_case(hr, hf, 50, 4, missing=True, cat=True)),
        KernelCase("predict/covertype/210x6/7classes", True,
                   _predict_case(cr, cf, 210, 6, n_classes=cc)),
        KernelCase("predict/150x6/missing+cat", True,
                   _predict_case(hr, hf, 150, 6, missing=True, cat=True)),
        KernelCase("predict/higgs/1000x6/missing+cat", True,
                   _predict_case(hr, hf, 1000, 6, missing=True, cat=True)),
        # The CTR model: ONE group of which 100 lanes hold a tree, both
        # routing tables, over 39 columns and a whole scoring chunk.
        KernelCase("predict/criteo/100x6/missing+cat", True,
                   _predict_case(CRITEO["rows"], CRITEO["features"], 100, 6,
                                 missing=True, cat=True)),
        # The folded routed form (the routes inside the weight tile,
        # `routes_in_tile`) at the CTR model's shape with each table
        # alone, and its edge with the missing table: 56 features fill
        # the tile ([x | m], 8 rows to spare), 57 keep the integer
        # routing on the VPU.
        KernelCase("predict/criteo/100x6/missing", True,
                   _predict_case(CRITEO["rows"], CRITEO["features"], 100, 6,
                                 missing=True)),
        KernelCase("predict/criteo/100x6/cat", True,
                   _predict_case(CRITEO["rows"], CRITEO["features"], 100, 6,
                                 cat=True)),
        KernelCase("predict/56f/130x5/missing", True,
                   _predict_case(hr, 56, 130, 5, missing=True)),
        KernelCase("predict/57f/130x5/missing", True,
                   _predict_case(hr, 57, 130, 5, missing=True)),
        KernelCase("predict/56f/130x5/missing+cat", True,
                   _predict_case(hr, 56, 130, 5, missing=True, cat=True)),
        # Two nodes a weight tile (F <= 64 and no routing table: 28
        # features with 4 K rows between the copies, 54 with 2), at
        # Covertype's own size: 28 groups in 4 blocks of 7, 128 weight
        # tiles a group; with a routing table, one node a tile at the
        # same size and blocks. Since PR 34 the groups hold WHOLE ROUNDS,
        # 126 trees, and a block's seven share one class dot on a
        # [128, 7] one-hot the whole ensemble shares; so do the ten
        # groups of a 3-class model (405 rounds: 1,216 padded trees).
        KernelCase("predict/covertype/3500x8/7classes", True,
                   _predict_case(cr, cf, 500 * cc, 8, n_classes=cc)),
        KernelCase("predict/covertype/3500x8/7classes/missing", True,
                   _predict_case(cr, cf, 500 * cc, 8, n_classes=cc,
                                 missing=True)),
        # Depth 8 with BOTH tables: served since the routes ride the
        # weight tile (PR 32; the integer routing's working set alone was
        # past the budget there): 28 groups in 5 blocks of 6.
        KernelCase("predict/covertype/3500x8/7classes/missing+cat", True,
                   _predict_case(cr, cf, 500 * cc, 8, n_classes=cc,
                                 missing=True, cat=True)),
        KernelCase("predict/3classes/1215x6", True,
                   _predict_case(hr, hf, 405 * 3, 6, n_classes=3)),
        KernelCase("predict/covertype/350x6/7classes/missing+cat", True,
                   _predict_case(cr, cf, 50 * cc, 6, n_classes=cc,
                                 missing=True, cat=True)),
        # The edges of the packing: 56 features leave the weight tile 8
        # rows for the mantissa row and no gap between the copies; 64 fill
        # it (the mantissa is one VPU add); 65 keep one node a tile.
        KernelCase("predict/56f/130x5", True,
                   _predict_case(hr, 56, 130, 5)),
        KernelCase("predict/64f/130x5", True,
                   _predict_case(hr, 64, 130, 5)),
        KernelCase("predict/65f/130x5", True,
                   _predict_case(hr, 65, 130, 5)),
        # The rows a grid step takes (`table_plan`'s `tile_rows`, PR 42)
        # at the rule's edges, over a whole 2M-row chunk: ONE group of 32
        # weight tiles (100 unrouted trees; the routed one-group step is
        # predict/criteo/100x6/missing+cat above), two groups (64 tiles),
        # both 1,024 rows; four groups and depth 8 in one group (128
        # tiles), 512; depth 7 folded (127 tiles), 512. Then the widest
        # shape of each form that still takes the step and the first that
        # falls back, by the kernel's VMEM at that tile: one node a tile
        # at 256 columns 1,024 and at 257 512, at 1,792 columns 512 and at
        # 1,793 256; the integer routing (57 columns, both tables) 512 at
        # depth 6 and 256 at depth 7.
        KernelCase("predict/higgs/100x6", True,
                   _predict_case(CRITEO["rows"], hf, 100, 6)),
        KernelCase("predict/higgs/250x6", True,
                   _predict_case(CRITEO["rows"], hf, 250, 6)),
        KernelCase("predict/higgs/500x6", True,
                   _predict_case(CRITEO["rows"], hf, 500, 6)),
        KernelCase("predict/higgs/100x8", True,
                   _predict_case(CRITEO["rows"], hf, 100, 8)),
        KernelCase("predict/criteo/100x7/missing+cat", True,
                   _predict_case(CRITEO["rows"], CRITEO["features"], 100, 7,
                                 missing=True, cat=True)),
        KernelCase("predict/256f/100x6", True,
                   _predict_case(CRITEO["rows"], 256, 100, 6)),
        KernelCase("predict/257f/100x6", True,
                   _predict_case(CRITEO["rows"], 257, 100, 6)),
        KernelCase("predict/1792f/100x6", True,
                   _predict_case(CRITEO["rows"], 1792, 100, 6)),
        KernelCase("predict/1793f/100x6", True,
                   _predict_case(CRITEO["rows"], 1793, 100, 6)),
        KernelCase("predict/57f/100x6/missing+cat", True,
                   _predict_case(CRITEO["rows"], 57, 100, 6, missing=True,
                                 cat=True)),
        KernelCase("predict/57f/100x7/missing+cat", True,
                   _predict_case(CRITEO["rows"], 57, 100, 7, missing=True,
                                 cat=True)),
        # The path-matrix form: node lists. LightGBM's Higgs model's own
        # shape (blocks of 8 trees), a tree of one 128-lane tile, and more
        # features than a bf16 sublane tile.
        KernelCase("paths/higgs/500x255leaves", True,
                   _paths_case(2_000_000, hf, 500, 255)),
        KernelCase("paths/9x15leaves", True, _paths_case(hr, hf, 9, 15)),
        KernelCase("paths/70f/40x200leaves", True,
                   _paths_case(hr, 70, 40, 200)),
        # Past one K-block of the select, with and without the NaN route;
        # Bosch's width (8 K-blocks, the last of 72 columns), a ragged
        # last row tile.
        KernelCase("paths/129f/12x255leaves", True,
                   _paths_case(4_999, 129, 12, 255)),
        # The packed select's edges: 64 columns (no K row left for the
        # mantissa: one VPU add), 65 (one node a lane), 512 lanes with the
        # NaN route on both bytes of the word.
        KernelCase("paths/64f/12x255leaves/nan", True,
                   _paths_case(4_999, 64, 12, 255, missing=True)),
        KernelCase("paths/65f/12x255leaves", True,
                   _paths_case(4_999, 65, 12, 255)),
        KernelCase("paths/56f/12x500leaves/nan", True,
                   _paths_case(4_999, 56, 12, 500, missing=True)),
        KernelCase("paths/bosch/968f/20x255leaves/nan", True,
                   _paths_case(BOSCH["chunk_rows"], BOSCH["features"], 20,
                               255, missing=True)),
        # CATEGORY SETS (LightGBM's categorical splits): the Allstate
        # cell's own shape, sets and ordinal nodes in one tree (six one-hot
        # K-blocks beside the ordinal one) under the spans the build finds
        # for that cell's drawn model (models/tree.choose_set_spans: three
        # one-hot blocks, the ordinal rows, three one-hot blocks; the
        # ordinal block read by both lane tiles, a one-hot block by one: 8
        # select tiles where 14, 12 weight tiles a tree where 18 until PR
        # 56), eight columns ALL categorical (five one-hot K-blocks, no
        # ordinal K row) under DENSE spans (what a model gets whose blocks
        # do not split: 14 tiles, the program of before the spans), and
        # sets beside ordinal nodes WITH NaN directions at Bosch's width
        # (the eight ordinal K-blocks and the sets' own, dense too).
        KernelCase("paths-cat/32f/500x255", True,
                   _paths_case(2_000_000, ALLSTATE["features"], 500, 255,
                               categories=ALLSTATE["categories"],
                               cat_spans=((0, 4), (3, 7)),
                               cat_ordinal_at=3)),
        KernelCase("paths-cat/8f/500x255", True,
                   _paths_case(2_000_000, ALL_SETS["features"], 500, 255,
                               categories=ALL_SETS["categories"])),
        KernelCase("paths-cat/968f/20x255leaves/nan", True,
                   _paths_case(BOSCH["chunk_rows"], BOSCH["features"], 20,
                               255, missing=True,
                               categories=((3, 40), (500, 200), (967, 7)))),
        # The SUB-TREE form (the chain and the class dot): the MNIST
        # forest's chunk (100 full-depth trees of up to 4,779 leaves: 1,695
        # sub-trees of 256 lanes since PR 53 packs their entries, 2,112
        # before, over 784 columns, 10 classes, the first
        # lane tile reading K-blocks 0-2 and the second 3-6 as the build
        # finds for that forest: 7 select tiles a sub-tree), the same with
        # a K-block both tiles read, and
        # the rule's edges: one sub-tree a tree with one column, with 85
        # (two class tiles, the most the rule takes) and with 128 (three:
        # refused), sub-trees of one tile with two activity tiles. The
        # exits' table (PR 49) is ONE lane tile in the forest's cases and at
        # one column (30 or 3 lanes of pieces and a chain of 25, 21 or no
        # link), [V | L] of three and four tiles at 85 and 128 classes and
        # under the 200-part trees' chain.
        KernelCase("forest/784f/100x4779x10", True,
                   _forest_case(FOREST["chunk_rows"], FOREST["features"],
                                100, 1695, 10, most_subtrees=20,
                                select_spans=((0, 3), (3, 7)))),
        KernelCase("forest/784f/12x20subtrees/shared-block", True,
                   _forest_case(4_999, FOREST["features"], 12, 245, 10,
                                select_spans=((0, 4), (3, 7)))),
        KernelCase("forest/28f/12x1subtree/c1", True,
                   _forest_case(4_999, hf, 12, 12, 1)),
        KernelCase("forest/28f/12x1subtree/c85", True,
                   _forest_case(4_999, hf, 12, 12, 85)),
        # (past the rule: three class tiles' output windows do not fit
        # beside a row tile; `predict_paths_fits` says so and the
        # jax.numpy form serves: tests/test_forest.py)
        KernelCase("forest/28f/12x1subtree/c128", False,
                   _forest_case(4_999, hf, 12, 12, 128)),
        KernelCase("forest/129f/3x200subtrees/c10/128lanes", True,
                   _forest_case(4_999, 129, 3, 600, 10, lanes=128)),
        # Softmax's round-major trees in the sub-tree form (PR 50): scalar
        # leaves in their class's lanes, the margins [rows, C] out; trees
        # of one sub-tree and of fifty in one table, the PACKED select
        # under the chain (54 columns: two nodes a result lane), at the
        # XGBoost Covertype cell's whole-set call of 581,012 rows and 7
        # classes with the HALVED resolve that cell's model gets since PR 51
        # (the path table's two diagonal blocks: 5 MXU weight tiles a
        # sub-tree, 1 + 2 + 2), at 3 classes with the whole path matrix (7:
        # 1 + 4 + 2, the layout every model had before), and the halves
        # under the unpacked select (100 columns: 2 + 2 + 2).
        KernelCase("paths/54f/softmax7/chain", True,
                   _forest_case(XGB["rows"], XGB["features"], 21, 300, 7,
                                most_subtrees=50, mean=False, packed=True,
                                halved=True)),
        KernelCase("paths/54f/softmax3/chain", True,
                   _forest_case(4_999, XGB["features"], 12, 100, 3,
                                most_subtrees=30, mean=False, packed=True)),
        KernelCase("paths/100f/softmax7/halved/chain", True,
                   _forest_case(4_999, 100, 21, 300, 7, most_subtrees=50,
                                mean=False, halved=True)),
        # The oblivious form: CatBoost's Epsilon model's chunk (63 groups,
        # 16 K-blocks), the depths and widths at the dispatch rule's edges
        # (depth 10 at 28 columns fits, depth 7 at 2000), one K-block with
        # a ragged last row tile, a K-block of one column; the pipeline's
        # edges (PR 40): the deepest tree it unrolls (8; 9 and 10 keep the
        # rolled step), a step of ONE sub-tile (no select to resolve under).
        KernelCase("oblivious/epsilon/8000x6", True,
                   _oblivious_case(EPSILON["chunk_rows"], EPSILON["features"],
                                   EPSILON["n_trees"], EPSILON["depth"])),
        KernelCase("oblivious/28f/300x10", True,
                   _oblivious_case(hr, hf, 300, 10)),
        KernelCase("oblivious/2000f/300x7", True,
                   _oblivious_case(EPSILON["chunk_rows"], 2000, 300, 7)),
        KernelCase("oblivious/28f/5x1", True,
                   _oblivious_case(4_999, hf, 5, 1)),
        KernelCase("oblivious/129f/130x6", True,
                   _oblivious_case(4_999, 129, 130, 6)),
        KernelCase("oblivious/28f/130x8", True,
                   _oblivious_case(4_999, hf, 130, 8)),
        KernelCase("oblivious/28f/130x6/1000rows", True,
                   _oblivious_case(1_000, hf, 130, 6)),
        # VECTOR LEAVES (PR 57): CatBoost's MultiClass defaults over
        # Covertype's chunk (8 groups, ONE K-block, 7 classes, the rolled
        # step; since PR 58 the leaves looked up by sublane gathers, the
        # `tpu.dynamic_gather` these cases lower: 56 gathers and 49 selects
        # a (row, tree) where the multiplexer took 441 selects), the longest
        # resolve the dispatch rule admits (7 classes at depth 7: a
        # multiplexer of 889 selects of 1023; 112 gathers and 105 selects)
        # and the longest that is unrolled beside a select (7 classes at
        # depth 5: 217 of 255, a carried leaf table of 7 x 16 KB), and the
        # shortest gather (depth 3: ONE vreg a class, a gather and no
        # select; under depth 3 the multiplexer serves).
        KernelCase("oblivious/54f/1000x6xC7", True,
                   _oblivious_case(2_000_000, COVERTYPE["features"], 1000, 6,
                                   classes=7)),
        KernelCase("oblivious/54f/130x7xC7", True,
                   _oblivious_case(4_999, COVERTYPE["features"], 130, 7,
                                   classes=7)),
        KernelCase("oblivious/54f/130x5xC7", True,
                   _oblivious_case(4_999, COVERTYPE["features"], 130, 5,
                                   classes=7)),
        KernelCase("oblivious/54f/130x3xC7", True,
                   _oblivious_case(4_999, COVERTYPE["features"], 130, 3,
                                   classes=7)),
        # Opt-in kernels.
        KernelCase("hist/higgs/255bins/N=32/int8", False,
                   _hist_case(hr, hf, 32, 255, "int8")),
        KernelCase("hist/higgs/255bins/N=32/int16", False,
                   _hist_case(hr, hf, 32, 255, "int16")),
        KernelCase("hist/higgs/64bins/N=32/int8", False,
                   _hist_case(hr, hf, 32, 64, "int8")),
        KernelCase("hist/higgs/64bins/N=32/int16", False,
                   _hist_case(hr, hf, 32, 64, "int16")),
        KernelCase("lut/higgs/1000x6/f16leaf", False,
                   _predict_case(hr, hf, 1000, 6, tier="lut")),
        KernelCase("lut/50x4/int8leaf/missing+cat", False,
                   _predict_case(hr, hf, 50, 4, missing=True, cat=True,
                                 tier="lut_int8leaf")),
        KernelCase("lut4/higgs/1000x6", False,
                   _predict_case(hr, hf, 1000, 6, tier="lut4")),
        KernelCase("lut4/50x4/missing+cat", False,
                   _predict_case(hr, hf, 50, 4, missing=True, cat=True,
                                 tier="lut4")),
    ]


# ------------------------------------------------------------------ #
# whole programs, as TPUDevice builds them
# ------------------------------------------------------------------ #

def _rounds_program(topo_devices, *, rows, features, n_rounds, mesh_shape,
                    cfg_kw):
    """(jit fn, ShapeDtypeStruct args) of the fused-rounds program for a
    mesh over the described devices (mesh_shape None = one device)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, SingleDeviceSharding

    from ddt_tpu.backends.tpu import TPUDevice
    from ddt_tpu.config import TrainConfig
    from ddt_tpu.parallel import mesh as mesh_lib

    cfg = TrainConfig(backend="tpu", **cfg_kw)
    C = cfg.n_classes if cfg.loss == "softmax" else 1
    if mesh_shape is None:
        be = TPUDevice(cfg)
        one = SingleDeviceSharding(topo_devices[0])
        sh_data = sh_vec = sh_mat = one
    else:
        pr, pf = mesh_shape
        mesh = jax.sharding.Mesh(
            np.asarray(topo_devices[:pr * pf]).reshape(pr, pf),
            (mesh_lib.ROWS_AXIS, mesh_lib.FEATURES_AXIS))
        be = TPUDevice(cfg, mesh=mesh)
        lay = be.layout
        sh_data = NamedSharding(mesh, lay.binned_data())
        sh_vec = NamedSharding(mesh, lay.row_vector())
        sh_mat = NamedSharding(mesh, lay.row_matrix())
    fn = be._build_rounds_fn(n_rounds)
    pred = (jax.ShapeDtypeStruct((rows, C), jnp.float32, sharding=sh_mat)
            if C > 1 else
            jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=sh_vec))
    ydt = jnp.int32 if cfg.loss == "softmax" else jnp.float32
    args = [
        jax.ShapeDtypeStruct((rows, features), jnp.uint8, sharding=sh_data),
        pred,
        jax.ShapeDtypeStruct((rows,), ydt, sharding=sh_vec),
        jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=sh_vec),
    ]
    return be, fn, args


def _scoring_program(topo_devices, *, rows, features, n_trees, depth,
                     n_classes=1, routed=False, leaves=0, oblivious=False,
                     leaf_columns=0, categories=()):
    """`routed`: a heap with the missing and the categorical table; a node
    list (`leaves`: a count, or a range (lo, hi) each tree draws from) with
    learned NaN directions. `oblivious`: symmetric trees of `depth`
    (models/tree.ObliviousEnsemble), of `n_classes` > 1 vector leaves and
    the program that ends in their softmax. Of a node list: `leaf_columns`
    an averaged forest's vector leaves, `n_classes` > 1 softmax's
    round-major trees and the program that ends in their softmax,
    `categories` the (column, cardinality) pairs that ask category sets."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ddt_tpu.backends.tpu import TPUDevice
    from ddt_tpu.config import TrainConfig

    be = TPUDevice(TrainConfig(backend="tpu", max_depth=depth or 6))
    if oblivious:
        import numpy as np

        from ddt_tpu.models.tree import random_oblivious

        ens = random_oblivious(np.random.default_rng(7), n_trees, depth,
                               features, scale=0.5, bias=0.25,
                               n_classes=n_classes if n_classes > 1 else 0)
    else:
        ens = (_random_node_list(n_trees, leaves, features, routed,
                                 categories, leaf_columns, n_classes)
               if leaves else
               _random_ensemble(n_trees, depth, features, n_classes, routed,
                                routed))
    fn, ens_dev = be._predict_entry(ens, link=be.links_on_device(ens))[:2]
    one = SingleDeviceSharding(topo_devices[0])
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
            for a in ens_dev]
    args.append(jax.ShapeDtypeStruct((rows, features), jnp.uint8,
                                     sharding=one))
    return jax.jit(fn), args


def program_cases(topo_devices) -> list:
    """(name, build) — build() -> (jit fn, sharded ShapeDtypeStruct args,
    [substrings the compiled text must contain])."""
    hr, hf = HIGGS["rows"], HIGGS["features"]
    cr, cf, cc = (COVERTYPE["rows"], COVERTYPE["features"],
                  COVERTYPE["classes"])
    higgs = dict(max_depth=6, n_bins=255)

    def rounds(mesh_shape, rows=hr, features=hf, cfg_kw=higgs,
               n_rounds=10):
        def build():
            be, fn, args = _rounds_program(
                topo_devices, rows=rows, features=features,
                n_rounds=n_rounds, mesh_shape=mesh_shape, cfg_kw=cfg_kw)
            want = ["tpu_custom_call"]
            if mesh_shape is not None and mesh_shape[0] > 1:
                assert be.split_comms == "reduce_scatter", be.split_comms
                want.append("reduce-scatter")
            return fn, args, want
        return build

    def scoring(n_trees, rows=hr, features=hf, depth=6, n_classes=1,
                routed=False, leaves=0, oblivious=False, **node_list):
        def build():
            fn, args = _scoring_program(topo_devices, rows=rows,
                                        features=features, n_trees=n_trees,
                                        depth=depth, n_classes=n_classes,
                                        routed=routed, leaves=leaves,
                                        oblivious=oblivious, **node_list)
            return fn, args, ["tpu_custom_call"]
        return build

    return [
        ("rounds/higgs/1dev", rounds(None)),
        ("rounds/higgs/rows=4", rounds((4, 1))),
        ("rounds/higgs/2x2", rounds((2, 2))),
        ("rounds/covertype/1dev", rounds(
            None, rows=cr, features=cf, n_rounds=2,
            cfg_kw=dict(max_depth=8, n_bins=255, loss="softmax",
                        n_classes=cc))),
        ("scoring/higgs/10x6", scoring(10)),
        ("scoring/higgs/1000x6", scoring(1000)),
        # Depth 8 costs the kernel tables only, no working set: a small
        # Covertype model is served by it.
        ("scoring/covertype/70x8", scoring(
            10 * cc, rows=cr, features=cf, depth=8, n_classes=cc)),
        # Covertype's own models, 500 rounds x 7 classes at depth 8: 28
        # tree groups whose tables (11 MB) stream in 4 blocks of 7.
        ("scoring/covertype/3500x8", scoring(
            500 * cc, rows=cr, features=cf, depth=8, n_classes=cc)),
        # The CTR model's chunk through the auto dispatch: the routed
        # form (both tables), one group.
        ("scoring/criteo/100x6/routed", scoring(
            100, rows=CRITEO["rows"], features=CRITEO["features"],
            routed=True)),
        # LightGBM's Higgs model's chunk through the auto dispatch: a node
        # list (`leaves`: no heap depth), the path-matrix form.
        ("scoring/higgs-lgbm/500x255leaves", scoring(
            500, rows=2_000_000, depth=0, leaves=255)),
        # LightGBM's Bosch model's chunk: 968 columns, NaN directions, the
        # K-blocked path kernel; the rows a chunk is at that width.
        ("scoring/bosch-lgbm/500x255leaves/nan", scoring(
            500, rows=BOSCH["chunk_rows"], features=BOSCH["features"],
            depth=0, leaves=255, routed=True)),
        # CatBoost's Epsilon model's chunk through the auto dispatch: an
        # oblivious ensemble, 63 groups of 128 trees streamed a group a
        # step over 2000 columns.
        ("scoring/epsilon-catboost/8000x6/oblivious", scoring(
            EPSILON["n_trees"], rows=EPSILON["chunk_rows"],
            features=EPSILON["features"], depth=EPSILON["depth"],
            oblivious=True)),
        # CatBoost's MultiClass defaults over Covertype: vector leaves, the
        # C-fold resolve, the softmax on the device.
        ("scoring/covtype-catboost/1000x6xC7/oblivious", scoring(
            1000, rows=2_000_000, features=COVERTYPE["features"], depth=6,
            n_classes=7, oblivious=True)),
        # The three other cells' programs, each at its cell's rows and
        # columns over a smaller random model of the cell's FORM (the
        # tables' entries are fewer; what the program is made of is the
        # cell's). scikit-learn's MNIST forest: vector leaves, every tree
        # cut into chained sub-trees, the select by its spans.
        ("scoring/mnist-rf/12x700-1400leavesxC10/chain", scoring(
            12, rows=FOREST["chunk_rows"], features=FOREST["features"],
            depth=0, leaves=(700, 1400), leaf_columns=10)),
        # XGBoost's deep Covertype model: softmax's round-major trees in
        # the sub-tree form, halved, under the packed select; one program
        # of the set's own rows that ends in the softmax (`link=True`).
        ("scoring/covtype-xgb/21x33-3000leaves/softmax7/link", scoring(
            21, rows=XGB["rows"], features=XGB["features"], depth=0,
            leaves=(33, 3000), n_classes=7)),
        # LightGBM's Allstate model: category sets and ordinal nodes in
        # one tree, under the spans the build finds for this random model.
        ("scoring/allstate-lgbm/500x255leaves/cat", scoring(
            500, rows=2_000_000, features=ALLSTATE["features"], depth=0,
            leaves=255, categories=ALLSTATE["categories"])),
    ]


# ------------------------------------------------------------------ #

@contextlib.contextmanager
def _mosaic_modules(into: list):
    """Collect the text of every Mosaic module lowered inside, without
    debug locations (the serialized body in the HLO carries the checkout's
    path and line numbers)."""
    from jax._src import tpu_custom_call as tcc

    lower = tcc._lower_mosaic_module_to_asm

    def recording(module, **kw):
        into.append(module.operation.get_asm(enable_debug_info=False))
        return lower(module, **kw)

    tcc._lower_mosaic_module_to_asm = recording
    try:
        yield
    finally:
        tcc._lower_mosaic_module_to_asm = lower


def _digest(hlo: str, mosaic: list) -> str:
    """`hlo <sha1> mosaic <sha1>` of a compiled program: the optimized HLO
    with the location tables, the source metadata and the kernels'
    serialized bodies cut, and the kernels' Mosaic modules as text."""
    hlo = re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n", hlo,
                 flags=re.S)                    # the location tables
    hlo = re.sub(r', metadata=\{[^{}]*\}', "", hlo)
    hlo = re.sub(r'"body":"[^"]*"', '"body":""', hlo)
    sha = lambda text: hashlib.sha1(text.encode()).hexdigest()[:16]
    return f"hlo {sha(hlo)} mosaic {sha(chr(10).join(mosaic))}"


def _first_line(e: BaseException) -> str:
    msg = " ".join(str(e).split())
    return f"{type(e).__name__}: {msg[:400]}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="",
                    help="run only the cases whose name contains this")
    ap.add_argument("--digest", action="store_true",
                    help="print each whole program's SHA-1s (HLO, Mosaic)")
    ap.add_argument("--tree", default="",
                    help="take ddt_tpu from this checkout, not this one")
    args = ap.parse_args(argv)
    if args.tree:
        sys.path.insert(0, os.path.abspath(args.tree))

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ddt_tpu.utils import device

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)
    devs = list(topo.devices)
    one = SingleDeviceSharding(devs[0])
    print(f"# compile-only check for {devs[0].device_kind} x {len(devs)} "
          f"({TOPOLOGY}), jax {jax.__version__}, host platform "
          f"{jax.default_backend()}", flush=True)

    failed_default = 0
    with device.assume_platform("tpu"):
        for case in kernel_cases():
            if args.only not in case.name:
                continue
            t0 = time.perf_counter()
            try:
                fn, shapes = case.build()
                sds = [jax.ShapeDtypeStruct(s, d, sharding=one)
                       for s, d in shapes]
                mosaic = []
                with _mosaic_modules(mosaic):
                    txt = jax.jit(fn, out_shardings=one).lower(
                        *sds).compile().as_text()
                verdict = "compiled"
                if args.digest:
                    verdict += "  " + _digest(txt, mosaic)
            except Exception as e:  # the report IS the failure message
                verdict = "REFUSED  " + _first_line(e)
                failed_default += case.default
            tag = "default" if case.default else "opt-in "
            print(f"{tag}  {case.name:<40s} "
                  f"{time.perf_counter() - t0:6.1f}s  {verdict}", flush=True)
        for name, build in program_cases(devs):
            if args.only not in name:
                continue
            t0 = time.perf_counter()
            try:
                fn, sds, want = build()
                mosaic = []
                with _mosaic_modules(mosaic):
                    txt = fn.lower(*sds).compile().as_text()
                missing = [w for w in want if w not in txt]
                verdict = ("compiled" if not missing else
                           f"REFUSED  compiled text lacks {missing}")
                if args.digest:
                    verdict += "  " + _digest(txt, mosaic)
                failed_default += bool(missing)
            except Exception as e:  # the report IS the failure message
                verdict = "REFUSED  " + _first_line(e)
                failed_default += 1
            print(f"program  {name:<40s} "
                  f"{time.perf_counter() - t0:6.1f}s  {verdict}", flush=True)
    print(f"# {failed_default} default-dispatch case(s) refused")
    return 1 if failed_default else 0


if __name__ == "__main__":
    sys.exit(main())

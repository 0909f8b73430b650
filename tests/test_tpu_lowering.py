"""Every Pallas kernel on the default dispatch LOWERS for a TPU.

The CPU suite runs the kernels interpreted, which never meets the Mosaic
lowering: at PR 21 the histogram kernel's row operands had a block shape
the TPU lowering refuses and the traversal kernel asked the MXU for a
16-bit accumulator, and every interpret-mode test passed. jax.export
lowers the COMPILED form (interpret=False) for platform "tpu" from a CPU
host — no chip and no libtpu needed — so both refusals fail here.

What this does not see: refusals inside the Mosaic compiler itself (vector
layouts, unsupported casts). Those need libtpu: scripts/tpu_aot_check.py
compiles the same cases (its `kernel_cases()` table is the one this file
reads) against a described v5e, and chip_smoke.py runs them.
"""

import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from ddt_tpu.utils import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "tpu_aot_check", os.path.join(REPO, "scripts", "tpu_aot_check.py"))
aot = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(aot)

DEFAULT_CASES = [c for c in aot.kernel_cases() if c.default]


HEAP_CASES = [c for c in DEFAULT_CASES if c.name.startswith("predict/")]
# (a `paths/.../chain` case is the sub-tree form over scalar leaves: its
# interface is the forest's)
PATH_CASES = [c for c in DEFAULT_CASES if c.name.startswith("paths/")
              and not c.name.endswith("/chain")]
OBLIVIOUS_CASES = [c for c in DEFAULT_CASES
                   if c.name.startswith("oblivious/")]
FOREST_CASES = [c for c in DEFAULT_CASES if c.name.startswith("forest/")
                or c.name.endswith("/chain")]


@functools.lru_cache(maxsize=None)
def _export_for_tpu(case):
    """(exported program, [(shape, dtype)] of its arguments)."""
    fn, shapes = case.build()
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    with device.assume_platform("tpu"):
        return jax.export.export(jax.jit(fn), platforms=("tpu",))(
            *args), shapes


@pytest.mark.parametrize("case", DEFAULT_CASES, ids=lambda c: c.name)
def test_kernel_lowers_for_tpu(case):
    exported, _ = _export_for_tpu(case)
    assert exported.platforms == ("tpu",)
    # The kernel is IN the program, compiled — not interpreted away.
    assert "tpu_custom_call" in exported.mlir_module()


@pytest.mark.parametrize("case", HEAP_CASES, ids=lambda c: c.name)
def test_heap_kernel_crosses_hbm_at_the_datas_width(case):
    """The two arrays between XLA and the heap traversal kernel: the
    uint8 chunk goes in as it comes, over a row count that is not whole
    tiles (no pad), and the scores come out class-major, [C, R] with the
    rows on the lanes. Nothing of the program holds the rows as int32
    (HBM pads its lanes to 128: 1 GB a 2M-row chunk whatever F) or a
    one-column f32 array of them (the same again)."""
    exported, shapes = _export_for_tpu(case)
    (rows, features), dtype = shapes[-1]
    assert dtype == jnp.uint8 and rows % 256
    text = exported.mlir_module()
    call, = [ln for ln in text.splitlines()
             if "@tpu_custom_call" in ln and "_traverse_kernel" in ln]
    operands, result = re.search(
        r"\}\s*:\s*\((.*)\)\s*->\s*(tensor<[^>]*>)", call).groups()
    assert operands.startswith(f"tensor<{rows}x{features}xui8>,")
    classes = int(re.fullmatch(rf"tensor<(\d+)x{rows}xf32>", result)[1])
    assert classes == (7 if "7classes" in case.name
                       else 3 if "3classes" in case.name else 1)
    assert f"tensor<{rows}x{features}xi32>" not in text
    assert f"tensor<{rows}x1xf32>" not in text
    # No padded row count either: every array of the rows has R of them.
    padded = -(-rows // 256) * 256
    assert f"tensor<{padded}x" not in text and f"x{padded}x" not in text


# The rows a grid step takes in every heap case's program (PR 42): 256,
# doubled while the step's MXU weight tiles (groups x tiles a group) times
# its rows stay within 256 x 256, 1,024 at most, halved where the kernel's
# VMEM at that tile would pass the budget; all of these programs are long
# enough (200k rows and more) for the whole step.
HEAP_STEP_ROWS = {
    "predict/higgs/1000x6": 256,                      # 8 x 32 tiles
    "predict/50x4/missing+cat": 1024,                 # 1 x 15
    "predict/covertype/210x6/7classes": 1024,         # 2 x 32
    "predict/150x6/missing+cat": 512,                 # 2 x 63
    "predict/higgs/1000x6/missing+cat": 256,          # 8 x 63
    "predict/criteo/100x6/missing+cat": 1024,         # 1 x 63: the CTR cell
    "predict/criteo/100x6/missing": 1024,
    "predict/criteo/100x6/cat": 1024,
    "predict/56f/130x5/missing": 1024,                # 2 x 31
    "predict/57f/130x5/missing": 1024,
    "predict/56f/130x5/missing+cat": 1024,
    "predict/covertype/3500x8/7classes": 256,         # 7 x 128
    "predict/covertype/3500x8/7classes/missing": 256,
    "predict/covertype/3500x8/7classes/missing+cat": 256,
    "predict/3classes/1215x6": 256,                   # 10 x 32
    "predict/covertype/350x6/7classes/missing+cat": 256,    # 3 x 63
    "predict/56f/130x5": 1024,                        # 2 x 16
    "predict/64f/130x5": 1024,
    "predict/65f/130x5": 1024,                        # 2 x 31
    # the rule's edges
    "predict/higgs/100x6": 1024,                      # 1 x 32
    "predict/higgs/250x6": 1024,                      # 2 x 32
    "predict/higgs/500x6": 512,                       # 4 x 32
    "predict/higgs/100x8": 512,                       # 1 x 128
    "predict/criteo/100x7/missing+cat": 512,          # 1 x 127
    # ... and its VMEM's: the widest shape that takes a step, the first
    # that falls back
    "predict/256f/100x6": 1024,
    "predict/257f/100x6": 512,
    "predict/1792f/100x6": 512,
    "predict/1793f/100x6": 256,
    "predict/57f/100x6/missing+cat": 512,     # the integer routing: by
    "predict/57f/100x7/missing+cat": 256,     # the node (1 x 63, 1 x 127)
}


def test_every_heap_case_names_its_step():
    assert sorted(HEAP_STEP_ROWS) == sorted(c.name for c in HEAP_CASES)


@pytest.mark.parametrize("case", HEAP_CASES, ids=lambda c: c.name)
def test_heap_kernel_takes_the_planned_step(case):
    """The row block of the kernel that is lowered is the plan's step,
    [rows a step, F] uint8, and the result's [C, rows a step]."""
    fn, shapes = case.build()
    (_, features), _ = shapes[-1]
    with device.assume_platform("tpu"):
        text = str(jax.make_jaxpr(fn)(
            *[jax.ShapeDtypeStruct(s, d) for s, d in shapes]))
    step = HEAP_STEP_ROWS[case.name]
    assert f"Ref<vmem>{{u8[{step},{features}]}}" in text
    for other in {256, 512, 1024} - {step}:
        assert f"u8[{other},{features}]" not in text


@pytest.mark.parametrize("case", PATH_CASES, ids=lambda c: c.name)
def test_path_kernel_crosses_hbm_at_the_datas_width(case):
    """The path-matrix kernel's interface is the heap kernel's (PR 37): the
    uint8 chunk as it comes, at 28 columns and at Bosch's 968 (where an
    int32 copy of a chunk would be 1 GB and more), the last row tile
    ragged, and the scores as `f32[1, R]`; no array of the program holds
    the rows as int32 or float32, padded, or as a one-column f32."""
    exported, shapes = _export_for_tpu(case)
    (rows, features), dtype = shapes[-1]
    assert dtype == jnp.uint8
    text = exported.mlir_module()
    call, = [ln for ln in text.splitlines()
             if "@tpu_custom_call" in ln and "_paths_kernel" in ln]
    operands, result = re.search(
        r"\}\s*:\s*\((.*)\)\s*->\s*(tensor<[^>]*>)", call).groups()
    assert operands.startswith(f"tensor<{rows}x{features}xui8>,")
    assert result == f"tensor<1x{rows}xf32>"
    for held in ("xi32>", "xf32>", "xbf16>"):
        assert f"tensor<{rows}x{features}{held}" not in text
    assert f"tensor<{rows}x1xf32>" not in text
    assert "ddt:predict:widen" not in text
    if rows % 4096:
        padded = -(-rows // 4096) * 4096
        assert f"tensor<{padded}x" not in text and f"x{padded}x" not in text


@pytest.mark.parametrize(
    "case", PATH_CASES + [c for c in FOREST_CASES if c.name.endswith("/chain")],
    ids=lambda c: c.name)
def test_path_kernel_takes_the_select_the_rule_packs(case):
    """Up to 64 columns and from 256 node lanes on, the kernel's select
    table is `pack_select`'s, [K2, W/2] over two copies of the features
    (and the mantissa's rows while the tile has them), handed over by the
    caller: nothing of the program is traced under `predict:tables`. Past
    64 columns or at 128 lanes it is [Fp, W], as the model compiles it."""
    from ddt_tpu.ops import predict_paths

    exported, shapes = _export_for_tpu(case)
    (trees, k_rows, sel_lanes), _ = shapes[0]
    (_, _, lanes), _ = shapes[1]
    (_, features), _ = shapes[-1]
    per_lane = predict_paths.select_nodes_per_lane(features, lanes)
    assert per_lane == (2 if features <= 64 and lanes >= 256 else 1)
    assert (k_rows, sel_lanes) == predict_paths._select_shape(
        lanes, features, per_lane)
    if per_lane == 2:
        stride = -(-features // 8) * 8
        assert sel_lanes == -(-lanes // 256) * 128
        assert k_rows == min(128, -(-(2 * stride + 8) // 16) * 16)
    text = exported.mlir_module()
    call, = [ln for ln in text.splitlines()
             if "@tpu_custom_call" in ln and "_paths_kernel" in ln]
    assert f"tensor<{trees}x{k_rows}x{sel_lanes}xbf16>" in call
    # (the three pads of no filler tree are traced there, and are nothing)
    assert "ddt:predict:tables/concatenate" not in text


# CATEGORY SETS (PR 55; scripts/tpu_aot_check.py `paths-cat/`): (one-hot
# K-blocks, K rows of the select, `select_k_blocks`, MXU weight tiles a tree,
# what the set test adds beside an ordinal model of the shape, trees a
# block, the select's spans and the one-hot blocks before the ordinal rows:
# PR 56): the Allstate cell's shape, sets and ordinal nodes in one tree over
# 32 columns (the ordinal K-block and six one-hot ones) under the spans the
# build finds for that cell's model (the ordinal block in both lane tiles,
# three one-hot blocks in each: 8 + 4, where 7 x 2 lane tiles + 4 under
# dense spans), eight columns ALL categorical (no ordinal K row: 5 blocks x
# 2 lane tiles + 4, dense) and sets beside ordinal nodes with NaN directions
# at Bosch's 968 columns (8 + 2 blocks, dense).
CAT_CASES = {
    "paths-cat/32f/500x255": (6, 32 + 768, 7, 12, 7, 20, ((0, 4), (3, 7)), 3),
    "paths-cat/8f/500x255": (5, 640, 5, 14, 9, 20, (), 0),
    "paths-cat/968f/20x255leaves/nan": (2, 976 + 256, 10, 24, 4, 5, (), 0),
}


def test_every_category_case_names_its_step():
    assert sorted(CAT_CASES) == sorted(
        c.name for c in DEFAULT_CASES if c.name.startswith("paths-cat/"))


@pytest.mark.parametrize(
    "case", [c for c in DEFAULT_CASES if c.name in CAT_CASES],
    ids=lambda c: c.name)
def test_category_set_kernel_takes_the_planned_step(case):
    """A node list with category sets: the kernel's interface is the path
    kernel's (the uint8 chunk as it comes, `f32[1, R]` back), its select
    the model's own [T, Fo + 128 B, W], one node a lane whatever F, and
    two small tables of the model beside the trees'; the plan says what
    the kernel asks of the MXU."""
    from ddt_tpu.ops import predict_paths

    blocks, k_rows, k_blocks, tiles, extra, per_step, spans, ordinal_at = \
        CAT_CASES[case.name]
    exported, shapes = _export_for_tpu(case)
    (trees, sel_rows, sel_lanes), _ = shapes[0]
    (_, _, lanes), _ = shapes[1]
    (rows, features), dtype = shapes[-1]
    fp = -(-features // 16) * 16
    assert dtype == jnp.uint8
    assert (sel_rows, sel_lanes) == (k_rows, lanes)
    assert shapes[3][0] == (blocks, fp, 128) and shapes[4][0] == (
        blocks, 8, 128)
    cat = predict_paths.CatSets(blocks, sel_rows, spans, ordinal_at)
    assert cat.ordinal_rows in (0, fp)
    plan = predict_paths.path_plan(trees, lanes, features, cat=cat)
    assert (plan.select_k_blocks, plan.path_mxu_tiles_per_tree,
            plan.catset_mxu_tiles_per_tree, plan.trees_per_step,
            plan.select_nodes_per_lane, plan.category_sets) == (
        k_blocks, tiles, extra, per_step, 1, 1)
    # the select's share of the tiles: its spans' K-blocks, or every block
    # a lane tile
    assert plan.select_mxu_tiles == tiles - plan.resolve_mxu_tiles == (
        sum(stop - start for start, stop in spans)
        or k_blocks * (lanes // 128))
    text = exported.mlir_module()
    call, = [ln for ln in text.splitlines()
             if "@tpu_custom_call" in ln and "_paths_kernel" in ln]
    operands, result = re.search(
        r"\}\s*:\s*\((.*)\)\s*->\s*(tensor<[^>]*>)", call).groups()
    assert operands.startswith(f"tensor<{rows}x{features}xui8>,")
    assert f"tensor<{blocks}x{fp}x128xbf16>" in operands
    assert result == f"tensor<1x{rows}xf32>"
    for held in ("xi32>", "xf32>", "xbf16>"):
        assert f"tensor<{rows}x{features}{held}" not in text
    # the one-hot is the kernel's, in VMEM: no stage of XLA's makes it
    assert "predict:catset" not in text and "predict:widen" not in text


@pytest.mark.parametrize("features,categories,k_blocks", [
    (aot.ALLSTATE["features"], aot.ALLSTATE["categories"], 7),
    (aot.ALL_SETS["features"], aot.ALL_SETS["categories"], 5),
], ids=["32f", "8f"])
def test_dense_set_spans_trace_the_program_of_no_spans(features, categories,
                                                       k_blocks):
    """A model with sets whose blocks do not split gets the DENSE spans,
    and those (given as (), or spelled out a lane tile) trace one program:
    what the kernel traced before it read spans; spans that split trace
    another."""
    def text(**spans):
        """The kernel's Mosaic module without debug locations, which is
        what `tpu_aot_check.py --digest` hashes."""
        case = aot.KernelCase("cat", True, aot._paths_case(
            4_999, features, 12, 255, categories=categories, **spans))
        mosaic = []
        with aot._mosaic_modules(mosaic):
            _export_for_tpu(case)
        kernel, = mosaic
        return kernel

    dense = text()
    assert text(cat_spans=((0, k_blocks),) * 2) == dense
    if k_blocks == 7:
        assert text(cat_spans=((0, 4), (3, 7)), cat_ordinal_at=3) != dense


# The exits' table of every forest case (PR 49): (its lanes, the class
# lanes of the kernel's result, the select's spans, `exit_mxu_tiles`, the
# MXU weight tiles a sub-tree; a sixth, PR 51: `resolve_mxu_tiles`, 2 where
# the path table holds the diagonal blocks of HALVED sub-trees alone). ONE lane tile where the three pieces of a
# leaf's values and the tree's chain fit it (models/tree.exit_table_lanes);
# [V | L], lane tiles of their own, at 85 and 128 classes and under a chain
# of 200 sub-trees.
FOREST_EXITS = {
    "forest/784f/100x4779x10": (128, 128, ((0, 3), (3, 7)), 2, 13),
    "forest/784f/12x20subtrees/shared-block":
        (128, 128, ((0, 4), (3, 7)), 2, 14),
    "forest/28f/12x1subtree/c1": (128, 128, (), 2, 7),
    "forest/28f/12x1subtree/c85": (384, 256, (), 6, 11),
    "forest/28f/12x1subtree/c128": (512, 384, (), 8, 13),
    "forest/129f/3x200subtrees/c10/128lanes": (384, 128, (), 3, 6),
    # softmax's round-major trees (PR 50): 21 and 9 lanes of pieces, chains
    # of 50 and 30, the packed select: 1 + 4 + 2 tiles a sub-tree with the
    # whole path matrix, 1 + 2 + 2 with the halves' (PR 51: the XGBoost
    # cell's), 2 + 2 + 2 under the unpacked select of 100 columns
    "paths/54f/softmax7/chain": (128, 128, (), 2, 5, 2),
    "paths/54f/softmax3/chain": (128, 128, (), 2, 7),
    "paths/100f/softmax7/halved/chain": (128, 128, (), 2, 6, 2),
}


def test_every_forest_case_names_its_exits():
    assert sorted(FOREST_EXITS) == sorted(
        c.name for c in aot.kernel_cases() if c.name.startswith("forest/")
        or c.name.endswith("/chain"))


@pytest.mark.parametrize("case", FOREST_CASES, ids=lambda c: c.name)
def test_subtree_form_crosses_hbm_at_the_datas_width(case):
    """The sub-tree form of the path kernel (the chain and the class dot)
    keeps the kernel's interface on the way in (the uint8 chunk as it
    comes, 784 columns at the MNIST forest's chunk, the last row tile
    ragged) and takes a fourth table, the exits', `[entries, lanes, 128]`
    bf16 where the pieces and the chain share ONE lane tile (the MNIST
    forest's), the class tiles and the activity's behind them where they do
    not; on the way out the rows lie on the sublanes, `f32[R, CL]` with the
    leaf values' three pieces in the first 3 C of the class lanes, which
    XLA folds to `[R, C]` and divides by the tree count under
    `predict:accumulate`."""
    from ddt_tpu.ops import predict_paths

    exported, shapes = _export_for_tpu(case)
    (rows, features), dtype = shapes[-1]
    (entries, lanes, exit_lanes), _ = shapes[3]
    path_shape, _ = shapes[2]
    assert dtype == jnp.uint8 and len(shapes) == 5
    text = exported.mlir_module()
    call, = [ln for ln in text.splitlines()
             if "@tpu_custom_call" in ln and "_paths_kernel" in ln]
    operands, result = re.search(
        r"\}\s*:\s*\((.*)\)\s*->\s*(tensor<[^>]*>)", call).groups()
    assert operands.startswith(f"tensor<{rows}x{features}xui8>,")
    assert f"tensor<{entries}x{lanes}x{exit_lanes}xbf16>" in operands
    class_lanes = int(re.fullmatch(
        rf"tensor<{rows}x(\d+)xf32>", result).group(1))
    want_exits, want_class, spans, exit_tiles, tiles, *resolve = \
        FOREST_EXITS[case.name]
    resolve, = resolve or [(lanes // 128) ** 2]
    assert (exit_lanes, class_lanes) == (want_exits, want_class)
    # the path table as the kernel's operand: whole, or the halves' blocks
    halved = resolve < (lanes // 128) ** 2
    assert path_shape == (entries, lanes // 2 if halved else lanes, lanes)
    assert "tensor<" + "x".join(map(str, path_shape)) + "xbf16>" in operands
    # one tile of exits IS the class lanes; else the activity lies behind
    assert class_lanes % 128 == 0 and (
        exit_lanes == class_lanes == 128 or exit_lanes >= class_lanes + 128)
    for held in ("xi32>", "xf32>", "xbf16>"):
        assert f"tensor<{rows}x{features}{held}" not in text
    assert "ddt:predict:widen" not in text
    assert "ddt:predict:accumulate" in text
    out, = exported.out_avals
    assert out.shape[0] == rows and out.shape[1] * 3 <= class_lanes
    # ... and what the plan says of it on the `ensemble` span
    chain = predict_paths.chain_of(1, out.shape[1], exit_lanes, spans,
                                   path_shape)
    assert chain.shared == (exit_lanes == 128)
    assert chain.halved == halved
    assert chain.at_hand == (3 * out.shape[1] if chain.shared else 0)
    plan = predict_paths.path_plan(entries, lanes, features, chain=chain)
    assert plan.exit_mxu_tiles == exit_tiles
    assert plan.resolve_mxu_tiles == resolve
    assert predict_paths.path_mxu_tiles_per_tree(
        lanes, features, None, exit_lanes, spans, chain.halved) == tiles


@pytest.mark.parametrize("case", OBLIVIOUS_CASES, ids=lambda c: c.name)
def test_oblivious_kernel_crosses_hbm_at_the_datas_width(case):
    """The oblivious form's interface is the other kernels': the uint8 chunk
    as it comes, at 28 columns and at the Epsilon model's 2000 (where an
    int32 copy of a 131,072-row chunk would be 1 GB), the last row tile
    ragged, and the scores as `f32[1, R]` (of vector leaves class-major,
    `f32[C, R]`, the heap kernel's interface); no array of the program
    holds the rows widened or padded, and nothing is traced under
    `predict:widen`."""
    exported, shapes = _export_for_tpu(case)
    (rows, features), dtype = shapes[-1]
    (groups, depth, fp, lanes), _ = shapes[0]
    classes = shapes[2][0][1] >> depth
    assert dtype == jnp.uint8 and lanes == 128
    text = exported.mlir_module()
    call, = [ln for ln in text.splitlines()
             if "@tpu_custom_call" in ln and "_oblivious_kernel" in ln]
    operands, result = re.search(
        r"\}\s*:\s*\((.*)\)\s*->\s*(tensor<[^>]*>)", call).groups()
    assert operands.startswith(
        f"tensor<{rows}x{features}xui8>, "
        f"tensor<{groups}x{depth}x{fp}x128xbf16>,")
    assert result == f"tensor<{classes}x{rows}xf32>"
    for held in ("xi32>", "xf32>", "xbf16>"):
        assert f"tensor<{rows}x{features}{held}" not in text
    assert f"tensor<{rows}x1xf32>" not in text
    assert "ddt:predict:widen" not in text
    if rows % 2048:
        padded = -(-rows // 2048) * 2048
        assert f"tensor<{padded}x" not in text and f"x{padded}x" not in text


def test_case_table_covers_the_default_dispatch():
    """Both histogram forms, feature-chunked at the Covertype width, and
    the traversal kernel with and without the optional operands, for one
    output and for seven, with whole tree groups and with a filled-up
    last one, two nodes a weight tile (every case of at most 64
    features without a routing table; Covertype's own 3,500 trees at
    depth 8 among them) and one, routed by integers on the VPU
    (57 features) and inside the weight tile (`routes_in_tile`)."""
    names = [c.name for c in DEFAULT_CASES]
    for needle in ("hist/higgs/255bins", "hist/higgs/64bins",
                   "hist/covertype", "predict/higgs/1000x6",
                   "predict/50x4/missing+cat", "7classes",
                   "predict/150x6/missing+cat",
                   "predict/higgs/1000x6/missing+cat",
                   "predict/criteo/100x6/missing+cat",
                   "predict/covertype/3500x8/7classes",
                   "predict/covertype/3500x8/7classes/missing",
                   "7classes/missing+cat", "predict/56f", "predict/64f",
                   "predict/65f",
                   # the folded routed form: each table alone, and its
                   # edge (56 features fold, 57 route on the VPU)
                   "predict/criteo/100x6/missing",
                   "predict/criteo/100x6/cat",
                   "predict/56f/130x5/missing",
                   "predict/57f/130x5/missing",
                   "predict/56f/130x5/missing+cat",
                   # the path-matrix form (node lists)
                   "paths/higgs/500x255leaves", "paths/9x15leaves",
                   "paths/70f",
                   # the packed select's edges: the mantissa on the VPU
                   # (64 columns), one node a lane (65), 512 lanes routed
                   "paths/64f/12x255leaves/nan", "paths/65f",
                   "paths/56f/12x500leaves/nan",
                   # past one K-block of the select; Bosch's width with
                   # the NaN route in the compare
                   "paths/129f", "paths/bosch/968f",
                   # category sets: the Allstate cell's shape (sets and
                   # ordinal nodes), a model of sets alone, and sets
                   # beside routed ordinal nodes at 968 columns
                   "paths-cat/32f/500x255", "paths-cat/8f/500x255",
                   "paths-cat/968f",
                   # the sub-tree form: the MNIST forest's chunk, one
                   # sub-tree a tree at one column and at 85, one-tile
                   # sub-trees with two activity tiles
                   "forest/784f/100x4779x10", "forest/28f/12x1subtree/c1",
                   "forest/28f/12x1subtree/c85", "forest/129f/3x200subtrees",
                   # softmax's round-major trees under the packed select
                   "paths/54f/softmax7/chain", "paths/54f/softmax3/chain",
                   # the oblivious form: the Epsilon model's chunk, the
                   # dispatch rule's edges, one and two K-blocks
                   "oblivious/epsilon/8000x6", "oblivious/28f/300x10",
                   "oblivious/2000f/300x7", "oblivious/28f/5x1",
                   "oblivious/129f",
                   # the pipeline's edges: the deepest tree it unrolls, a
                   # step of one sub-tile
                   "oblivious/28f/130x8", "oblivious/28f/130x6/1000rows",
                   # vector leaves: the Covertype CatBoost model's chunk and
                   # the gathered lookup's edges (depth 7, 5 and 3)
                   "oblivious/54f/1000x6xC7", "oblivious/54f/130x7xC7",
                   "oblivious/54f/130x5xC7", "oblivious/54f/130x3xC7"):
        assert any(needle in n for n in names), (needle, names)


def test_assume_platform_restores():
    assert device.platform() == "cpu"
    with device.assume_platform("tpu"):
        assert device.platform() == "tpu"
        with pytest.raises(RuntimeError):
            with device.assume_platform("gpu"):
                assert device.platform() == "gpu"
                raise RuntimeError("boom")
        assert device.platform() == "tpu"
    assert device.platform() == "cpu"

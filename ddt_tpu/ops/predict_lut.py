"""TreeLUT-style int8 quantized traversal — the low-latency scoring path.

TreeLUT (arXiv:2501.01511) shows that latency-critical GBDT inference
wants the model as small fixed-point lookup tables, not f32 node arrays:
int8 thresholds, low-precision leaf tables, and a traversal shaped like
table lookups. This module is that representation for the serving tier
(docs/SERVING.md): `quantize_compiled` turns a CompiledEnsemble's
pushed-down arrays into `QuantizedTables`, and `predict_effective_lut`
scores binned rows against them with a Pallas kernel (interpret-mode CPU
fallback, the hist_pallas/predict_pallas pattern).

Why it is faster per request than the f32 Pallas path (docs/PERF.md
"Serving latency"):

- the binned rows stream from HBM as RAW uint8 — the f32 kernel streams
  an int32-widened copy, so the row traffic (the only O(rows) HBM term)
  drops 4x;
- thresholds live as int8 (4x smaller than the int32 effective table)
  and leaves as fp16 or int8+scale (2-4x smaller than f32) — at
  single-row micro-batches the tree tables ARE the working set, so the
  resident footprint shrinks by the same factor;
- the descent itself is unchanged in SHAPE (one-hot colval matmul +
  indexed selects, all in VMEM) — the quantization changes what crosses
  HBM, not what the VPU does.

Bitwise rounding contract (tests/test_predict_lut.py pins all three):

1. THRESHOLDS ARE EXACT. Bin ids occupy [0, 255]; `thr_i8 = clip(
   eff_thr, 0, 255) - 128` (round-to-nearest is vacuous — the values
   are integers) loses nothing: a pushed-down leaf's +BIG threshold
   clips to 255, and "fv > 255" is false for every uint8 bin value —
   exactly the always-left routing +BIG encoded. Descent (and therefore
   leaf CHOICE) is bit-identical to the f32 path.
2. LEAVES ROUND ONCE, DOCUMENTED. fp16 mode: leaf tables are
   np.float16(bot_val) (IEEE round-to-nearest-even); int8 mode:
   `q = round(bot_val / scale_t)` with one f32 scale per tree row,
   scale_t = max|bot_val[t]| / 127. Dequantization (f16 -> f32 cast,
   q * scale in f32) is exact, so the ONLY error source is that single
   rounding step.
3. MAX-ABS-ERROR BOUND, COMPUTED NOT HOPED. `QuantizedTables.
   max_abs_err` = learning_rate * sum over trees of the tree's worst
   node rounding error — an exact, per-model bound on |lut - f32| for
   any input (each tree contributes exactly one leaf per row; softmax
   classes see a subset of trees, so the scalar bound is conservative).
   The tests drive random inputs across n_classes x missing x
   categorical and assert the bound holds with only f32-accumulation
   slack on top.

Parity contract: the kernel mirrors the one-hot path's accumulation
term-for-term, so `predict_effective_lut(tables, X)` is BIT-EXACT to
`predict_raw_effective(..., use_pallas=False)` fed the DEQUANTIZED
tables — the interpret-mode reference the tests pin. Dispatch:
cfg.predict_impl="lut" / `cli predict --quantized` / ServeEngine
(quantize=True), auto-guarded by `predict_lut_fits` (the ddtlint
pallas-vmem-guard contract) with the f32 path as fallback.

int4 TIER (ISSUE 12, the microsecond single-row bar of arXiv:2501.01511
/ arXiv:2409.16075): `quantize_compiled(ce, leaf_dtype="int4")` rounds
leaves onto a 4-bit grid (`q = round(bot_val / scale_t)`, scale_t =
max|bot_val[t]| / 7, clipped to [-7, 7]) — the SAME single documented
rounding step as int8, just a coarser grid, so the max_abs_err bound
formula extends unchanged (lr * sum of per-tree worst node error).
`QuantizedTables.pack_int4()` then bit-packs the device layout
two-nibbles-per-byte: leaf planes pair (j, j + n_leaves/2) into one
byte block, and thresholds ride the nibble pack too WHEN every real
threshold fits (value <= 14; nibble 15 is the always-left sentinel,
decoded in-VPU to 256 > any uint8 bin — models trained with <= 15 bins,
the TreeLUT regime). Descent stays EXACT either way: unpackable
thresholds keep the lossless int8 form. `_lut4_kernel` unpacks in-VPU
(shift/mask on int32 lanes) and keeps the whole walk in VMEM — at
single-row micro-batches the tables ARE the working set, and the int4
pack halves the int8 tier's resident bytes again. Dispatch:
cfg.predict_impl="lut4" / `--quantized int4` / ServeEngine
(quantize="int4"), guarded by `predict_lut4_fits` with the int8 LUT
tier, then f32, as the fallback ladder (backends/tpu.py).

int4 exactness contract (tests/test_predict_lut4.py): DESCENT — and
therefore leaf CHOICE — is bit-identical to the f32 path (thresholds
dequantize exactly at either width), and each selected leaf dequantizes
to exactly `leaf_q * scale` in f32 (the kernel performs that very
multiply, pre-select, on the unpacked table). The one remaining float
degree of freedom is f32 SUMMATION ORDER across trees, which XLA's
fusion choices own, not this kernel (the same slack every kernel-parity
contract in this repo carries — tests/test_hist_fused.py pins its
bitwise claims on integer-valued inputs for exactly this reason). The
tests therefore pin BITWISE equality to the one-hot reference on
order-free exact-grid leaf values (power-of-two scale, integer leaf_q)
across the full variant matrix, and hold random-value models to the
computed max_abs_err bound with f32-accumulation slack only.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddt_tpu.telemetry.annotations import op_scope, traced_scope
from ddt_tpu.telemetry.costmodel import costed
from ddt_tpu.utils import device

# Same ceiling discipline as predict_pallas: the per-tile colval/comp
# working set + the (now int8/fp16) resident tables + Mosaic's
# double-buffered windows must fit ~16 MB/core with headroom.
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024
_DEFAULT_TILE_R = 256
_MAX_TRACE_SELECTS = 32_768
# Working-set bytes per (row, lane) of a tree chunk's [TILE_R, Nint*Tc]
# colval (f32) and comparison bits (int32) under the k-indexed descent
# (the f32 kernel has no such array and counts by the row:
# predict_pallas._ROW_BYTES). NOT 8: the compiler streams both through
# vector registers plane by plane and spills little. Taken from the
# compiler's own account of this descent with f32 tables — AOT compiles
# for a described v5e with the scoped limit forced to 1 MiB, so that
# each reports its allocation (PERF.md, PR 21): <= 0.9 B
# per (row, lane) at every probed shape (tile 256 and 512, depth 4-8,
# with and without the missing/cat operands, 1 and 7 classes). 2 keeps
# every admitted shape inside what was shown to compile.
WORK_BYTES_PER_LANE = 2

#: int8 bin recentering offset: uint8 bins [0, 255] -> [-128, 127].
_I8_OFFSET = 128

#: largest REAL threshold a nibble can carry (15 is the always-left
#: sentinel — pack_int4's threshold-packability condition).
_NIB_THR_MAX = 14
#: what the sentinel nibble decodes to in-kernel: 256 > every uint8 bin
#: value, so "fv > 256" is always False — the +BIG always-left contract
#: in 4-bit clothing (exact in f32).
_NIB_BIG = 256


@dataclasses.dataclass(frozen=True)
class QuantizedTables:
    """int8/fp16 LUT scoring tables for one model version (host arrays;
    device backends key their resident copies on `token`, exactly like
    the f32 CompiledEnsemble path)."""

    token: str                  # source CompiledEnsemble.token
    tree_chunk: int
    max_depth: int
    n_classes_out: int
    learning_rate: float
    base_score: float
    loss: str
    missing_bin_value: int      # raw (unrecentred) reserved-NaN bin, -1=off
    leaf_dtype: str             # "float16" | "int8"
    max_abs_err: float          # documented |lut - f32| bound (module doc)
    eff_feat: np.ndarray        # int32 [Tpad, N] pushed-down features
    thr_i8: np.ndarray          # int8  [Tpad, N] recentred thresholds
    leaf_q: np.ndarray          # f16 [Tpad, 2^D] or int8 [Tpad, 2^D]
    leaf_scale: np.ndarray | None   # f32 [Tpad] per-tree scale (int8 mode)
    cls_oh: np.ndarray          # f32 [Tpad, C] round-major class one-hot
    eff_dl: np.ndarray | None   # bool [Tpad, N] or None
    eff_cat: np.ndarray | None  # bool [Tpad, N] or None

    @property
    def n_trees_padded(self) -> int:
        return int(self.eff_feat.shape[0])

    def arrays(self) -> tuple:
        """Device-uploadable operand tuple in predict_effective_lut's
        argument order (optional masks appended when present)."""
        out = [self.eff_feat, self.thr_i8, self.leaf_q]
        if self.leaf_scale is not None:
            out.append(self.leaf_scale)
        out.append(self.cls_oh)
        if self.eff_dl is not None:
            out.append(self.eff_dl)
        if self.eff_cat is not None:
            out.append(self.eff_cat)
        return tuple(out)

    def dequantized(self) -> tuple[np.ndarray, np.ndarray]:
        """(eff_thr int32, bot_val f32) EXACTLY as the kernel sees them —
        the reference arrays the parity tests feed the f32 one-hot path
        (dequantization is exact; module doc, contract 2)."""
        thr = self.thr_i8.astype(np.int32) + _I8_OFFSET
        if self.leaf_scale is not None:
            val = (self.leaf_q.astype(np.float32)
                   * self.leaf_scale[:, None].astype(np.float32))
        else:
            val = self.leaf_q.astype(np.float32)
        return thr, val

    def pack_int4(self) -> "PackedTables":
        """Bit-pack the int4 tier's DEVICE layout two-nibbles-per-byte
        (module doc "int4 TIER"): leaf planes (j, j + half) share a
        byte block; thresholds join the pack when every real threshold
        fits a nibble (value <= 14 — nibble 15 decodes to the 256
        always-left sentinel in-VPU), else they keep the lossless int8
        node-major form. Built ONCE per model version; the serving
        backend uploads `ops` device-resident."""
        if self.leaf_dtype != "int4":
            raise ValueError(
                f"pack_int4 needs leaf_dtype='int4' tables, got "
                f"{self.leaf_dtype!r}; quantize with leaf_dtype='int4'")
        q = self
        tc = q.tree_chunk
        n_tc = q.n_trees_padded // tc
        n_int = (1 << q.max_depth) - 1
        n_leaves = 1 << q.max_depth
        # Thresholds: raw (unrecentred) values in [0, 255]; +BIG clipped
        # to 255 at quantize time. Packable iff every REAL threshold is
        # <= 14 — 255 (the clipped +BIG) maps to the sentinel, and for
        # NUMERIC ">" splits a genuine 255 would be always-left for
        # uint8 bins anyway. Categorical nodes get NO 255 exemption:
        # their comparison is equality, and remapping a category id to
        # the 256 sentinel would flip "bin == 255 goes left" into
        # always-right — cat-active nodes must fit the nibble verbatim.
        thr_raw = q.thr_i8[:, :n_int].astype(np.int32) + _I8_OFFSET
        ok = (thr_raw <= _NIB_THR_MAX) | (thr_raw >= 255)
        if q.eff_cat is not None:
            cat_nodes = (q.eff_cat[:, :n_int].astype(bool)
                         & (q.eff_feat[:, :n_int] >= 0))
            ok &= ~cat_nodes | (thr_raw <= _NIB_THR_MAX)
        thr_packed = bool(np.all(ok))
        if thr_packed:
            nib = np.where(thr_raw >= 255, 15, thr_raw).astype(np.uint8)
            h_n = (n_int + 1) // 2          # n_int = 2^D - 1 is odd
            # Pad the node axis with the always-left sentinel so low/high
            # halves pair up; the kernel's lane slice drops the pad.
            nib = np.pad(nib, ((0, 0), (0, 2 * h_n - n_int)),
                         constant_values=15)
            thr_op = _pack_nibbles(
                _node_major(nib[:, :h_n], n_tc, tc, h_n, np.uint8),
                _node_major(nib[:, h_n:], n_tc, tc, h_n, np.uint8))
        else:
            thr_op = _node_major(q.thr_i8[:, :n_int], n_tc, tc, n_int,
                                 np.int8)
        # Leaves: int4 values in [-7, 7]; plane j pairs with j + h_l
        # (low/high nibble), two's-complement low nibble per value.
        h_l = (n_leaves + 1) // 2
        leaf = np.pad(q.leaf_q.astype(np.int16),
                      ((0, 0), (0, 2 * h_l - n_leaves)))
        leaf_op = _pack_nibbles(
            _node_major(leaf[:, :h_l] & 0xF, n_tc, tc, h_l, np.uint8),
            _node_major(leaf[:, h_l:] & 0xF, n_tc, tc, h_l, np.uint8))
        ops = [
            _node_major(q.eff_feat[:, :n_int], n_tc, tc, n_int, np.int32),
            thr_op,
            leaf_op,
            q.leaf_scale.reshape(n_tc, tc).astype(np.float32),
            np.asarray(q.cls_oh, np.float32),
        ]
        if q.eff_dl is not None:
            ops.append(_node_major(q.eff_dl[:, :n_int], n_tc, tc, n_int,
                                   np.int8))
        if q.eff_cat is not None:
            # Pre-gate on eff_feat >= 0 so pushed-down leaves stay
            # always-left, exactly like the int8/f32 paths.
            cat_eff = (q.eff_cat[:, :n_int].astype(bool)
                       & (q.eff_feat[:, :n_int] >= 0))
            ops.append(_node_major(cat_eff, n_tc, tc, n_int, np.int8))
        return PackedTables(tables=q, thr_packed=thr_packed,
                            ops=tuple(ops))


@dataclasses.dataclass(frozen=True)
class PackedTables:
    """The int4 tier's bit-packed device operand layout for one model
    version (QuantizedTables.pack_int4): node-major arrays in kernel
    argument order, leaf nibbles (and threshold nibbles when
    `thr_packed`) two-per-byte. `tables` keeps the logical int4 tier —
    token, error bound, and the npz round trip all ride on it."""

    tables: QuantizedTables
    thr_packed: bool            # thresholds rode the nibble pack
    ops: tuple                  # node-major operand arrays

    @property
    def token(self) -> str:
        return self.tables.token

    @property
    def max_abs_err(self) -> float:
        return self.tables.max_abs_err

    def arrays(self) -> tuple:
        """Device-uploadable operand tuple in predict_effective_lut4_ops
        argument order."""
        return self.ops

    def static_kwargs(self) -> dict:
        """The kernel's static argument set — one home shared by the
        backend dispatch and the AOT export closure."""
        t = self.tables
        return dict(
            max_depth=t.max_depth, learning_rate=t.learning_rate,
            base=t.base_score, n_classes=t.n_classes_out,
            tree_chunk=t.tree_chunk, n_trees_padded=t.n_trees_padded,
            missing_bin_value=t.missing_bin_value,
            use_missing=t.eff_dl is not None,
            use_cat=t.eff_cat is not None,
            thr_packed=self.thr_packed,
        )


def _pack_nibbles(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Two uint8 nibble arrays -> one byte array (lo | hi << 4)."""
    return ((lo.astype(np.uint8) & 0xF)
            | ((hi.astype(np.uint8) & 0xF) << 4)).astype(np.uint8)


def quantize_compiled(ce, leaf_dtype: str = "float16") -> QuantizedTables:
    """CompiledEnsemble -> QuantizedTables (the rounding contract in the
    module doc; pure NumPy — models/tree.CompiledEnsemble.quantize calls
    this lazily so the models layer stays jax-free). leaf_dtype "int4"
    is the bit-packed tier's logical form: leaf_q holds the 4-bit
    integers [-7, 7] in an int8 array (the npz round trip and
    `dequantized()` stay dtype-generic); `pack_int4()` makes the
    two-nibbles-per-byte device layout."""
    if leaf_dtype not in ("float16", "int8", "int4"):
        raise ValueError(
            f"leaf_dtype must be float16|int8|int4, got {leaf_dtype!r}")
    # Contract 1: integer bin thresholds survive the int8 recentring
    # exactly; +BIG (pushed-down leaves) clips to 255 = always-left.
    thr_i8 = (np.clip(ce.eff_thr, 0, 255) - _I8_OFFSET).astype(np.int8)
    bot = np.asarray(ce.bot_val, np.float32)
    if leaf_dtype == "float16":
        leaf_q = bot.astype(np.float16)
        leaf_scale = None
        deq = leaf_q.astype(np.float32)
    else:
        # Same single documented rounding step at both integer widths —
        # only the grid changes (contract 2; the int4 step is the
        # "extended to the int4 rounding step" of the bound, contract 3).
        qmax = 7.0 if leaf_dtype == "int4" else 127.0
        max_abs = np.abs(bot).max(axis=1)                   # [Tpad]
        leaf_scale = np.where(max_abs > 0, max_abs / qmax,
                              1.0).astype(np.float32)
        leaf_q = np.clip(np.rint(bot / leaf_scale[:, None]),
                         -qmax, qmax).astype(np.int8)
        deq = leaf_q.astype(np.float32) * leaf_scale[:, None]
    # Contract 3: exact per-model bound — each tree contributes one leaf
    # per row, so worst-node error per tree sums across trees.
    per_tree = np.abs(bot - deq).max(axis=1)                # [Tpad]
    max_abs_err = float(ce.learning_rate * per_tree.sum())
    return QuantizedTables(
        token=ce.token, tree_chunk=ce.tree_chunk, max_depth=ce.max_depth,
        n_classes_out=ce.n_classes_out, learning_rate=ce.learning_rate,
        base_score=ce.base_score, loss=ce.loss,
        missing_bin_value=ce.missing_bin_value, leaf_dtype=leaf_dtype,
        max_abs_err=max_abs_err,
        eff_feat=np.asarray(ce.eff_feat, np.int32), thr_i8=thr_i8,
        leaf_q=leaf_q, leaf_scale=leaf_scale,
        cls_oh=np.asarray(ce.cls_oh, np.float32),
        eff_dl=ce.eff_dl, eff_cat=ce.eff_cat,
    )


def predict_lut_fits(
    n_trees_padded: int,
    tree_chunk: int,
    max_depth: int,
    n_features: int,
    n_classes: int,
    tile_r: int | None = None,
) -> bool:
    """Whether the LUT kernel's VMEM working set (and trace size) fits at
    this shape — the guard behind the "lut" dispatch (backends/tpu.py
    falls back to the f32 path when it fails; the ddtlint
    pallas-vmem-guard contract)."""
    if tile_r is None:
        tile_r = _DEFAULT_TILE_R
    if n_trees_padded % tree_chunk != 0:
        return False
    n_int = (1 << max_depth) - 1
    n_leaves = 1 << max_depth
    n_tc = n_trees_padded // tree_chunk
    if n_tc * (n_int + n_leaves) > _MAX_TRACE_SELECTS:
        return False
    lanes = n_int * tree_chunk
    work = tile_r * lanes * WORK_BYTES_PER_LANE   # the same descent
    # Resident tables: feat int32 + thr int8 + leaves (2B f16 / 1B int8
    # + 4B scale) + class one-hot — the quantized footprint.
    trees = n_tc * (lanes * 5 + n_leaves * tree_chunk * 2)
    trees += n_trees_padded * (n_classes * 4 + 4)
    x_tile = tile_r * n_features              # raw uint8 rows
    out = tile_r * max(n_classes, 8) * 4
    return work + trees + x_tile + out <= _VMEM_BUDGET_BYTES


def _lut_kernel(x_ref, feat_ref, thr_ref, val_ref, *rest,
                n_tc: int, tc: int, n_int: int, n_leaves: int,
                n_feat: int, max_depth: int, missing_bin_value: int,
                use_missing: bool, use_cat: bool, use_scale: bool):
    """One row tile against the int8/fp16 tables, fully in VMEM.

    x_ref [TILE_R, F] RAW uint8 bins (the 4x HBM saving — no widened
    copy); feat [n_tc, Nint*Tc] int32 node-major; thr [n_tc, Nint*Tc]
    int8 recentred; val [n_tc, W*Tc] f16 or int8; optional scale
    [n_tc, Tc] f32; coh [Tpad, C] f32; optional dl/cat [n_tc, Nint*Tc]
    int8; out [TILE_R, C] f32. A k-indexed descent and leaf select over
    tree_chunk-lane planes; the f32 kernel (predict_pallas) walks a value
    mux tree over 128-lane tree groups instead (ROADMAP C1 decides these
    kernels)."""
    rest = list(rest)
    out_ref = rest.pop()
    scale_ref = rest.pop(0) if use_scale else None
    coh_ref = rest.pop(0)
    dl_ref = rest.pop(0) if use_missing else None
    cat_ref = rest.pop(0) if use_cat else None
    tile_r = x_ref.shape[0]
    lanes = n_int * tc
    # uint8 -> bf16 has no Mosaic lowering; widen through int32 (the
    # cast the histogram kernel uses). Bins <= 255 are exact in bf16.
    xb = x_ref[:].astype(jnp.int32).astype(jnp.bfloat16)
    f_iota = jax.lax.broadcasted_iota(jnp.int32, (n_feat, lanes), 0)
    acc = jnp.zeros((tile_r, out_ref.shape[1]), jnp.float32)
    for c in range(n_tc):
        # Feature one-hot (sublane broadcast vs lane iota — the
        # hist_pallas trick); feat = -1 matches no sublane -> colval 0.
        feat = jnp.broadcast_to(feat_ref[c:c + 1, :], (n_feat, lanes))
        fohT = (feat == f_iota).astype(jnp.bfloat16)      # [F, Nint*Tc]
        # f32 accumulator, f32 compares, int32 comparison bits: the
        # three forms the v5e compiler takes (predict_pallas.
        # _traverse_kernel says why).
        colval = jax.lax.dot_general(
            xb, fohT, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # [T, Nint*Tc]
        # Undo the int8 recentring in VMEM: int8 -> f32 is exact, and
        # +128 keeps every value an exact integer <= 255. A clipped
        # +BIG threshold decodes to 255 -> "fv > 255" is always False,
        # the always-left contract (module doc, contract 1).
        thr = jnp.broadcast_to(
            thr_ref[c:c + 1, :].astype(jnp.float32)
            + jnp.float32(_I8_OFFSET), (tile_r, lanes))
        comp = (colval > thr).astype(jnp.int32)
        if use_cat:
            cat = jnp.broadcast_to(
                cat_ref[c:c + 1, :].astype(jnp.int32),
                (tile_r, lanes)) != 0
            comp = jnp.where(cat, (colval != thr).astype(jnp.int32), comp)
        if use_missing:
            # Reserved-NaN-bin rows (raw bin space — x streams
            # unrecentred) follow the learned direction; pushed-down
            # leaves have colval 0, never the reserved bin.
            miss = colval == jnp.float32(missing_bin_value)
            not_dl = 1 - jnp.broadcast_to(
                dl_ref[c:c + 1, :].astype(jnp.int32), (tile_r, lanes))
            comp = jnp.where(miss, not_dl, comp)
        # Indexed descent: k-select the path node's bit per level (every
        # node plane a static lane slice of the node-major comp).
        k = jnp.zeros((tile_r, tc), jnp.int32)
        for d in range(max_depth):
            lo = (1 << d) - 1
            go = jnp.zeros((tile_r, tc), jnp.int32)
            for i in range(1 << d):
                n = lo + i
                go = jnp.where(k == i, comp[:, n * tc:(n + 1) * tc], go)
            k = 2 * k + go
        # Bottom-level leaf select, dequantizing in VMEM: f16 -> f32 cast
        # is exact; int8 * f32 scale is exact in f32 (contract 2).
        vals = jnp.zeros((tile_r, tc), jnp.float32)
        for j in range(n_leaves):
            plane = jnp.broadcast_to(
                val_ref[c:c + 1, j * tc:(j + 1) * tc], (tile_r, tc)
            ).astype(jnp.float32)
            vals = jnp.where(k == j, plane, vals)
        if use_scale:
            vals = vals * jnp.broadcast_to(
                scale_ref[c:c + 1, :], (tile_r, tc)).astype(jnp.float32)
        # Same dot, precision, and per-chunk add order as the one-hot
        # path's scan body — the bit-stable mirror the parity test pins.
        acc = acc + jax.lax.dot_general(
            vals, coh_ref[c * tc:(c + 1) * tc, :],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    out_ref[:] = acc


def _node_major(a: np.ndarray, n_tc: int, tree_chunk: int, width: int,
                dtype) -> np.ndarray:
    """[Tpad, width] -> [n_tc, width*Tc], lane block n = node n of every
    tree in the chunk (host-side, once per model version)."""
    return (np.ascontiguousarray(
        np.asarray(a, dtype).reshape(n_tc, tree_chunk, width)
        .transpose(0, 2, 1)).reshape(n_tc, width * tree_chunk))


def lut_device_operands(tables: QuantizedTables) -> tuple:
    """Host-side kernel operand layout for one model version — node-major
    tables in their quantized dtypes, built ONCE (the serving tier and
    the backend cache upload these; per-request work is rows only)."""
    q = tables
    n_tc = q.n_trees_padded // q.tree_chunk
    n_int = (1 << q.max_depth) - 1
    n_leaves = 1 << q.max_depth
    ops = [
        _node_major(q.eff_feat[:, :n_int], n_tc, q.tree_chunk, n_int,
                    np.int32),
        _node_major(q.thr_i8[:, :n_int], n_tc, q.tree_chunk, n_int,
                    np.int8),
        _node_major(q.leaf_q, n_tc, q.tree_chunk, n_leaves,
                    np.float16 if q.leaf_scale is None else np.int8),
    ]
    if q.leaf_scale is not None:
        ops.append(q.leaf_scale.reshape(n_tc, q.tree_chunk)
                   .astype(np.float32))
    ops.append(np.asarray(q.cls_oh, np.float32))
    if q.eff_dl is not None:
        ops.append(_node_major(q.eff_dl[:, :n_int], n_tc, q.tree_chunk,
                               n_int, np.int8))
    if q.eff_cat is not None:
        # Pre-gate on eff_feat >= 0 so pushed-down leaves stay
        # always-left, exactly like the f32 paths.
        cat_eff = (q.eff_cat[:, :n_int].astype(bool)
                   & (q.eff_feat[:, :n_int] >= 0))
        ops.append(_node_major(cat_eff, n_tc, q.tree_chunk, n_int,
                               np.int8))
    return tuple(ops)


def predict_effective_lut_ops(
    ops: tuple,                # lut_device_operands(tables) (host or device)
    Xc: jax.Array,             # [R, F] uint8 bins
    *,
    max_depth: int,
    learning_rate,
    base,
    n_classes: int,
    tree_chunk: int,
    n_trees_padded: int,
    missing_bin_value: int,
    use_missing: bool,
    use_cat: bool,
    use_scale: bool,
    tile_r: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """LUT scoring core on prebuilt node-major operands (jit-safe; the
    backend caches the device copies of `ops` per model token)."""
    if interpret is None:
        interpret = device.platform() != "tpu"
    if tile_r is None:
        tile_r = _DEFAULT_TILE_R
    if not jnp.issubdtype(Xc.dtype, jnp.integer):
        raise ValueError(
            "the LUT kernel requires binned integer data; raw-threshold "
            "scoring has no quantized form")
    R, F = Xc.shape
    C = n_classes
    if R == 0:
        out = jnp.full((0, C), base, jnp.float32)
        return out[:, 0] if C == 1 else out
    if not interpret and not predict_lut_fits(
            n_trees_padded, tree_chunk, max_depth, F, C, tile_r):
        raise ValueError(
            f"LUT shape (trees_padded={n_trees_padded}, "
            f"tree_chunk={tree_chunk}, depth={max_depth}, F={F}, C={C}) "
            "exceeds the Pallas VMEM/trace budget; use the f32 path")
    n_tc = n_trees_padded // tree_chunk
    n_int = (1 << max_depth) - 1
    n_leaves = 1 << max_depth
    lanes = n_int * tree_chunk

    Xu = Xc.astype(jnp.uint8)        # raw bins stream as 1 B/feature
    n_tiles = -(-R // tile_r)
    rpad = n_tiles * tile_r - R
    if rpad:
        Xu = jnp.pad(Xu, ((0, rpad), (0, 0)))

    kernel = functools.partial(
        _lut_kernel, n_tc=n_tc, tc=tree_chunk, n_int=n_int,
        n_leaves=n_leaves, n_feat=F, max_depth=max_depth,
        missing_bin_value=missing_bin_value, use_missing=use_missing,
        use_cat=use_cat, use_scale=use_scale,
    )
    pinned = pl.BlockSpec((n_tc, lanes), lambda i: (0, 0),
                          memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((tile_r, F), lambda i: (i, 0),
                     memory_space=pltpu.VMEM),             # rows (uint8)
        pinned,                                            # feat
        pinned,                                            # thr (int8)
        pl.BlockSpec((n_tc, n_leaves * tree_chunk), lambda i: (0, 0),
                     memory_space=pltpu.VMEM),             # leaf table
    ]
    if use_scale:
        in_specs.append(pl.BlockSpec((n_tc, tree_chunk), lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
    in_specs.append(pl.BlockSpec((n_trees_padded, C), lambda i: (0, 0),
                                 memory_space=pltpu.VMEM))  # coh
    in_specs += [pinned] * (int(use_missing) + int(use_cat))
    cost = pl.CostEstimate(
        flops=2 * n_tiles * tile_r * (F * n_tc * lanes
                                      + n_trees_padded * C),
        # The honest HBM story: rows cross at 1 B/feature, tables at
        # their quantized widths (vs 4 B/feature + f32 tables on the
        # f32 kernel).
        bytes_accessed=n_tiles * tile_r * (F + C * 4)
        + n_tc * lanes * 5 + n_trees_padded * C * 4,
        transcendentals=0,
    )
    with traced_scope("predict"):
        with traced_scope("predict:traverse"):
            acc = pl.pallas_call(
                kernel,
                grid=(n_tiles,),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((tile_r, C), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((n_tiles * tile_r, C),
                                               jnp.float32),
                cost_estimate=cost,
                interpret=interpret,
            )(Xu, *ops)
        with traced_scope("predict:accumulate"):
            out = base + learning_rate * acc[:R]
    return out[:, 0] if C == 1 else out


@costed("predict_lut", phase="predict")
@op_scope("predict")
def predict_effective_lut(
    tables: QuantizedTables,
    Xc,                         # [R, F] uint8 bins (host or device)
    tile_r: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Standalone host entry (tests): builds the
    node-major operands from the tables and runs the kernel. The backend
    path (TPUDevice._predict_fn with cfg.predict_impl="lut") caches the
    operands device-resident instead — this entry rebuilds them per call
    and exists for correctness work, not the hot loop."""
    ops = lut_device_operands(tables)
    return predict_effective_lut_ops(
        tuple(jnp.asarray(a) for a in ops), jnp.asarray(Xc),
        max_depth=tables.max_depth, learning_rate=tables.learning_rate,
        base=tables.base_score, n_classes=tables.n_classes_out,
        tree_chunk=tables.tree_chunk,
        n_trees_padded=tables.n_trees_padded,
        missing_bin_value=tables.missing_bin_value,
        use_missing=tables.eff_dl is not None,
        use_cat=tables.eff_cat is not None,
        use_scale=tables.leaf_scale is not None,
        tile_r=tile_r, interpret=interpret,
    )


# --------------------------------------------------------------------- #
# int4 bit-packed tier (module doc "int4 TIER")
# --------------------------------------------------------------------- #

def predict_lut4_fits(
    n_trees_padded: int,
    tree_chunk: int,
    max_depth: int,
    n_features: int,
    n_classes: int,
    tile_r: int | None = None,
    thr_packed: bool = False,
) -> bool:
    """Whether the int4 kernel's VMEM working set (and trace size) fits
    at this shape — the guard behind the "lut4" dispatch (backends/
    tpu.py degrades to the int8 LUT tier, then f32, when it fails; the
    ddtlint pallas-vmem-guard contract)."""
    if tile_r is None:
        tile_r = _DEFAULT_TILE_R
    if n_trees_padded % tree_chunk != 0:
        return False
    n_int = (1 << max_depth) - 1
    n_leaves = 1 << max_depth
    n_tc = n_trees_padded // tree_chunk
    if n_tc * (n_int + n_leaves) > _MAX_TRACE_SELECTS:
        return False
    lanes = n_int * tree_chunk
    work = tile_r * lanes * WORK_BYTES_PER_LANE   # the same descent
    # Resident tables at the PACKED widths: feat int32 + thr (half a
    # byte/node when nibble-packed, else int8) + leaf nibbles (half a
    # byte per leaf) + f32 scale + class one-hot — half the int8 tier's
    # threshold/leaf bytes again.
    h_l = (n_leaves + 1) // 2
    thr_bytes = ((n_int + 1) // 2 if thr_packed else n_int) * tree_chunk
    trees = n_tc * (lanes * 4 + thr_bytes + h_l * tree_chunk
                    + tree_chunk * 4)
    trees += n_trees_padded * n_classes * 4
    # In-VPU unpack temporaries: the per-chunk int32 nibble planes the
    # shift/mask decode materialises before the descent consumes them.
    unpack = (lanes + h_l * 2 * tree_chunk) * 4
    x_tile = tile_r * n_features              # raw uint8 rows
    out = tile_r * max(n_classes, 8) * 4
    return work + trees + unpack + x_tile + out <= _VMEM_BUDGET_BYTES


def _lut4_kernel(x_ref, feat_ref, thr_ref, val_ref, scale_ref, coh_ref,
                 *rest, n_tc: int, tc: int, n_int: int, n_leaves: int,
                 n_feat: int, max_depth: int, missing_bin_value: int,
                 use_missing: bool, use_cat: bool, thr_packed: bool):
    """One row tile against the bit-packed int4 tables, fully in VMEM.

    x_ref [TILE_R, F] RAW uint8 bins; feat [n_tc, Nint*Tc] int32
    node-major; thr packed uint8 [n_tc, ((Nint+1)/2)*Tc] (nibble pairs
    (n, n+h); 15 = always-left sentinel -> 256) or lossless int8
    [n_tc, Nint*Tc]; val packed uint8 [n_tc, ((W+1)/2)*Tc] (leaf pairs
    (j, j+h), two's-complement nibbles); scale [n_tc, Tc] f32; coh
    [Tpad, C] f32; optional dl/cat [n_tc, Nint*Tc] int8; out [TILE_R, C]
    f32. Unpacking is shift/mask on int32 lanes + a lane-axis concat —
    the nibble planes land exactly node-major, so the descent below is
    _lut_kernel's, plane for plane."""
    rest = list(rest)
    out_ref = rest.pop()
    dl_ref = rest.pop(0) if use_missing else None
    cat_ref = rest.pop(0) if use_cat else None
    tile_r = x_ref.shape[0]
    lanes = n_int * tc
    h_l = (n_leaves + 1) // 2
    # uint8 -> bf16 has no Mosaic lowering; widen through int32 (the
    # cast the histogram kernel uses). Bins <= 255 are exact in bf16.
    xb = x_ref[:].astype(jnp.int32).astype(jnp.bfloat16)
    f_iota = jax.lax.broadcasted_iota(jnp.int32, (n_feat, lanes), 0)
    acc = jnp.zeros((tile_r, out_ref.shape[1]), jnp.float32)
    for c in range(n_tc):
        feat = jnp.broadcast_to(feat_ref[c:c + 1, :], (n_feat, lanes))
        fohT = (feat == f_iota).astype(jnp.bfloat16)      # [F, Nint*Tc]
        colval = jax.lax.dot_general(
            xb, fohT, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,    # see _lut_kernel
        )                                                 # [T, Nint*Tc]
        if thr_packed:
            # In-VPU nibble decode: low/high nibbles are node blocks
            # [0, h) and [h, 2h) — the lane concat rebuilds node-major
            # order; sentinel 15 -> 256 = always-left for any uint8 bin.
            tp = thr_ref[c:c + 1, :].astype(jnp.int32)
            nib = jnp.concatenate(
                [jnp.bitwise_and(tp, 15),
                 jnp.right_shift(tp, 4)], axis=1)[:, :lanes]
            thr_row = jnp.where(nib >= 15, jnp.int32(_NIB_BIG),
                                nib).astype(jnp.float32)
        else:
            # Lossless int8 form (a model whose thresholds exceed the
            # nibble): undo the recentring exactly like _lut_kernel.
            thr_row = (thr_ref[c:c + 1, :].astype(jnp.float32)
                       + jnp.float32(_I8_OFFSET))
        thr = jnp.broadcast_to(thr_row, (tile_r, lanes))
        comp = (colval > thr).astype(jnp.int32)
        if use_cat:
            cat = jnp.broadcast_to(
                cat_ref[c:c + 1, :].astype(jnp.int32),
                (tile_r, lanes)) != 0
            comp = jnp.where(cat, (colval != thr).astype(jnp.int32), comp)
        if use_missing:
            miss = colval == jnp.float32(missing_bin_value)
            not_dl = 1 - jnp.broadcast_to(
                dl_ref[c:c + 1, :].astype(jnp.int32), (tile_r, lanes))
            comp = jnp.where(miss, not_dl, comp)
        k = jnp.zeros((tile_r, tc), jnp.int32)
        for d in range(max_depth):
            lo = (1 << d) - 1
            go = jnp.zeros((tile_r, tc), jnp.int32)
            for i in range(1 << d):
                n = lo + i
                go = jnp.where(k == i, comp[:, n * tc:(n + 1) * tc], go)
            k = 2 * k + go
        # Unpack + dequantize the WHOLE leaf table once per chunk:
        # two's-complement sign extension of each nibble, then the one
        # f32 multiply by the per-tree scale — the very multiply the
        # host-side dequantized() reference performs, BEFORE the
        # k-select, so selected values are bit-identical to the
        # reference table (a post-select multiply invites XLA to fuse
        # it into the class dot and costs the last ULP — measured).
        vp = val_ref[c:c + 1, :].astype(jnp.int32)
        vnib = jnp.concatenate(
            [jnp.bitwise_and(vp, 15),
             jnp.bitwise_and(jnp.right_shift(vp, 4), 15)], axis=1)
        sext = jnp.where(vnib >= 8, vnib - 16,
                         vnib).astype(jnp.float32)        # [1, 2h*Tc]
        scale_row = scale_ref[c:c + 1, :].astype(jnp.float32)  # [1, Tc]
        deq = sext * jnp.concatenate([scale_row] * (2 * h_l), axis=1)
        vals = jnp.zeros((tile_r, tc), jnp.float32)
        for j in range(n_leaves):
            plane = jnp.broadcast_to(
                deq[:, j * tc:(j + 1) * tc], (tile_r, tc))
            vals = jnp.where(k == j, plane, vals)
        acc = acc + jax.lax.dot_general(
            vals, coh_ref[c * tc:(c + 1) * tc, :],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    out_ref[:] = acc


def predict_effective_lut4_ops(
    ops: tuple,                # PackedTables.ops (host or device)
    Xc: jax.Array,             # [R, F] uint8 bins
    *,
    max_depth: int,
    learning_rate,
    base,
    n_classes: int,
    tree_chunk: int,
    n_trees_padded: int,
    missing_bin_value: int,
    use_missing: bool,
    use_cat: bool,
    thr_packed: bool,
    tile_r: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """int4 scoring core on prebuilt bit-packed operands (jit-safe; the
    backend caches the device copies of `ops` per model token, the AOT
    export lowers exactly this computation per bucket shape)."""
    if interpret is None:
        interpret = device.platform() != "tpu"
    if tile_r is None:
        tile_r = _DEFAULT_TILE_R
    if not jnp.issubdtype(Xc.dtype, jnp.integer):
        raise ValueError(
            "the LUT kernel requires binned integer data; raw-threshold "
            "scoring has no quantized form")
    R, F = Xc.shape
    C = n_classes
    if R == 0:
        out = jnp.full((0, C), base, jnp.float32)
        return out[:, 0] if C == 1 else out
    if not interpret and not predict_lut4_fits(
            n_trees_padded, tree_chunk, max_depth, F, C, tile_r,
            thr_packed=thr_packed):
        raise ValueError(
            f"int4 LUT shape (trees_padded={n_trees_padded}, "
            f"tree_chunk={tree_chunk}, depth={max_depth}, F={F}, C={C}) "
            "exceeds the Pallas VMEM/trace budget; use the int8/f32 "
            "ladder")
    n_tc = n_trees_padded // tree_chunk
    n_int = (1 << max_depth) - 1
    n_leaves = 1 << max_depth
    lanes = n_int * tree_chunk
    h_n = (n_int + 1) // 2
    h_l = (n_leaves + 1) // 2

    Xu = Xc.astype(jnp.uint8)        # raw bins stream as 1 B/feature
    n_tiles = -(-R // tile_r)
    rpad = n_tiles * tile_r - R
    if rpad:
        Xu = jnp.pad(Xu, ((0, rpad), (0, 0)))

    kernel = functools.partial(
        _lut4_kernel, n_tc=n_tc, tc=tree_chunk, n_int=n_int,
        n_leaves=n_leaves, n_feat=F, max_depth=max_depth,
        missing_bin_value=missing_bin_value, use_missing=use_missing,
        use_cat=use_cat, thr_packed=thr_packed,
    )
    pinned = pl.BlockSpec((n_tc, lanes), lambda i: (0, 0),
                          memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((tile_r, F), lambda i: (i, 0),
                     memory_space=pltpu.VMEM),             # rows (uint8)
        pinned,                                            # feat
        pl.BlockSpec((n_tc, (h_n if thr_packed else n_int) * tree_chunk),
                     lambda i: (0, 0),
                     memory_space=pltpu.VMEM),             # thr (packed)
        pl.BlockSpec((n_tc, h_l * tree_chunk), lambda i: (0, 0),
                     memory_space=pltpu.VMEM),             # leaf nibbles
        pl.BlockSpec((n_tc, tree_chunk), lambda i: (0, 0),
                     memory_space=pltpu.VMEM),             # scale
        pl.BlockSpec((n_trees_padded, C), lambda i: (0, 0),
                     memory_space=pltpu.VMEM),             # coh
    ]
    in_specs += [pinned] * (int(use_missing) + int(use_cat))
    cost = pl.CostEstimate(
        flops=2 * n_tiles * tile_r * (F * n_tc * lanes
                                      + n_trees_padded * C),
        # The honest HBM story: rows at 1 B/feature, thresholds/leaves
        # at HALF a byte each when packed — the int4 pack's whole point.
        bytes_accessed=n_tiles * tile_r * (F + C * 4)
        + n_tc * (lanes * 4
                  + (h_n if thr_packed else n_int) * tree_chunk
                  + h_l * tree_chunk + tree_chunk * 4)
        + n_trees_padded * C * 4,
        transcendentals=0,
    )
    with traced_scope("predict"):
        with traced_scope("predict:traverse"):
            acc = pl.pallas_call(
                kernel,
                grid=(n_tiles,),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((tile_r, C), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((n_tiles * tile_r, C),
                                               jnp.float32),
                cost_estimate=cost,
                interpret=interpret,
            )(Xu, *ops)
        with traced_scope("predict:accumulate"):
            out = base + learning_rate * acc[:R]
    return out[:, 0] if C == 1 else out


@costed("predict_lut4", phase="predict")
@op_scope("predict")
def predict_effective_lut4(
    packed,                     # PackedTables (or int4 QuantizedTables)
    Xc,                         # [R, F] uint8 bins (host or device)
    tile_r: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Standalone host entry for the int4 tier (tests): packs on
    demand and runs the kernel. The backend path (TPUDevice._predict_fn
    with cfg.predict_impl="lut4") caches the packed operands
    device-resident instead — this entry exists for correctness work,
    not the hot loop."""
    if isinstance(packed, QuantizedTables):
        packed = packed.pack_int4()
    return predict_effective_lut4_ops(
        tuple(jnp.asarray(a) for a in packed.ops), jnp.asarray(Xc),
        **packed.static_kwargs(), tile_r=tile_r, interpret=interpret,
    )

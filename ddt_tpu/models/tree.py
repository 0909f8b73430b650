"""TreeEnsemble: SoA tensor representation of a boosted-tree ensemble.

Layer L6 of SURVEY.md §1. The reference stores trees as arrays-of-nodes and
exposes `TreeEnsemble.predict` for batch scoring [BASELINE]. TPU realisation:
structure-of-arrays tensors in complete-heap layout so prediction lowers to
depth-unrolled gather+compare with fully static shapes (no pointers, no
recursion — XLA-friendly by construction).

Heap layout: a tree of `max_depth` split levels occupies 2^(max_depth+1)-1 node
slots; node i's children are 2i+1 (left) and 2i+2 (right). Early-stopped nodes
are marked `is_leaf` and traversal freezes there. Every node slot stores a
`leaf_value` (its value as-if-leaf), so traversal needs no special casing.

Split semantics (shared repo-wide, see data/quantizer.py): binned row goes LEFT
iff bin[feature] <= threshold_bin; raw row goes LEFT iff value <= threshold_raw.
"""

from __future__ import annotations

import dataclasses
import hashlib
import typing

import numpy as np

from ddt_tpu.utils.atomic import atomic_savez


@dataclasses.dataclass
class TreeEnsemble:
    """Boosted ensemble as stacked per-tree SoA arrays.

    Shapes: [n_trees, n_nodes_total] for all node arrays. For multiclass
    (softmax), trees are interleaved round-major: tree t scores class
    `t % n_classes` (n_trees = rounds * n_classes).
    """

    feature: np.ndarray        # int32  [T, N] split feature (-1 on leaves)
    threshold_bin: np.ndarray  # int32  [T, N] split bin (go left if <=)
    threshold_raw: np.ndarray  # float32 [T, N] raw-value threshold (same rule)
    is_leaf: np.ndarray        # bool   [T, N]
    leaf_value: np.ndarray     # float32 [T, N]
    split_gain: np.ndarray     # float32 [T, N] gain of the split (0 on leaves)
    max_depth: int
    n_features: int
    learning_rate: float
    base_score: float          # raw-score offset (per class for softmax)
    loss: str                  # logloss | mse | softmax
    n_classes: int = 2
    has_raw_thresholds: bool = False  # True once a BinMapper filled threshold_raw
    # Missing-value support (cfg.missing_policy="learn"): NaN rows occupy
    # the reserved top bin (n_bins-1) and route by the per-node learned
    # default direction. default_left is None for models trained without
    # the policy (and treated as all-False).
    default_left: np.ndarray | None = None   # bool [T, N]
    missing_bin: bool = False  # True: bin n_bins-1 is the NaN bin
    n_bins: int = 0            # binning width the model was trained with
    #   (0 = unknown/legacy; required when missing_bin is True)
    # Categorical one-vs-rest splits (cfg.cat_features): nodes splitting on
    # these FEATURE indices route "bin == threshold_bin goes left" instead
    # of "bin <= threshold_bin" — the split type derives from the feature,
    # no extra per-node storage. None/empty = all-ordinal model.
    cat_features: np.ndarray | None = None   # int32, sorted

    @property
    def n_trees(self) -> int:
        return int(self.feature.shape[0])

    @property
    def n_nodes_total(self) -> int:
        return int(self.feature.shape[1])

    @property
    def n_nodes(self) -> int:
        """The nodes that ask a question, over all trees (what the span
        of the model's build counts as `nodes`)."""
        return int((~self.is_leaf & (self.feature >= 0)).sum())

    @property
    def has_cat_splits(self) -> bool:
        """Whether any feature uses categorical one-vs-rest routing (the
        single home of the cat_features presence test)."""
        return self.cat_features is not None and len(self.cat_features) > 0

    @property
    def n_splits(self) -> int:
        """Internal nodes that split, over all trees."""
        return int(((~self.is_leaf) & (self.feature >= 0)).sum())

    # ------------------------------------------------------------------ #
    # compiled scoring layout (device predict fast path)
    # ------------------------------------------------------------------ #

    def cache_token(self) -> str:
        """Content digest of everything the device scoring program depends
        on — the CompiledEnsemble cache key. The node arrays are mutated
        in place by every trainer (ens.feature[t] = ...), so identity
        cannot key a cache; hashing the ~MBs of node arrays costs
        single-digit milliseconds against the seconds of re-upload/
        re-pushdown a miss would pay."""
        h = hashlib.sha1()
        for a in (self.feature, self.threshold_bin, self.is_leaf,
                  self.leaf_value):
            h.update(np.ascontiguousarray(a).tobytes())
        if self.default_left is not None:
            h.update(np.ascontiguousarray(self.default_left).tobytes())
        if self.has_cat_splits:
            h.update(np.ascontiguousarray(self.cat_features).tobytes())
        h.update(repr((self.max_depth, self.learning_rate, self.base_score,
                       self.loss, self.n_classes, self.missing_bin,
                       self.n_bins)).encode())
        return h.hexdigest()

    layout = "heap"            # of the model and its compiled form: a key
    #   of ops/predict.LAYOUTS, which names the layout's kernel module

    def compile(self, tree_chunk: int = 64) -> "CompiledEnsemble":
        """Host-side compiled scoring layout (see CompiledEnsemble)."""
        return CompiledEnsemble.build(self, tree_chunk=tree_chunk)

    # ------------------------------------------------------------------ #
    # NumPy prediction (oracle-grade; the fast path is ops/predict.py)
    # ------------------------------------------------------------------ #

    def _traverse_np(self, X: np.ndarray, binned: bool) -> np.ndarray:
        """Leaf index per (tree, row): int32 [T, R]."""
        if not binned and not self.has_raw_thresholds:
            raise ValueError(
                "Ensemble has no raw-value thresholds (trained without a "
                "BinMapper); predict on binned data with binned=True, or "
                "train/fill with a mapper first."
            )
        T = self.n_trees
        R = X.shape[0]
        node = np.zeros((T, R), dtype=np.int64)
        thr = self.threshold_bin if binned else self.threshold_raw
        Xc = X.astype(np.int32) if binned else X.astype(np.float32)
        use_missing = self.missing_bin and self.default_left is not None
        use_cat = self.has_cat_splits
        for _ in range(self.max_depth):
            feat = np.take_along_axis(self.feature, node, axis=1)
            t = np.take_along_axis(thr, node, axis=1)
            leaf = np.take_along_axis(self.is_leaf, node, axis=1)
            fv = np.stack([Xc[np.arange(R), np.maximum(feat[k], 0)]
                           for k in range(T)])
            go_right = fv > t
            if use_cat:
                # One-vs-rest: matched category goes left. Categorical
                # columns hold bin ids in BOTH representations (the
                # encoder output passes through identity edges), so the
                # comparison is against threshold_bin either way.
                tb = np.take_along_axis(self.threshold_bin, node, axis=1)
                go_right = np.where(np.isin(feat, self.cat_features),
                                    fv != tb, go_right)
            if use_missing:
                # NaN rows: binned = the reserved top bin; raw = NaN itself
                # (NaN > t is already False, but the learned direction may
                # be RIGHT). Route by per-node default_left.
                dl = np.take_along_axis(self.default_left, node, axis=1)
                miss = (fv == self.n_bins - 1) if binned else np.isnan(fv)
                go_right = np.where(miss, ~dl, go_right)
            nxt = 2 * node + 1 + go_right
            node = np.where(leaf, node, nxt)
        return node.astype(np.int32)

    def aggregate_leaves(self, leaf_idx: np.ndarray) -> np.ndarray:
        """Raw scores from precomputed leaf indices [T, R] — the single home
        of the leaf-value aggregation rule (lr scale, base score, softmax
        tree-to-class interleave: tree t scores class t % n_classes). Used
        by predict_raw here and by the native-traversal CPU backend path."""
        vals = np.take_along_axis(self.leaf_value, leaf_idx.astype(np.int64),
                                  axis=1)               # [T, R]
        vals = vals * self.learning_rate
        if self.loss == "softmax":
            C = self.n_classes
            R = leaf_idx.shape[1]
            out = np.full((R, C), self.base_score, dtype=np.float32)
            for t in range(self.n_trees):
                out[:, t % C] += vals[t]
            return out
        return (self.base_score + vals.sum(axis=0)).astype(np.float32)

    def predict_raw(self, X: np.ndarray, binned: bool = False) -> np.ndarray:
        """Raw (margin) scores. Binary/regression: [R]; softmax: [R, C]."""
        return self.aggregate_leaves(self._traverse_np(X, binned=binned))

    def _traverse_native(self, Xb: np.ndarray) -> "np.ndarray | None":
        """Leaf indices [T, R] via the native C++ kernel on BINNED data,
        or None when the library is unavailable — the ONE home of the
        native routing-flag derivation, shared by CPUDevice.predict_raw
        and predict_raw_roundwise. Missing-bin routing needs the learned
        directions; without default_left the reserved bin falls through
        to ordinary compares, exactly like _traverse_np's use_missing
        guard. Results are bitwise equal to _traverse_np (the
        predict-path fuzz asserts it)."""
        try:
            from ddt_tpu.native import traverse_native
        except Exception:   # no toolchain, or an unloadable .so (OSError
            return None     # from ctypes.CDLL) — NumPy path either way
        cat_node = (
            np.isin(self.feature, self.cat_features)
            if self.has_cat_splits else None
        )
        use_missing = self.missing_bin and self.default_left is not None
        return traverse_native(
            np.asarray(Xb), self.feature, self.threshold_bin,
            self.is_leaf, self.max_depth,
            default_left=self.default_left,
            missing_bin_value=self.n_bins - 1 if use_missing else -1,
            cat_node=cat_node,
        )

    def predict_raw_roundwise(self, X: np.ndarray,
                              binned: bool = False) -> np.ndarray:
        """predict_raw with the SAME float32 accumulation order as the
        Driver's fit loop (one sequential add per tree, in tree order) —
        aggregate_leaves' vals.sum(axis=0) uses NumPy pairwise summation,
        whose ULP-level differences would make checkpoint resume only
        approximately equal to an uninterrupted run. Used to reconstitute
        boosting state on resume so recovery is bit-exact.

        Traversal prefers the native C++ kernel on binned data: leaf
        indices are exact integers on every engine (the predict-path
        fuzz asserts native == NumPy bitwise — results are identical,
        measured), so only the accumulation below carries the ordering
        contract. On this 1-core build box the two traversals time the
        same (~21 s for 320 trees x 1M rows); the native path exists
        for many-core hosts, where the OpenMP parallel-for scales and
        NumPy stays single-threaded."""
        leaf_idx = self._traverse_native(X) if binned else None
        if leaf_idx is None:
            leaf_idx = self._traverse_np(X, binned=binned)      # [T, R]
        if self.loss == "softmax":
            # aggregate_leaves' softmax branch is already a sequential
            # per-tree loop in tree order — identical accumulation.
            return self.aggregate_leaves(leaf_idx)
        vals = np.take_along_axis(self.leaf_value,
                                  leaf_idx.astype(np.int64), axis=1)
        vals = (vals * self.learning_rate).astype(np.float32)
        out = np.full((leaf_idx.shape[1],), self.base_score, dtype=np.float32)
        for t in range(self.n_trees):
            out += vals[t]
        return out

    def predict(self, X: np.ndarray, binned: bool = False) -> np.ndarray:
        """Probability predictions (or raw values for mse)."""
        raw = self.predict_raw(X, binned=binned)
        if self.loss == "logloss":
            return 1.0 / (1.0 + np.exp(-raw))
        if self.loss == "softmax":
            z = raw - raw.max(axis=1, keepdims=True)
            e = np.exp(z)
            return e / e.sum(axis=1, keepdims=True)
        return raw

    # ------------------------------------------------------------------ #
    # Serialization (SURVEY.md §5 checkpoint/resume: ensembles are tiny)
    # ------------------------------------------------------------------ #

    def feature_importances(self, kind: str = "split") -> np.ndarray:
        """Normalized per-feature importance, float32 [n_features].

        kind="split": fraction of internal-node splits using the feature;
        kind="gain": fraction of total split gain attributed to the feature
        (LightGBM's importance_type="split"/"gain")."""
        return _importances(self, (~self.is_leaf) & (self.feature >= 0),
                            kind)

    def dump(self, tree: int) -> dict:
        """One tree as a nested plain-Python dict (debugging / interop).

        Split nodes: {"split": {"feature", "bin", "threshold" (raw value or
        None), "gain"}, "left", "right"}; leaves: {"leaf": value}. The raw
        threshold is only present when the ensemble holds BinMapper-filled
        thresholds."""
        t = int(tree)

        def node(i: int) -> dict:
            if self.is_leaf[t, i] or self.feature[t, i] < 0:
                return {"leaf": float(self.leaf_value[t, i])}
            return {
                "split": {
                    "feature": int(self.feature[t, i]),
                    "bin": int(self.threshold_bin[t, i]),
                    "threshold": (
                        float(self.threshold_raw[t, i])
                        if self.has_raw_thresholds else None
                    ),
                    "gain": float(self.split_gain[t, i]),
                },
                "left": node(2 * i + 1),
                "right": node(2 * i + 2),
            }

        return node(0)

    def dump_text(self, tree: int) -> str:
        """Indented text rendering of one tree."""
        lines: list[str] = []

        def walk(d: dict, depth: int) -> None:
            pad = "  " * depth
            if "leaf" in d:
                lines.append(f"{pad}leaf={d['leaf']:+.6f}")
                return
            s = d["split"]
            thr = (f" (<= {s['threshold']:.6g})"
                   if s["threshold"] is not None else "")
            lines.append(
                f"{pad}f{s['feature']} <= bin {s['bin']}{thr}  "
                f"gain={s['gain']:.4g}"
            )
            walk(d["left"], depth + 1)
            walk(d["right"], depth + 1)

        walk(self.dump(tree), 0)
        return "\n".join(lines)

    def to_lightgbm_text(self, feature_names: list[str] | None = None
                         ) -> str:
        """LightGBM model.txt rendering (models/lightgbm_io.py): load with
        lightgbm.Booster(model_str=...) or diff against a LightGBM model
        tree-by-tree (docs/REAL_DATA.md)."""
        from ddt_tpu.models.lightgbm_io import to_lightgbm_text

        return to_lightgbm_text(self, feature_names=feature_names)

    @staticmethod
    def from_lightgbm_text(text: str) -> "TreeEnsemble":
        """Parse a LightGBM model.txt (models/lightgbm_io.py)."""
        from ddt_tpu.models.lightgbm_io import from_lightgbm_text

        return from_lightgbm_text(text)

    def to_dict(self) -> dict:
        return {
            "layout": np.bytes_(b"heap"),
            "feature": self.feature,
            "threshold_bin": self.threshold_bin,
            "threshold_raw": self.threshold_raw,
            "is_leaf": self.is_leaf,
            "leaf_value": self.leaf_value,
            "split_gain": self.split_gain,
            "default_left": self._dl(),
            "max_depth": np.int64(self.max_depth),
            "n_features": np.int64(self.n_features),
            "learning_rate": np.float64(self.learning_rate),
            "base_score": np.float64(self.base_score),
            "loss": np.bytes_(self.loss.encode()),
            "n_classes": np.int64(self.n_classes),
            "has_raw_thresholds": np.bool_(self.has_raw_thresholds),
            "missing_bin": np.bool_(self.missing_bin),
            "n_bins": np.int64(self.n_bins),
            # NB: named so it does NOT collide with the model-artifact
            # encoder keys ("cat_"-prefixed, api.save_model).
            "categorical_features": (
                self.cat_features if self.cat_features is not None
                else np.zeros(0, np.int32)
            ),
        }

    @staticmethod
    def from_dict(d: dict) -> "TreeEnsemble":
        return TreeEnsemble(
            feature=np.asarray(d["feature"], np.int32),
            threshold_bin=np.asarray(d["threshold_bin"], np.int32),
            threshold_raw=np.asarray(d["threshold_raw"], np.float32),
            is_leaf=np.asarray(d["is_leaf"], bool),
            leaf_value=np.asarray(d["leaf_value"], np.float32),
            split_gain=np.asarray(
                d["split_gain"] if "split_gain" in d
                else np.zeros_like(d["leaf_value"]),
                np.float32),    # absent in pre-gain saves: zeros
            default_left=(
                np.asarray(d["default_left"], bool)
                if "default_left" in d
                else np.zeros(np.asarray(d["is_leaf"]).shape, bool)
            ),
            max_depth=int(d["max_depth"]),
            n_features=int(d["n_features"]),
            learning_rate=float(d["learning_rate"]),
            base_score=float(d["base_score"]),
            loss=bytes(d["loss"]).decode(),
            n_classes=int(d["n_classes"]),
            has_raw_thresholds=bool(d.get("has_raw_thresholds", False)),
            missing_bin=bool(d.get("missing_bin", False)),
            n_bins=int(d.get("n_bins", 0)),
            cat_features=(
                np.asarray(d["categorical_features"], np.int32)
                if "categorical_features" in d
                and np.asarray(d["categorical_features"]).size
                else None
            ),
        )

    def save(self, path: str) -> None:
        # tmp-then-replace (the atomic-artifact-write contract): a kill
        # mid-save never leaves a torn model file behind. The embedded
        # manifest (schema version, content digest, git rev —
        # registry/manifest.py) makes the bare-ensemble artifact
        # self-describing too; `load` ignores the extra key, and
        # api.load_model digest-verifies it (docs/REGISTRY.md).
        from ddt_tpu.registry import manifest as manifest_mod

        d = self.to_dict()
        manifest_mod.embed_npz_manifest(d, kind="tree_ensemble")
        atomic_savez(path, compressed=True, deterministic=True, **d)

    @staticmethod
    def load(path: str
             ) -> "TreeEnsemble | NodeListEnsemble | ObliviousEnsemble":
        """The saved ensemble, in the layout it was saved in: whichever of
        the three its `layout` key names (`ensemble_from_dict`), so a
        caller that needs a heap asks `isinstance` of what comes back."""
        with np.load(path) as d:
            return ensemble_from_dict(dict(d))

    def _dl(self) -> np.ndarray:
        return (self.default_left if self.default_left is not None
                else np.zeros_like(self.is_leaf))

    def truncate(self, n_trees: int) -> "TreeEnsemble":
        """First `n_trees` trees (early stopping keeps the best round)."""
        return dataclasses.replace(
            self,
            feature=self.feature[:n_trees],
            threshold_bin=self.threshold_bin[:n_trees],
            threshold_raw=self.threshold_raw[:n_trees],
            is_leaf=self.is_leaf[:n_trees],
            leaf_value=self.leaf_value[:n_trees],
            split_gain=self.split_gain[:n_trees],
            default_left=self._dl()[:n_trees],
        )

    @staticmethod
    def concat(ensembles: list["TreeEnsemble"]) -> "TreeEnsemble":
        """Stack ensembles trained sequentially (used by checkpoint resume)."""
        head = ensembles[0]
        return dataclasses.replace(
            head,
            feature=np.concatenate([e.feature for e in ensembles]),
            threshold_bin=np.concatenate([e.threshold_bin for e in ensembles]),
            threshold_raw=np.concatenate([e.threshold_raw for e in ensembles]),
            is_leaf=np.concatenate([e.is_leaf for e in ensembles]),
            leaf_value=np.concatenate([e.leaf_value for e in ensembles]),
            split_gain=np.concatenate([e.split_gain for e in ensembles]),
            default_left=np.concatenate([e._dl() for e in ensembles]),
        )


def _effective_arrays_np(feature, thr, is_leaf, leaf_value, max_depth):
    """Host twin of ops/predict._effective_arrays (leaf-chain pushdown):
    (eff_feat, eff_thr, eff_val) with every node below a leaf inheriting
    the leaf's value, leaf/inherited nodes carrying feature=-1 and
    thr=+BIG. Bitwise-identical to the traced version — both are pure
    integer/copy selects — so hoisting the pushdown to host (the
    CompiledEnsemble cache) changes no prediction."""
    big = (np.asarray(np.inf, thr.dtype)
           if np.issubdtype(thr.dtype, np.floating)
           else np.asarray(2 ** 30, thr.dtype))
    eff_feat = np.where(is_leaf, np.int32(-1), feature).astype(np.int32)
    eff_thr = np.where(is_leaf, big, thr).astype(thr.dtype)
    eff_val = np.array(leaf_value, np.float32)
    chained = np.array(is_leaf, bool)
    for d in range(1, max_depth + 1):
        lo, hi = (1 << d) - 1, (1 << (d + 1)) - 1
        par = (np.arange(lo, hi) - 1) // 2
        pch = chained[:, par]
        eff_feat[:, lo:hi] = np.where(pch, -1, eff_feat[:, lo:hi])
        eff_thr[:, lo:hi] = np.where(pch, big, eff_thr[:, lo:hi])
        eff_val[:, lo:hi] = np.where(pch, eff_val[:, par],
                                     eff_val[:, lo:hi])
        chained[:, lo:hi] = pch | is_leaf[:, lo:hi]
    return eff_feat, eff_thr, eff_val


@dataclasses.dataclass(frozen=True)
class CompiledEnsemble:
    """Precomputed BINNED scoring layout for one model: pushdown applied,
    trees padded to a tree_chunk multiple, class one-hot built — every
    per-call rebuild the old predict path paid (with the upload, the
    span `ddt:predict:ensemble`: 18 ms for 1000 trees on the v5e),
    hoisted to ONE host-side build per model version.

    Consumed by ops/predict.predict_raw_effective (one-hot or Pallas
    core); device backends key a small LRU of device-resident copies on
    `token` (TPUDevice._predict_fn), so repeated scoring calls against an
    unchanged model re-upload nothing and re-push nothing. Raw-threshold
    (float) scoring keeps the uncompiled predict_raw path — the device
    batch-scoring contract is binned."""

    token: str                 # TreeEnsemble.cache_token() at build time
    tree_chunk: int
    max_depth: int
    n_classes_out: int         # C: softmax n_classes, else 1
    learning_rate: float
    base_score: float
    loss: str
    missing_bin_value: int     # reserved NaN bin id, -1 = no missing
    eff_feat: np.ndarray       # int32 [Tpad, N] pushed-down
    eff_thr: np.ndarray        # int32 [Tpad, N] pushed-down (bins)
    bot_val: np.ndarray        # float32 [Tpad, 2^D] bottom-level values
    cls_oh: np.ndarray         # float32 [Tpad, C] round-major class 1-hot
    eff_dl: np.ndarray | None  # bool [Tpad, N] or None
    eff_cat: np.ndarray | None  # bool [Tpad, N] or None

    @property
    def n_trees_padded(self) -> int:
        return int(self.eff_feat.shape[0])

    layout = TreeEnsemble.layout

    def arrays(self) -> tuple:
        """Device-uploadable operand tuple in predict_raw_effective's
        argument order (optional masks appended when present)."""
        out = [self.eff_feat, self.eff_thr, self.bot_val, self.cls_oh]
        if self.eff_dl is not None:
            out.append(self.eff_dl)
        if self.eff_cat is not None:
            out.append(self.eff_cat)
        return tuple(out)

    def quantize(self, leaf_dtype: str = "float16"):
        """TreeLUT-style quantized scoring tables (ops/predict_lut.
        QuantizedTables): int8 recentred thresholds (EXACT — bin ids are
        integers in [0, 255]), fp16 / int8+scale / int4+scale leaf
        tables ("int4" is the bit-packed tier's logical form —
        `.pack_int4()` makes the two-nibbles-per-byte device layout),
        and a computed `max_abs_err` bound on |lut - f32| (the rounding
        contract documented in ops/predict_lut.py). The low-latency
        serving opt-in (cfg.predict_impl="lut"/"lut4" / `cli predict
        --quantized[=int4]` / ServeEngine(quantize=...)). Lazy import
        keeps this module jax-free for hosts that never score quantized.

        Memoized per leaf_dtype (this instance is immutable — frozen
        snapshot of one model version): the serving tier quantizes at
        publish for its error-bound reporting and the backend quantizes
        again on first LUT dispatch — one O(model) host pass, shared."""
        memo = self.__dict__.get("_quant_memo")
        if memo is None:
            memo = {}
            object.__setattr__(self, "_quant_memo", memo)
        if leaf_dtype not in memo:
            from ddt_tpu.ops.predict_lut import quantize_compiled

            memo[leaf_dtype] = quantize_compiled(
                self, leaf_dtype=leaf_dtype)
        return memo[leaf_dtype]

    def seed_quantized(self, tables) -> None:
        """Install pre-built tables as this instance's quantization:
        `quantize(leaf_dtype=tables.leaf_dtype)` — including the
        backend's first LUT dispatch — returns them verbatim instead of
        re-deriving. The registry loader seeds the artifact's CARRIED
        lut_tables.npz here so the exported int8 representation is what
        serves, even across version skew in the quantization routine."""
        memo = self.__dict__.get("_quant_memo")
        if memo is None:
            memo = {}
            object.__setattr__(self, "_quant_memo", memo)
        memo[tables.leaf_dtype] = tables

    @staticmethod
    def build(ens: TreeEnsemble, tree_chunk: int = 64
              ) -> "CompiledEnsemble":
        T, N = ens.feature.shape
        n_tc = -(-T // tree_chunk)
        tpad = n_tc * tree_chunk - T

        def pad_t(a, fill=0):
            return np.pad(a, ((0, tpad), (0, 0)), constant_values=fill)

        # Padded trees are all-leaf at the root with value 0 ->
        # contribute exactly 0.0 to their class column (the same padding
        # predict_raw applies in-trace).
        ef, et, ev = _effective_arrays_np(
            pad_t(ens.feature, -1).astype(np.int32),
            pad_t(ens.threshold_bin).astype(np.int32),
            pad_t(ens.is_leaf, True), pad_t(ens.leaf_value),
            ens.max_depth,
        )
        C = ens.n_classes if ens.loss == "softmax" else 1
        lo = (1 << ens.max_depth) - 1
        cls = np.arange(n_tc * tree_chunk, dtype=np.int64) % C
        cls_oh = np.zeros((n_tc * tree_chunk, C), np.float32)
        cls_oh[np.arange(len(cls)), cls] = 1.0
        use_missing = ens.missing_bin and ens.default_left is not None
        eff_dl = pad_t(ens.default_left) if use_missing else None
        eff_cat = (pad_t(np.isin(ens.feature, ens.cat_features))
                   if ens.has_cat_splits else None)
        return CompiledEnsemble(
            token=ens.cache_token(), tree_chunk=tree_chunk,
            max_depth=ens.max_depth, n_classes_out=C,
            learning_rate=float(ens.learning_rate),
            base_score=float(ens.base_score), loss=ens.loss,
            missing_bin_value=(ens.n_bins - 1 if use_missing else -1),
            eff_feat=ef, eff_thr=et,
            bot_val=np.ascontiguousarray(ev[:, lo:]),
            cls_oh=cls_oh, eff_dl=eff_dl, eff_cat=eff_cat,
        )


# ---------------------------------------------------------------------- #
# The NODE LIST: the second ensemble layout
# ---------------------------------------------------------------------- #

# Lanes a node-list tree's nodes and leaves are padded to in its compiled
# tables: one vreg's lanes, one MXU weight tile's columns.
PATH_LANES = 128
# A tree of more lanes than this is CUT into connected sub-trees
# (`CompiledNodeList`): one path matrix costs (W/128)^2 MXU weight tiles of
# resolve, 16 at 512 lanes (the widest the path kernel was measured at) and
# 64 at 1,024, where four sub-trees of 256 cost 16 and 8 more for the chain.
PATH_UNCUT_LANES = 4 * PATH_LANES
# ... and the lanes of a sub-tree: at most 255 internal nodes and 256
# exits. Two weight tiles wide: at 128 a sub-tree's fixed costs (the chain
# step, a leaf table of at least a tile) are paid twice as often and the
# cut fills its sub-trees no better; at 512 the resolve doubles a node.
SUBTREE_LANES = 2 * PATH_LANES
# uint32 words of a category set over a column's bins: 256 bits, a uint8's.
CAT_SET_WORDS = 8


@dataclasses.dataclass
class NodeListEnsemble:
    """A boosted ensemble whose trees are NODE LISTS, not heaps: tree t is
    `n_leaves[t] - 1` internal nodes and `n_leaves[t]` leaves in LightGBM's
    own numbering. A row starts at node 0; at internal node n it goes LEFT,
    to `left_child[n]`, when `bin[feature[n]] <= threshold_bin[n]` (raw
    rows: `value <= threshold_raw[n]`), else RIGHT, to `right_child[n]`; a
    negative child c is leaf `~c` and the tree scores `leaf_value[~c]`. A
    tree of one leaf has no node and scores `leaf_value[0]`. With learned
    directions for missing values (`missing_bin` and `default_left`, the
    heap's own fields and rule: LightGBM's `use_missing`) a row whose bin
    is the reserved NaN bin `n_bins - 1` (raw rows: NaN) goes LEFT where
    `default_left[n]`, else RIGHT, whatever the threshold.

    This is the layout of a tree that is deep and sparse, as best-first
    (leaf-wise) growth makes them: 255 leaves 13-20 levels down are 509
    entries here and 2^21 heap slots in `TreeEnsemble`. The trainer writes
    heaps; a node list is an import (`models/lightgbm_io.py`), a conversion
    (`from_heap`) or hand-built.

    CATEGORY SETS (LightGBM's categorical splits; `cat_index` and the set
    tables): node n of tree t with `cat_index[t, n] = s >= 0` asks no
    threshold. LightGBM's rule, stated here once: a row goes LEFT iff its
    value is a non-negative integer (the float truncated toward zero, as
    the library's `static_cast<int>`) whose bit is set in the node's bitset
    (`cat_boundaries` / `cat_threshold`, the library's own arrays over RAW
    category ids, one run of uint32 words a set); an id past the bitset, a
    negative value and an id the set does not name go RIGHT. NaN goes RIGHT
    where the node's missing type is NaN, and counts as id 0 elsewhere
    (`cat_nan_as_zero[s]`: the library's missing types None and Zero).
    Binned rows: `cat_bin_sets[s]` is the same set over the column's BINS
    (256 bits, uint32 [8]: bit b set, bin b goes left), which
    `lightgbm_io.threshold_bin_mapper` fills: every raw id some set of the
    column names has a bin of its own and every other value ONE bin that
    no set holds. The learned NaN directions of ordinal nodes (`default_
    left`) mean nothing at a set node, whose column has no NaN bin. A heap's
    one-vs-rest node (`from_heap`) is a set of one bit.

    SEVERAL CLASSES (`loss` "softmax"; an import of `models/xgboost_io.py`
    or `models/lightgbm_io.py`, `from_heap` of a multiclass heap): the
    heap's own rule, round-major trees of ONE column each, tree t scoring
    class `t % n_classes` with a scalar leaf value; the margins are
    `base_score + learning_rate x` the class's sum, float32 [rows, C], and
    their softmax the probabilities.

    VECTOR LEAVES, the averaged forest (`loss` "mean"; an import of
    `models/sklearn_io.py` or hand-built): `leaf_value` is [T, L, C], a
    leaf holds C output columns (a class distribution, or C regression
    targets; C = 1 is [T, L, 1]) and the score is the MEAN over the trees
    of the reached leaves' vectors, float32 [rows, C]: no link function, no
    learning rate, no base score (both refused unless 1 and 0).
    `to_lightgbm_text` of vector leaves is refused by name.

    Node arrays are [T, N] and `leaf_value` [T, L] (or [T, L, C]), N and L
    the widest tree's counts; a tree's unused slots hold feature -1,
    children 0 and value 0 and are never visited."""

    feature: np.ndarray        # int32  [T, N] split feature (-1: unused)
    threshold_bin: np.ndarray  # int32  [T, N] split bin (go left if <=)
    threshold_raw: np.ndarray  # float32 [T, N] raw-value threshold
    left_child: np.ndarray     # int32  [T, N] node index, or ~leaf
    right_child: np.ndarray    # int32  [T, N]
    leaf_value: np.ndarray     # float32 [T, L], or [T, L, C]: vector leaves
    n_leaves: np.ndarray       # int32  [T] leaves of each tree (>= 1)
    split_gain: np.ndarray     # float32 [T, N]
    n_features: int
    learning_rate: float
    base_score: float
    loss: str                  # logloss | mse | softmax | mean (vector leaves)
    n_classes: int = 2
    has_raw_thresholds: bool = False
    # False: threshold_bin is not filled yet (an import carries raw
    # thresholds only, until `lightgbm_io.threshold_bin_mapper` ranks them).
    has_bin_thresholds: bool = True
    n_bins: int = 0
    # Learned directions for missing values, as TreeEnsemble holds them:
    # with both set, bin n_bins-1 is the NaN bin and a row in it follows
    # default_left[n] (None: no such routing; thresholds then lie below
    # n_bins-1, the value bins').
    default_left: np.ndarray | None = None   # bool [T, N]
    missing_bin: bool = False
    # Category sets (the docstring's CATEGORY SETS): which nodes, and each
    # one's set over the column's bins and over raw ids. None: no set node.
    cat_index: np.ndarray | None = None      # int32 [T, N]: -1 ordinal, else
    #   the node's row s of the set tables
    cat_bin_sets: np.ndarray | None = None   # uint32 [S, 8]: bit b, BIN b left
    cat_boundaries: np.ndarray | None = None  # int64 [S + 1]: set s is words
    #   cat_threshold[cat_boundaries[s]:cat_boundaries[s + 1]] (raw ids)
    cat_threshold: np.ndarray | None = None  # uint32 [...]: LightGBM's bitsets
    cat_nan_as_zero: np.ndarray | None = None    # bool [S]: NaN counts as id 0

    # What a heap ensemble answers for beside `has_cat_splits`, so that
    # scoring entry points ask one question of either layout (a node list
    # names its set NODES, not whole columns).
    cat_features = None

    def __post_init__(self):
        if self.cat_index is not None:
            S = int(self.cat_index.max(initial=-1)) + 1
            if self.cat_index.shape != self.feature.shape or any(
                    a is None or len(a) < S for a in (
                        self.cat_bin_sets, self.cat_nan_as_zero)) or (
                    self.has_raw_thresholds and (
                        self.cat_boundaries is None
                        or len(self.cat_boundaries) < S + 1)):
                raise ValueError(
                    f"category-set nodes need cat_index {self.feature.shape}"
                    f" and a row of cat_bin_sets [S, {CAT_SET_WORDS}], of "
                    "cat_nan_as_zero [S] and (with raw thresholds) of "
                    f"cat_boundaries [S + 1] for each of the {S} sets")
        if self.loss == "softmax" and self.n_classes < 2:
            raise ValueError(
                "a softmax node list scores tree t into class t % n_classes:"
                f" n_classes {self.n_classes} is no class count")
        if (self.loss == "mean") != self.vector_leaves or (
                self.vector_leaves and (self.learning_rate != 1.0
                                        or self.base_score != 0.0
                                        or self.leaf_columns < 1)):
            raise ValueError(
                "a node-list ensemble with vector leaves (leaf_value "
                "[trees, leaves, columns]) is an averaged forest: loss "
                "'mean', learning_rate 1 and base_score 0, and loss 'mean' "
                f"needs vector leaves; got loss {self.loss!r}, leaf_value "
                f"{self.leaf_value.shape}, learning_rate "
                f"{self.learning_rate}, base_score {self.base_score}")
        T, N = self.feature.shape
        L = self.leaf_value.shape[1]
        if self.n_leaves.shape != (T,) or int(self.n_leaves.min(
                initial=1)) < 1 or int(self.n_leaves.max(initial=1)) > min(
                    L, N + 1):
            raise ValueError(
                f"n_leaves must be [trees] in 1..{min(L, N + 1)} for node "
                f"arrays [{T}, {N}] and leaf values [{T}, {L}]")
        if self.missing_routes and (
                self.default_left.shape != (T, N)
                or (self.has_bin_thresholds and self.n_bins < 3)):
            raise ValueError(
                f"learned NaN directions need default_left [{T}, {N}] and, "
                "with bin thresholds, n_bins (the NaN bin is n_bins - 1; "
                f"got {self.n_bins})")

    @property
    def n_trees(self) -> int:
        return int(self.feature.shape[0])

    @property
    def n_nodes(self) -> int:
        """The nodes that ask a question, over all trees."""
        return int(np.maximum(self.n_leaves.astype(np.int64) - 1, 0).sum())

    @property
    def cat_nodes(self) -> np.ndarray:
        """bool [T, N]: the live nodes that ask a category set."""
        if self.cat_index is None:
            return np.zeros(self.feature.shape, bool)
        return self.live_nodes & (self.cat_index >= 0)

    @property
    def has_cat_splits(self) -> bool:
        """Whether some node asks a category set (a fact of the model)."""
        return bool(self.cat_nodes.any())

    def cat_set_bits(self, sets=slice(None)) -> np.ndarray:
        """bool [S, 256]: set s over the column's bins, a bit a column (of
        the rows `sets` of the table alone, where given)."""
        return np.unpackbits(
            np.ascontiguousarray(self.cat_bin_sets[sets], "<u4").view(
                np.uint8), axis=-1, bitorder="little").astype(bool)

    @property
    def vector_leaves(self) -> bool:
        """Whether a leaf holds a vector (`leaf_value` [T, L, C]) and the
        score is the mean over the trees: the averaged forest."""
        return self.leaf_value.ndim == 3

    @property
    def leaf_columns(self) -> int:
        """Output columns of the score: C of vector leaves (a leaf holds
        them all), the classes of softmax's round-major trees (a leaf of
        tree t holds column t % C alone), else 1."""
        if self.vector_leaves:
            return int(self.leaf_value.shape[2])
        return int(self.n_classes) if self.loss == "softmax" else 1

    @property
    def missing_routes(self) -> bool:
        """Whether NaN rows follow learned directions (the heap's rule:
        the reserved bin AND the directions; else it is a bin like any)."""
        return bool(self.missing_bin) and self.default_left is not None

    @property
    def missing_bin_value(self) -> int:
        """The reserved NaN bin, -1 without learned directions."""
        return self.n_bins - 1 if self.missing_routes else -1

    @property
    def live_nodes(self) -> np.ndarray:
        """bool [T, N]: the node slots that hold a tree's internal node."""
        return np.arange(self.feature.shape[1])[None, :] \
            < (self.n_leaves[:, None] - 1)

    def _parents(self) -> tuple:
        """(node_parent [T, N], node_side [T, N], leaf_parent [T, L],
        leaf_side [T, L]): the node above every node and leaf (-1: none)
        and the side it hangs on (-1 left, +1 right), from the child
        arrays; proves on the way that every node and leaf has exactly one
        parent and no child index leaves its tree."""
        T, N = self.feature.shape
        L = self.leaf_value.shape[1]
        n_int = self.n_leaves.astype(np.int64) - 1
        live = self.live_nodes
        node_parent = np.full((T, N), -1, np.int64)
        node_side = np.zeros((T, N), np.int8)
        leaf_parent = np.full((T, L), -1, np.int64)
        leaf_side = np.zeros((T, L), np.int8)
        seen_n = np.zeros((T, N), np.int64)
        seen_l = np.zeros((T, L), np.int64)
        t_idx, n_idx = np.nonzero(live)
        for child, side in ((self.left_child, -1), (self.right_child, 1)):
            c = child[t_idx, n_idx].astype(np.int64)
            to_leaf = c < 0
            lt, ll, lp = t_idx[to_leaf], ~c[to_leaf], n_idx[to_leaf]
            nt, nn, np_ = t_idx[~to_leaf], c[~to_leaf], n_idx[~to_leaf]
            if (ll >= self.n_leaves[lt]).any() or (
                    nn >= n_int[nt]).any() or (nn == 0).any():
                raise ValueError("node list: a child index points outside "
                                 "its tree, or back at the root")
            leaf_parent[lt, ll], leaf_side[lt, ll] = lp, side
            node_parent[nt, nn], node_side[nt, nn] = np_, side
            np.add.at(seen_l, (lt, ll), 1)
            np.add.at(seen_n, (nt, nn), 1)
        has_leaf = np.arange(L)[None, :] < self.n_leaves[:, None]
        lone = (self.n_leaves == 1)[:, None]
        if not np.array_equal(seen_l == 1, has_leaf & ~lone) or \
                not np.array_equal(seen_n[:, 1:] == 1, live[:, 1:]):
            raise ValueError("node list: not every node and leaf has "
                             "exactly one parent")
        return node_parent, node_side, leaf_parent, leaf_side

    def _walk_up(self, visit=None) -> np.ndarray:
        """Every leaf walked up to its tree's root, all leaves a step:
        `visit(tt, ll, node, sign)` sees each (tree, leaf, node on its
        path, side the leaf lies on) once. Returns the nodes passed, int32
        [T, L] (-1: no such leaf). A walk longer than the node count is a
        cycle among the nodes."""
        T, N = self.feature.shape
        L = self.leaf_value.shape[1]
        node_parent, node_side, leaf_parent, leaf_side = self._parents()
        has_leaf = np.arange(L)[None, :] < self.n_leaves[:, None]
        plen = np.where(has_leaf, 0, -1).astype(np.int32)
        tt, ll = np.nonzero(has_leaf & ~(self.n_leaves == 1)[:, None])
        cur, sign = leaf_parent[tt, ll], leaf_side[tt, ll]
        for _ in range(N + 1):
            if not len(tt):
                break
            if visit is not None:
                visit(tt, ll, cur, sign)
            plen[tt, ll] += 1
            up = node_parent[tt, cur] >= 0
            sign, cur = node_side[tt, cur], node_parent[tt, cur]
            tt, ll, cur, sign = tt[up], ll[up], cur[up], sign[up]
        else:
            raise ValueError("node list: a cycle among the nodes")
        return plen

    def path_matrix(self) -> "tuple[np.ndarray, np.ndarray]":
        """(signed path matrix int8 [T, N, L], path length int32 [T, L]):
        `P[t, n, l]` is +1 where leaf l lies in node n's RIGHT subtree, -1
        where in its LEFT, 0 elsewhere; `len[t, l]` counts the nodes on
        leaf l's path (-1: no such leaf). Walking every leaf up to the
        root also proves the lists are trees: each node and leaf has one
        parent and the root is reached. Not cached: node arrays may be
        mutated in place (0.4 s for 500 trees of 255 leaves). DENSE over
        the whole tree: what compiles a tree of `PATH_UNCUT_LANES` lanes at
        most, and the tests; a larger tree is cut first (`cut_subtrees`)
        and no [N, L] matrix of it is ever made."""
        T, N = self.feature.shape
        P = np.zeros((T, N, self.leaf_value.shape[1]), np.int8)

        def visit(tt, ll, cur, sign):
            P[tt, cur, ll] = sign

        return P, self._walk_up(visit)

    @property
    def deepest_leaf(self) -> int:
        """Nodes on the longest root-to-leaf path of any tree (a walk up
        from the leaves: no path matrix is built)."""
        return int(self._walk_up().max(initial=0))

    max_depth = deepest_leaf       # what `cli inspect` prints of a heap

    @property
    def n_splits(self) -> int:
        """Internal nodes, over all trees."""
        return int((self.n_leaves - 1).sum())

    # ------------------------------------------------------------------ #

    def cache_token(self) -> str:
        """Content digest of what the device scoring program depends on
        (the compiled-ensemble cache key, as `TreeEnsemble.cache_token`)."""
        h = hashlib.sha1(b"node_list")
        h.update(np.ascontiguousarray(self.n_leaves).tobytes())
        # A tree's own entries, in place: trees of 30 and of 11,000 leaves
        # share arrays as wide as the widest, and a digest of the padding
        # was 0.85 s of every call of a 2,100-tree model (PERF.md, PR 50).
        n = self.n_leaves.astype(np.int64)
        for a, short in ((self.feature, 1), (self.threshold_bin, 1),
                         (self.left_child, 1), (self.right_child, 1),
                         (self.leaf_value, 0)) + (
                             ((self.default_left, 1),)
                             if self.missing_routes else ()):
            a = np.ascontiguousarray(a)
            for t in range(len(n)):
                h.update(a[t, :n[t] - short])
        h.update(repr((self.learning_rate, self.base_score, self.loss,
                       self.n_features)).encode())
        if self.missing_routes:
            h.update(repr(("missing_bin", self.n_bins)).encode())
        if self.leaf_columns > 1 or self.vector_leaves:
            # [T, L, C] and [T, L * C] hash alike; the same trees under
            # another class count score other columns
            h.update(repr(("leaf_columns", self.leaf_columns)).encode())
        at = self.cat_nodes
        if at.any():
            h.update(b"category_sets")
            h.update(np.flatnonzero(at).tobytes())
            h.update(np.ascontiguousarray(
                self.cat_bin_sets[self.cat_index[at]]).tobytes())
        return h.hexdigest()

    layout = "node_list"       # (ops/predict.LAYOUTS)

    def compile(self, tree_chunk: int = 64) -> "CompiledNodeList":
        """Host-side compiled scoring tables (see CompiledNodeList;
        `tree_chunk` is the heap layout's and means nothing here)."""
        return CompiledNodeList.build(self)

    # ------------------------------------------------------------------ #

    def _leaf_np(self, X: np.ndarray, binned: bool) -> np.ndarray:
        """Leaf index per (tree, row), int64 [T, R]: the node walk."""
        if binned and not self.has_bin_thresholds:
            raise ValueError(
                "this node-list ensemble carries raw thresholds only; rank "
                "them first (models/lightgbm_io.threshold_bin_mapper), or "
                "predict on raw values")
        if not binned and not self.has_raw_thresholds:
            raise ValueError(
                "Ensemble has no raw-value thresholds; predict on binned "
                "data with binned=True")
        thr = self.threshold_bin if binned else self.threshold_raw
        Xc = X.astype(np.int32) if binned else X.astype(np.float32)
        T, R = self.n_trees, X.shape[0]
        rows = np.arange(R)
        # ~0 from the start in a tree of one leaf
        cur = np.where(self.n_leaves > 1, 0, -1)[:, None].repeat(R, 1)
        while (cur >= 0).any():
            at = np.maximum(cur, 0)
            feat = np.take_along_axis(self.feature, at, axis=1)
            fv = np.stack([Xc[rows, np.maximum(feat[t], 0)]
                           for t in range(T)])
            go_right = fv > np.take_along_axis(thr, at, axis=1)
            if self.missing_routes:
                miss = (fv == self.n_bins - 1) if binned else np.isnan(fv)
                go_right = np.where(
                    miss, ~np.take_along_axis(self.default_left, at, axis=1),
                    go_right)
            if self.cat_index is not None:
                s = np.take_along_axis(self.cat_index, at, axis=1)
                go_right = np.where(
                    s >= 0, ~self._in_set(np.maximum(s, 0), fv, binned),
                    go_right)
            nxt = np.where(go_right,
                           np.take_along_axis(self.right_child, at, axis=1),
                           np.take_along_axis(self.left_child, at, axis=1))
            cur = np.where(cur >= 0, nxt, cur)
        return ~cur

    def _in_set(self, s: np.ndarray, v: np.ndarray,
                binned: bool) -> np.ndarray:
        """Whether value `v` (bins, or raw float32 values) lies in set `s`,
        elementwise: the docstring's CATEGORY SETS."""
        if binned:
            b = np.asarray(v, np.int64)
            ok = (b >= 0) & (b < 32 * CAT_SET_WORDS)
            b = np.where(ok, b, 0)
            return ok & ((self.cat_bin_sets[s, b >> 5] >> (b & 31)) & 1
                         ).astype(bool)
        if not len(self.cat_threshold):
            return np.zeros(np.shape(v), bool)
        nan = np.isnan(v)
        zero = nan & self.cat_nan_as_zero[s]
        # the library's static_cast<int>: toward zero (-0.5 is id 0)
        i = np.trunc(np.where(nan, 0.0, np.clip(v, -1.0, 2.0 ** 31 - 1))
                     ).astype(np.int64)
        lo = self.cat_boundaries[s]
        ok = (~nan | zero) & (i >= 0) & (
            i < 32 * (self.cat_boundaries[s + 1] - lo))
        i = np.where(ok, i, 0)
        word = self.cat_threshold[np.minimum(
            lo + (i >> 5), len(self.cat_threshold) - 1)]
        return ok & ((word >> (i & 31).astype(np.uint32)) & 1).astype(bool)

    def predict_raw(self, X: np.ndarray, binned: bool = False) -> np.ndarray:
        """Raw (margin) scores [R], float32; of softmax's round-major
        trees the margins [R, C]; of vector leaves the mean over the trees
        of the reached leaves' vectors, float32 [R, C]."""
        leaf = self._leaf_np(np.asarray(X), binned)
        if self.vector_leaves:
            total = np.zeros((leaf.shape[1], self.leaf_columns), np.float32)
            for t in range(self.n_trees):
                total += self.leaf_value[t, leaf[t]]
            return total / np.float32(self.n_trees)
        vals = np.take_along_axis(self.leaf_value, leaf, axis=1)
        vals = vals * np.float32(self.learning_rate)
        if self.loss == "softmax":      # TreeEnsemble.aggregate_leaves' rule
            out = np.full((leaf.shape[1], self.n_classes), self.base_score,
                          np.float32)
            for t in range(self.n_trees):
                out[:, t % self.n_classes] += vals[t]
            return out
        return (self.base_score + vals.sum(axis=0)).astype(np.float32)

    def predict(self, X: np.ndarray, binned: bool = False) -> np.ndarray:
        """Probability predictions (or raw values for mse; the mean
        vectors of an averaged forest, whose argmax is its class)."""
        from ddt_tpu.utils.metrics import predict_proba_np

        return predict_proba_np(self.predict_raw(X, binned=binned),
                                self.loss)

    # ------------------------------------------------------------------ #

    @staticmethod
    def from_heap(ens: TreeEnsemble) -> "NodeListEnsemble":
        """The same trees as a node list, nodes and leaves numbered in
        pre-order (root = node 0), learned NaN directions and softmax's
        round-major classes with them. A heap's one-vs-rest category node
        (`bin == k` goes left) is a set of the one bit k, over bins and
        over raw ids alike (a heap's category columns are identity-binned);
        one that sends NaN LEFT is refused by name: a set node sends NaN
        right (the class's docstring)."""
        routed = ens.missing_bin and ens.default_left is not None
        cat = set(int(f) for f in ens.cat_features) \
            if ens.has_cat_splits else set()
        sets: list = []                 # the set nodes' one bit, in order
        at_set: list = []               # ... and their (tree, node)
        trees = []
        for t in range(ens.n_trees):
            nodes, leaves = [], []

            def walk(slot: int) -> int:
                if ens.is_leaf[t, slot] or ens.feature[t, slot] < 0:
                    leaves.append(ens.leaf_value[t, slot])
                    return -len(leaves)
                i = len(nodes)
                if int(ens.feature[t, slot]) in cat:
                    if routed and ens.default_left[t, slot]:
                        _refuse_routes("from_heap", nan_left_category=True)
                    sets.append([int(ens.threshold_bin[t, slot])])
                    at_set.append((t, i))
                nodes.append([ens.feature[t, slot],
                              ens.threshold_bin[t, slot],
                              ens.threshold_raw[t, slot],
                              ens.split_gain[t, slot], 0, 0]
                             + ([ens.default_left[t, slot]] if routed
                                else []))
                nodes[i][4] = walk(2 * slot + 1)
                nodes[i][5] = walk(2 * slot + 2)
                return i

            walk(0)
            trees.append((nodes, leaves))
        out = node_list_from_trees(
            trees, n_features=ens.n_features,
            learning_rate=ens.learning_rate, base_score=ens.base_score,
            loss=ens.loss, n_classes=ens.n_classes,
            has_raw_thresholds=ens.has_raw_thresholds, n_bins=ens.n_bins,
            missing_bin=bool(routed))
        if sets:
            out.set_category_nodes(at_set, sets, bin_sets=sets)
        return out

    def set_category_nodes(self, at: list, raw_sets: list,
                           bin_sets: list | None = None,
                           nan_as_zero=None) -> None:
        """Make nodes `at[i] = (tree, node)` category-set nodes: set i holds
        the raw ids `raw_sets[i]` and (where known) the bins `bin_sets[i]`
        (else `lightgbm_io.threshold_bin_mapper` fills them);
        `nan_as_zero[i]`: NaN counts as id 0 there (default: goes right)."""
        S = len(at)
        self.cat_index = np.full(self.feature.shape, -1, np.int32)
        tt, nn = np.asarray(at, np.int64).reshape(S, 2).T
        self.cat_index[tt, nn] = np.arange(S)
        self.cat_boundaries, self.cat_threshold = bitset_words(raw_sets)
        self.cat_bin_sets = np.zeros((S, CAT_SET_WORDS), np.uint32)
        if bin_sets is not None:
            for i, bins in enumerate(bin_sets):
                set_bits(self.cat_bin_sets[i], bins)
        self.cat_nan_as_zero = np.zeros(S, bool) if nan_as_zero is None \
            else np.asarray(nan_as_zero, bool)
        self.threshold_bin[tt, nn] = 0
        self.threshold_raw[tt, nn] = 0.0

    def feature_importances(self, kind: str = "split") -> np.ndarray:
        """As `TreeEnsemble.feature_importances`, over the live nodes."""
        return _importances(self, self.live_nodes, kind)

    def dump_text(self, tree: int) -> str:
        """Indented text rendering of one tree, as TreeEnsemble.dump_text."""
        t, lines = int(tree), []

        def walk(ref: int, depth: int) -> None:
            pad = "  " * depth
            if ref < 0:
                v = self.leaf_value[t, ~ref]
                lines.append(f"{pad}leaf=" + (
                    "[" + " ".join(f"{c:.6g}" for c in v) + "]"
                    if self.vector_leaves else f"{v:+.6f}"))
                return
            thr = (f" (<= {self.threshold_raw[t, ref]:.6g})"
                   if self.has_raw_thresholds else "")
            nan = (f"  nan->{'L' if self.default_left[t, ref] else 'R'}"
                   if self.missing_routes else "")
            if self.cat_index is not None and self.cat_index[t, ref] >= 0:
                bins = np.flatnonzero(
                    self.cat_set_bits(self.cat_index[t, ref]))
                lines.append(f"{pad}f{self.feature[t, ref]} in bins {{"
                             + ",".join(map(str, bins)) + "}  "
                             f"gain={self.split_gain[t, ref]:.4g}")
            else:
                lines.append(f"{pad}f{self.feature[t, ref]} <= bin "
                             f"{self.threshold_bin[t, ref]}{thr}{nan}  "
                             f"gain={self.split_gain[t, ref]:.4g}")
            walk(int(self.left_child[t, ref]), depth + 1)
            walk(int(self.right_child[t, ref]), depth + 1)

        walk(0 if self.n_leaves[t] > 1 else -1, 0)
        return "\n".join(lines)

    def to_lightgbm_text(self, feature_names: list[str] | None = None
                         ) -> str:
        """LightGBM model.txt rendering (models/lightgbm_io.py)."""
        from ddt_tpu.models.lightgbm_io import to_lightgbm_text

        if self.vector_leaves:
            raise ValueError(
                "to_lightgbm_text: LightGBM's model text holds one value a "
                "leaf; an averaged forest's vector leaves (leaf_value "
                f"{self.leaf_value.shape}) have no rendering there")
        return to_lightgbm_text(self, feature_names=feature_names)

    _ARRAYS = (("feature", np.int32), ("threshold_bin", np.int32),
               ("threshold_raw", np.float32), ("left_child", np.int32),
               ("right_child", np.int32), ("leaf_value", np.float32),
               ("n_leaves", np.int32), ("split_gain", np.float32))

    def to_dict(self) -> dict:
        d = {k: getattr(self, k) for k, _ in self._ARRAYS}
        d.update(
            layout=np.bytes_(b"node_list"),
            n_features=np.int64(self.n_features),
            learning_rate=np.float64(self.learning_rate),
            base_score=np.float64(self.base_score),
            loss=np.bytes_(self.loss.encode()),
            n_classes=np.int64(self.n_classes),
            has_raw_thresholds=np.bool_(self.has_raw_thresholds),
            has_bin_thresholds=np.bool_(self.has_bin_thresholds),
            n_bins=np.int64(self.n_bins),
            missing_bin=np.bool_(self.missing_bin))
        if self.default_left is not None:
            d["default_left"] = self.default_left
        if self.cat_index is not None:
            d.update({k: getattr(self, k) for k, _ in self._CAT_ARRAYS
                      if getattr(self, k) is not None})
        return d

    _CAT_ARRAYS = (("cat_index", np.int32), ("cat_bin_sets", np.uint32),
                   ("cat_boundaries", np.int64), ("cat_threshold", np.uint32),
                   ("cat_nan_as_zero", bool))

    @staticmethod
    def from_dict(d: dict) -> "NodeListEnsemble":
        return NodeListEnsemble(
            **{k: np.asarray(d[k], dt) for k, dt in NodeListEnsemble._ARRAYS},
            **{k: np.asarray(d[k], dt)
               for k, dt in NodeListEnsemble._CAT_ARRAYS if k in d},
            default_left=(np.asarray(d["default_left"], bool)
                          if "default_left" in d else None),
            missing_bin=bool(d.get("missing_bin", False)),
            n_features=int(d["n_features"]),
            learning_rate=float(d["learning_rate"]),
            base_score=float(d["base_score"]),
            loss=bytes(d["loss"]).decode(),
            n_classes=int(d["n_classes"]),
            has_raw_thresholds=bool(d["has_raw_thresholds"]),
            has_bin_thresholds=bool(d["has_bin_thresholds"]),
            n_bins=int(d["n_bins"]))

    save = TreeEnsemble.save       # to_dict, the manifest, one atomic npz


def _importances(ens, nodes: np.ndarray, kind: str) -> np.ndarray:
    """Normalized per-feature importance over the splitting `nodes` (a
    mask of the node arrays) of either layout."""
    used = ens.feature[nodes]
    if kind == "split":
        w = np.ones(used.shape[0])
    elif kind == "gain":
        w = ens.split_gain[nodes].astype(np.float64)
    else:
        raise ValueError(f"unknown importance kind {kind!r}")
    counts = np.bincount(used, weights=w, minlength=ens.n_features)
    counts = counts[: ens.n_features].astype(np.float64)
    tot = counts.sum()
    return (counts / tot if tot > 0 else counts).astype(np.float32)


def ensemble_from_dict(
        d: dict) -> "TreeEnsemble | NodeListEnsemble | ObliviousEnsemble":
    """The ensemble a saved artifact holds, in the layout it was saved in.
    Every `to_dict` writes the layout's name under `layout` (`LAYOUTS`);
    two older forms are still read: a heap saved before it had the key (no
    `layout` at all), and the node list, which always wrote it."""
    name = bytes(d["layout"]).decode() if "layout" in d else "heap"
    if name not in LAYOUTS:
        raise ValueError(
            f"saved ensemble names the layout {name!r}; this program reads "
            f"{sorted(LAYOUTS)}")
    return LAYOUTS[name].from_dict(d)


def _refuse_routes(where: str, *, chained_sets: bool = False,
                   nan_left_category: bool = False) -> None:
    """The one list of what a node list cannot carry or score, named.
    (Several classes it carries, as softmax's round-major trees or as the
    vector leaves of an averaged forest, and category sets in trees that
    one path matrix holds: `NodeListEnsemble`.)"""
    if chained_sets:
        raise ValueError(
            f"{where}: category-set nodes in the SUB-TREE form of the path "
            "tables (a tree past PATH_UNCUT_LANES lanes, vector leaves or "
            "softmax's round-major trees) are not supported: a sub-tree's "
            "glue and spine copies have no set tables yet; the host walk "
            "(backend 'cpu') scores such a model")
    if nan_left_category:
        raise ValueError(
            f"{where}: a heap's category node that sends NaN LEFT has no "
            "node-list form: a category-set node sends NaN right "
            "(LightGBM's rule for its missing type NaN)")


def set_bits(words: np.ndarray, bits) -> None:
    """Set the bits `bits` of the uint32 row `words` in place (bit i of
    word w is 32 w + i)."""
    b = np.asarray(bits, np.int64)
    np.bitwise_or.at(words, b >> 5, (1 << (b & 31)).astype(np.uint32))


def bitset_words(sets: list) -> tuple:
    """(boundaries int64 [S + 1], words uint32 [...]): LightGBM's
    `cat_boundaries` / `cat_threshold` of the id lists `sets`, set s the
    words boundaries[s]:boundaries[s + 1], bit i of word w the id 32 w + i
    (a set of no id is a run of one zero word)."""
    runs = []
    for ids in sets:
        runs.append(np.zeros(max(ids, default=0) // 32 + 1, np.uint32))
        set_bits(runs[-1], ids)
    bounds = np.cumsum([0] + [len(r) for r in runs], dtype=np.int64)
    return bounds, np.concatenate(runs + [np.zeros(0, np.uint32)])


def node_list_from_trees(trees: list, **meta) -> NodeListEnsemble:
    """A NodeListEnsemble from per-tree lists: `trees[t] = (nodes, leaves)`,
    `nodes[n] = (feature, threshold_bin, threshold_raw, gain, left,
    right)`, `leaves[l]` the leaf's value, or its vector (every leaf of
    every tree, or none: `leaf_value` is then [T, L, C]). A seventh entry
    on the nodes is `default_left` (every node has it, or none)."""
    T = len(trees)
    N = max(1, max(len(n) for n, _ in trees))
    L = max(len(lv) for _, lv in trees)
    columns = np.shape(trees[0][1])[1:]     # () or (C,)
    out = dict(
        feature=np.full((T, N), -1, np.int32),
        threshold_bin=np.zeros((T, N), np.int32),
        threshold_raw=np.zeros((T, N), np.float32),
        split_gain=np.zeros((T, N), np.float32),
        left_child=np.zeros((T, N), np.int32),
        right_child=np.zeros((T, N), np.int32),
        leaf_value=np.zeros((T, L) + columns, np.float32),
        n_leaves=np.asarray([len(lv) for _, lv in trees], np.int32))
    keys = ("feature", "threshold_bin", "threshold_raw", "split_gain",
            "left_child", "right_child")
    if any(len(n[0]) > len(keys) for n, _ in trees if n):
        keys += ("default_left",)
        out["default_left"] = np.zeros((T, N), bool)
    for t, (nodes, leaves) in enumerate(trees):
        if nodes:
            for k, col in zip(keys, zip(*nodes)):
                out[k][t, :len(nodes)] = col
        out["leaf_value"][t, :len(leaves)] = leaves
    return NodeListEnsemble(**out, **meta)


def random_node_list(rng, n_trees: int, n_leaves: int, n_features: int,
                     n_bins: int = 255, dyadic: bool = False,
                     missing: bool = False, leaf_columns: int = 0,
                     categories: tuple = (), max_set: int = 32,
                     **meta) -> NodeListEnsemble:
    """A random leaf-wise ensemble for tests, chip_smoke.py and the compile
    check (no trainer grows one): a random leaf is split until `n_leaves`
    are there, so depths are uneven (about 20 levels at 255 leaves);
    features and threshold bins uniform; leaf values N(0, 1), or eighths in
    -2..2 (`dyadic`: sums of them round nowhere). `missing`: bin n_bins-1
    is the NaN bin, thresholds lie in the value bins below it and every
    node's default direction is a fair coin. `leaf_columns` C > 0: vector
    leaves [T, L, C] of an averaged forest (`loss` "mean"), and `n_leaves`
    may be a range (lo, hi): each tree draws its own count. `categories`:
    (column, cardinality k) pairs; a node on such a column asks a CATEGORY
    SET of 1 to min(`max_set`, k) of the column's bins 0..k-1 (bin k, every
    unnamed value's, is in no set), over bins and raw ids alike."""
    trees = []
    cats = dict(categories)
    at_set, sets = [], []
    shape = (leaf_columns,) if leaf_columns else ()
    if leaf_columns:
        meta = dict(learning_rate=1.0, base_score=0.0, loss="mean",
                    n_classes=leaf_columns) | meta
    for _ in range(n_trees):
        nodes, where = [], [None]        # leaf -> (parent node, child slot)
        leaves = n_leaves if np.ndim(n_leaves) == 0 else int(
            rng.integers(n_leaves[0], n_leaves[1] + 1))
        for _ in range(leaves - 1):
            leaf, n = int(rng.integers(len(where))), len(nodes)
            nodes.append([int(rng.integers(n_features)),
                          int(rng.integers(n_bins - 1 - missing)), 0.0, 0.0,
                          ~leaf, ~len(where)]
                         + ([bool(rng.integers(2))] if missing else []))
            if nodes[-1][0] in cats:
                k = cats[nodes[-1][0]]
                at_set.append((len(trees), n))
                sets.append(sorted(int(b) for b in rng.choice(
                    k, int(rng.integers(1, min(max_set, k) + 1)),
                    replace=False)))
            if where[leaf] is not None:
                nodes[where[leaf][0]][where[leaf][1]] = n
            where[leaf] = (n, 4)
            where.append((n, 5))
        trees.append((nodes, rng.integers(-16, 17, (leaves,) + shape) / 8.0
                      if dyadic else rng.standard_normal((leaves,) + shape)))
    ens = node_list_from_trees(trees, n_features=n_features, n_bins=n_bins,
                               missing_bin=missing, **meta)
    if sets:
        ens.set_category_nodes(at_set, sets, bin_sets=sets)
    return ens


class SubtreeCut(typing.NamedTuple):
    """A node list's trees cut into the ENTRIES of the sub-tree form
    (`cut_subtrees`): an entry holds one or several connected PIECES of its
    tree, glued into one binary tree by copies of their common ancestors.
    The arrays are [K] over the SLOTS, the lanes-to-be: first the model's M
    internal nodes themselves, the trees in a row (node n of tree t is slot
    `first_node[t] + n`, first_node the running sum of the trees' node
    counts), then the G GLUE copies, by their entry (one node may be glue
    in several entries: a slot each)."""

    n_subtrees: np.ndarray     # int64 [T] entries of each tree (>= 1)
    tree: np.ndarray           # int64 [K] the slot's tree ...
    origin: np.ndarray         # int64 [K] ... and the node there whose
    #   question it asks: itself, or the common ancestor a glue copy stands
    #   for
    root: np.ndarray           # bool  [K] the nodes that root a piece
    subtree: np.ndarray        # int32 [K] a slot's entry, numbered in its
    #   tree in the order the entries were filled: the parent of a piece's
    #   root lies in an earlier one
    lane: np.ndarray           # int32 [K] its number in the entry: the
    #   pre-order of the glued tree (its top 0), or under `spans` the
    #   pre-order inside the 128-lane tile whose K-blocks hold the slot's
    #   column, or HALVED the pre-order inside its half (the second half's
    #   behind the copies)
    copy: np.ndarray | None    # HALVED alone: int32 [K] the lane of the
    #   slot's SPINE copy in its entry's second half (128..; 0: it has none)
    up: np.ndarray             # int64 [K] the slot above it in its entry: a
    #   node's parent, above a piece's root the glue copy it hangs on (-1:
    #   the entry's top)
    side: np.ndarray           # int8  [K] ... and the side it hangs on
    #   (-1 left, +1 right)


class _Flat(typing.NamedTuple):
    """A model's internal nodes in a row: the trees in their order, each in
    the pre-order that visits a node's HEAVIER child first (equal ones: the
    left), so that the uncut tree below position q is the positions
    [q, q + size[q]) and a prefix of them is connected."""

    tree: np.ndarray           # int64 [M] the node's tree ...
    node: np.ndarray           # int64 [M] ... and its index there
    first: np.ndarray          # int64 [T + 1] a tree's first position
    size: np.ndarray           # int64 [M] nodes of the uncut tree below it
    tall: np.ndarray           # int64 [M] nodes on the longest path down it
    depth: np.ndarray          # int64 [M] nodes above it in its tree
    up: np.ndarray             # int64 [M] its parent's position (-1: none)
    side: np.ndarray           # int8  [M] the side it hangs on (-1, +1)
    kids: np.ndarray           # int64 [M, 2] its children's positions, the
    #   heavier first (-1: a leaf)
    pre: np.ndarray            # int64 [M] its number in the pre-order that
    #   visits the LEFT child first (the lanes' order)


def _levels(ens: NodeListEnsemble) -> list:
    """The internal nodes of every tree by their depth: [(tree index, node
    index)] a level, the roots first. `_parents` has proved one parent a
    node, so what the roots reach are trees; nodes they do not reach are a
    cycle among themselves."""
    t = np.nonzero(ens.n_leaves > 1)[0]
    n = np.zeros(len(t), np.int64)
    levels = []
    while len(t):
        levels.append((t, n))
        kids = np.stack([ens.left_child[t, n], ens.right_child[t, n]], 1)
        down = kids >= 0
        t, n = np.repeat(t, 2)[down.ravel()], kids[down].astype(np.int64)
    if sum(len(t) for t, _ in levels) != ens.n_splits:
        raise ValueError("node list: a cycle among the nodes")
    return levels


def _first_nodes(ens: NodeListEnsemble) -> np.ndarray:
    """int64 [T + 1]: the internal nodes before each tree, the trees in a
    row (`SubtreeCut`'s slot of a tree's node 0), and behind them all."""
    return np.concatenate([[0], np.cumsum(np.maximum(
        ens.n_leaves.astype(np.int64) - 1, 0))])


def _flat(ens: NodeListEnsemble) -> _Flat:
    """`cut_subtrees`'s view of the model (a level of all the trees a
    step)."""
    ens._parents()                      # in range, one parent each
    levels = _levels(ens)
    T, N = ens.feature.shape
    lt = np.concatenate([t for t, _ in levels] or [np.zeros(0, np.int64)])
    ln = np.concatenate([n for _, n in levels] or [np.zeros(0, np.int64)])
    M = len(lt)
    bounds = np.concatenate([[0], np.cumsum([len(t) for t, _ in levels])])
    where = np.full((T, N), M, np.int32)    # by levels; M: no node
    where[lt, ln] = np.arange(M)
    raw = np.stack([ens.left_child[lt, ln], ens.right_child[lt, ln]], 1)
    kid = np.where(raw >= 0, where[lt[:, None], np.maximum(raw, 0)],
                   M).astype(np.int64)
    size, tall = np.ones(M + 1, np.int64), np.ones(M + 1, np.int64)
    size[M] = tall[M] = 0
    for lo, hi in zip(bounds[-2::-1], bounds[:0:-1]):   # children first
        size[lo:hi] += size[kid[lo:hi]].sum(axis=1)
        tall[lo:hi] += tall[kid[lo:hi]].max(axis=1)
    # the two pre-orders top-down: the first child follows its parent, the
    # second the first's whole tree
    swap = size[kid[:, 1]] > size[kid[:, 0]]
    heavy = np.where(swap[:, None], kid[:, ::-1], kid)
    pre, hpre = np.zeros(M + 1, np.int64), np.zeros(M + 1, np.int64)
    depth, up = np.zeros(M + 1, np.int64), np.full(M + 1, -1, np.int64)
    side = np.zeros(M + 1, np.int8)
    for d, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
        for order, number in ((kid, pre), (heavy, hpre)):
            a, b = order[lo:hi, 0], order[lo:hi, 1]
            number[a] = number[lo:hi] + 1
            number[b] = number[lo:hi] + 1 + size[a]
        depth[lo:hi] = d
        up[kid[lo:hi, 0]] = up[kid[lo:hi, 1]] = np.arange(lo, hi)
        side[kid[lo:hi, 0]], side[kid[lo:hi, 1]] = -1, 1
    first = _first_nodes(ens)
    q = np.append(first[lt] + hpre[:M], -1)      # a level's node's position
    by_q = np.argsort(q[:M])
    return _Flat(lt[by_q], ln[by_q], first, size[by_q], tall[by_q],
                 depth[by_q], q[up[by_q]], side[by_q], q[heavy[by_q]],
                 pre[by_q])


def dense_spans(n_features: int, lanes: int) -> tuple:
    """The select's spans that bound nothing: every 128-lane tile of a
    sub-tree reads all the K-blocks of 128 columns."""
    return ((0, -(-n_features // PATH_LANES)),) * -(-lanes // PATH_LANES)


def _tile_bound(ens: NodeListEnsemble, flat: _Flat, lanes: int, spans: tuple,
                halved: bool) -> np.ndarray | None:
    """The nodes that only the first / only the second lane tile may hold
    under `spans`, bool [2, M] (None: the spans bound nothing); refuses
    spans and halves that no cut can number."""
    block = ens.feature[flat.tree, flat.node] // PATH_LANES
    only = np.stack([block < spans[-1][0], block >= spans[0][1]])
    if only.any() and (len(spans) != 2 or lanes != 2 * PATH_LANES):
        raise ValueError("the select's spans bound a cut into sub-trees of "
                         f"two lane tiles; got {spans} at {lanes} lanes")
    if (spans[0][0], spans[-1][1]) != dense_spans(ens.n_features, lanes)[0] \
            or spans[-1][0] > spans[0][1]:
        raise ValueError(f"the select's spans {spans} leave a K-block of "
                         f"{ens.n_features} columns to no lane tile")
    if halved and (only.any() or lanes != 2 * PATH_LANES):
        raise ValueError("a halved sub-tree is two lane tiles under dense "
                         f"spans; got {spans} at {lanes} lanes")
    return only if only.any() else None


def _runs(key: np.ndarray) -> tuple:
    """(where each run of equal values of the sorted `key` begins, every
    entry's run, numbered 0..)."""
    new = np.concatenate([[True], key[1:] != key[:-1]])
    return np.flatnonzero(new), np.cumsum(new) - 1


def _largest(size: np.ndarray, seg: np.ndarray,
             among: np.ndarray) -> np.ndarray:
    """Of the indices `among` (their `seg` sorted), the one of the largest
    `size` in each run of one `seg`: the first of equal ones."""
    if not len(among):
        return among
    starts, run = _runs(seg[among])
    best = np.maximum.reduceat(size[among], starts)[run]
    return among[np.minimum.reduceat(np.where(
        size[among] == best, np.arange(len(among)), len(among)), starts)]


def _common_ancestor(flat: _Flat, before: np.ndarray,
                     after: np.ndarray) -> np.ndarray:
    """The lowest common ancestors of the nodes at `before` and at `after`
    (later in `_Flat`'s order, neither above the other): the deepest node
    above the later one that lies before the earlier one."""
    x = flat.up[after]
    while (far := x > before).any():
        x[far] = flat.up[x[far]]
    return x


def _glue_tiles(flat: _Flat, only: np.ndarray, frontier: np.ndarray,
                seg: np.ndarray, taken: np.ndarray,
                pick: np.ndarray) -> np.ndarray:
    """What the glue copy that hangs frontier sub-tree `pick` beside the
    ones its entry has `taken` costs either lane tile, int [2, picks]: 1
    where only that tile may hold the copy's node. The copy: the deeper of
    the sub-tree's common ancestors with its neighbours among the taken,
    before and behind it (none: the entry's first piece)."""
    at, end = np.arange(len(frontier)), len(frontier)
    glue = np.full(len(pick), -1, np.int64)
    for lo, hi in ((np.maximum.accumulate(np.where(taken, at, -1))[pick],
                    pick),
                   (pick, np.minimum.accumulate(
                       np.where(taken, at, end)[::-1])[::-1][pick])):
        ok = (lo >= 0) & (hi < end)
        ok[ok] = seg[lo[ok]] == seg[hi[ok]]
        glue[ok] = np.maximum(glue[ok], _common_ancestor(
            flat, frontier[lo[ok]], frontier[hi[ok]]))
    return np.where(glue >= 0, only[:, np.maximum(glue, 0)], False).astype(
        np.int64)


def _filled(flat: _Flat, lanes: int, only: np.ndarray | None,
            halved: bool) -> tuple:
    """`cut_subtrees`'s packer: the PIECES, (first position, nodes, entry
    in its tree) each, a round of all the trees' entries a step. A piece is
    a whole frontier sub-tree or a prefix of one (`_Flat`'s order), so an
    interval of positions."""
    cap = lanes - 1                     # nodes and glue copies of an entry
    cum = None
    if only is not None:
        cum = np.zeros((2, len(flat.tree) + 1), np.int64)
        np.cumsum(only, axis=1, out=cum[:, 1:])
    frontier = flat.first[:-1][np.diff(flat.first) > 0]
    pieces, entry = [], 0
    while len(frontier):
        starts, seg = _runs(flat.tree[frontier])
        # an entry's own nodes, pieces, longest piece and tile-bound nodes
        n, p, h = (np.zeros(len(starts), np.int64) for _ in range(3))
        c = np.zeros((2, len(starts)), np.int64)
        size, tall = flat.size[frontier], flat.tall[frontier]
        count = cum[:, frontier + size] - cum[:, frontier] \
            if cum is not None else None
        taken, out = (np.zeros(len(frontier), bool) for _ in range(2))
        cand = np.arange(len(frontier))
        while True:
            # whole sub-trees, the largest that fits first. One more piece
            # is one more glue copy: a lane, one on the longest path
            # (charged as more than it can cost) and a node of the tile its
            # column says, which is known of the one picked alone: one that
            # does not fit for its copy's sake is out for this entry.
            s = seg[cand]
            fit = n[s] + size[cand] + p[s] <= cap
            if halved:
                fit &= n[s] + size[cand] + 2 * p[s] + np.maximum(
                    h[s], tall[cand]) <= cap
            if cum is not None:
                fit &= (c[:, s] + count[:, cand] <= PATH_LANES).all(axis=0)
            cand = cand[fit]
            if not len(cand):
                break
            pick = _largest(size, seg, cand)
            if cum is not None:
                glue = count[:, pick] + _glue_tiles(
                    flat, only, frontier, seg, taken, pick)
                ok = (c[:, seg[pick]] + glue <= PATH_LANES).all(axis=0)
                out[pick[~ok]] = True
                pick = pick[ok]
                c[:, seg[pick]] += glue[:, ok]
            s = seg[pick]
            n[s] += size[pick]
            h[s] = np.maximum(h[s], tall[pick])
            p[s] += 1
            taken[pick] = True
            pieces.append((frontier[pick], size[pick],
                           np.full(len(pick), entry)))
            cand = cand[~(taken | out)[cand]]
        # ... then a TOP piece of the largest sub-tree left: as long a
        # prefix as fits (a threshold on its length buys nothing: 13,386
        # entries of the XGBoost model at 1 node, 13,438 at 16, 13,728 at 64)
        big = _largest(size, seg, np.flatnonzero(~taken))
        s = seg[big]
        m = cap - n[s] - p[s]
        if halved:
            # its longest path is no longer than the sub-tree's, nor than
            # the piece
            room = cap - n[s] - 2 * p[s]
            m = np.minimum(m, np.maximum(
                room - np.maximum(h[s], tall[big]),
                np.minimum(room - h[s], room // 2)))
        if cum is not None:
            glue = _glue_tiles(flat, only, frontier, seg, taken, big)
            for tile in range(2):
                m = np.minimum(m, np.searchsorted(
                    cum[tile], cum[tile, frontier[big]] + PATH_LANES
                    - c[tile, s] - glue[tile], side="right") - 1
                    - frontier[big])
        m = np.minimum(m, size[big])
        big, m = big[m >= 1], m[m >= 1]
        taken[big] = True
        pieces.append((frontier[big], m, np.full(len(big), entry)))
        # the children a prefix cuts off hang on the path up from its last
        # node, and lie behind it: the next entries' frontier
        top, end = frontier[big], frontier[big] + m
        x, new = end - 1, [frontier[~taken]]
        while len(x):
            kids = flat.kids[x]
            new.append(kids[kids >= end[:, None]])
            go = x != top
            x, top, end = flat.up[x[go]], top[go], end[go]
        frontier = np.sort(np.concatenate(new))
        entry += 1
    if not pieces:
        return (np.zeros(0, np.int64),) * 3
    return tuple(np.concatenate(a) for a in zip(*pieces))


def _rank(key: np.ndarray, mask: np.ndarray | None = None) -> np.ndarray:
    """Each entry's number among those of its `key` (sorted) that `mask`
    takes, in the order given."""
    mask = np.ones(len(key), bool) if mask is None else mask
    before = np.cumsum(mask) - mask
    return before - before[np.searchsorted(key, key)]


def _numbered(flat: _Flat, pieces: tuple, only: np.ndarray | None,
              halved: bool) -> SubtreeCut:
    """`cut_subtrees`'s entries from the packer's pieces: the glue copies,
    every slot's place in its entry's glued tree, and the lanes."""
    T, M = len(flat.first) - 1, len(flat.tree)
    at, nodes, entry = (a[np.argsort(pieces[0])] for a in pieces)
    if M and (at[0] != 0 or (at[1:] != at[:-1] + nodes[:-1]).any()
              or at[-1] + nodes[-1] != M):
        raise ValueError("the cut's pieces are no partition of the nodes")
    n_subtrees = np.ones(T, np.int64)
    np.maximum.at(n_subtrees, flat.tree[at], entry + 1)
    first = np.concatenate([[0], np.cumsum(n_subtrees)])
    gid = first[flat.tree[at]] + entry          # a piece's entry, of all
    # The glue: of an entry's pieces in pre-order, every two neighbours'
    # lowest common ancestor (the deepest node above the later one that
    # lies before the earlier one): the branching nodes of the pieces'
    # common-ancestor tree, each once.
    by_entry = np.lexsort((at, gid))
    g, a = gid[by_entry], at[by_entry]
    same = g[1:] == g[:-1]
    before, glue_gid = a[:-1][same], g[1:][same]
    glue = _common_ancestor(flat, before, a[1:][same])
    G = len(glue)
    # Slots: the nodes at their positions, the glue behind. The slot above
    # a piece's root or a glue copy: the first glue copy of its own entry
    # on the way up its tree (none at or above the entry's highest).
    s_at = np.concatenate([np.arange(M), glue])         # the node it asks
    s_gid = np.concatenate([np.repeat(gid, nodes), glue_gid])
    s_up = np.concatenate([flat.up, np.full(G, -1)])
    s_up[at] = -1
    s_side = np.concatenate([flat.side, np.zeros(G, np.int8)])
    if G:
        key = glue_gid * M + glue
        by_key = np.argsort(key)
        key = key[by_key]
        highest = np.full(int(first[-1]), M, np.int64)
        np.minimum.at(highest, glue_gid, glue)
        hangs = np.concatenate([at[highest[gid] < M], M + np.arange(G)])
        cur = s_at[hangs]
        while len(hangs):
            above, sd = flat.up[cur], flat.side[cur]
            on = above >= highest[s_gid[hangs]]
            hangs, above, sd = hangs[on], above[on], sd[on]
            k = np.minimum(np.searchsorted(key, s_gid[hangs] * M + above),
                           G - 1)
            hit = key[k] == s_gid[hangs] * M + above
            s_up[hangs[hit]] = M + by_key[k[hit]]
            s_side[hangs[hit]] = sd[hit]
            hangs, cur = hangs[~hit], above[~hit]
    # an entry's slots in the pre-order of its glued tree: that of the
    # uncut tree, in which a glue copy stands where its node does
    order = np.lexsort((flat.pre[s_at], s_gid))
    part = s_gid[order]
    lane = np.zeros(M + G, np.int64)
    copy = None
    if halved:
        lane, copy = _halves(flat, s_at, s_up, order, part)
    elif only is None:
        lane[order] = _rank(part)
    else:
        # told apart by their tile: a slot only one tile may hold lies
        # there, the others fill the first tile's room and then the second's
        one0, one1 = only[:, s_at[order]]
        free = ~(one0 | one1)
        room = PATH_LANES - np.bincount(part, weights=one0)[part]
        second = one1 | (free & (_rank(part, free) >= room))
        by_tile = np.lexsort((np.arange(len(part)), second, part))
        lane[order[by_tile]] = PATH_LANES * second[by_tile] + _rank(
            (2 * part + second)[by_tile])
    # ... the nodes' slots in the order of the node list
    slot = np.concatenate([flat.first[flat.tree] + flat.node,
                           M + np.arange(G)])

    def by_slot(values, dtype):
        out = np.empty(M + G, dtype)
        out[slot] = values
        return out

    root = np.zeros(M + G, bool)
    root[at] = True
    s_tree = flat.tree[s_at]
    return SubtreeCut(
        n_subtrees, by_slot(s_tree, np.int64),
        by_slot(flat.node[s_at], np.int64), by_slot(root, bool),
        by_slot(s_gid - first[s_tree], np.int32), by_slot(lane, np.int32),
        by_slot(copy, np.int32) if halved else None,
        by_slot(np.where(s_up >= 0, slot[s_up], -1), np.int64),
        by_slot(s_side, np.int8))


def _halves(flat: _Flat, s_at: np.ndarray, s_up: np.ndarray,
            order: np.ndarray, part: np.ndarray) -> tuple:
    """The lanes and the spine copies' lanes of HALVED entries
    (`cut_subtrees`), a slot each: `order` the slots by entry and in it in
    pre-order, `part` (sorted) their entries."""
    # slots above it in its entry: a slot's parent lies higher in the tree
    depth = np.zeros(len(s_at), np.int64)
    by_level = np.argsort(flat.depth[s_at], kind="stable")
    for level in np.split(by_level, np.cumsum(np.bincount(
            flat.depth[s_at]))[:-1]) if len(s_at) else ():
        level = level[s_up[level] >= 0]
        depth[level] = depth[s_up[level]] + 1
    pos, deep = _rank(part), depth[order]
    starts = np.nonzero(pos == 0)[0]            # an entry's first slot
    nodes = np.diff(np.append(starts, len(part)))
    seg = np.cumsum(pos == 0) - 1
    # k: the second half begins at the entry's slot k, behind copies of the
    # `deep` slots above it, and hangs one exit a node and one more a tree
    # of its nodes (at most `deep` + 1 of them) in lanes of its own. The
    # fewest copies; of those the fullest first half. An entry whose exits
    # fit one half has no second one.
    fits = (pos >= 1) & (pos <= PATH_LANES) & (
        nodes[seg] - pos + deep + 1 <= PATH_LANES)
    never = 2 * PATH_LANES * PATH_LANES
    best = np.minimum.reduceat(np.where(
        fits, deep * (PATH_LANES + 1) + PATH_LANES - pos, never), starts) \
        if len(part) else nodes
    one = nodes < PATH_LANES
    if (~one & (best == never)).any():
        raise ValueError("a sub-tree's nodes fit no two halves of "
                         f"{PATH_LANES} lanes: the cut did not hold the "
                         "halved bound")
    k = np.where(one, nodes, PATH_LANES - best % (PATH_LANES + 1))[seg]
    spine = np.where(one, 0, best // (PATH_LANES + 1))[seg]
    lane = np.zeros(len(s_at), np.int64)
    lane[order] = np.where(pos < k, pos, PATH_LANES + spine + pos - k)
    copy = np.zeros(len(s_at), np.int64)
    x = s_up[order[pos == k]]                   # slot k is no top: k >= 1
    while len(x):
        copy[x] = PATH_LANES + depth[x]
        x = s_up[x]
        x = x[x >= 0]
    return lane, copy


def cut_subtrees(ens: NodeListEnsemble, lanes: int,
                 spans: tuple | None = None,
                 halved: bool = False) -> SubtreeCut:
    """Every tree cut into the ENTRIES of the sub-tree form, each of at
    most `lanes` - 1 node lanes and so of at most `lanes` EXITS (an exit is
    a child that is a leaf, or a link: a child that lies in another entry).
    Every internal node lies in ONE entry; an entry holds one or several
    connected PIECES of its tree, none above another, each piece's root
    hanging on a node of an EARLIER entry of the tree (the tree's root lies
    in entry 0), and p pieces are GLUED into one binary tree by p - 1
    copies of their lowest common ancestors: a copy asks its node's
    question in a lane of its own, hangs no exit and has one piece (or
    copy) on either side. Every row that reaches a piece passed the nodes
    the copies stand for and answered them as the copies do, so of an
    active entry's exits exactly one fires, the one the node walk takes.

    The cut FILLS its entries (connected parts come out ragged: 178 of 255
    lanes in the XGBoost model, PERF.md PR 53). A tree keeps a frontier of
    the sub-trees whose parent lies in a closed entry, at first the tree
    itself. An entry takes the largest frontier sub-tree that fits WHOLE
    beside what it holds, again and again; then a TOP piece of the largest
    one left: as long a prefix of its pre-order as fits, the heavier child
    first, which leaves the lighter sub-trees to the frontier, and those
    pack whole. The children
    a top piece cuts off join the frontier when the entry closes. A tree
    that fits an entry is ONE piece; a tree of one leaf one entry of no
    node.

    `spans`: the K-blocks of the feature select that each of an entry's
    TWO 128-lane tiles reads, ((0, stop), (start, blocks)) with start <=
    stop (`CompiledNodeList.select_spans`). An entry then holds three
    bounds, glue copies counted, so that its slots can be numbered with
    every lane's K-block inside its tile's span: those whose block only the
    first tile reads (below `start`) are at most 128, those only the second
    reads (from `stop`) at most 128, all of them at most `lanes` - 1. The
    numbers: a slot that only one tile reads lies there, the others fill
    the first tile's room and then the second's; inside a tile the
    pre-order. Without spans (or with dense ones, which bound nothing) the
    lanes are the pre-order, 0.. with no gap. `halved`: two halves of 128
    lanes that share their spine (`_halves`), for which an entry's slots
    and those on its longest path are at most `lanes` - 1 together.

    No [N, L] matrix of a whole tree is made, and no node is walked in
    Python: a level of all the trees, or an entry of each, a step."""
    flat = _flat(ens)
    spans = spans or dense_spans(ens.n_features, lanes)
    only = _tile_bound(ens, flat, lanes, spans, halved)
    return _numbered(flat, _filled(flat, lanes, only, halved), only, halved)


def subtree_mxu_tiles(spans: tuple, lanes: int, exit_lanes: int,
                      halved: bool = False) -> int:
    """MXU weight tiles a sub-tree of `lanes` lanes costs a tile of rows
    (ops/predict_paths.path_mxu_tiles_per_tree, the unpacked select): the
    select's, a tile a K-block of every lane tile's span, the resolve's
    (`halved`: the diagonal blocks alone), the exits' table's."""
    w = lanes // PATH_LANES
    return (sum(stop - start for start, stop in spans)
            + (w if halved else w * w) + w * (exit_lanes // PATH_LANES))


def exit_table_lanes(leaf_columns: int, n_subtrees: np.ndarray) -> tuple:
    """(lanes of the exits' table, the lane of a link to the NEXT sub-tree)
    of a model of `leaf_columns` values a leaf whose trees are cut into
    `n_subtrees` [T] parts: THE RULE of the table's width, the one place
    that decides it (`CompiledNodeList` builds by it, the kernel and its
    twin read the layout back from the table's width:
    ops/predict_paths.chain_of).

    ONE lane tile where the three bfloat16 pieces of a leaf's C values
    (lanes 0 .. 3C - 1) and the chain fit 128 lanes together. The chain of a
    tree of n sub-trees takes n - 1 lanes behind the pieces: a link from
    sub-tree k to sub-tree j lies in lane 3C + (j - k - 1), j - k - 1 in
    0 .. n - 2, and the kernel shifts the lanes down by one a sub-tree, all
    128 of them, so what the class lanes held comes round to lane 127 one
    sub-tree after it reached lane 0 and is back at lane 3C, the one lane
    the kernel reads, 129 - 3C sub-trees after it was written, at the
    soonest: past the tree's last sub-tree exactly where the links fit,

        3C + n - 1 <= 128        (10 classes: trees of up to 99 sub-trees)

    and the tree's root clears every lane. Otherwise the class lanes and
    the activity lanes are lane tiles of their own (whole 128s each, the
    activity's more than a tree's sub-trees), the first link behind the
    class tiles: 85 classes, 128 classes, a 10-class tree of 100
    sub-trees."""
    pieces = LEAF_PIECES * leaf_columns
    most = int(n_subtrees.max())
    if pieces + most - 1 <= PATH_LANES:
        return PATH_LANES, pieces
    class_lanes = -(-pieces // PATH_LANES) * PATH_LANES
    act_lanes = -(-(most + 1) // PATH_LANES) * PATH_LANES
    return class_lanes + act_lanes, class_lanes


def choose_select_spans(ens: NodeListEnsemble, lanes: int) -> tuple:
    """(`CompiledNodeList.select_spans`, the model's cut under them): which
    K-blocks of the feature select each 128-lane tile of a sub-tree reads,
    from what the model shows. A node's one K row lies in ONE K-block, and
    which block a lane reads is decided by the order of the lanes alone, so
    lanes ordered by their column's block let a tile skip the blocks none
    of its nodes reads (all-zero weight tiles), at the price of a cut that
    holds a bound a tile (`cut_subtrees`) and so makes a few more entries.

    The candidates split the blocks at the one in which the cumulative
    share of the model's internal nodes passes one half: that block read by
    both tiles, by the first alone, by the second alone; the dense spans;
    and the dense spans over HALVED sub-trees (`cut_subtrees`: the lanes
    ordered by the tree's shape, not by columns, so that the path resolve
    is 2 weight tiles where 4; the cut says so by its `copy`). Taken is the
    one whose cut asks the fewest MXU weight tiles a tree
    (`subtree_mxu_tiles` x the sub-trees, the exits' table as wide as that
    cut's model gets it, `exit_table_lanes`), the earlier one where two ask
    alike: the halved layout wherever the select is one K-block (F <= 128:
    6 tiles a sub-tree against 8), the blocks' spans where the columns
    split well (the MNIST forest: 13 against the halves' 18). Sub-trees of
    another width than two tiles, a model of no internal node: the dense
    spans. Every candidate is counted by the cut it would get, the PACKED
    one."""
    flat = _flat(ens)
    dense = dense_spans(ens.n_features, lanes)
    blocks = dense[0][1]
    candidates = [(dense, False)]
    if lanes == 2 * PATH_LANES and ens.n_splits:
        if blocks > 1:
            share = np.cumsum(np.bincount(
                ens.feature[ens.live_nodes] // PATH_LANES, minlength=blocks))
            h = int(np.searchsorted(share, share[-1] / 2, side="right"))
            candidates += [
                (((0, stop), (start, blocks)), False) for stop, start in (
                    (h + 1, h), (h + 1, h + 1), (h, h))
                if stop > 0 and start < blocks]
        candidates.append((dense, True))
    # No cut makes fewer entries than a tree's nodes over an entry's most,
    # nor an exits' table under one tile: a candidate that cannot ask fewer
    # tiles than the best cut so far is not cut. The cheapest bound first;
    # of equal counts the earlier listed.
    fewest = int(np.maximum(-(-(ens.n_leaves.astype(np.int64) - 1)
                              // (lanes - 1)), 1).sum())
    bound = [fewest * subtree_mxu_tiles(spans, lanes, PATH_LANES, halved)
             for spans, halved in candidates]
    best = None
    for i in sorted(range(len(candidates)), key=lambda i: (bound[i], i)):
        if best is not None and (bound[i], i) > best[:2]:
            continue
        spans, halved = candidates[i]
        only = _tile_bound(ens, flat, lanes, spans, halved)
        pieces = _filled(flat, lanes, only, halved)
        per_tree = np.ones(ens.n_trees, np.int64)
        np.maximum.at(per_tree, flat.tree[pieces[0]], pieces[2] + 1)
        tiles = int(per_tree.sum()) * subtree_mxu_tiles(
            spans, lanes, exit_table_lanes(ens.leaf_columns, per_tree)[0],
            halved)
        if best is None or (tiles, i) < best[:2]:
            best = (tiles, i, spans, halved, only, pieces)
    *_, spans, halved, only, pieces = best
    return spans, _numbered(flat, pieces, only, halved)


# Components of K-blocks `choose_set_spans` searches whole: 3^8 assignments.
SET_SPAN_COMPONENTS = 8


def choose_set_spans(counts: np.ndarray, blocks: np.ndarray,
                     lanes: int) -> np.ndarray | None:
    """`choose_select_spans` for the UNCUT tree with CATEGORY SETS
    (`CompiledNodeList.select_spans`): which of a tree's two 128-lane tiles
    reads each COMPONENT of the select's K-blocks, int [C]: 0 the first, 1
    the second, 2 both; None: the dense spans. A set node's K rows lie in one
    one-hot block, or in two where its column names more than 128 ids, and an
    ordinal node's in an ordinal one; blocks tied by a node that reads two of
    them are a component, the ordinal blocks one more, and a node lies in ONE
    component. `counts` int [T, C]: the nodes of tree t in component c;
    `blocks` int [C]: the component's K-blocks. A tree is not cut here, so
    where `choose_select_spans` counts a candidate by the cut it would get,
    an assignment has to FIT every tree as it is: the nodes only the first
    tile may hold at most 128, and likewise the second (the nodes of a
    component both read fill what room is left in either). Taken is the
    fitting assignment of the FEWEST weight tiles (a component's blocks once
    for every tile that reads it; the Allstate model: the ordinal block,
    129.9 nodes a tree, in both and each of the six one-hot blocks in one, 8
    where 14), of equal counts the one whose fullest tile is least full, then
    the first in `itertools.product`'s order. Every assignment is scored on
    the small table, 3^C of them: up to `SET_SPAN_COMPONENTS` components are
    searched whole; past that, at any other width than two lane tiles (128:
    nothing to skip; 384 and 512), and where no assignment under the dense
    count fits every tree, the answer is None and the tables and the program
    are the dense ones, instruction for instruction."""
    import itertools

    if lanes != 2 * PATH_LANES or not 1 < len(blocks) <= SET_SPAN_COMPONENTS:
        return None
    tiles = np.array(list(itertools.product((0, 1, 2), repeat=len(blocks))))
    cost = ((tiles == 2) + 1) @ blocks                        # [A]
    fullest = np.maximum(counts @ (tiles == 0).T,
                         counts @ (tiles == 1).T).max(axis=0, initial=0)
    # (a tile reads SOME block: the kernel sums every lane tile's `v`)
    fits = ((fullest <= PATH_LANES) & (cost < 2 * blocks.sum())
            & (tiles != 0).any(axis=1) & (tiles != 1).any(axis=1))
    if not fits.any():
        return None
    return tiles[min(np.flatnonzero(fits),
                     key=lambda a: (cost[a], fullest[a], a))]


# bfloat16 pieces a float32 leaf value is held in (`split_bfloat16`; the
# kernel's side of it is ops/predict_paths._LEAF_PIECES).
LEAF_PIECES = 3


def split_bfloat16(v: np.ndarray, pieces: int = LEAF_PIECES) -> list:
    """float32 `v` as a sum of `pieces` bfloat16 arrays, largest first:
    three hold every float32 exactly (8 bits of the mantissa each, round
    to nearest), and added smallest first in float32 they give `v` back.
    What lets a 0/1 matrix pick float32 values through a bfloat16 MXU."""
    import ml_dtypes

    out, rest = [], np.asarray(v, np.float32)
    for _ in range(pieces):
        out.append(rest.astype(ml_dtypes.bfloat16))
        rest = rest - out[-1].astype(np.float32)
    return out


@dataclasses.dataclass(frozen=True)
class CompiledNodeList:
    """A node-list model's BINNED scoring tables, the path-matrix form
    (ops/predict.py has the equations): per tree, W lanes (`PATH_LANES`
    multiples) of nodes and of leaves,

        sel    [T, Fp, W] bf16   feature one-hot of the tree's nodes
        planes [T, 8, W]  f32    row 0 the nodes' threshold bins (unused
                                 lanes +BIG: never right), row 1 the
                                 leaves' path lengths (-1: no such leaf),
                                 row 2 the leaf values, row 3 (read with
                                 learned NaN directions alone,
                                 `missing_bin_value` >= 0) the bin from
                                 which a node stops answering right: the
                                 NaN bin where it sends NaN left, +BIG
                                 where right, so that a node's answer is
                                 `thr < v < up` and the NaN bin, above
                                 every threshold, needs no test of its own
        paths  [T, W, W]  bf16   P[n, l]: +1 leaf l in node n's right
                                 subtree, -1 in its left, 0 elsewhere

    The SUB-TREE form (`n_subtrees` > 0: vector leaves, or a tree of more
    than `PATH_UNCUT_LANES` lanes): the same three tables with one entry a
    SUB-TREE of `cut_subtrees` (W = `SUBTREE_LANES`; S entries, a tree's
    in a row, parents before children), a sub-tree's "leaves" its EXITS,
    and a fourth table that says what an exit is. An entry holds one
    connected piece of its tree or SEVERAL (`pieces` over all entries),
    glued into one binary tree by copies of their lowest common ancestors
    (`glue_copies`): a copy has its node's K row, threshold and NaN bound in
    a lane of its own, hangs no exit, and lies on the path of every exit
    below it with the sign of the side that exit's piece hangs on; a link
    goes to the entry that holds the child. A sub-tree's lanes are
    numbered BY THE K-BLOCK OF THEIR COLUMN: `select_spans` says for each of
    its 128-lane tiles which K-blocks of 128 columns the nodes there may
    read, ((0, 3), (3, 7)) at the MNIST forest's 784 columns (the same for
    every entry; `choose_select_spans` reads them from the model), so that
    the kernel asks the MXU for a tile's own blocks alone and not for the
    all-zero tiles of `sel`: 7 select tiles a sub-tree where 14. Inside a
    lane tile the order is the pre-order of the cut; a tile's unused lanes
    are those of no node (threshold +BIG, no path), wherever they lie;
    `planes` rows 0 and 3, `paths`' rows and the exits' order follow the
    lanes. Dense spans (every tile reads every block: F <= 128, or a model
    the split buys nothing) number the nodes in pre-order, 0.. with no gap,
    or, HALVED (`cut_subtrees`; `choose_select_spans` takes it where it asks
    the fewest tiles: every model of one K-block), as two halves of 128
    lanes that share their spine: the second half from lane 128, copies of
    the slots (nodes or glue copies) above its first and then its own; a
    copy has its node's K row, threshold and NaN bound and hangs no exit; the exits of a half
    lie in the half's own 128 exit lanes (`planes` rows 1, `leaves`' rows);
    and `paths` is the two diagonal blocks alone, side by side:

        paths  [S, W/2, W] bf16  P[n, l] of exit l's OWN half: row n is node
                                 lane n for l < 128 and node lane 128 + n
                                 for l >= 128 (`halved`; `spine_copies` the
                                 lanes that hold a copy, over all entries)

        planes row 4             1 in the entry that roots a tree, else 0
                                 (row 2 is not read)
        leaves [S, W, E] bf16    row e, an exit; E by `exit_table_lanes`,
                                 THE RULE of its width. A real leaf: its
                                 float32 vector as three bfloat16 pieces
                                 (`split_bfloat16`), piece p's column c in
                                 lane p C + c (of softmax's round-major
                                 trees, `loss` "softmax": tree t's scalar
                                 in column t % C and exact zeros in the
                                 others, so a tree adds into its class
                                 alone: 21
                                 lanes of pieces at 7 classes, trees of up
                                 to 108 sub-trees in ONE lane tile). A link
                                 to the tree's
                                 sub-tree j from its sub-tree k: 1 in lane
                                 link0 + (j - k - 1), which the chain
                                 shifts down a lane a sub-tree.
                                 E = 128, ONE lane tile (PR 49), where the
                                 pieces and a tree's chain fit it together,
                                 3 C + (most sub-trees a tree) - 1 <= 128:
                                 link0 = 3 C, the links right behind the
                                 pieces (the MNIST forest: 30 lanes of
                                 pieces, 24 of links at most). Else
                                 E = CL + A, the pieces in CL class lanes
                                 and the links in A activity lanes behind
                                 them (whole 128s each, A more than a
                                 tree's sub-trees), link0 = CL: 85 or 128
                                 classes, a 10-class tree of 100 sub-trees.

    CATEGORY SETS (the uncut form alone; the sub-tree form refuses them by
    name). The set test stays a row of the select: the kernel widens every
    category column to the ONE-HOT of its bin, 128 K rows a block
    (`cat_blocks` B: small columns share a block, a column of more than 128
    named bins takes two), and a set node's column of `sel` is MULTI-hot
    over its column's K rows, so that v is 1 where the row's bin is in the
    set and 0 elsewhere, the threshold 0, and `v > thr` says IN THE SET:
    left. The node's row of `paths` is negated to say so (+1 left), and the
    resolve, the accumulate and the fold never know. Two small tables say
    what a K row of a block is,

        sel        [T, Fo + 128 B, W]  Fo = Fp where a node is ordinal (its
                                   rows as above), 0 where every node asks a
                                   set; then the blocks' K rows
        cat_expand [B, Fp, 128] bf16   1 in row c of lane j where K row j of
                                   the block reads column c: x @ it is the
                                   row's bin of that column, in lane j
        cat_bins   [B, 8, 128] f32     row 0 the bin K row j stands for
                                   (-1: no K row), so one compare gives the
                                   block's one-hot

    A bin no K row stands for (every unnamed value's, or one past the
    mapper's) is in no set: v is 0 and the row goes right.

    The node lanes of such a tree are numbered BY THE K-BLOCK THEY READ
    where the tree is two lane tiles and the model's blocks split
    (`choose_set_spans`, `_lanes_by_k_block`; PR 56): `select_spans` says
    which K-blocks of `sel`, in its rows' order, each lane tile reads,
    ((0, 4), (3, 7)) for the Allstate model, whose `sel` holds three
    one-hot blocks, the ordinal rows (`cat_ordinal_at` 3) and three more;
    lane n is then NOT node n: `sel`'s columns, `planes` rows 0 and 3 and
    `paths`' rows follow the lanes, the leaf lanes (`planes` rows 1 and 2,
    `paths`' columns) stay where they are. Dense (`select_spans` (),
    `cat_ordinal_at` 0): the ordinal rows first, the blocks as packed, lane
    n node n.

    `mean`: the score is the sum over the trees divided by their number
    (an averaged forest), else base + learning_rate x sum ([rows] of one
    column; [rows, C] of softmax's classes, whose trees take the sub-tree
    form whatever their size: a small tree is ONE entry, a tree of one leaf
    an entry of no node).
    Built ONCE per model version on the host; backends keep them device-
    resident under `token` (the same cache as CompiledEnsemble's)."""

    token: str
    learning_rate: float
    base_score: float
    loss: str
    n_trees: int
    lanes: int                 # W
    deepest_leaf: int
    sel: np.ndarray
    planes: np.ndarray
    paths: np.ndarray
    missing_bin_value: int = -1    # reserved NaN bin id, -1 = no routing
    leaves: np.ndarray | None = None   # the sub-tree form's exits
    n_subtrees: int = 0        # S; 0: one uncut path matrix a tree
    leaf_columns: int = 1      # C
    mean: bool = False
    widest_tree: int = 0       # lanes the widest tree would take uncut
    select_spans: tuple = ()   # (first, stop) K-blocks of the select a
    #   lane tile: of a sub-tree, or of an uncut tree with category sets
    #   (there the K-blocks as `sel` holds them; (): every tile reads all)
    subtrees_max: int = 1      # the largest tree's entries ...
    single_subtree_trees: int = 0   # ... and the trees that are ONE entry
    spine_copies: int = 0      # halved: the lanes that hold a node's copy
    pieces: int = 0            # the connected pieces of the S entries ...
    glue_copies: int = 0       # ... and the lanes that hold a glue copy
    cat_expand: np.ndarray | None = None   # category sets: the K rows'
    cat_bins: np.ndarray | None = None     #   columns and bins
    category_nodes: int = 0    # the nodes that ask a set ...
    category_set_bits_max: int = 0     # ... and the widest set's bins
    cat_ordinal_at: int = 0    # the one-hot blocks whose K rows lie BEFORE
    #   the ordinal ones in `sel` (0: the ordinal rows first)

    @property
    def cat_blocks(self) -> int:
        """K-blocks of 128 one-hot rows the category sets read."""
        return 0 if self.cat_expand is None else len(self.cat_expand)

    @property
    def ordinal_rows(self) -> int:
        """K rows of `sel` that read the bins themselves (ordinal nodes)."""
        return self.sel.shape[1] - PATH_LANES * self.cat_blocks

    @property
    def n_classes_out(self) -> int:
        return self.leaf_columns

    @property
    def chained(self) -> bool:
        return self.leaves is not None

    @property
    def halved(self) -> bool:
        """Whether `paths` holds the two diagonal blocks alone."""
        return self.paths.shape[1] < self.paths.shape[2]

    layout = NodeListEnsemble.layout

    @property
    def compile_counts(self) -> dict:
        """Of this form, on the `ddt:predict:ensemble:compile` span."""
        return {"subtrees": self.n_subtrees}

    def arrays(self) -> tuple:
        return (self.sel, self.planes, self.paths) + (
            (self.leaves,) if self.chained else ()) + (
            (self.cat_expand, self.cat_bins) if self.cat_blocks else ())

    @staticmethod
    def build(ens: NodeListEnsemble) -> "CompiledNodeList":
        import ml_dtypes

        if not ens.has_bin_thresholds:
            raise ValueError(
                "this node-list ensemble carries raw thresholds only; rank "
                "them first (models/lightgbm_io.threshold_bin_mapper)")
        T, N = ens.feature.shape
        L = ens.leaf_value.shape[1]
        W = -(-max(N, L) // PATH_LANES) * PATH_LANES
        Fp = -(-ens.n_features // 16) * 16      # bf16 sublane tiles
        live = ens.live_nodes
        nan_bin = ens.missing_bin_value
        if nan_bin >= 0 and (ens.threshold_bin[live] >= nan_bin).any():
            raise ValueError(
                f"a node's threshold bin is the NaN bin {nan_bin} or "
                "above it: with learned NaN directions thresholds lie "
                "in the value bins")
        sets = ens.cat_nodes
        if ens.leaf_columns > 1 or ens.vector_leaves \
                or W > PATH_UNCUT_LANES:
            _refuse_routes("CompiledNodeList.build",
                           chained_sets=bool(sets.any()))
            return CompiledNodeList._build_subtrees(ens, W, Fp)
        P, plen = ens.path_matrix()
        thr = np.where(live, np.where(sets, 0, ens.threshold_bin), 2.0 ** 30)
        up = np.where(live & ens.default_left & ~sets, nan_bin, 2.0 ** 30) \
            if nan_bin >= 0 else None
        if not sets.any():
            sel = np.zeros((T, Fp, W), ml_dtypes.bfloat16)
            t_idx, n_idx = np.nonzero(live)
            sel[t_idx, ens.feature[t_idx, n_idx], n_idx] = 1.0
            lane, cat = None, {}
        else:
            sel, lane, cat = CompiledNodeList._select_with_sets(
                ens, sets, Fp, W)
            P[sets] = -P[sets]      # +1 of a set node: in the set, LEFT
        if lane is not None:
            # The node lanes ordered by their K-block (`_lanes_by_k_block`):
            # `sel`'s columns are, the thresholds and P's rows follow; the
            # leaf lanes stay where they are.
            def by_lane(a, fill):
                out = np.full((T, W) + a.shape[2:], fill, a.dtype)
                out[np.arange(T)[:, None], lane] = a
                return out

            thr, P = by_lane(thr, 2.0 ** 30), by_lane(P, 0)
            up = by_lane(up, 2.0 ** 30) if up is not None else None
            N = W
        planes = np.zeros((T, 8, W), np.float32)
        planes[:, 0, :] = 2.0 ** 30
        planes[:, 0, :N] = thr
        planes[:, 1, :] = -1.0
        planes[:, 1, :L] = plen
        planes[:, 2, :L] = ens.leaf_value
        if up is not None:
            planes[:, 3, :] = 2.0 ** 30
            planes[:, 3, :N] = up
        paths = np.zeros((T, W, W), ml_dtypes.bfloat16)
        paths[:, :N, :L] = P
        return CompiledNodeList(
            token=ens.cache_token(),
            learning_rate=float(ens.learning_rate),
            base_score=float(ens.base_score), loss=ens.loss,
            n_trees=T, lanes=W, deepest_leaf=int(plen.max(initial=0)),
            sel=sel, planes=planes, paths=paths, missing_bin_value=nan_bin,
            widest_tree=W, single_subtree_trees=T, **cat)

    @staticmethod
    def _select_with_sets(ens: NodeListEnsemble, sets: np.ndarray, Fp: int,
                          W: int) -> tuple:
        """(`sel` [T, Fo + 128 B, W], the lane [T, N] of every node, the
        fields that say what `sel`'s K rows are):
        `build` of a model with category sets (the class's docstring,
        CATEGORY SETS). A column's K rows are the bins its sets name, 0 up
        to the largest; the columns are packed into blocks of 128 rows
        first-fit, the largest first, a column of more than 128 rows as
        whole blocks and a rest. The blocks' order in `sel`, the place of
        the ordinal rows among them and the nodes' lanes are
        `_lanes_by_k_block`'s: as packed, the ordinal rows first and lane n
        node n where the spans are dense."""
        import ml_dtypes

        T = ens.feature.shape[0]
        bits = ens.cat_set_bits()                   # [S, 256]
        tt, nn = np.nonzero(sets)
        ss = ens.cat_index[tt, nn]
        col = ens.feature[tt, nn]
        rows = {}                                   # column -> its K rows
        for c in np.unique(col):
            named = np.flatnonzero(bits[ss[col == c]].any(axis=0))
            rows[int(c)] = int(named.max(initial=0)) + 1
        # (rows, column, first bin) pieces of at most a block each
        pieces = sorted(((min(PATH_LANES, k - b0), c, b0)
                         for c, k in rows.items()
                         for b0 in range(0, k, PATH_LANES)),
                        key=lambda p: (-p[0], p[1], p[2]))
        free: list = []                             # rows left a block
        row_of = np.full((ens.n_features, 32 * CAT_SET_WORDS), -1, np.int64)
        where = []
        for k, c, b0 in pieces:
            blk = next((i for i, f in enumerate(free) if f >= k), len(free))
            if blk == len(free):
                free.append(PATH_LANES)
            lane = PATH_LANES - free[blk]
            free[blk] -= k
            row_of[c, b0:b0 + k] = blk * PATH_LANES + lane + np.arange(k)
            where.append((blk, lane, k, c, b0))
        B = len(free)
        ordinal = ens.live_nodes & ~sets
        Fo = Fp if ordinal.any() else 0
        at, b = np.nonzero(bits[ss])                # (set node, bin in it)
        k_row = row_of[col[at], b]                  # as packed
        new_block, ordinal_at, spans, lane = \
            CompiledNodeList._lanes_by_k_block(
                ens, ordinal, (tt, nn), at, k_row // PATH_LANES, B, W)
        expand = np.zeros((B, Fp, PATH_LANES), ml_dtypes.bfloat16)
        bins = np.zeros((B, 8, PATH_LANES), np.float32)
        bins[:, 0, :] = -1.0
        for blk, first, k, c, b0 in where:
            expand[new_block[blk], c, first:first + k] = 1.0
            bins[new_block[blk], 0, first:first + k] = b0 + np.arange(k)
        # K rows of `sel`: the one-hot blocks in their new order, the
        # ordinal rows behind the first `ordinal_at` of them
        k_row = new_block[k_row // PATH_LANES] * PATH_LANES \
            + k_row % PATH_LANES
        k_row += Fo * (k_row >= ordinal_at * PATH_LANES)
        sel = np.zeros((T, Fo + PATH_LANES * B, W), ml_dtypes.bfloat16)
        t_idx, n_idx = np.nonzero(ordinal)
        sel[t_idx, ordinal_at * PATH_LANES + ens.feature[t_idx, n_idx],
            lane[t_idx, n_idx]] = 1.0
        sel[tt[at], k_row, lane[tt[at], nn[at]]] = 1.0
        return sel, lane, dict(
            cat_expand=expand, cat_bins=bins, category_nodes=len(ss),
            category_set_bits_max=int(bits[ss].sum(axis=1).max(initial=0)),
            select_spans=spans, cat_ordinal_at=ordinal_at)

    @staticmethod
    def _lanes_by_k_block(ens: NodeListEnsemble, ordinal: np.ndarray,
                          set_nodes: tuple, at: np.ndarray, block: np.ndarray,
                          B: int, W: int) -> tuple:
        """(the place of each packed one-hot block among `sel`'s [B], the
        one-hot blocks that lie before the ordinal K rows, `select_spans`,
        the lane of every node [T, N]) of an uncut model with
        category sets: the K-BLOCK SPARSE select (ops/predict_paths.py) by
        the order of the node lanes. `ordinal` bool [T, N]: the ordinal
        nodes; `set_nodes` (tree, node) of the set nodes; `block[i]` the
        packed one-hot block that K row i of set node `at[i]` lies in.

        The K-blocks tied by a node that reads two of them are a component,
        the ordinal blocks one more (the first), and `choose_set_spans` says
        which lane tile reads each. The blocks are laid in the order (the
        first tile's own, the shared, the second's own), a component's
        together, so that a tile's blocks are one (first, stop) span of the
        K-blocks as `sel` holds them; a node that only one tile can hold
        lies there, the others fill the first tile's room and then the
        second's, the node order inside each. Dense: the blocks as packed,
        0, (), lane n node n."""
        T, N = ordinal.shape
        tt, nn = set_nodes
        # the blocks a set node reads: one, or two of a column of more
        # than 128 named ids (a set no K row stands for: block 0's lanes)
        lo, hi = np.full(len(tt), B), np.full(len(tt), -1)
        np.minimum.at(lo, at, block)
        np.maximum.at(hi, at, block)
        lo, hi = np.where(hi < 0, 0, lo), np.maximum(hi, 0)
        root = np.arange(B)                 # a component's first block
        for a, b in np.unique(np.stack([lo, hi], 1)[lo != hi], axis=0):
            root[root == max(root[a], root[b])] = min(root[a], root[b])
        has_ordinal = int(ordinal.any())
        comp_of = np.unique(root, return_inverse=True)[1] + has_ordinal
        blocks = np.bincount(comp_of)
        if has_ordinal:
            blocks[0] = -(-ens.n_features // PATH_LANES)
        comp = np.full((T, N), -1)
        comp[ordinal] = 0
        comp[tt, nn] = comp_of[lo]
        counts = (comp[:, :, None] == np.arange(len(blocks))).sum(axis=1)
        tiles = choose_set_spans(counts, blocks, W)
        if tiles is None:
            return np.arange(B), 0, (), np.broadcast_to(np.arange(N), (T, N))
        group = np.array([0, 2, 1])[tiles]  # first's own, shared, second's
        by_place = np.lexsort((np.arange(B), comp_of, group[comp_of]))
        new_block = np.empty(B, np.int64)
        new_block[by_place] = np.arange(B)
        ordinal_at = int((group[comp_of] < group[0]).sum()) * has_ordinal
        first, shared, _ = (int(n) for n in np.bincount(
            group, blocks, minlength=3))
        spans = ((0, first + shared), (first, int(blocks.sum())))
        # lanes: a tile's own nodes from its first lane, then the shared
        # ones (and the slots of no node) in the room left, tile 0's first
        which = np.where(comp >= 0, tiles[comp], 2)
        rank = [np.cumsum(which == k, axis=1) - 1 for k in range(3)]
        own = [rank[k][:, -1:] + 1 for k in range(2)]
        room = PATH_LANES - own[0]
        lane = np.select(
            [which == 0, which == 1, rank[2] < room],
            [rank[0], PATH_LANES + rank[1], own[0] + rank[2]],
            PATH_LANES + own[1] + rank[2] - room)
        return new_block, ordinal_at, spans, lane

    @staticmethod
    def _build_subtrees(ens: NodeListEnsemble, widest: int,
                        Fp: int) -> "CompiledNodeList":
        """`build` of the sub-tree form (the class's docstring)."""
        import ml_dtypes

        bf16 = ml_dtypes.bfloat16
        T, N = ens.feature.shape
        W, C = SUBTREE_LANES, ens.leaf_columns    # the module's, as it is
        spans, cut = choose_select_spans(ens, W)
        halved = cut.copy is not None
        deepest = len(_levels(ens))     # the levels that hold a node
        first = np.concatenate([[0], np.cumsum(cut.n_subtrees)])
        S = int(first[-1])
        exit_lanes, link0 = exit_table_lanes(C, cut.n_subtrees)
        # the cut's slots: the nodes, and behind them the entries' glue
        t_idx, n_idx = cut.tree, cut.origin
        own = np.arange(len(t_idx)) < ens.n_splits
        at = first[t_idx] + cut.subtree         # the entry
        ln = cut.lane
        # the lanes that ask a node's question: its own, a glue copy's,
        # and (halved) a spine copy's in the second half; a copy hangs no
        # exit
        asks = [(t_idx, n_idx, at, ln)]
        if halved:
            c = np.nonzero(cut.copy)[0]
            asks.append((t_idx[c], n_idx[c], at[c], cut.copy[c]))
        sel = np.zeros((S, Fp, W), bf16)
        planes = np.zeros((S, 8, W), np.float32)
        planes[:, 0, :] = 2.0 ** 30
        planes[:, 1, :] = -1.0
        planes[first[:-1], 4, :] = 1.0
        if ens.missing_routes:
            planes[:, 3, :] = 2.0 ** 30
        for t, n, entry, lane in asks:
            sel[entry, ens.feature[t, n], lane] = 1.0
            planes[entry, 0, lane] = ens.threshold_bin[t, n]
            if ens.missing_routes:
                left = ens.default_left[t, n]
                planes[entry[left], 3, lane[left]] = ens.missing_bin_value
        # The exits: every child of a node that is a leaf or lies in another
        # entry, in the order of (entry, the node's lane, left before
        # right); halved, a run a half: the second half's nodes' exits from
        # lane 128.
        child = np.stack([ens.left_child[t_idx, n_idx],
                          ens.right_child[t_idx, n_idx]], 1).astype(np.int64)
        node0 = _first_nodes(ens)[t_idx]        # the slot of its tree's
        linked = (child >= 0) & (cut.subtree[
            node0[:, None] + np.maximum(child, 0)] != cut.subtree[:, None])
        is_exit = ((child < 0) | linked) & own[:, None]
        order = np.lexsort((ln, at))
        # [nodes, 2] in that order; an exit's lane its rank in the entry
        e_node, e_side = np.nonzero(is_exit[order])
        e_node = order[e_node]
        e_at = at[e_node]
        e_half = halved & (ln[e_node] >= PATH_LANES)
        run = 2 * e_at + e_half
        e_lane = np.arange(len(e_at)) - np.searchsorted(
            run, np.arange(2 * S))[run] + PATH_LANES * e_half
        e_child = child[e_node, e_side]
        e_tree = t_idx[e_node]
        leaves = np.zeros((S, W, exit_lanes), bf16)

        def put_leaves(entry, exit_lane, trees, leaf):
            """Leaf `leaf[i]` of tree `trees[i]` into row `exit_lane[i]`
            of entry `entry[i]`: a vector's pieces in all C columns, a
            scalar's in ONE, the class's of a softmax tree (t % C; the
            others keep their exact zeros)."""
            values = ens.leaf_value[trees, leaf]
            if ens.vector_leaves:
                for p, piece in enumerate(split_bfloat16(values)):
                    leaves[entry, exit_lane, p * C:(p + 1) * C] = piece
                return
            for p, piece in enumerate(split_bfloat16(values)):
                leaves[entry, exit_lane, p * C + trees % C] = piece

        real = e_child < 0
        put_leaves(e_at[real], e_lane[real], e_tree[real], ~e_child[real])
        to = first[e_tree[~real]] + cut.subtree[
            node0[e_node[~real]] + e_child[~real]]
        leaves[e_at[~real], e_lane[~real],
               link0 + (to - e_at[~real] - 1)] = 1.0
        # A tree of one leaf: an entry of no node whose exit 0 has a path
        # of no node (every row reaches it).
        lone = np.nonzero(ens.n_leaves == 1)[0]
        planes[first[lone], 1, 0] = 0.0
        put_leaves(first[lone], 0, lone, np.zeros(len(lone), np.int64))
        # An exit's path inside its entry: up from the node it hangs on
        # to the entry's top (through the glue copies above its piece),
        # all exits a step.
        # (written as bfloat16's bits: +1 is 0x3F80 and -1 0xBF80; a cast
        # of 132M int8 entries to bfloat16 took 10 s of a 12 s build)
        # Halved, the two diagonal [128, 128] blocks alone, side by side:
        # row n of an exit's column is node lane n of the exit's own half
        # (a second half's exit reads its first-half ancestors' COPIES).
        paths = np.zeros((S, W // 2 if halved else W, W), np.uint16)
        plen = np.zeros((S, W), np.float32)
        cur, ex = e_node, np.arange(len(e_at))
        sign = np.where(e_side == 0, -1, 1).astype(np.int8)
        while len(cur):
            lane = cut.lane[cur]
            if halved:
                lane = np.where(e_half[ex] & (lane < PATH_LANES),
                                cut.copy[cur], lane)
                if ((lane >= PATH_LANES) != e_half[ex]).any():
                    raise ValueError("halved sub-trees: an exit's path "
                                     "leaves the exit's half")
                lane = lane % PATH_LANES
            paths[e_at[ex], lane, e_lane[ex]] = np.where(
                sign > 0, 0x3F80, 0xBF80)
            plen[e_at[ex], e_lane[ex]] += 1.0     # (an exit once a step)
            sign, cur = cut.side[cur], cut.up[cur]
            up = cur >= 0
            cur, ex, sign = cur[up], ex[up], sign[up]
        planes[e_at, 1, e_lane] = plen[e_at, e_lane]
        return CompiledNodeList(
            token=ens.cache_token(),
            learning_rate=float(ens.learning_rate),
            base_score=float(ens.base_score), loss=ens.loss,
            n_trees=T, lanes=W, deepest_leaf=deepest,
            sel=sel, planes=planes, paths=paths.view(bf16),
            missing_bin_value=ens.missing_bin_value, leaves=leaves,
            n_subtrees=S, leaf_columns=C, mean=ens.vector_leaves,
            widest_tree=widest, select_spans=spans,
            subtrees_max=int(cut.n_subtrees.max()),
            single_subtree_trees=int((cut.n_subtrees == 1).sum()),
            spine_copies=int(np.count_nonzero(cut.copy)) if halved else 0,
            pieces=int(np.count_nonzero(cut.root)) + int(
                (ens.n_leaves == 1).sum()),
            glue_copies=int(np.count_nonzero(~own)))


# ---------------------------------------------------------------------- #
# The OBLIVIOUS ensemble: the third ensemble layout
# ---------------------------------------------------------------------- #

# Trees a group of the compiled oblivious tables holds: one lane each.
OBLIVIOUS_GROUP = 128
# A split no bin passes (uint8 bins end at 255): the filler of a tree
# shallower than the ensemble's depth, its bit never set.
OBLIVIOUS_NEVER = 255


@dataclasses.dataclass
class ObliviousEnsemble:
    """A boosted ensemble of OBLIVIOUS (symmetric) trees, CatBoost's own:
    every node of a level asks the same question, so tree t of depth D is D
    splits and 2^D leaf values, and a row's leaf is a D-bit number,

        idx_t(x) = sum_d [bin(x)[split_feature[t, d]] > split_bin[t, d]] << d

    (the FIRST split is the LOW bit; raw rows: `x > split_raw[t, d]`), and

        score(x) = scale * sum_t leaf_value[t, idx_t(x)] + bias.

    VECTOR LEAVES (the library's `MultiClass` objective: `loss` "softmax",
    `leaf_value` [T, 2^D, C], `bias` [C]): a leaf holds one value a class,

        m_c(x) = scale * sum_t leaf_value[t, idx_t(x), c] + bias[c]

    the margins [rows, C], and the answer their softmax. One index a (row,
    tree) whatever C: the splits are the tree's, not a class's.

    `bin > split_bin` sets the bit where the heap's `bin <= threshold_bin`
    goes left: the repository's one split rule. No missing-value route and
    no category split: the import (`models/catboost_io.py`) refuses a model
    that needs another by name.
    A tree shallower than D carries never-true splits in its HIGH bits
    (`OBLIVIOUS_NEVER`, raw +inf) and zeros in the leaves they would reach.
    The trainer writes heaps; an oblivious ensemble is an import or
    hand-built. `to_heap` / `to_node_list` expand it for the tests' sake
    (63 nodes where this layout holds 6 splits, and C heap trees for a tree
    of vector leaves): no scoring path does."""

    split_feature: np.ndarray  # int32  [T, D]
    split_bin: np.ndarray      # int32  [T, D] bit d set where bin > this
    leaf_value: np.ndarray     # float32 [T, 2^D]; vector leaves [T, 2^D, C]
    n_features: int
    scale: float = 1.0
    bias: "float | np.ndarray" = 0.0   # vector leaves: float64 [C]
    loss: str = "logloss"      # logloss | mse | softmax (vector leaves)
    n_bins: int = 0
    split_raw: np.ndarray | None = None    # float32 [T, D] raw borders
    # float32 [F, n_bins - 1], +inf past a feature's last border: the
    # model's own border lists, whose ranks `split_bin` holds (an import's;
    # `bin_mapper` bins raw rows by them).
    borders: np.ndarray | None = None
    # The classes C of vector leaves (0: read from `leaf_value`); 2 for the
    # one-column losses, as the other layouts answer.
    n_classes: int = 0

    # What the other layouts answer for, so that scoring entry points ask
    # one question of any of the three.
    has_cat_splits = False
    cat_features = None
    missing_bin = False
    default_left = None
    has_bin_thresholds = True

    def __post_init__(self):
        T, D = self.split_feature.shape
        vector = self.loss == "softmax"
        C = self.leaf_value.shape[2] if self.leaf_value.ndim == 3 else 0
        leaves = (T, 1 << D) + (C,) * vector
        if (self.split_bin.shape != (T, D) or D < 1
                or self.leaf_value.shape != leaves
                or vector and (C < 2 or self.n_classes not in (0, C))):
            raise ValueError(
                f"an oblivious ensemble of {T} trees x depth {D} needs "
                f"split_bin [{T}, {D}] and leaf_value [{T}, {1 << D}] (loss "
                f"\"softmax\": vector leaves [{T}, {1 << D}, C] of C >= 2 "
                f"= n_classes values), got {self.split_bin.shape} and "
                f"{self.leaf_value.shape} with loss {self.loss!r} and "
                f"n_classes {self.n_classes}")
        bias = np.asarray(self.bias, np.float64)
        if bias.size != 1 and not (vector and bias.shape == (C,)):
            raise ValueError(
                "an oblivious ensemble's bias is a scalar"
                + f" or, of {C} classes, [{C}]" * vector
                + f", got {bias.shape}")
        self.bias = (np.broadcast_to(bias.reshape(-1), (C,)).copy() if vector
                     else float(bias.reshape(-1)[0]))
        self.n_classes = C if vector else 2
        if T and not (0 <= int(self.split_feature.min())
                      and int(self.split_feature.max()) < self.n_features):
            raise ValueError("a split's feature lies outside 0 .. "
                             f"{self.n_features - 1}")

    @property
    def leaf_columns(self) -> int:
        """Values a leaf holds: C of vector leaves, else 1."""
        return self.n_classes if self.loss == "softmax" else 1

    @property
    def n_trees(self) -> int:
        return int(self.split_feature.shape[0])

    @property
    def depth(self) -> int:
        return int(self.split_feature.shape[1])

    @property
    def n_nodes(self) -> int:
        """The questions this layout holds: one a level of a tree."""
        return self.n_trees * self.depth

    max_depth = depth              # what `cli inspect` prints of a heap

    @property
    def has_raw_thresholds(self) -> bool:
        return self.split_raw is not None

    @property
    def learning_rate(self) -> float:
        return self.scale

    @property
    def base_score(self):
        """The bias as the other layouts name it (what `cli inspect`
        prints): a float, or a list of C."""
        return self.bias.tolist() if self.leaf_columns > 1 else self.bias

    @property
    def live_splits(self) -> np.ndarray:
        """bool [T, D]: the splits a bin can pass (not a shallow tree's
        filler)."""
        return self.split_bin < OBLIVIOUS_NEVER

    @property
    def n_splits(self) -> int:
        return int(self.live_splits.sum())

    def cache_token(self) -> str:
        """Content digest of what the device scoring program depends on
        (the compiled-ensemble cache key, as `TreeEnsemble.cache_token`)."""
        h = hashlib.sha1(b"oblivious")
        for a in (self.split_feature, self.split_bin, self.leaf_value):
            h.update(np.ascontiguousarray(a).tobytes())
        h.update(repr((self.scale, self.base_score, self.loss,
                       self.n_features)).encode())
        if self.leaf_columns > 1:       # [T, 2^D, C]: C is in the shape alone
            h.update(repr(self.leaf_value.shape).encode())
        return h.hexdigest()

    layout = "oblivious"       # (ops/predict.LAYOUTS)

    def compile(self, tree_chunk: int = 64) -> "CompiledOblivious":
        """Host-side compiled scoring tables (see CompiledOblivious;
        `tree_chunk` is the heap layout's and means nothing here)."""
        return CompiledOblivious.build(self)

    # ------------------------------------------------------------------ #

    def _leaf_np(self, X: np.ndarray, binned: bool) -> np.ndarray:
        """Leaf index per (tree, row), int64 [T, R]: the bit walk."""
        if not binned and not self.has_raw_thresholds:
            raise ValueError(
                "Ensemble has no raw-value thresholds; predict on binned "
                "data with binned=True")
        thr = self.split_bin if binned else self.split_raw
        Xc = X.astype(np.int32) if binned else X.astype(np.float32)
        idx = np.zeros((self.n_trees, X.shape[0]), np.int64)
        for d in range(self.depth):
            fv = Xc[:, self.split_feature[:, d]].T              # [T, R]
            idx |= (fv > thr[:, d:d + 1]).astype(np.int64) << d
        return idx

    def predict_raw(self, X: np.ndarray, binned: bool = False) -> np.ndarray:
        """Raw (margin) scores, float32 [R] ([R, C] of vector leaves); the
        rows in blocks, so that the [T, rows] index of a block (and the
        [T, rows, C] values it picks) stays near 128 MB."""
        X = np.asarray(X)
        C = self.leaf_columns
        block = max(1, (1 << 24) // max(1, self.n_trees * C))
        total = np.empty((X.shape[0],) + (C,) * (C > 1), np.float32)
        trees = np.arange(self.n_trees)[:, None]
        for i in range(0, X.shape[0], block):
            leaf = self._leaf_np(X[i:i + block], binned)
            total[i:i + block] = self.leaf_value[trees, leaf].sum(axis=0)
        return (self.bias + np.float32(self.scale) * total
                ).astype(np.float32)

    def predict(self, X: np.ndarray, binned: bool = False) -> np.ndarray:
        """Probability predictions (or raw values for mse)."""
        from ddt_tpu.utils.metrics import predict_proba_np

        return predict_proba_np(self.predict_raw(X, binned=binned),
                                self.loss)

    def bin_mapper(self):
        """The BinMapper of the model's own border lists (`borders`): a
        row's bin is the number of its feature's borders below the value,
        so `x > border_k` is `bin(x) > k` EXACTLY (the argument of
        `lightgbm_io.threshold_bin_mapper`)."""
        from ddt_tpu.data.quantizer import BinMapper

        if self.borders is None:
            raise ValueError("this oblivious ensemble carries no borders")
        return BinMapper(edges=self.borders, n_bins=self.n_bins)

    # ------------------------------------------------------------------ #

    def to_heap(self) -> TreeEnsemble:
        """The same trees as full heaps of depth D (2^D - 1 nodes where
        this layout holds D splits): a TEST ORACLE, so that the heap
        scorers give a second opinion. The heap's root decides the leaf
        position's HIGH bit, so level l asks split D - 1 - l. A tree of
        VECTOR leaves becomes C heap trees, round-major (heap tree t C + c
        is class c's: the same splits, column c of the leaves), and the
        bias vector, which a heap has no field for, one more round of
        trees that are a root leaf each, `bias[c] / scale` (a float32
        rounding where the scale is not 1)."""
        T, D = self.split_feature.shape
        C = self.leaf_columns
        ens = empty_ensemble(
            (T + (C > 1)) * C, D, self.n_features, self.scale,
            self.bias if C == 1 else 0.0, self.loss, n_classes=self.n_classes,
            n_bins=self.n_bins)
        raw = self.split_raw if self.has_raw_thresholds else np.zeros(
            (T, D), np.float32)
        # (a tree's splits, once a class: [T, D] -> [T C, D])
        feature, border, raw = (np.repeat(a, C, axis=0) for a in (
            self.split_feature, self.split_bin, raw))
        for level in range(D):
            lo, hi = (1 << level) - 1, (1 << (level + 1)) - 1
            d = D - 1 - level
            ens.feature[:T * C, lo:hi] = feature[:, d:d + 1]
            ens.threshold_bin[:T * C, lo:hi] = border[:, d:d + 1]
            ens.threshold_raw[:T * C, lo:hi] = raw[:, d:d + 1]
        ens.is_leaf[:T * C, (1 << D) - 1:] = True
        ens.leaf_value[:T * C, (1 << D) - 1:] = self.leaf_value.reshape(
            T, 1 << D, C).transpose(0, 2, 1).reshape(T * C, 1 << D)
        if C > 1:
            ens.is_leaf[T * C:, 0] = True
            ens.leaf_value[T * C:, 0] = self.bias / self.scale
        ens.has_raw_thresholds = self.has_raw_thresholds
        return ens

    def to_node_list(self) -> "NodeListEnsemble":
        """`to_heap()` as a node list: the third opinion, a test oracle."""
        return NodeListEnsemble.from_heap(self.to_heap())

    def feature_importances(self, kind: str = "split") -> np.ndarray:
        """As `TreeEnsemble.feature_importances`, over the live splits; no
        gain is recorded in this layout, so "gain" is all zeros (what a
        heap saved before gains were recorded answers: `cli inspect` falls
        back to the split counts)."""
        if kind not in ("split", "gain"):
            raise ValueError(f"unknown importance kind {kind!r}")
        counts = np.bincount(self.split_feature[self.live_splits],
                             minlength=self.n_features).astype(np.float64)
        tot = counts.sum()
        return (counts / tot if tot > 0 and kind == "split"
                else counts * 0).astype(np.float32)

    def dump_text(self, tree: int) -> str:
        """One tree as text: its splits, low bit first, and its leaves."""
        t = int(tree)
        lines = []
        for d in range(self.depth):
            raw = (f" (> {self.split_raw[t, d]:.6g})"
                   if self.has_raw_thresholds else "")
            lines.append(f"bit {d}: f{self.split_feature[t, d]} > bin "
                         f"{self.split_bin[t, d]}{raw}")
        if self.leaf_columns > 1:
            lines += [f"leaf {i}: " + " ".join(f"{v:+.6f}" for v in vec)
                      for i, vec in enumerate(self.leaf_value[t])]
        else:
            lines.append("leaves: " + " ".join(
                f"{v:+.6f}" for v in self.leaf_value[t]))
        return "\n".join(lines)

    def to_dict(self) -> dict:
        d = dict(
            layout=np.bytes_(b"oblivious"),
            split_feature=self.split_feature, split_bin=self.split_bin,
            leaf_value=self.leaf_value,
            n_features=np.int64(self.n_features),
            scale=np.float64(self.scale),
            bias=np.asarray(self.bias, np.float64),     # 0-d, or [C]
            loss=np.bytes_(self.loss.encode()),
            n_bins=np.int64(self.n_bins))
        if self.split_raw is not None:
            d["split_raw"] = self.split_raw
        if self.borders is not None:
            d["borders"] = self.borders
        return d

    @staticmethod
    def from_dict(d: dict) -> "ObliviousEnsemble":
        return ObliviousEnsemble(
            split_feature=np.asarray(d["split_feature"], np.int32),
            split_bin=np.asarray(d["split_bin"], np.int32),
            leaf_value=np.asarray(d["leaf_value"], np.float32),
            n_features=int(d["n_features"]), scale=float(d["scale"]),
            bias=np.asarray(d["bias"], np.float64),
            loss=bytes(d["loss"]).decode(),
            n_bins=int(d["n_bins"]),
            split_raw=(np.asarray(d["split_raw"], np.float32)
                       if "split_raw" in d else None),
            borders=(np.asarray(d["borders"], np.float32)
                     if "borders" in d else None))

    save = TreeEnsemble.save       # to_dict, the manifest, one atomic npz


def random_oblivious(rng, n_trees: int, depth: int, n_features: int,
                     n_bins: int = 255, dyadic: bool = False,
                     n_classes: int = 0, **meta) -> ObliviousEnsemble:
    """A random oblivious ensemble for tests, chip_smoke.py and the compile
    check: features uniform, borders uniform over the ranks 0 .. n_bins-2,
    leaf values N(0, 1), or eighths in -2..2 (`dyadic`: sums of them round
    nowhere). `n_classes` C >= 2: vector leaves, `loss` "softmax"."""
    shape = (n_trees, 1 << depth) + ((n_classes,) if n_classes else ())
    if n_classes:
        meta = {"loss": "softmax", **meta}
    leaves = (rng.integers(-16, 17, shape) / 8.0 if dyadic
              else rng.standard_normal(shape))
    return ObliviousEnsemble(
        split_feature=rng.integers(0, n_features, (n_trees, depth),
                                   dtype=np.int32),
        split_bin=rng.integers(0, n_bins - 1, (n_trees, depth),
                               dtype=np.int32),
        leaf_value=leaves.astype(np.float32), n_features=n_features,
        n_bins=n_bins, **meta)


@dataclasses.dataclass(frozen=True)
class CompiledOblivious:
    """An oblivious model's BINNED scoring tables (ops/predict_oblivious.py
    has the equations): the trees in groups of `OBLIVIOUS_GROUP`, a tree a
    lane, a split a lane tile,

        sel  [G, D, Fp, 128] bf16  one-hot of split d's feature, a tree a
                                   lane (Fp: F up to whole bf16 sublane
                                   tiles of 16)
        thr  [G, Dp, 128]    f32   split d's bin (Dp: D up to whole
                                   sublane tiles of 8)
        leaf [G, C 2^D, 128] f32   the leaf values, leaf i of a tree in
                                   row i of its lane; of vector leaves
                                   class c's in rows c 2^D .. (c + 1) 2^D

    `bias` is a float, or a tuple of C (hashable: the scoring program
    takes it as a static argument). A lane past the last tree holds no
    one-hot, the threshold +BIG (its bits never set) and leaves of 0: it
    adds 0. Built ONCE per model version on the host; backends keep them
    device-resident under `token` (the same cache as the other
    layouts')."""

    token: str
    scale: float
    bias: "float | tuple"
    loss: str
    n_trees: int
    depth: int
    sel: np.ndarray
    thr: np.ndarray
    leaf: np.ndarray
    n_classes_out: int = 1     # C of vector leaves: the answer is [rows, C]

    layout = ObliviousEnsemble.layout

    def arrays(self) -> tuple:
        return (self.sel, self.thr, self.leaf)

    @staticmethod
    def build(ens: ObliviousEnsemble) -> "CompiledOblivious":
        import ml_dtypes

        T, D = ens.split_feature.shape
        G = max(1, -(-T // OBLIVIOUS_GROUP))
        Fp = -(-ens.n_features // 16) * 16
        pad = G * OBLIVIOUS_GROUP - T
        sel = np.zeros((G, D, Fp, OBLIVIOUS_GROUP), ml_dtypes.bfloat16)
        g, lane = np.divmod(np.arange(T), OBLIVIOUS_GROUP)
        for d in range(D):
            sel[g, d, ens.split_feature[:, d], lane] = 1.0
        thr = np.full((G * OBLIVIOUS_GROUP, -(-D // 8) * 8), 2.0 ** 30,
                      np.float32)
        thr[:T, :D] = ens.split_bin
        C = ens.leaf_columns
        leaf = np.pad(ens.leaf_value.astype(np.float32).reshape(T, 1 << D, C),
                      ((0, pad), (0, 0), (0, 0)))
        return CompiledOblivious(
            token=ens.cache_token(), scale=float(ens.scale),
            bias=float(ens.bias) if C == 1 else tuple(ens.bias.tolist()),
            loss=ens.loss, n_trees=T, depth=D, sel=sel,
            thr=np.ascontiguousarray(
                thr.reshape(G, OBLIVIOUS_GROUP, -1).transpose(0, 2, 1)),
            # [G, lane, leaf, class] -> [G, class, leaf, lane]
            leaf=np.ascontiguousarray(
                leaf.reshape(G, OBLIVIOUS_GROUP, 1 << D, C).transpose(
                    0, 3, 2, 1)).reshape(G, C << D, OBLIVIOUS_GROUP),
            n_classes_out=C)


def empty_ensemble(
    n_trees: int,
    max_depth: int,
    n_features: int,
    learning_rate: float,
    base_score: float,
    loss: str,
    n_classes: int = 2,
    missing_bin: bool = False,
    n_bins: int = 0,
    cat_features: tuple = (),
) -> TreeEnsemble:
    n_nodes = 2 ** (max_depth + 1) - 1
    return TreeEnsemble(
        feature=np.full((n_trees, n_nodes), -1, np.int32),
        threshold_bin=np.zeros((n_trees, n_nodes), np.int32),
        threshold_raw=np.zeros((n_trees, n_nodes), np.float32),
        is_leaf=np.zeros((n_trees, n_nodes), bool),
        leaf_value=np.zeros((n_trees, n_nodes), np.float32),
        split_gain=np.zeros((n_trees, n_nodes), np.float32),
        default_left=np.zeros((n_trees, n_nodes), bool),
        max_depth=max_depth,
        n_features=n_features,
        learning_rate=learning_rate,
        base_score=base_score,
        loss=loss,
        n_classes=n_classes,
        missing_bin=missing_bin,
        n_bins=n_bins,
        cat_features=(np.asarray(cat_features, np.int32)
                      if cat_features else None),
    )


# A saved ensemble's `layout` key (every `to_dict` writes it) -> its class.
LAYOUTS = {"heap": TreeEnsemble, "node_list": NodeListEnsemble,
           "oblivious": ObliviousEnsemble}

"""Quantile binning: float features -> uint8 bin indices (<=255 bins).

Layer L7 of SURVEY.md §1: the reference runs an offline quantizer producing
<=255-bin binned matrices before training ([BASELINE] "features are quantized
into bins (255 bins named explicitly)"). TPU realisation: a NumPy/JAX quantile
sketch on a row sample, then `searchsorted` to produce a uint8 matrix that is
the only large tensor ever shipped to the device.

Bin semantics (shared by every kernel in this repo — oracle, XLA, Pallas, C++):
  bin b covers values v with  edges[b-1] < v <= edges[b]   (edges ascending)
  i.e. bin = searchsorted(edges, v, side='left') clipped to [0, n_bins-1].
A split "(feature f, threshold bin t)" routes rows with bin <= t LEFT.
The raw-value threshold equivalent is edges[t] (go left iff v <= edges[t]).

NaN policy (cfg.missing_policy): "zero" maps NaN to bin 0 (the v1 policy);
"learn" reserves the TOP bin (n_bins-1) for NaN and every split learns a
default direction for it (ops/split.py, reference/numpy_trainer.py) — the
standard histogram-GBDT missing-value treatment.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class BinMapper:
    """Per-feature bin edges + the binned-matrix transform.

    With `missing_bin=True` (cfg.missing_policy="learn") the TOP bin
    (n_bins-1) is reserved for NaN: real values occupy bins 0..n_bins-2 and
    every split learns a default direction for bin n_bins-1 downstream."""

    edges: np.ndarray       # [n_features, n_bins-1] float32, ascending per row
    n_bins: int
    missing_bin: bool = False
    # Columns fitted with IDENTITY edges (values are category/bin ids, never
    # quantile-merged). Recorded so train/predict can verify that a model's
    # cat_features were identity-binned by THIS mapper — a mapper fitted
    # without them would silently merge/permute category ids (failing loudly
    # beats silently, same as the missing_bin guard).
    cat_features: tuple = ()
    # Per-feature REFERENCE bin histogram of the training matrix (ISSUE 19,
    # the drift observatory's baseline): int64 [n_features, n_bins] raw
    # counts, attached by api.train after binning (None when never
    # captured — binned=True training has no mapper-visible matrix, and
    # every pre-drift artifact loads with None). Raw counts, not
    # normalized: the sample size stays visible and the divergence
    # scorer (serve/drift.py) owns the epsilon smoothing. The mapper
    # owns the bin space, so it owns the reference distribution too —
    # save()/load() round-trip it through the same `mapper_*` npz
    # channel as every other field.
    ref_counts: "np.ndarray | None" = None
    # CATEGORY-SET columns (a LightGBM model's categorical features, made
    # by models/lightgbm_io.threshold_bin_mapper; PR 55): column ->
    # (ids, nan_as_zero). `ids` are the raw category ids the model's sets
    # name, ascending: id ids[i] takes bin i, and EVERY other value (an id
    # the model never names, a negative value, NaN where `nan_as_zero` is
    # False) the one bin len(ids) that no set holds. A value is truncated
    # toward zero first, as LightGBM's `static_cast<int>`; `nan_as_zero`:
    # NaN counts as id 0 (the library's missing types None and Zero). The
    # column's `edges` are not read.
    category_ids: "dict | None" = None

    @property
    def n_features(self) -> int:
        return self.edges.shape[0]

    @property
    def n_value_bins(self) -> int:
        """Bins available to real values (excludes the reserved NaN bin)."""
        return self.n_bins - 1 if self.missing_bin else self.n_bins

    def non_identity_columns(self, features) -> list[int]:
        """Subset of `features` whose edges do NOT identity-map integer bin
        ids (i.e. were quantile-fitted, so category ids would be merged or
        permuted by transform). Checks the edges themselves rather than the
        recorded `cat_features` metadata, so mappers saved before that field
        existed — or hand-built ones — are judged by the invariant that
        actually matters.

        Memoized per feature tuple: api.predict runs this check on EVERY
        call (scoring correctness must not depend on call history), but
        the edge scan is O(cat_features x bins) against edges that never
        mutate after fit — paying it once per (mapper, feature-set) keeps
        the serving request path's prologue flat (ISSUE 8 satellite).
        Mutating `edges` in place after fit voids the memo (and every
        other consistency property of a fitted mapper)."""
        key = tuple(sorted(int(f) for f in features))
        cache = self.__dict__.setdefault("_non_identity_memo", {})
        if key in cache:
            return list(cache[key])
        bad = sorted(f for f in key if not 0 <= f < self.n_features)
        if bad:
            raise ValueError(
                f"cat_features indices {bad} out of range for "
                f"{self.n_features} features"
            )
        nv = self.n_value_bins
        want = np.arange(nv - 1, dtype=np.float32)
        out = sorted(
            f for f in key
            if not np.array_equal(self.edges[f, : nv - 1], want)
        )
        cache[key] = tuple(out)
        return out

    def transform(self, X: np.ndarray) -> np.ndarray:
        """Bin a float matrix [rows, n_features] -> uint8 [rows, n_features]."""
        X = np.asarray(X, dtype=np.float32)
        if X.ndim != 2 or X.shape[1] != self.n_features:
            raise ValueError(
                f"X must be [rows, {self.n_features}], got {X.shape}"
            )
        out = np.empty(X.shape, dtype=np.uint8)
        nv = self.n_value_bins
        for f in range(self.n_features):
            col = X[:, f]
            if self.category_ids and f in self.category_ids:
                out[:, f] = self._category_bins(f, col)
                continue
            binned = np.searchsorted(self.edges[f, : nv - 1], col,
                                     side="left")
            np.clip(binned, 0, nv - 1, out=binned)
            # NaN policy: reserved top bin under missing_bin, else bin 0
            # (v1 policy, module doc). +/-inf fall naturally into the
            # top/bottom VALUE bin via searchsorted.
            binned[np.isnan(col)] = self.n_bins - 1 if self.missing_bin else 0
            out[:, f] = binned.astype(np.uint8)
        return out

    def _category_bins(self, f: int, col: np.ndarray) -> np.ndarray:
        """Bins of a category-set column (`category_ids`)."""
        ids, nan_as_zero = self.category_ids[f]
        nan = np.isnan(col)
        v = np.trunc(np.where(nan, 0.0 if nan_as_zero else -1.0,
                              np.clip(col, -1.0, 2.0 ** 31 - 1))
                     ).astype(np.int64)
        at = np.minimum(np.searchsorted(ids, v), max(len(ids) - 1, 0))
        named = (ids[at] == v) if len(ids) else np.zeros(len(v), bool)
        return np.where(named, at, len(ids)).astype(np.uint8)

    def transform_device(self, X: np.ndarray) -> np.ndarray:
        """transform() on the default JAX device (ops/quantize.py) —
        bit-identical output. Worth it when the float matrix is already
        on (or headed to) the device; otherwise the f32 upload (4x the
        uint8 result) is the cost to beat (on the chip: not
        measured)."""
        from ddt_tpu.ops.quantize import transform_device

        if self.category_ids:       # a table lookup, not an edge search
            return self.transform(X)
        return transform_device(self, X)

    def threshold_value(self, feature: int, threshold_bin: int) -> float:
        """Raw-value threshold for a (feature, bin) split: go left iff v <= it."""
        t = int(threshold_bin)
        if t >= self.n_value_bins - 1:
            return float("inf")  # rightmost value bin: every value goes left
        return float(self.edges[feature, t])

    def save(self) -> dict:
        d = {"edges": self.edges, "n_bins": np.int64(self.n_bins),
             "missing_bin": np.bool_(self.missing_bin),
             "cat_features": np.asarray(self.cat_features, np.int32)}
        if self.ref_counts is not None:
            d["ref_counts"] = np.asarray(self.ref_counts, np.int64)
        if self.category_ids:
            cols = sorted(self.category_ids)
            d["category_columns"] = np.asarray(cols, np.int32)
            d["category_nan_as_zero"] = np.asarray(
                [self.category_ids[c][1] for c in cols], bool)
            d["category_counts"] = np.asarray(
                [len(self.category_ids[c][0]) for c in cols], np.int64)
            d["category_values"] = np.concatenate(
                [np.asarray(self.category_ids[c][0], np.int64)
                 for c in cols])
        return d

    @staticmethod
    def load(d: dict) -> "BinMapper":
        ref = d.get("ref_counts")
        ids = None
        if "category_columns" in d:
            runs = np.split(np.asarray(d["category_values"], np.int64),
                            np.cumsum(d["category_counts"])[:-1])
            ids = {int(c): (run, bool(z)) for c, run, z in zip(
                d["category_columns"], runs, d["category_nan_as_zero"])}
        return BinMapper(category_ids=ids,
                         edges=np.asarray(d["edges"], np.float32),
                         n_bins=int(d["n_bins"]),
                         missing_bin=bool(d.get("missing_bin", False)),
                         cat_features=tuple(
                             int(f) for f in d.get("cat_features", ())),
                         ref_counts=(None if ref is None
                                     else np.asarray(ref, np.int64)))


def feature_bincounts(Xb: np.ndarray, n_bins: int) -> np.ndarray:
    """Per-feature bin histogram of a binned uint8 matrix: [rows, F] ->
    int64 [F, n_bins] counts. The ONE bincount home shared by the
    training-time reference capture (api.train -> mapper.ref_counts) and
    the serve-side online accumulator (serve/drift.py), so the two sides
    of a PSI comparison count bins identically. Vectorized: one flat
    bincount over feature-offset codes, no per-feature Python loop."""
    Xb = np.asarray(Xb)
    if Xb.ndim != 2:
        raise ValueError(f"Xb must be [rows, features], got {Xb.shape}")
    n_f = Xb.shape[1]
    flat = (np.arange(n_f, dtype=np.intp)[None, :] * n_bins
            + Xb.astype(np.intp, copy=False)).ravel()
    return np.bincount(flat, minlength=n_f * n_bins).reshape(n_f, n_bins)


def fit_bin_mapper(
    X: np.ndarray,
    n_bins: int = 255,
    max_sample: int = 200_000,
    seed: int = 0,
    missing_policy: str = "zero",
    cat_features: tuple = (),
) -> BinMapper:
    """Fit per-feature quantile bin edges on (a sample of) X.

    Edges are non-decreasing per feature (np.maximum.accumulate). Duplicate
    edge values form runs that searchsorted(side='left') always resolves to
    the first edge of the run, so the corresponding higher bins are simply
    never assigned — constant / low-cardinality features occupy few distinct
    bins, matching histogram-GBDT convention. Backends must not assume
    strictly increasing edges.
    """
    X = np.asarray(X, dtype=np.float32)
    rows, n_features = X.shape
    if rows > max_sample:
        rng = np.random.default_rng(seed)
        idx = rng.choice(rows, size=max_sample, replace=False)
        Xs = X[idx]
    else:
        Xs = X

    missing = missing_policy == "learn"
    if missing and n_bins < 3:
        raise ValueError("missing_policy='learn' needs n_bins >= 3")
    # Under the reserved-NaN-bin policy real values get n_bins-1 bins, so
    # they need n_bins-2 interior edges; the edges array keeps its
    # [n_features, n_bins-1] width (trailing column unused = +inf) so the
    # serialized layout is policy-independent.
    n_val = n_bins - 1 if missing else n_bins
    qs = np.linspace(0.0, 1.0, n_val + 1)[1:-1]   # n_val-1 interior quantiles
    edges = np.full((n_features, n_bins - 1), np.float32(np.inf))
    cat = set(int(f) for f in cat_features)
    for f in range(n_features):
        if f in cat:
            # Categorical column: values ARE bin ids (CategoricalEncoder
            # output) — identity edges so quantile re-binning cannot merge
            # or permute categories. Bin b covers (edges[b-1], edges[b]]
            # under searchsorted(side='left'), so edges [0, 1, ..] map
            # integer v to bin v exactly.
            edges[f, : n_val - 1] = np.arange(n_val - 1, dtype=np.float32)
            continue
        col = Xs[:, f]
        col = col[np.isfinite(col)]
        if col.size == 0:
            edges[f, : n_val - 1] = np.arange(n_val - 1, dtype=np.float32)
            continue
        e = np.quantile(col, qs).astype(np.float32)
        # Force strict monotonicity: collapse duplicates upward by epsilon-free
        # padding — duplicates become a run that searchsorted('left') resolves
        # to the first edge, so dup bins are simply never assigned.
        e = np.maximum.accumulate(e)
        edges[f, : n_val - 1] = e
    return BinMapper(edges=edges, n_bins=n_bins, missing_bin=missing,
                     cat_features=tuple(sorted(cat)))


def quantize(
    X: np.ndarray, n_bins: int = 255, max_sample: int = 200_000,
    seed: int = 0, missing_policy: str = "zero",
) -> tuple[np.ndarray, BinMapper]:
    """fit + transform convenience: returns (binned uint8 matrix, mapper)."""
    mapper = fit_bin_mapper(X, n_bins=n_bins, max_sample=max_sample,
                            seed=seed, missing_policy=missing_policy)
    return mapper.transform(X), mapper


def fit_bin_mapper_streaming(
    chunk_fn,
    n_chunks: int,
    n_bins: int = 255,
    max_sample: int = 200_000,
    seed: int = 0,
    missing_policy: str = "zero",
    cat_features: tuple = (),
) -> BinMapper:
    """Fit bin edges from STREAMED raw-float chunks (the 10B-row config's
    L7 story: no full matrix ever materialises). A priority-based
    reservoir keeps a uniform `max_sample`-row subsample across chunks —
    each row draws a U(0,1) priority from a per-(seed, chunk) generator
    and the globally smallest `max_sample` priorities survive — then the
    edges are fitted exactly like `fit_bin_mapper` on that sample.
    Deterministic given (seed, chunk order); with
    max_sample >= total rows the sample IS the dataset, so the edges
    equal the in-memory fit's (np.quantile is order-invariant).

    `chunk_fn(c) -> (X_chunk float [rows_c, F], y_chunk)` — the same
    signature `streaming.fit_streaming` consumes (y is ignored here)."""
    buf = None          # [k, F] sampled rows
    pri = None          # [k] their priorities
    for c in range(n_chunks):
        Xc = np.asarray(chunk_fn(c)[0], np.float32)
        pc = np.random.default_rng((seed, 15485863, c)).random(len(Xc))
        if buf is None:
            buf, pri = Xc, pc
        else:
            if len(pri) >= max_sample:
                # Saturated: a newcomer survives only by beating the
                # current worst kept priority — pre-filter so the append
                # shrinks as 1/chunks_seen instead of copying the whole
                # reservoir + chunk every time (identical output: the
                # filtered-out rows could never be among the k smallest).
                sel = pc < pri.max()
                Xc, pc = Xc[sel], pc[sel]
                if not len(pc):
                    continue
            buf = np.concatenate([buf, Xc])
            pri = np.concatenate([pri, pc])
        if len(pri) > max_sample:
            keep = np.argpartition(pri, max_sample)[:max_sample]
            buf, pri = buf[keep], pri[keep]
    if buf is None:
        raise ValueError("no chunks")
    return fit_bin_mapper(buf, n_bins=n_bins, max_sample=len(buf),
                          seed=seed, missing_policy=missing_policy,
                          cat_features=cat_features)

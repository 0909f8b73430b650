"""Job kind `score`: one job is one `api.predict` of the configuration's
ensemble over its batch: host uint8 rows in, host float32 raw scores out, both
transfers counted. Reports `score_mrows_per_s`: all the rows of the calls that
finished over all the time of the window.

`check` holds a seeded sample of rows of EVERY call of the window to the plain
reference's float64 traversal (`reference.py`); limits are in the
configuration's file under "check", each with the readings it was set from.
"""

from __future__ import annotations

import numpy as np

import datagen
import reference
from jobs import common


class Job:
    def __init__(self, cell: dict, seed: int, rehearse: bool, control: dict):
        self.cell, self.seed, self.rehearse = cell, seed, rehearse
        self.shapes = common.scaled_shapes(cell, rehearse)
        self.cfg = common.train_config(cell, seed, rehearse, control)
        self.limits = cell["config"]["check"]

    def setup(self) -> None:
        from ddt_tpu.models.tree import empty_ensemble

        s, m = self.shapes, self.cell["config"]["model"]
        self.tables = datagen.random_full_trees(
            s["n_trees"], s["max_depth"], s["features"], s["n_bins"],
            self.seed)
        self.ens = empty_ensemble(s["n_trees"], s["max_depth"],
                                  s["features"], m["learning_rate"],
                                  m["base_score"], m["loss"])
        for k, v in self.tables.items():
            getattr(self.ens, k)[:] = v
        self.Xb = datagen.uniform_bins(s["rows"], s["features"], s["n_bins"],
                                       self.seed)

    def one_job(self):
        from ddt_tpu import api

        return api.predict(self.ens, self.Xb, binned=True, raw=True,
                           cfg=self.cfg)

    def unsound(self, outputs: list) -> int:
        """Calls that returned a non-finite score (looked for after the
        window: a scan of 100M scores takes 0.1 s)."""
        return sum(not np.isfinite(o).all() for o in outputs)

    def end_to_end(self, win: dict) -> dict:
        rows = self.shapes["rows"] * len(win["walls"])
        return {"score_mrows_per_s": rows / win["span"] / 1e6}

    def divisors(self, n_jobs: int) -> dict:
        return {"jobs": n_jobs, "calls": n_jobs}

    # ------------------------------------------------------------------ #

    def check(self, outputs: list, warm_up) -> list:
        if not outputs:
            return []
        s, lim, m = self.shapes, self.limits, self.cell["config"]["model"]
        checks = []
        shaped = all(o.shape == (s["rows"],) and o.dtype == np.float32
                     for o in outputs)
        checks.append(("every call returned float32 [rows]", shaped, True,
                       shaped))
        differ = sum(not np.array_equal(o, warm_up) for o in outputs)
        checks.append(("calls of the window whose scores differ from the "
                       "warm-up call's in any bit", differ, 0, differ == 0))

        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 4]))
        idx = np.sort(rng.choice(s["rows"],
                                 size=min(lim["sample_rows"], s["rows"]),
                                 replace=False))
        want = reference.raw_scores(self.tables, s["max_depth"],
                                    m["learning_rate"], m["base_score"],
                                    self.Xb[idx])
        gap = max(float(np.max(np.abs(o[idx].astype(np.float64) - want)))
                  for o in outputs)
        checks.append((f"scores of {len(idx)} sampled rows in each of "
                       f"{len(outputs)} calls vs the float64 reference "
                       f"(|score| up to {float(np.abs(want).max()):.2f}), "
                       "max |gap|", gap, lim["score_atol"],
                       bool(gap <= lim["score_atol"])))
        checks.extend(self._what_ran())
        return checks

    def _what_ran(self) -> list:
        """The Pallas traversal kernel, compiled (as chip_smoke.py asserts)."""
        import jax

        from ddt_tpu.backends import get_backend
        from ddt_tpu.utils import device

        if device.platform() != "tpu":
            return []
        s = self.shapes
        be = get_backend(self.cfg)
        fn, ens_dev = be._predict_fn(self.ens)
        rows = min(s["rows"], be.PREDICT_ROW_CHUNK * max(1, be.row_shards))
        x_spec = jax.ShapeDtypeStruct(
            (-(-rows // be.row_shards) * be.row_shards, s["features"]),
            np.uint8, sharding=be._row_sharding(extra_dims=1))
        has = "tpu_custom_call" in jax.jit(fn).lower(
            *ens_dev, x_spec).as_text()
        return [("scoring program carries a compiled Pallas kernel "
                 "(tpu_custom_call)", has, True, has)]

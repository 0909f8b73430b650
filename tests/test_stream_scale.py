"""Config-5 memory witness at this box's capacity (round-3 verdict
item 3): "O(chunk) by construction" meets multi-GB-class data. The
defining property of O(chunk) is that peak memory tracks the CHUNK
size, not the dataset size — so the test trains TWICE at the same
500k-row chunk size, with the dataset quadrupled (2.5M -> 10M rows; 80
-> 320 MB binned, 320 MB -> 1.28 GB as the float32 matrix the
in-memory path would hold), each in a FRESH subprocess (RSS high-water
marks are process-wide), and asserts the peak-RSS growth is flat. On
this CPU platform the "device" is host RAM, so a path that held the
dataset device-side would show up too (it would add ~+240 MB binned /
+960 MB float between the runs); the device chunk cache is explicitly
OFF in the worker for the same reason.

The full-size runs (20M and 50M x 64: throughput, and peak RSS flat in
the row count) were measured on the earlier host, rounds 3-5, and are
not re-measured on this chip.
"""

import json
import os
import subprocess
import sys

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "stream_rss_worker.py")

FEATURES, BINS, CHUNK_ROWS = 32, 31, 500_000


def _measure(rows, n_chunks, work_dir):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)          # worker pins cpu itself
    # The pytest session exports an 8-virtual-device XLA_FLAGS
    # (conftest.py) which the worker would inherit: 8 device arenas +
    # thread pools add ~100 MB of RSS *and* most of its run-to-run
    # jitter — measured swings up to 208 MB on the diff-of-diffs this
    # test asserts at 120. The streaming run under measurement is
    # single-device; measure it that way.
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, _WORKER, str(rows), str(FEATURES),
         str(n_chunks), str(BINS), str(work_dir)],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["rc"] == 0 and rec["n_chunks"] == n_chunks
    return rec


def test_stream_dir_memory_is_o_chunk(tmp_path):
    small = _measure(5 * CHUNK_ROWS, 5, tmp_path / "small")
    big = _measure(20 * CHUNK_ROWS, 20, tmp_path / "big")

    # The shard writer holds one generated chunk + npz buffers — flat in
    # dataset size by construction, bounded in chunk size.
    for rec in (small, big):
        shard_delta = rec["rss_sharded_mb"] - rec["rss_baseline_mb"]
        assert shard_delta < 8 * rec["chunk_mb"], rec

    # Training: peak RSS grows with the chunk (per-chunk buffers, XLA
    # intermediates sized [chunk_rows, ...], async-dispatch queue depth)
    # plus small per-dataset state (the cached per-chunk preds: rows x
    # 4 B = 10 -> 40 MB, labels). Quadrupling the dataset at fixed chunk
    # size must NOT move the peak by anywhere near the dataset growth
    # (+240 MB binned / +960 MB float if a path held it); 120 MB of
    # headroom absorbs queue-depth jitter under CPU contention while
    # staying half the smallest held-data signature.
    d_small = small["rss_trained_mb"] - small["rss_baseline_mb"]
    d_big = big["rss_trained_mb"] - big["rss_baseline_mb"]
    assert d_big - d_small < 120, (small, big)

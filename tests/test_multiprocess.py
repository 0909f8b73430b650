"""True multi-process distributed execution (round-2 verdict, item 1).

Everything else multi-device in this suite runs in ONE process over
virtual devices; these tests spawn real OS processes (two, and four for
the wider DCN-axis case), wire them with jax.distributed.initialize
(coordinator bootstrap over localhost, gloo CPU collectives), train over
(hosts=N, rows=2) pod meshes built from the GLOBAL device list, and
assert the fetched ensembles are bit-identical across processes AND to a
single-process run of the identical mesh shape.
This is the process-level failure surface a virtual mesh cannot reach:
per-process device visibility, cross-process psum, non-addressable-shard
placement (TPUDevice._put), replicated-output fetch (fetch_tree /
eval_round's all_gather path), and fit_streaming's per-(chunk, level)
device placement over on-disk shards (round-3 verdict item 4).

Contract: SURVEY.md §5 "Distributed communication backend"
("jax.distributed.initialize for the v5e-64 pod config"), BASELINE
config 5.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "mp_worker.py")

# Capability gate (ISSUE 11 satellite): some images' XLA CPU builds
# cannot run true multi-process programs at all — every collective
# compile fails with this exact runtime error. That is an environment
# capability, not a regression in this repo, so the tests SKIP with the
# error quoted (tier-1 stays green-or-meaningful) instead of carrying a
# known failure into every PR's triage; where the runtime supports
# multi-process CPU (gloo) or a real pod, they run fully.
_MP_CPU_ERR = "Multiprocess computations aren't implemented on the CPU backend"


def _skip_if_multiprocess_unsupported(logs) -> None:
    joined = "\n".join(logs)
    if _MP_CPU_ERR in joined:
        pytest.skip(
            "XLA capability gate: this image's CPU backend refuses "
            f"multi-process programs (worker failed with: {_MP_CPU_ERR!r})")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _spawn(coord, nproc, pid, dev_per_proc, out, tmp_path,
           host_partitions=2):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)     # worker pins cpu itself
    # Isolate XLA compile caches per worker: two processes racing one
    # cache directory is a real hazard but not what this test is for.
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / f"cache{pid}")
    return subprocess.Popen(
        [sys.executable, _WORKER, coord, str(nproc), str(pid),
         str(dev_per_proc), out,
         str(tmp_path / f"shards_{nproc}_{pid}"), str(host_partitions)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )


@pytest.mark.parametrize("nproc,host_partitions", [(2, 2), (4, 4)],
                         ids=["2proc", "4proc"])
def test_multiprocess_bringup_bit_identical(nproc, host_partitions,
                                            tmp_path):
    """N OS processes over a (hosts=N, rows=2) pod mesh (2*N global
    devices). Ensembles must be bitwise identical ACROSS processes for
    EVERY path (fused, granular/eval, streamed-from-shards: replicas of
    one global computation) and match a single-process run of the
    identical mesh shape bitwise in structure, float-close in leaves
    (gloo may sum the allreduce in a different order than the single-
    controller collective — ops/split.py "Determinism boundary")."""
    port = _free_port()
    coord = f"localhost:{port}"
    outs = [str(tmp_path / f"p{i}.npz") for i in range(nproc)]
    single = str(tmp_path / "single.npz")

    procs = [_spawn(coord, nproc, i, 2, outs[i], tmp_path,
                    host_partitions=host_partitions)
             for i in range(nproc)]
    logs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=900)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        logs.append(stdout)
    _skip_if_multiprocess_unsupported(logs)
    assert all(p.returncode == 0 for p in procs), (
        "worker failed:\n" + "\n----\n".join(logs))

    # Single-process comparator: the same (hosts=N, rows=2) mesh over
    # 2*N virtual devices in one controller — identical program, so
    # identical trees prove the multi-process run computed the same
    # thing.
    ps = _spawn("unused", 1, 0, 2 * nproc, single, tmp_path,
                host_partitions=host_partitions)
    stdout, _ = ps.communicate(timeout=900)
    assert ps.returncode == 0, stdout

    ds = np.load(single)
    data = [np.load(o) for o in outs]
    for i, d in enumerate(data):
        assert int(d["process_index"]) == i
    keys = ("feature", "threshold_bin", "is_leaf", "leaf_value")
    for prefix in ("", "g_", "s_"):
        for i in range(1, nproc):
            for k in keys:
                np.testing.assert_array_equal(
                    data[0][prefix + k], data[i][prefix + k],
                    err_msg=f"proc {i} {prefix}{k}")
        for k in ("feature", "threshold_bin", "is_leaf"):
            np.testing.assert_array_equal(data[0][prefix + k],
                                          ds[prefix + k],
                                          err_msg=prefix + k)
        np.testing.assert_allclose(data[0][prefix + "leaf_value"],
                                   ds[prefix + "leaf_value"],
                                   rtol=2e-4, atol=2e-5)


def test_initialize_multihost_guard():
    """The idempotence guard itself, in-process (no coordinator needed:
    the guard trips before jax.distributed is touched)."""
    from ddt_tpu.parallel import mesh

    orig = mesh._init_args
    try:
        mesh._init_args = {"coordinator_address": "localhost:1",
                           "num_processes": 2, "process_id": 0}
        # same args: no-op
        mesh.initialize_multihost("localhost:1", 2, 0)
        # different args: loud
        with pytest.raises(RuntimeError, match="cannot\n?\\s*re-initialise"):
            mesh.initialize_multihost("localhost:1", 2, 1)
    finally:
        mesh._init_args = orig


def test_cli_multihost_train(tmp_path):
    """The CLI's own multihost bring-up (--multihost-*): two OS processes
    run the SAME train command, each fetches a replicated ensemble,
    bit-identical across processes. (The wrapper pins the jax config to
    cpu before invoking the CLI — what a multihost launcher script does
    on a non-TPU host.)"""
    port = _free_port()
    outs = [str(tmp_path / f"cli{i}.npz") for i in range(2)]
    procs = []
    for i in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / f"cc{i}")
        wrapper = ("import jax, sys; "
                   "jax.config.update('jax_platforms', 'cpu'); "
                   "from ddt_tpu.cli import main; "
                   "sys.exit(main(sys.argv[1:]))")
        procs.append(subprocess.Popen(
            [sys.executable, "-c", wrapper, "train",
             "--backend=tpu", "--rows=2048", "--trees=3", "--depth=3",
             "--bins=31", "--host-partitions=2", "--partitions=2",
             f"--multihost-coordinator=localhost:{port}",
             "--multihost-processes=2", f"--multihost-id={i}",
             f"--out={outs[i]}"],
            env=env, cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))
    logs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=900)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        logs.append(stdout)
    _skip_if_multiprocess_unsupported(logs)
    assert all(p.returncode == 0 for p in procs), (
        "cli multihost worker failed:\n" + "\n----\n".join(logs))
    d0 = np.load(outs[0])
    d1 = np.load(outs[1])
    for k in ("feature", "threshold_bin", "is_leaf", "leaf_value"):
        np.testing.assert_array_equal(d0[k], d1[k], err_msg=k)

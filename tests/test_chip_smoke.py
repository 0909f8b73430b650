"""chip_smoke.py off the chip, and where the compile cache is placed.

The smoke proves something only on a TPU; here it must REFUSE a CPU (exit
non-zero, say why, print no result), and its `--rehearse` mode must keep
running the whole control flow — four-device phases included — so that the
script does not rot between chip runs. Each run is a subprocess: the smoke
pins its own platform and owns process-global JAX config.

The compile cache (backends/tpu.enable_persistent_compile_cache): where
$JAX_COMPILATION_CACHE_DIR is set the program sets no directory in code;
where it is not, the cache is <checkout>/.jax_cache, the same path on every
run. Checked in fresh processes, because JAX reads the variable at import.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_chip_smoke_refuses_a_cpu():
    p = subprocess.run([sys.executable, SMOKE], env=_env(), cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "not 'tpu'" in p.stderr, p.stderr[-2000:]
    # The device line comes first; no result follows it.
    assert p.stdout.startswith("device: platform=cpu"), p.stdout
    assert '"ok"' not in p.stdout


def test_chip_smoke_rehearsal_runs_every_phase():
    p = subprocess.run(
        [sys.executable, SMOKE, "--rehearse"], cwd=REPO,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-3000:])
    out = p.stdout
    assert out.startswith("device: platform=cpu device_kind=cpu count=4")
    for phase in ("one device train:", "scores:", "parity vs reference",
                  "block_until_ready waits for the device",
                  "rows=4: 4 devices hold a quarter each",
                  "2x2: 4 devices hold a quarter each"):
        assert phase in out, (phase, out[-3000:])
    # A rehearsal never prints the pass line.
    assert out.rstrip().splitlines()[-1].startswith("REHEARSAL")
    assert '"ok"' not in out


_PROBE = (
    "import jax, jax.numpy as jnp;"
    "from ddt_tpu.backends.tpu import enable_persistent_compile_cache as e;"
    "e(); jax.jit(lambda x: x * 2 + 1)(jnp.arange(7.0)).block_until_ready();"
    "print(jax.config.jax_compilation_cache_dir)")


def _probe(**extra):
    # Threshold 0 so that the probe's tiny program is worth an entry.
    p = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env=_env(JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **extra))
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout.strip().splitlines()[-1]


def _listing(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    default = os.path.join(REPO, ".jax_cache")
    before = _listing(default)
    placed = str(tmp_path / "placed")
    assert _probe(JAX_COMPILATION_CACHE_DIR=placed) == placed
    assert _listing(placed), "no entry under $JAX_COMPILATION_CACHE_DIR"
    assert _listing(default) == before, \
        "the checkout's .jax_cache was touched although the variable is set"


def test_compile_cache_defaults_to_the_checkout():
    default = os.path.join(REPO, ".jax_cache")
    assert _probe() == default
    assert _probe() == default            # the same path on every run
    assert _listing(default), "no entry under <checkout>/.jax_cache"
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

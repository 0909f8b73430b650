"""The one home of "which device is this program built for" and of the
device barrier.

Several dispatch rules depend on the platform: the Pallas kernels compile
on a TPU and run interpreted elsewhere, the histogram resolves to the
kernel / the MXU matmul / the scatter form, sibling subtraction and slab
pipelining default on only where a chip backs the run, the one-hot
traversal replaces the kernel off-chip. Every one of those rules reads
`platform()` here and nothing else, so a run can assert on what was
resolved (chip_smoke.py does) and a compile-only check can build the TPU
programs from a CPU host (`assume_platform` — scripts/tpu_aot_check.py,
tests/test_tpu_lowering.py).
"""

from __future__ import annotations

import contextlib

import jax

# Set only inside assume_platform(); None = ask JAX.
_assumed: str | None = None


def platform() -> str:
    """Platform the traced programs target: "tpu" | "cpu" | ... — JAX's
    default backend unless a compile-only check has assumed another."""
    return _assumed if _assumed is not None else jax.default_backend()


@contextlib.contextmanager
def assume_platform(name: str):
    """Resolve every platform-dependent dispatch rule as on `name` for the
    duration — for LOWERING programs for a platform this process does not
    run on (jax.export with platforms=(name,), or an AOT compile against a
    described topology). Programs built under it must not be executed
    here: a compiled-mode Pallas kernel has no CPU lowering."""
    global _assumed
    prev, _assumed = _assumed, name
    try:
        yield
    finally:
        _assumed = prev


def device_stamp() -> dict:
    """{"platform", "device_kind", "n_devices"} as JAX reports them — the
    stamp every printed result and run-log manifest carries, so a number
    can never be read without the device it came from."""
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "n_devices": len(devs)}


def device_sync(x):
    """Device barrier on `x`'s producer chain: jax.block_until_ready.
    Device programs execute in submission order, so syncing on the last
    output of a sequence fences the whole sequence.

    What was found on this machine (TPU v5 lite, PR 21, chip_smoke.py's
    barrier experiment, medians of 5): one 1M x 28 histogram build took
    9.8 ms under block_until_ready and 10.2 ms under a scalar read-back
    (`float(jnp.sum(out))`, which cannot return before the program ran,
    and pays a second small program), against 0.25 ms to enqueue it. So
    block_until_ready waits for the device here, and is the barrier:
    no extra program, no D2H copy."""
    return jax.block_until_ready(x)

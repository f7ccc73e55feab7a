"""Pallas TPU kernels for the perf-critical compute hot spots.

Each kernel package ships <name>.py (pl.pallas_call + BlockSpec VMEM
tiling), ops.py (jit'd public wrapper), ref.py (pure-jnp oracle); all are
validated against their oracles in interpret mode (tests/test_kernels.py).
"""


def default_interpret() -> bool:
    """Run Pallas kernels in interpret mode only on the CPU backend.

    On an accelerator a kernel compiles for it or raises; an error while
    the backend starts propagates instead of selecting the interpreter."""
    import jax
    return jax.default_backend() == "cpu"

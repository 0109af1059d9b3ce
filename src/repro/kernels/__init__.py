# Performance-critical compute of the paper: modulated scoring (the Phase-2
# matmul + modulation epilogue) and MMR diverse selection. Each kernel ships
# kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd public wrapper with
# padding/layout), ref.py (pure-jnp oracle).

from __future__ import annotations


def check_interpret(interpret: bool) -> bool:
    """Validate a kernel's Pallas interpret flag; returns it unchanged.

    Interpret mode exists for the CPU test suite, which turns it on
    explicitly.  Nothing infers it from the platform: a TPU always
    compiles its kernels, and asking any platform other than the CPU to
    interpret them is an error — a run that lands on the wrong device
    fails instead of timing the emulator.
    """
    if interpret:
        import jax

        platform = jax.devices()[0].platform
        if platform != "cpu":
            raise ValueError(
                f"Pallas interpret mode requested on {platform!r}; only the "
                "CPU test platform interprets kernels")
    return interpret

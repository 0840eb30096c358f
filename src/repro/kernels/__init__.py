"""Pallas kernels of the BAD data plane (plus the LM flash kernels).

Every kernel entry point takes ``interpret`` explicitly; the ops wrappers
pass ``interpret=not on_tpu()``, so a kernel runs compiled (Mosaic) on a
TPU and in the Pallas interpreter everywhere else, and no caller can fall
into interpret mode on the chip by omission.
"""
import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"

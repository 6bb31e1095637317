"""Which path a kernel wrapper runs: the one rule all three share.

On a TPU backend the Pallas kernel always runs.  Elsewhere the wrapper's
jnp path runs, unless the caller asks for the kernel (``use_kernel=True``);
off the TPU the kernel can then only run in Pallas interpret mode, so the
caller must also pass ``interpret=True`` — otherwise this raises rather
than silently running something other than what was asked for.
"""
from __future__ import annotations

import jax


def use_pallas(name: str, *, use_kernel: bool, interpret: bool) -> bool:
    """True when ``name``'s wrapper should call its Pallas kernel."""
    if jax.default_backend() == "tpu":
        return True
    if use_kernel and not interpret:
        raise RuntimeError(
            f"{name}: use_kernel=True needs a TPU backend, and JAX's backend "
            f"is {jax.default_backend()!r}; pass interpret=True to run the "
            f"kernel in Pallas interpret mode")
    return use_kernel

"""Feature-detected Pallas execution mode: compiled vs. interpret.

Every Pallas kernel in ``repro.kernels`` takes an ``interpret=None``
argument and resolves it through this module instead of hard-coding a
per-signature default. The rule is the paper's co-design seam applied to
the execution substrate:

  * a real TPU backend is present  -> ``interpret=False`` (Mosaic-compiled
    kernels, the measured hot path);
  * anything else (CPU tests, the forced-host-device dry-run, GPU boxes
    without a Mosaic path) -> ``interpret=True`` (Python-interpreter
    validation of the identical kernel body).

On a TPU nothing resolves to interpret mode or to the oracle unless the
caller asks for it (``interpret=True``, ``set_backend("jnp")``, an op's
``fallback=True``).

Unlike the other compat seams (pure ``hasattr`` checks), backend
detection initializes the JAX runtime, so it is deferred to the *first
kernel call* and cached — merely importing ``repro.compat`` must stay
side-effect free (multi-host launchers call
``jax.distributed.initialize`` after importing repro modules, which
requires an uninitialized backend). ``support_matrix()`` reports the
resolved mode so CI logs show which path ran. ``default_kernel_mode()``
feeds the same detection into ``repro.kernels.ops.KernelBackend`` so the
model stack's kernel dispatch (fused packed matmul, packed KV decode)
lands on compiled Pallas on hardware and on the XLA reference oracle
elsewhere, without every caller re-deriving the platform.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax


@functools.lru_cache(maxsize=None)
def on_tpu() -> bool:
    """Whether a real TPU backend is present (cached; first call
    initializes the JAX backend, so only kernel/launch code should ask).
    A backend that fails to initialize raises: reporting "not TPU" would
    quietly route a TPU run onto the jnp oracle."""
    return jax.default_backend() == "tpu"


def pallas_interpret_default(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` argument (None -> detected mode:
    compiled on real TPU, interpret everywhere else)."""
    if interpret is None:
        return not on_tpu()
    return bool(interpret)


def default_kernel_mode() -> str:
    """Default ``KernelBackend`` mode: compiled Pallas on TPU, the jnp
    oracle elsewhere (interpret mode stays an explicit opt-in — it runs
    kernel bodies at Python speed and is for validation only)."""
    return "pallas" if on_tpu() else "jnp"

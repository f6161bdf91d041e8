"""Public wrapper: dispatches [B,T,H,Dh]-layout attention (the model zoo's
convention) onto the [B,H,T,Dh] Pallas kernel, with a support predicate so
callers can fall back to the XLA path for unsupported shapes."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (
    DEFAULT_Q_BLOCK,
    flash_attention as _kernel,
)


def supported(q, k, v, mask) -> bool:
    # the kernel handles causal/window masks internally; arbitrary mask
    # tensors are not supported
    if mask is not None:
        return False
    b, t, h, dh = q.shape
    return t % min(DEFAULT_Q_BLOCK, t) == 0 and dh <= 256


def flash_attention(q, k, v, mask=None, *, causal=True, window=None,
                    interpret=None):
    """q [B,T,H,Dh]; k,v [B,S,KH,Dh] -> [B,T,H,Dh]."""
    del mask
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _kernel(qt, kt, vt, causal=causal, window=window,
                  interpret=interpret)
    return jnp.swapaxes(out, 1, 2)

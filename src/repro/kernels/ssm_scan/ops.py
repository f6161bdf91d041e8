"""Wrapper exposing the Pallas SSD scan in the model-zoo layout.

``repro.models.mamba.ssd_chunked(..., use_kernel=True)`` dispatches here:
inputs arrive time-major-per-batch ([B, T, NH, HD] / groups [B, T, NG, DS])
and the wrapper broadcasts groups to heads, transposes to head-major, and
runs the kernel.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ssm_scan.kernel import ssd_scan


def ssd_chunked(cfg, x, bmat, cmat, alog, h0=None, interpret=None):
    """Same contract as models.mamba.ssd_chunked (h0 must be None: the
    kernel owns the initial state)."""
    assert h0 is None, "kernel path owns the scan state"
    b, t, nh, hd = x.shape
    ng = bmat.shape[2]
    rep = nh // ng
    bm = jnp.repeat(bmat, rep, axis=2)  # [B,T,NH,DS]
    cm = jnp.repeat(cmat, rep, axis=2)
    xh = jnp.moveaxis(x, 1, 2)  # [B,NH,T,HD]
    al = jnp.moveaxis(alog, 1, 2)  # [B,NH,T]
    bmh = jnp.moveaxis(bm, 1, 2)
    cmh = jnp.moveaxis(cm, 1, 2)
    y, h_final = ssd_scan(
        xh, al, bmh, cmh, chunk=cfg.chunk, interpret=interpret
    )
    # back to [B,T,NH,HD]; state layout matches mamba cache [B,NH,DS,HD]
    return jnp.moveaxis(y, 1, 2), h_final

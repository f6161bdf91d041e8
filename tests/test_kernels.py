"""Per-kernel shape/dtype sweeps vs the pure-jnp ref oracles (interpret
mode executes the Pallas kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention import ref as flash_ref
from repro.kernels import prng
from repro.kernels.quantize import ops as q_ops
from repro.kernels.quantize import ref as q_ref
from repro.kernels import resolve_interpret
from repro.kernels.sparse_gather import ops as sg_ops
from repro.kernels.sparse_gather import ref as sg_ref
from repro.kernels.ssm_scan.kernel import ssd_scan
from repro.kernels.ssm_scan.ref import ssd_scan_ref

KEY = jax.random.key(0)


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize(
    "shape", [(2048,), (1000,), (64, 48), (7,), (3, 333)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_kernel_matches_ref(bits, shape, dtype):
    x = jax.random.normal(
        jax.random.fold_in(KEY, bits * 1000 + sum(shape)), shape
    ).astype(dtype)
    payload = q_ops.quantize_tensor(KEY, x, bits=bits)
    flat = jnp.reshape(x, (1, -1)).astype(jnp.float32)
    s0, s1 = prng.key_seed(KEY)
    expected, scale = q_ref.quantize_seeded_ref(
        s0[None], s1[None], flat, bits=bits
    )
    # payload carries exact wire bytes — the pad tail never travels
    assert payload["q"].shape == (q_ops.wire_len(flat.shape[1], bits),)
    np.testing.assert_array_equal(np.asarray(payload["q"]),
                                  np.asarray(expected[0]))
    assert float(payload["scale"]) == float(scale[0])
    rec = q_ops.dequantize_tensor(payload, shape, bits=bits)
    # quantization error bound: one level
    bound = float(scale[0]) / (2 ** (bits - 1) - 1) + 1e-2
    assert float(jnp.max(jnp.abs(rec - x.astype(jnp.float32)))) <= bound


def test_interpret_auto_selects_by_backend():
    """interpret=None -> interpret everywhere except TPU (this CI is
    CPU); explicit choices always win."""
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


# ---------------------------------------------------------------------------
# sparse gather / scatter (packed-plane RandK/TopK path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k", [(5, 2), (1000, 250), (4096, 1024), (77, 30)])
def test_sparse_gather_scatter_match_ref(n, k):
    x = jax.random.normal(jax.random.fold_in(KEY, n), (n,))
    idx = jax.random.permutation(jax.random.fold_in(KEY, n + k), n)[:k]
    np.testing.assert_array_equal(
        np.asarray(sg_ops.sparse_gather(x, idx)),
        np.asarray(sg_ref.sparse_gather_ref(x, idx)),
    )
    v = jax.random.normal(jax.random.fold_in(KEY, k), (k,))
    np.testing.assert_array_equal(
        np.asarray(sg_ops.sparse_scatter(v, idx, n, gain=n / k)),
        np.asarray(sg_ref.sparse_scatter_ref(v, idx, n, gain=n / k)),
    )


@pytest.mark.parametrize("n,k", [(5, 2), (1000, 250), (2048, 2048)])
def test_cyclic_gather_scatter_match_ref(n, k):
    """Block-RandK kernels: every offset, incl. wraparound windows."""
    x = jax.random.normal(jax.random.fold_in(KEY, n), (n,))
    v = jax.random.normal(jax.random.fold_in(KEY, k + 1), (k,))
    for off_v in [0, 1, n // 2, n - 1, max(0, n - k)]:
        off = jnp.int32(off_v)
        np.testing.assert_array_equal(
            np.asarray(sg_ops.cyclic_gather(x, off, k)),
            np.asarray(sg_ref.cyclic_gather_ref(x, off, k)),
        )
        np.testing.assert_array_equal(
            np.asarray(sg_ops.cyclic_scatter(v, off, n, gain=2.5)),
            np.asarray(sg_ref.cyclic_scatter_ref(v, off, n, gain=2.5)),
        )


def test_sparse_kernels_compose_with_vmap():
    """The packed admm path vmaps compression over (agents, slots)."""
    xs = jax.random.normal(KEY, (3, 4, 500))
    offs = jax.random.randint(KEY, (3, 4), 0, 500)
    got = jax.vmap(jax.vmap(
        lambda xx, oo: sg_ops.cyclic_gather(xx, oo, 125)
    ))(xs, offs)
    want = jax.vmap(jax.vmap(
        lambda xx, oo: sg_ref.cyclic_gather_ref(xx, oo, 125)
    ))(xs, offs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,h,kh,t,s,dh,causal,window",
    [
        (2, 4, 2, 256, 256, 64, True, None),
        (1, 8, 8, 128, 128, 128, True, None),
        (2, 4, 1, 256, 256, 32, True, 64),
        (1, 2, 2, 128, 384, 64, False, None),
        (1, 4, 4, 384, 200, 64, False, None),  # padded kv
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref(b, h, kh, t, s, dh, causal, window, dtype):
    kq, kk, kv = jax.random.split(jax.random.fold_in(KEY, t + s + dh), 3)
    q = jax.random.normal(kq, (b, t, h, dh)).astype(dtype)
    k = jax.random.normal(kk, (b, s, kh, dh)).astype(dtype)
    v = jax.random.normal(kv, (b, s, kh, dh)).astype(dtype)
    out = flash_ops.flash_attention(q, k, v, causal=causal, window=window)
    expected = jnp.swapaxes(
        flash_ref.attention_ref(
            jnp.swapaxes(q, 1, 2).astype(jnp.float32),
            jnp.swapaxes(k, 1, 2).astype(jnp.float32),
            jnp.swapaxes(v, 1, 2).astype(jnp.float32),
            causal=causal,
            window=window,
        ),
        1, 2,
    )
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        atol=tol, rtol=tol,
    )


def test_flash_used_by_sdpa_dispatch():
    from repro.models.attention import sdpa

    q = jax.random.normal(KEY, (1, 128, 4, 64))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 128, 2, 64))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 128, 2, 64))
    out_flash = sdpa(q, k, v, None, use_flash=True)
    out_ref = sdpa(q, k, v, None, use_flash=False)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_ref), atol=2e-5, rtol=2e-5
    )


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,nh,t,hd,ds,chunk",
    [
        (2, 3, 256, 64, 16, 64),
        (1, 2, 128, 32, 64, 32),
        (2, 1, 64, 16, 8, 64),
        (1, 4, 512, 32, 16, 128),
    ],
)
def test_ssd_kernel_matches_naive_recurrence(b, nh, t, hd, ds, chunk):
    ks = jax.random.split(jax.random.fold_in(KEY, t + hd + ds), 4)
    x = jax.random.normal(ks[0], (b, nh, t, hd)) * 0.5
    alog = -jnp.abs(jax.random.normal(ks[1], (b, nh, t))) * 0.2
    bm = jax.random.normal(ks[2], (b, nh, t, ds)) * 0.5
    cm = jax.random.normal(ks[3], (b, nh, t, ds)) * 0.5
    yk, hk = ssd_scan(x, alog, bm, cm, chunk=chunk)
    yr, hr = ssd_scan_ref(x, alog, bm, cm)
    np.testing.assert_allclose(
        np.asarray(yk), np.asarray(yr), atol=5e-4, rtol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(hk), np.asarray(hr), atol=5e-4, rtol=2e-3
    )


def test_mamba_forward_kernel_path_matches_jnp_path():
    from repro.models import mamba as mb
    from repro.models.common import init_params

    cfg = mb.SSMConfig(64, d_state=16, head_dim=32, chunk=32)
    params = init_params(KEY, mb.mamba_specs(cfg))
    x = jax.random.normal(KEY, (2, 128, 64))
    y_jnp = mb.mamba_forward(params, cfg, x, use_kernel=False)
    y_ker = mb.mamba_forward(params, cfg, x, use_kernel=True)
    np.testing.assert_allclose(
        np.asarray(y_jnp), np.asarray(y_ker), atol=2e-4, rtol=2e-3
    )


def test_mamba_chunked_matches_decode_loop():
    """Chunked training scan == step-by-step decode recurrence."""
    from repro.models import mamba as mb
    from repro.models.common import init_params

    cfg = mb.SSMConfig(32, d_state=8, head_dim=16, chunk=16)
    params = init_params(KEY, mb.mamba_specs(cfg))
    x = jax.random.normal(KEY, (1, 48, 32))
    y_full = mb.mamba_forward(params, cfg, x)
    cache = mb.mamba_init_cache(cfg, 1, jnp.float32)
    outs = []
    for t in range(48):
        y, cache = mb.mamba_decode(
            params, cfg, cache, x[:, t : t + 1], jnp.int32(t)
        )
        outs.append(y)
    y_steps = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(y_full), np.asarray(y_steps), atol=2e-4, rtol=2e-3
    )

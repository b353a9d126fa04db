"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle, swept over
shapes / dtypes / masks (assignment item c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def allclose(got, want, dtype):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,T,Hq,Hkv,D",
    [
        (1, 128, 128, 2, 2, 64),   # MHA, single block
        (2, 256, 256, 4, 1, 64),   # MQA, multi-block
        (1, 384, 384, 4, 2, 128),  # GQA, non-square block count
        (1, 100, 100, 2, 2, 64),   # ragged (padding path)
        (1, 128, 256, 2, 2, 64),   # cross: kv longer than q
    ],
)
def test_flash_vs_ref_causal(B, S, T, Hq, Hkv, D, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    got = ops.flash_attention(q, k, v, causal=True, interpret=True, block_q=128, block_k=128)
    want = jnp.swapaxes(
        ref.attention_ref(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), causal=True
        ),
        1,
        2,
    )
    allclose(got, want, dtype)


@pytest.mark.parametrize("window", [16, 64, 1024])
def test_flash_sliding_window(window):
    B, S, H, D = 1, 256, 2, 64
    ks = jax.random.split(jax.random.key(1), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in ks)
    got = ops.flash_attention(q, k, v, causal=True, window=window, interpret=True)
    want = jnp.swapaxes(
        ref.attention_ref(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            causal=True, window=window,
        ),
        1,
        2,
    )
    allclose(got, want, jnp.float32)


def test_flash_noncausal():
    B, S, H, D = 1, 128, 2, 64
    ks = jax.random.split(jax.random.key(2), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in ks)
    got = ops.flash_attention(q, k, v, causal=False, interpret=True)
    want = jnp.swapaxes(
        ref.attention_ref(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), causal=False
        ),
        1,
        2,
    )
    allclose(got, want, jnp.float32)


@pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128)])
def test_flash_block_shape_invariance(bq, bk):
    """Output must not depend on the BlockSpec tiling."""
    B, S, H, D = 1, 256, 2, 64
    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in ks)
    base = ops.flash_attention(q, k, v, interpret=True, block_q=128, block_k=128)
    got = ops.flash_attention(q, k, v, interpret=True, block_q=bq, block_k=bk)
    allclose(got, base, jnp.float32)


def test_flash_matches_model_xla_path():
    """The model's chunked-XLA attention and the kernel agree."""
    from repro.configs import get_config
    from repro.models.attention import _attend_chunked, _attend_full

    cfg = get_config("qwen3-4b", smoke=True)
    B, S, Hq, Hkv, D = 1, 128, 4, 2, 16
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    kern = ops.flash_attention(q, k, v, causal=True, interpret=True, block_q=64, block_k=64)
    full = _attend_full(q, k, v, cfg)
    chunked = _attend_chunked(q, k, v, cfg)
    allclose(kern, full, jnp.float32)
    allclose(chunked, full, jnp.float32)


# ---------------------------------------------------------------------------
# splash attention (the training path on a TPU) and the path selection
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "Hq,Hkv,window",
    [
        (4, 2, None),  # GQA, causal
        (4, 1, None),  # MQA, causal
        (4, 2, 64),  # GQA, sliding window
    ],
)
def test_splash_matches_model_full_path(Hq, Hkv, window):
    """Output and the gradients of q, k and v against the model's XLA path in
    float32, from the same bf16 inputs: the kernel rounds its output and
    gradients to bf16 (2**-9 of each) and scales q in bf16 once."""
    from repro.configs import get_config
    from repro.models.attention import _attend_full

    cfg = get_config("granite-3-8b", smoke=True)
    B, S, D = 1, 256, 128
    ks = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.bfloat16)
    do = jax.random.normal(ks[3], (B, S, Hq, D), jnp.float32)

    def kernel_loss(q, k, v):
        o = ops.splash_attention(q, k, v, window=window, interpret=True)
        return jnp.sum(o.astype(jnp.float32) * do), o

    def ref_loss(q, k, v):
        o = _attend_full(q, k, v, cfg, window=window)
        return jnp.sum(o * do), o

    (_, got), g_got = jax.value_and_grad(kernel_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    (_, want), g_want = jax.value_and_grad(ref_loss, argnums=(0, 1, 2), has_aux=True)(*f32)
    allclose(got, want, jnp.bfloat16)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        rel = float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b))
        assert rel < 1e-2, rel
    if window is not None:  # the window is applied: a causal-only reference is far off
        causal = _attend_full(*f32, cfg)
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - causal))) > 0.1


def test_splash_with_narrower_values_matches_model_full_path():
    """Latent attention's core: q/k heads of 192, v heads of 128, a score
    scale other than 1/sqrt(D).  Output and the q, k and v gradients against
    the model's XLA path in float32, as above."""
    from repro.configs import get_config
    from repro.models.attention import _attend_full

    cfg = get_config("deepseek-v2-lite", smoke=True)
    B, S, H, D, Dv, scale = 1, 256, 2, 192, 128, 0.1147
    ks = jax.random.split(jax.random.key(15), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, Dv), jnp.bfloat16)
    do = jax.random.normal(ks[3], (B, S, H, Dv), jnp.float32)

    def kernel_loss(q, k, v):
        o = ops.splash_attention(q, k, v, scale=scale, interpret=True)
        return jnp.sum(o.astype(jnp.float32) * do), o

    def ref_loss(q, k, v):
        o = _attend_full(q, k, v, cfg, scale=scale)
        return jnp.sum(o * do), o

    (_, got), g_got = jax.value_and_grad(kernel_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    (_, want), g_want = jax.value_and_grad(ref_loss, argnums=(0, 1, 2), has_aux=True)(*f32)
    assert got.shape == (B, S, H, Dv)
    allclose(got, want, jnp.bfloat16)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        rel = float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b))
        assert rel < 1e-2, rel
    # the scale is applied: the default 1/sqrt(192) is far off
    default = _attend_full(*f32, cfg)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - default))) > 0.1


def test_latent_attention_takes_the_kernel_on_a_tpu():
    """MLA's 16 heads of 192 (q/k) and 128 (v) fit the kernel on a TPU; the
    CPU keeps the XLA path; heads of 64 stay off the kernel."""
    from repro.configs import get_config
    from repro.models.attention import attention_path

    cfg = get_config("deepseek-v2-lite")
    assert attention_path(cfg, 4096, 4096, 16, 16, 192, "tpu", 128) == "splash"
    assert attention_path(cfg, 4096, 4096, 16, 16, 192, "cpu", 128) == "full"
    assert attention_path(cfg, 4096, 4096, 16, 16, 64, "tpu", 64) == "full"
    assert attention_path(cfg, 4096, 4096, 16, 16, 192, "tpu", 96) == "full"


def test_model_attention_through_splash_matches_xla():
    """``attention()`` under ``pallas_interpret`` runs the kernel and agrees
    with the same layer under ``xla``."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.attention import attention, attention_spec
    from repro.models.modules import init_params

    cfg = dataclasses.replace(get_config("granite-3-8b", smoke=True), d_model=256, n_heads=2, n_kv_heads=1,
                              head_dim_=128)
    params = init_params(attention_spec(cfg), jax.random.key(12))
    x = jax.random.normal(jax.random.key(13), (1, 128, cfg.d_model), jnp.float32)
    pos = jnp.arange(128)[None]
    want = attention(params, x, dataclasses.replace(cfg, attention_impl="xla"), pos)
    got = attention(params, x, dataclasses.replace(cfg, attention_impl="pallas_interpret"), pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


def test_attention_path_follows_platform_and_shapes():
    import dataclasses
    from types import SimpleNamespace

    from repro.configs import get_config, list_archs
    from repro.models.attention import attention_path
    from repro.sharding.ctx import sharding_ctx

    granite = get_config("granite-3-8b")  # 32 q and 8 kv heads of 128
    assert granite.attention_impl == "auto"
    # The CPU keeps the XLA paths for every configuration, as before the kernel.
    for arch in list_archs():
        cfg = get_config(arch)
        for S in (64, 4096, 32768):
            want = "chunked" if S > cfg.chunk_threshold else "full"
            assert attention_path(cfg, S, S, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, "cpu") == want
    # A TPU takes the kernel where its tiles fit, and the XLA path elsewhere.
    assert attention_path(granite, 4096, 4096, 32, 8, 128, "tpu") == "splash"
    assert attention_path(granite, 32768, 32768, 32, 8, 128, "tpu") == "splash"
    assert attention_path(granite, 384, 384, 32, 8, 128, "tpu") == "splash"  # 128-wide tiles
    assert attention_path(granite, 64, 64, 32, 8, 128, "tpu") == "full"  # shorter than one tile
    assert attention_path(granite, 4096, 4096, 32, 8, 64, "tpu") == "full"  # D=64 (musicgen)
    assert attention_path(granite, 4096, 4096, 12, 8, 128, "tpu") == "full"  # heads do not group
    xla = dataclasses.replace(granite, attention_impl="xla")
    assert attention_path(xla, 4096, 4096, 32, 8, 128, "tpu") == "full"
    assert attention_path(xla, 16384, 16384, 32, 8, 128, "tpu") == "chunked"
    forced = dataclasses.replace(granite, attention_impl="pallas_interpret")
    assert attention_path(forced, 4096, 4096, 32, 8, 128, "cpu") == "splash"
    # Under a mesh of several devices the kernel would not be partitioned.
    with sharding_ctx(SimpleNamespace(size=4), {}):
        assert attention_path(granite, 4096, 4096, 32, 8, 128, "tpu") == "full"
    with sharding_ctx(SimpleNamespace(size=1), {}):
        assert attention_path(granite, 4096, 4096, 32, 8, 128, "tpu") == "splash"


def test_rglru_scan_stays_xla_under_auto(monkeypatch):
    """The RG-LRU scan reads ``attention_impl`` as its own impl: ``auto``
    keeps its XLA scan."""
    import repro.models.rglru as rg
    from repro.configs import get_config
    from repro.models.modules import init_params

    seen = []
    real = rg.rglru

    def spy(*args, impl="xla", **kw):
        seen.append(impl)
        return real(*args, impl=impl, **kw)

    monkeypatch.setattr(rg, "rglru", spy)
    cfg = get_config("recurrentgemma-9b", smoke=True)
    assert cfg.attention_impl == "auto"
    params = init_params(rg.recurrent_block_spec(cfg), jax.random.key(14))
    rg.recurrent_block(params, jnp.ones((1, 16, cfg.d_model), jnp.float32), cfg)
    assert seen == ["xla"]


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,W,bs,bw",
    [
        (1, 128, 512, 128, 512),  # single block
        (2, 256, 512, 128, 256),  # multi block both axes
        (1, 200, 300, 128, 256),  # ragged padding
        (1, 512, 128, 64, 128),   # long sequence, short width
    ],
)
def test_rglru_vs_ref(B, S, W, bs, bw, dtype):
    ks = jax.random.split(jax.random.key(5), 2)
    # decays in (0,1): realistic RG-LRU regime
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))).astype(dtype)
    b = jax.random.normal(ks[1], (B, S, W)).astype(dtype)
    got = ops.rglru_scan(a, b, block_s=bs, block_w=bw, interpret=True)
    want = ref.rglru_ref(a, b)
    allclose(got, want, dtype)


def test_rglru_state_carries_across_seq_blocks():
    """With a=1, b=1 the output is a running count — any state loss between
    sequence blocks would show as a reset."""
    B, S, W = 1, 256, 128
    a = jnp.ones((B, S, W), jnp.float32)
    b = jnp.ones((B, S, W), jnp.float32)
    got = ops.rglru_scan(a, b, block_s=64, block_w=128, interpret=True)
    want = jnp.broadcast_to(jnp.arange(1, S + 1, dtype=jnp.float32)[None, :, None], (B, S, W))
    allclose(got, want, jnp.float32)


def test_rglru_matches_model_assoc_scan():
    from repro.models.rglru import rglru, rglru_spec
    from repro.models.modules import init_params

    B, S, W = 2, 64, 128
    params = init_params(rglru_spec(W), jax.random.key(6))
    x = jax.random.normal(jax.random.key(7), (B, S, W), jnp.float32)
    y_xla, _ = rglru(params, x, impl="xla")
    y_pallas, _ = rglru(params, x, impl="pallas_interpret")
    allclose(y_pallas, y_xla, jnp.float32)


# ---------------------------------------------------------------------------
# fused rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 128), (2, 7, 256), (1, 1000, 512)])
def test_rmsnorm_vs_ref(shape, dtype):
    ks = jax.random.split(jax.random.key(8), 2)
    x = jax.random.normal(ks[0], shape, dtype)
    scale = jax.random.normal(ks[1], (shape[-1],), jnp.float32) * 0.1
    got = ops.fused_rmsnorm(x, scale, interpret=True)
    want = ref.rmsnorm_ref(x, scale)
    allclose(got, want, dtype)


def test_rmsnorm_matches_model_impl():
    from repro.models.modules import rms_norm

    x = jax.random.normal(jax.random.key(9), (4, 64, 256), jnp.bfloat16)
    scale = jax.random.normal(jax.random.key(10), (256,), jnp.float32) * 0.1
    got = ops.fused_rmsnorm(x, scale, interpret=True)
    want = rms_norm({"scale": scale}, x)
    allclose(got, want, jnp.bfloat16)

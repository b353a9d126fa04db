"""Per-architecture smoke tests: reduced config, one forward + one train step
+ one decode step on CPU, asserting shapes and no NaNs (assignment item f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, list_archs, shape_applicable
from repro.models import Model

ARCHS = [
    "recurrentgemma-9b",
    "qwen3-4b",
    "llama3.2-3b",
    "gemma-2b",
    "granite-3-8b",
    "qwen2-vl-2b",
    "xlstm-125m",
    "deepseek-moe-16b",
    "qwen3-moe-235b-a22b",
    "musicgen-medium",
]

B, S = 2, 32


def make_batch(cfg, key, batch=B, seq=S):
    ks = jax.random.split(key, 3)
    out = {}
    if cfg.input_mode == "tokens":
        out["tokens"] = jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab)
    else:
        out["embeds"] = jax.random.normal(ks[0], (batch, seq, cfg.d_model), jnp.bfloat16)
    if cfg.mrope:
        pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None, :, None], (batch, seq, 3))
        out["positions"] = pos
    out["labels"] = jax.random.randint(ks[1], (batch, seq), 0, cfg.vocab)
    out["loss_mask"] = jnp.ones((batch, seq), jnp.float32)
    return out


def test_all_assigned_archs_registered():
    assert set(ARCHS) <= set(list_archs())
    # deepseek-v2-lite has no decode path yet (latent cache): tests/test_mla_moe.py runs it
    assert set(list_archs()) - set(ARCHS) == {"deepseek-v2-lite"}


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, jax.random.key(1))
    logits, lb = jax.jit(model.forward)(params, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), "non-finite logits"
    assert np.isfinite(float(lb))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_decreases_loss(arch):
    """One SGD step on a repeated batch must reduce loss (end-to-end grad flow)."""
    cfg = get_config(arch, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, jax.random.key(1))

    @jax.jit
    def step(p):
        (loss, aux), g = jax.value_and_grad(model.loss, has_aux=True)(p, batch)
        # normalized SGD: robust to per-arch gradient scale differences
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(g)))
        new_p = jax.tree.map(lambda w, gw: w - 0.05 * gw / (gnorm + 1e-6), p, g)
        return loss, new_p

    loss0, params = step(params)
    assert np.isfinite(float(loss0)), "loss not finite"
    for _ in range(5):
        loss1, params = step(params)
    assert float(loss1) < float(loss0), f"loss did not decrease: {loss0} -> {loss1}"


@pytest.mark.parametrize("arch", ARCHS)
def test_grads_finite_and_nonzero(arch):
    cfg = get_config(arch, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, jax.random.key(1))
    (_, _), grads = jax.jit(jax.value_and_grad(model.loss, has_aux=True))(params, batch)
    leaves = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in leaves)
    assert any(float(jnp.abs(g).max()) > 0 for g in leaves), "all-zero gradients"


def test_xlstm_grads_finite_at_full_chunk():
    """At the full config's mLSTM chunk (256) the masked-out gate exponents
    overflow; their gradients must still be finite."""
    from dataclasses import replace

    cfg = replace(get_config("xlstm-125m", smoke=True), chunk=get_config("xlstm-125m").chunk)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, jax.random.key(1), batch=1, seq=cfg.chunk)
    (loss, _), grads = jax.jit(jax.value_and_grad(model.loss, has_aux=True))(params, batch)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step(arch):
    cfg = get_config(arch, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    state = model.init_decode_state(batch=B, max_len=64)
    if cfg.input_mode == "tokens":
        batch = {"tokens": jnp.zeros((B, 1), jnp.int32)}
    else:
        batch = {"embeds": jnp.zeros((B, 1, cfg.d_model), jnp.bfloat16)}
    step = jax.jit(model.decode_step)
    logits, state = step(params, batch, state, jnp.int32(0))
    assert logits.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    logits2, state = step(params, batch, state, jnp.int32(1))
    assert np.isfinite(np.asarray(logits2, np.float32)).all()


@pytest.mark.parametrize("arch", ["qwen3-4b", "gemma-2b", "xlstm-125m", "recurrentgemma-9b"])
def test_decode_matches_prefill(arch):
    """Greedy decode logits must match teacher-forced forward (causality +
    cache correctness), for representative families."""
    cfg = get_config(arch, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    T = 8
    tokens = jax.random.randint(jax.random.key(2), (1, T), 0, cfg.vocab)
    fwd_logits, _ = jax.jit(model.forward)(params, {"tokens": tokens})
    state = model.init_decode_state(batch=1, max_len=32)
    step = jax.jit(model.decode_step)
    errs = []
    for t in range(T):
        logits, state = step(params, {"tokens": tokens[:, t : t + 1]}, state, jnp.int32(t))
        errs.append(float(jnp.abs(logits[0] - fwd_logits[0, t]).max()))
    assert max(errs) < 0.05, f"decode/prefill divergence: {errs}"


def test_full_configs_match_assignment():
    """Exact architecture numbers from the assignment table."""
    expect = {
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
        "gemma-2b": (18, 2048, 8, 1, 16384, 256000),
        "granite-3-8b": (40, 4096, 32, 8, 12800, 49155),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
    }
    for arch, (L, d, h, kv, ff, v) in expect.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.n_heads == h, arch
        assert cfg.n_kv_heads == kv, arch
        assert cfg.d_ff == ff, arch
        assert cfg.vocab == v, arch
    moe = get_config("deepseek-moe-16b")
    assert (moe.n_experts, moe.top_k, moe.n_shared_experts) == (64, 6, 2)
    q3 = get_config("qwen3-moe-235b-a22b")
    assert (q3.n_experts, q3.top_k) == (128, 8)


def test_param_counts_in_expected_range():
    """Full-config parameter counts should be near the advertised sizes."""
    expect_range = {
        "qwen3-4b": (3.0e9, 5.5e9),
        "llama3.2-3b": (2.5e9, 4.0e9),
        "gemma-2b": (2.0e9, 3.2e9),
        "granite-3-8b": (7.0e9, 9.5e9),
        "recurrentgemma-9b": (7.5e9, 11e9),
        "deepseek-moe-16b": (14e9, 20e9),
        "qwen3-moe-235b-a22b": (200e9, 260e9),
        "xlstm-125m": (0.08e9, 0.2e9),
        "musicgen-medium": (1.2e9, 2.2e9),
        "qwen2-vl-2b": (1.2e9, 2.2e9),
    }
    for arch, (lo, hi) in expect_range.items():
        n = Model(get_config(arch)).n_params
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]B"


def test_long_500k_applicability():
    """Sub-quadratic archs run long_500k; full-attention archs skip (by rule)."""
    runs = {a for a in ARCHS if shape_applicable(get_config(a), SHAPES["long_500k"])[0]}
    assert runs == {"recurrentgemma-9b", "xlstm-125m"}

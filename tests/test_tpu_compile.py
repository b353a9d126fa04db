"""Compile-only checks for one TPU v5e chip, at the widths the main path runs.

Nothing here runs on a chip: the TPU compiler compiles for a described
``v5e:2x2`` topology, which rejects what the chip would reject (unaligned
tiles, too much VMEM, a program that does not fit).  The topology is described
inside a fixture, never at import, so every test worker collects the same
tests and only the one given this file loads the TPU library.
"""

import hashlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.hlo_tree import build_device_tree
from repro.kernels import ops
from repro.launch.steps import make_serve_step
from repro.models import Model


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it means: not here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "batch,seq,q_heads,kv_heads,head_dim,window",
    [
        (1, 2048, 32, 8, 128, None),  # qwen3-4b causal attention
        (1, 4096, 16, 1, 256, 2048),  # recurrentgemma-9b MQA local attention
        (1, 64, 32, 8, 128, None),  # a sequence shorter than one block
    ],
)
def test_flash_attention_compiles(one_chip, batch, seq, q_heads, kv_heads, head_dim, window):
    q = _spec((batch, seq, q_heads, head_dim), jnp.bfloat16, one_chip)
    kv = _spec((batch, seq, kv_heads, head_dim), jnp.bfloat16, one_chip)
    compiled = ops.flash_attention.lower(q, kv, kv, causal=True, window=window, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "seq,q_heads,kv_heads,head_dim,window",
    [
        (4096, 32, 8, 128, None),  # granite-3-8b causal GQA
        (4096, 8, 1, 256, None),  # gemma-2b causal MQA
        (4096, 16, 1, 256, 2048),  # recurrentgemma-9b local attention
    ],
)
def test_splash_attention_and_its_gradient_compile(one_chip, seq, q_heads, kv_heads, head_dim, window):
    q = _spec((1, seq, q_heads, head_dim), jnp.bfloat16, one_chip)
    kv = _spec((1, seq, kv_heads, head_dim), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(ops.splash_attention(q, k, v, window=window).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    text = compiled.as_text()
    # the forward kernel and the fused dq+dkv kernel, each custom-call on one
    # line of the HLO text with its scope path
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(kernels) == 2
    assert all('op_name="' in ln and "splash_attention/" in ln for ln in kernels)


def test_rglru_scan_compiles(one_chip):
    x = _spec((2, 2048, 4096), jnp.float32, one_chip)
    compiled = ops.rglru_scan.lower(x, x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_xlstm_serve_step_compiles_and_is_costed(one_chip):
    """The full xlstm-125m decode step fits one chip, and the device plane
    reads the TPU's HLO: matmuls lowered to convolutions inside fusions, and a
    layer scan with no ``known_trip_count``."""
    model = Model(get_config("xlstm-125m"))
    cfg = model.cfg
    batch = 8

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.key(0)))
    state = on_chip(jax.eval_shape(lambda: model.init_decode_state(batch, 128)))
    tokens = {"tokens": _spec((batch, 1), jnp.int32, one_chip)}
    step = jax.jit(make_serve_step(model), donate_argnums=(2,))
    compiled = step.lower(params, tokens, state, _spec((), jnp.int32, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9
    tree = build_device_tree(compiled.as_text())
    # Every weight but the embedding table takes one multiply-add per token.
    want = 2 * batch * (model.n_params - cfg.vocab * cfg.d_model)
    assert tree.total("flops") == pytest.approx(want, rel=0.05)


def test_latent_attention_splash_and_its_gradient_compile(one_chip):
    """DeepSeek-V2-Lite's MLA core: 16 heads, q/k of 192 (the kernel's blocks
    take the whole 192 lanes), v of 128, its YaRN-scaled score scale."""
    q = _spec((1, 4096, 16, 192), jnp.bfloat16, one_chip)
    v = _spec((1, 4096, 16, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(ops.splash_attention(q, k, v, scale=0.1147).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, v).compile().as_text()
    assert len([ln for ln in text.splitlines() if "tpu_custom_call" in ln]) == 2


def test_held_experts_layer_and_its_gradient_compile(one_chip, monkeypatch):
    """The dropless expert layer at the cell's sizes: 4 x 4096 tokens, top 6
    of 64 experts, 8 held, their products in the megablox kernel as a TPU
    runs them."""
    import dataclasses

    from repro.models.modules import abstract_params
    from repro.models.moe import moe, moe_spec

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # grouped_swiglu reads the platform

    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), held_experts=(0, 8))
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), abstract_params(moe_spec(cfg)))
    x = _spec((4, 4096, cfg.d_model), jnp.bfloat16, one_chip)

    def loss(p, x):
        y, aux = moe(p, x, cfg)
        return jnp.sum(y.astype(jnp.float32)) + aux["lb_loss"]

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile()
    # three products forward, and each one's two backward kernels (gmm, tgmm)
    assert compiled.as_text().count("tpu_custom_call") >= 9
    assert compiled.memory_analysis().temp_size_in_bytes < 8e9


def test_granite_train_step_lowers_for_the_chip_as_before(one_chip, monkeypatch):
    """Granite's train step on the chip's path (the splash kernel's Mosaic
    bodies included), lowered for a described v5e, source locations left out,
    hashes as the parent of the MLA and held-expert layers lowered it."""
    from test_mla_moe import granite_step_text

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # attention_path reads the platform
    text = granite_step_text(one_chip)
    assert text.count("tpu_custom_call") >= 3
    want = "2b9c642e5f8eb75925a72f80f5e659516ebc4c68fd69fd51a8f4d14a8bb69183"
    assert hashlib.sha256(text.encode()).hexdigest() == want

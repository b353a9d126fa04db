"""Latent attention, YaRN and the expert layer that holds a share of the
experts, on the CPU: the dropless dispatch against the static-capacity one,
what each drops under skewed routing, which one a mesh of several devices
takes, the balance term, the decode path MLA refuses, and granite's train
step lowered as before these layers came."""

import dataclasses
import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, shape_applicable
from repro.configs.base import ModelConfig
from repro.launch.steps import make_train_step
from repro.models import Model
from repro.models.attention import mla_scale
from repro.models.modules import init_params, yarn_freqs, yarn_mscale
from repro.models.moe import grouped_swiglu, moe, moe_capacity, moe_spec
from repro.optim import AdamWConfig, adamw_init, cosine_schedule

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def moe_cfg(**kw):
    """8 routed experts of 16, top 2, no shared expert, float32 weights."""
    cfg = dataclasses.replace(get_config("deepseek-moe-16b", smoke=True), n_shared_experts=0)
    return dataclasses.replace(cfg, **kw)


def dense_moe(params, x, cfg):
    """Every routed expert on every token, weighted by the router's top-k
    weights there (0 off the top k): the layer with nothing dropped."""
    xt = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(xt @ params["router"]["w"], axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / w.sum(-1, keepdims=True)
    y = jnp.zeros_like(xt)
    for e in range(cfg.n_experts):
        gate = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        h = jax.nn.silu(xt @ params["wg"][e]) * (xt @ params["wi"][e])
        y = y + gate[:, None] * (h @ params["wo"][e])
    return y.reshape(x.shape)


def collapsed_tokens(cfg, key, n=64):
    """Near-identical tokens: every one routes to the same top-k experts."""
    v = 3.0 * jax.random.normal(key, (cfg.d_model,))
    noise = 0.01 * jax.random.normal(jax.random.fold_in(key, 1), (2, n // 2, cfg.d_model))
    return v + noise


def test_dropless_drops_nothing_under_skewed_routing():
    cfg = moe_cfg()
    params = init_params(moe_spec(cfg), jax.random.key(0))
    x = collapsed_tokens(cfg, jax.random.key(1))
    with jax.default_matmul_precision("highest"):
        y, aux = moe(params, x, cfg)
        want = dense_moe(params, x, cfg)
        _, aux_cap = moe_capacity(params, x, cfg)
    # every token took the same two experts: the capacity dispatch drops most slots
    assert float(aux["load_max"]) == pytest.approx(cfg.n_experts / cfg.top_k)
    assert float(aux_cap["dropped_frac"]) > 0.5
    assert float(aux["held_tokens"]) == x.shape[0] * x.shape[1] * cfg.top_k
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_dropless_is_the_capacity_dispatch_when_nothing_is_dropped():
    cfg = moe_cfg(capacity_factor=8.0, n_shared_experts=2)
    params = init_params(moe_spec(cfg), jax.random.key(2))
    x = jax.random.normal(jax.random.key(3), (2, 16, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        (y, aux), (y_cap, aux_cap) = moe(params, x, cfg), moe_capacity(params, x, cfg)
        g, g_cap = (jax.grad(lambda p, f=f: jnp.sum(jnp.sin(f(p, x, cfg)[0])))(params) for f in (moe, moe_capacity))
    assert float(aux_cap["dropped_frac"]) == 0.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_cap), rtol=1e-5, atol=1e-5)
    assert float(aux["lb_loss"]) == pytest.approx(float(aux_cap["lb_loss"]))
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_cap), strict=True):  # float32 sums in another order
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5


def test_held_share_computes_only_its_experts():
    """Experts 2-5 of 8: the router scores all 8, the layer gives what experts
    2-5 give for the tokens routed to them, with unnormalised weights."""
    cfg = moe_cfg(norm_topk_prob=False)
    full = init_params(moe_spec(cfg), jax.random.key(4))
    share = dataclasses.replace(cfg, held_experts=(2, 6))
    params = dict(full, **{k: full[k][2:6] for k in ("wi", "wg", "wo")})
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(lambda s: s.shape, moe_spec(share),
                                                          is_leaf=lambda s: hasattr(s, "logical"))
    x = jax.random.normal(jax.random.key(5), (2, 16, cfg.d_model))
    zeroed = dict(full, **{k: full[k].at[jnp.array([0, 1, 6, 7])].set(0.0) for k in ("wo",)})
    with jax.default_matmul_precision("highest"):
        y, aux = moe(params, x, share)
        want = dense_moe(zeroed, x, cfg)
        probs = jax.nn.softmax(x.reshape(-1, cfg.d_model) @ full["router"]["w"], axis=-1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
    ids = jax.lax.top_k(probs, cfg.top_k)[1]
    assert float(aux["held_tokens"]) == float(jnp.sum((ids >= 2) & (ids < 6)))


def unwritten_tail(real):
    """``jax.lax.ragged_dot`` as the TPU runs it: the rows past its groups are
    left unwritten (here NaN), in the output and in the lhs gradient."""

    def tail(out, lhs, sizes):
        return jnp.where((jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None], out, jnp.nan)

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, sizes):
        return tail(real(lhs, rhs, sizes), lhs, sizes)

    def fwd(lhs, rhs, sizes):
        return ragged_dot(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)[1](g)
        return tail(d_lhs, lhs, sizes), d_rhs, None

    ragged_dot.defvjp(fwd, bwd)
    return ragged_dot


def test_rows_past_the_held_experts_never_reach_the_result(monkeypatch):
    """With the grouped matmul's unwritten rows NaN, the held share's output,
    the gradient of its input and of every weight are as before."""
    cfg = moe_cfg(held_experts=(2, 6), norm_topk_prob=False, n_shared_experts=2)
    params = init_params(moe_spec(cfg), jax.random.key(6))
    x = jax.random.normal(jax.random.key(7), (2, 16, cfg.d_model))

    def run():
        with jax.default_matmul_precision("highest"):
            f = lambda p, x: jnp.sum(jnp.sin(moe(p, x, cfg)[0]))  # noqa: E731
            return moe(params, x, cfg)[0], jax.grad(f, argnums=(0, 1))(params, x)

    want = run()
    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten_tail(jax.lax.ragged_dot))
    got = run()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


def test_gmm_kernel_is_ragged_dot():
    """The grouped products in megablox's kernel (interpret mode), as a TPU
    runs them, against ``jax.lax.ragged_dot``: the output and every gradient,
    with 1024 slots of which 400 are held, in four uneven groups."""
    N, D, F = 1024, 256, 384
    ks = jax.random.split(jax.random.key(8), 5)
    xs = jax.random.normal(ks[0], (N, D))
    params = {"wi": jax.random.normal(ks[1], (4, D, F)) / 16, "wg": jax.random.normal(ks[2], (4, D, F)) / 16,
              "wo": jax.random.normal(ks[3], (4, F, D)) / 20}
    sizes = jnp.array([100, 37, 250, 13], jnp.int32)
    dy = jax.random.normal(ks[4], (N, D))

    def run(kernel):
        loss = lambda xs, p: jnp.sum(grouped_swiglu(xs, p, sizes, kernel=kernel) * dy)  # noqa: E731
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, argnums=(0, 1))(xs, params)

    for a, b in zip(jax.tree.leaves(run(True)), jax.tree.leaves(run(False)), strict=True):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(jnp.max(jnp.abs(b)))


def test_gmm_only_without_a_mesh_of_several_devices(monkeypatch):
    """On a TPU the held experts' products take the megablox kernel on one
    device, and ``jax.lax.ragged_dot`` under a mesh of several, which a Mosaic
    kernel is not partitioned for."""
    from jax.sharding import AbstractMesh

    from repro.sharding.ctx import sharding_ctx

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # grouped_swiglu reads the platform
    N, D, F = 512, 128, 128
    params = {k: jnp.zeros((2, *s)) for k, s in (("wi", (D, F)), ("wg", (D, F)), ("wo", (F, D)))}
    sizes = jnp.array([100, 50], jnp.int32)

    def traced():
        return str(jax.make_jaxpr(lambda xs: grouped_swiglu(xs, params, sizes))(jnp.zeros((N, D))))

    assert "pallas_call" in traced()
    for shape in ((1, 1), (2, 4)):
        with sharding_ctx(AbstractMesh(shape, ("data", "model")), {"batch": "data"}):
            text = traced()
        assert ("pallas_call" in text) == (shape == (1, 1))
        assert ("ragged_dot" in text) == (shape != (1, 1))


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get_config
from repro.models.modules import init_params
from repro.models.moe import moe, moe_capacity, moe_spec
from repro.models.transformer import _apply_moe
from repro.sharding.ctx import sharding_ctx

cfg = get_config("deepseek-moe-16b", smoke=True)
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
rules = {"batch": ("data",), "expert_buf": "model"}
params = init_params(moe_spec(cfg), jax.random.key(0))
x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model), jnp.float32)

with jax.default_matmul_precision("highest"):
    y_cap, aux_cap = jax.jit(lambda p, x: moe_capacity(p, x, cfg))(params, x)
    y_dropless, _ = jax.jit(lambda p, x: moe(p, x, cfg))(params, x)
    with mesh, sharding_ctx(mesh, rules):
        layer = jax.jit(lambda p, x: _apply_moe(p, x, cfg))
        y, aux = layer(params, x)
        text = layer.lower(params, x).as_text()
        y_mesh, _ = jax.jit(lambda p, x: moe(p, x, cfg))(params, x)
        held = dataclasses.replace(cfg, held_experts=(0, 2))
        try:
            _apply_moe(init_params(moe_spec(held), jax.random.key(0)), x, held)
            refused = False
        except ValueError:
            refused = True

# the model's layer on the mesh is the static-capacity dispatch, sharded at its seams
np.testing.assert_allclose(np.asarray(y), np.asarray(y_cap), rtol=1e-5, atol=1e-5)
assert float(aux["dropped_frac"]) == float(aux_cap["dropped_frac"])
assert float(aux["held_tokens"]) == 4 * 16 * cfg.top_k
assert text.count('<@mesh, [{"model"}, {}, {}]>') == 2  # the expert buffer, in and out
assert f'<@mesh, [{{"data"}}, {{}}]> : tensor<{4 * 16 * cfg.top_k}x' in text  # the slots, over the batch
# the dropless layer, called under the mesh, computes what it computes on one device
np.testing.assert_allclose(np.asarray(y_mesh), np.asarray(y_dropless), rtol=1e-5, atol=1e-5)
assert refused
print("OK")
"""


def test_expert_layer_on_a_mesh_of_8_devices():
    """On a mesh of several devices the model's expert layer is the
    static-capacity dispatch with its sharding constraints, as it was before
    the dropless one came; the dropless layer stays right under the mesh; and
    a held share refuses the mesh."""
    import subprocess
    import sys

    r = subprocess.run([sys.executable, "-c", MESH_SCRIPT], cwd=REPO, capture_output=True, text=True, timeout=420,
                       env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"), "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout[-3000:] + "\n" + r.stderr[-3000:]
    assert "OK" in r.stdout


def test_balance_term_per_sequence_and_its_weight():
    """With ``seq_aux`` the balance term is the batch-level term of each
    sequence, averaged over the sequences; the loss weighs it by
    ``aux_loss_alpha``."""
    cfg = moe_cfg(n_shared_experts=2)
    params = init_params(moe_spec(cfg), jax.random.key(9))
    x = jax.random.normal(jax.random.key(10), (3, 16, cfg.d_model))
    per_seq = [float(moe(params, x[i:i + 1], cfg)[1]["lb_loss"]) for i in range(3)]
    seq = dataclasses.replace(cfg, seq_aux=True)
    assert float(moe(params, x, seq)[1]["lb_loss"]) == pytest.approx(np.mean(per_seq), rel=1e-6)
    assert float(moe_capacity(params, x, seq)[1]["lb_loss"]) == pytest.approx(np.mean(per_seq), rel=1e-6)
    assert float(moe(params, x, cfg)[1]["lb_loss"]) != pytest.approx(np.mean(per_seq), rel=1e-6)

    lite = get_config("deepseek-v2-lite", smoke=True)
    assert (lite.seq_aux, lite.aux_loss_alpha) == (True, 0.001)
    model = Model(lite)
    t = jax.random.randint(jax.random.key(11), (2, 33), 0, lite.vocab)
    batch = {"tokens": t[:, :-1], "labels": t[:, 1:], "loss_mask": jnp.ones((2, 32))}
    total, aux = model.loss(model.init(jax.random.key(12)), batch)
    assert float(aux["lb_loss"]) > 0
    assert float(total) == pytest.approx(float(aux["ce"] + aux["z_loss"] + 0.001 * aux["lb_loss"]), rel=1e-6)


def test_yarn_matches_the_published_formulas():
    """DeepSeek-V2-Lite's YaRN, written out: dim 64, theta 10000, factor 40,
    original context 4096, beta 32 and 1, mscale 0.707."""
    dim, theta, factor, L = 64, 10000.0, 40.0, 4096
    low = math.floor(dim * math.log(L / (32 * 2 * math.pi)) / (2 * math.log(theta)))
    high = math.ceil(dim * math.log(L / (1 * 2 * math.pi)) / (2 * math.log(theta)))
    assert (low, high) == (10, 23)
    want = []
    for i in range(dim // 2):
        extra = theta ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / factor * ramp + extra * (1 - ramp))
    np.testing.assert_allclose(np.asarray(yarn_freqs(dim, theta, factor, L, 32.0, 1.0)), want, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40.0, 0.707) == pytest.approx(m) and m == pytest.approx(1.2608, abs=1e-4)
    assert mla_scale(get_config("deepseek-v2-lite")) == pytest.approx(m * m / math.sqrt(192))


def test_deepseek_v2_lite_trains_on_the_cpu():
    cfg = get_config("deepseek-v2-lite", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    t = jax.random.randint(jax.random.key(1), (2, 33), 0, cfg.vocab)
    batch = {"tokens": t[:, :-1], "labels": t[:, 1:], "loss_mask": jnp.ones((2, 32))}
    step = jax.jit(make_train_step(model, cosine_schedule(3e-3, warmup_steps=0, total_steps=100)))
    opt = adamw_init(params)
    losses = []
    for _ in range(4):
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert all(math.isfinite(x) for x in losses) and losses[-1] < losses[0]
    # 2 expert layers x 64 tokens x top 3, all 8 experts held
    assert float(metrics["moe_held_tokens"]) == 2 * 64 * 3
    assert float(metrics["moe_load_max"]) >= 1.0


def test_full_config_is_the_published_model():
    cfg = get_config("deepseek-v2-lite")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab, cfg.first_dense, cfg.dense_d_ff) == (
        27, 2048, 16, 102400, 1, 10944)
    assert (cfg.kv_lora_rank, cfg.head_dim, cfg.qk_rope_dim, cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.n_shared_experts, cfg.moe_d_ff, cfg.norm_topk_prob) == (64, 6, 2, 1408, False)
    assert 15.6e9 < cfg.n_params() < 15.8e9  # 15.7B published


def test_mla_has_no_decode_path():
    cfg = get_config("deepseek-v2-lite", smoke=True)
    with pytest.raises(NotImplementedError, match="latent"):
        Model(cfg).init_decode_state(1, 16)
    ok, why = shape_applicable(cfg, SHAPES["decode_32k"])
    assert not ok and "latent" in why
    assert shape_applicable(cfg, SHAPES["train_4k"])[0]


#: sha256 of granite's lowered train step at its cell's sizes (batch 1 x 4096)
#: on the CPU's XLA attention path, source locations left out, as the parent
#: of the MLA and held-expert layers lowered it.
GRANITE_STEP_SHA256 = "190be220db3e531f29c790b98589223d1bd2c60f29f96b734f9f5d3111b4fdc8"


def granite_step_text(sharding=None) -> str:
    """granite-3-8b.1chip's train step, lowered as the trainer lowers it."""
    with open(os.path.join(REPO, "chipbench", "configs", "granite-3-8b.1chip.json")) as f:
        fields = json.load(f)["model"]
    cfg = ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in fields.items()})
    model = Model(cfg)
    step = jax.jit(make_train_step(model, cosine_schedule(3e-4, warmup_steps=0, total_steps=10**9), AdamWConfig()),
                   donate_argnums=(0, 1))
    params = jax.eval_shape(model.init, jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 4096), jnp.int32),
             "labels": jax.ShapeDtypeStruct((1, 4096), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((1, 4096), jnp.float32)}
    args = (params, jax.eval_shape(adamw_init, params), batch)
    if sharding is not None:
        args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), args)
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        return step.lower(*args).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)


def test_granite_train_step_lowers_as_before():
    assert hashlib.sha256(granite_step_text().encode()).hexdigest() == GRANITE_STEP_SHA256

"""The DeepSeek-V2 family (``chipbench/reference/deepseek_v2.py``) against the
program at small sizes on the CPU: latent attention and the held experts in
float32, the shares of the expert layer against the uncut layer, the whole
loss in the program's bfloat16, YaRN, and the cell's pinned yardstick.
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import flops, harness  # noqa: E402
from chipbench.reference import deepseek_v2 as ref  # noqa: E402
from chipbench.reference import layout  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.models import attention as p_attention  # noqa: E402
from repro.models import moe as p_moe  # noqa: E402
from repro.models.modules import yarn_freqs  # noqa: E402

CELL = "train.deepseek-v2-lite.s4096"
# float32 through equivalent formulas (an online softmax in blocks, sums in
# another order): a few ulps a step.
F32_TOL = 1e-4


def smoke(**kw):
    """The program's smoke configuration (YaRN on, 8 experts, top 3) and its dict."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite", smoke=True), **kw)
    return cfg, dataclasses.asdict(cfg)


def moe_layer(params):
    return jax.tree.map(lambda a: a[0], params["layers"]["scan"]["block0"])


def close(a, b, tol=F32_TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)) <= tol


def test_mla_matches_program_in_float32():
    cfg, cd = smoke()
    p = moe_layer(layout.init_params(ref, cd, jax.random.key(1)))["attn"]
    x = jax.random.normal(jax.random.key(2), (2, 32, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))
    with jax.default_matmul_precision("highest"):
        f_prog = lambda q: p_attention.mla(q, x, cfg, pos)  # noqa: E731
        f_ref = lambda q: ref.mla(q, x, cd, q_block=8)  # noqa: E731
        assert close(f_prog(p), f_ref(p))
        g_prog = jax.grad(lambda q: jnp.sum(jnp.sin(f_prog(q))))(p)
        g_ref = jax.grad(lambda q: jnp.sum(jnp.sin(f_ref(q))))(p)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref), strict=True):
        assert close(a, b)


def test_held_experts_match_program_in_float32():
    """Experts 2-5 of 8 with unnormalised weights: the output, the balance
    term and every gradient."""
    cfg, cd = smoke(held_experts=(2, 6))
    p = moe_layer(layout.init_params(ref, cd, jax.random.key(3)))["moe"]
    x = jax.random.normal(jax.random.key(4), (2, 32, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        (y, aux), (y_ref, counts, prob_sum) = p_moe.moe(p, x, cfg), ref.moe(p, x, cd)
        assert close(y, y_ref)
        assert float(aux["lb_loss"]) == pytest.approx(float(ref.balance(counts, prob_sum, cd)), rel=1e-6)

        def f_prog(q):
            y, aux = p_moe.moe(q, x, cfg)
            return jnp.sum(jnp.sin(y)) + aux["lb_loss"]

        def f_ref(q):
            y, counts, prob_sum = ref.moe(q, x, cd)
            return jnp.sum(jnp.sin(y)) + ref.balance(counts, prob_sum, cd)

        g_prog, g_ref = jax.grad(f_prog)(p), jax.grad(f_ref)(p)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref), strict=True):
        assert close(a, b)


def test_shares_add_up_to_the_uncut_layer():
    """Eight chips each hold 2 of 16 experts: the program's shares, each
    computing its own experts' part with the shared experts counted once,
    add up to the reference's uncut layer; every share gives the same
    balance term, which is the uncut layer's."""
    cfg, cd = smoke(n_experts=16, top_k=6)
    p = moe_layer(layout.init_params(ref, cd, jax.random.key(5)))["moe"]
    x = jax.random.normal(jax.random.key(6), (2, 32, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want, counts, prob_sum = ref.moe(p, x, cd)
        bal = ref.balance(counts, prob_sum, cd)
        shared = ref.transformer.mlp(p["shared"], x)
        total, held = shared, 0.0
        for lo in range(0, 16, 2):
            share = dataclasses.replace(cfg, held_experts=(lo, lo + 2))
            q = dict(p, **{k: p[k][lo:lo + 2] for k in ("wi", "wg", "wo")})
            y, aux = p_moe.moe(q, x, share)
            total = total + (y - shared)
            held += float(aux["held_tokens"])
            assert float(aux["lb_loss"]) == pytest.approx(float(bal), rel=1e-6)
    assert close(total, want)
    assert held == 2 * 32 * 6  # every slot routed to exactly one share


def test_yarn_matches_program():
    _, cd = smoke()
    full = dataclasses.asdict(get_config("deepseek-v2-lite"))
    for c in (cd, full):
        want = yarn_freqs(c["qk_rope_dim"], c["rope_theta"], c["yarn_factor"], c["yarn_original_len"],
                          c["yarn_beta_fast"], c["yarn_beta_slow"])
        assert close(ref.rope_freqs(c), want, 1e-6)
    assert ref.score_scale(full) == pytest.approx(p_attention.mla_scale(get_config("deepseek-v2-lite")))


def test_model_loss_matches_program():
    """The program in its own bfloat16 against the float32 reference, experts
    2-5 of 8 held: the loss (with the balance term) within 1e-3 relative."""
    cfg, cd = smoke(held_experts=(2, 6))
    params = layout.init_params(ref, cd, jax.random.key(7))
    model = Model(cfg)
    want = jax.eval_shape(model.init, jax.random.key(0))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    assert all(a.shape == b.shape for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(params), strict=True))
    t = jax.random.randint(jax.random.key(8), (2, 33), 0, cfg.vocab)
    batch = {"tokens": t[:, :-1], "labels": t[:, 1:], "loss_mask": jnp.ones((2, 32))}
    prog, _ = model.loss(params, batch)
    with jax.default_matmul_precision("highest"):
        got = ref.loss(params, batch, cd)
    assert abs(float(prog) - float(got)) <= 1e-3 * abs(float(got))


def cell_model():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    _, config, traffic = harness.resolve(bench, CELL)
    return config, traffic


def test_cell_holds_what_the_program_holds():
    """535M parameters, by the reference's layout and by the program."""
    from chipbench.drivers.train import model_config

    config, _ = cell_model()
    model = config["model"]
    assert model["held_experts"] == [0, 8] and model["n_experts"] == 64
    shapes = layout.tree_shapes(ref, model)
    leaves = jax.tree.leaves(shapes, is_leaf=lambda t: isinstance(t, tuple) and isinstance(t[0], tuple))
    n = sum(math.prod(s) for s, _ in leaves)
    assert n == Model(model_config(model)).n_params == 535060992
    assert ref.layer_params(model, "mla", "dense_mlp") == {"attention": 13762560, "mlp": 67239936}
    assert ref.layer_params(model, "mla", "moe") == {"attention": 13762560, "moe": 131072 + 17301504 + 69206016}


#: The cell's yardstick: required operations and least bytes, as this PR sets them.
DEEPSEEK = {
    "train_flops_per_token": 1862347776.0,
    "flops": {"attention": 11919792537600.0, "mlp": 6609954668544.0, "moe": 9405978378240.0},
    "bytes": {"attention": 1892679680.0, "mlp": 806354944.0, "moe": 3846176768.0},
    "experts": (2551210573824.0, 3019898880.0),
}


@pytest.mark.parametrize("scope", ["attention", "mlp", "moe"])
def test_deepseek_yardstick_is_pinned(scope):
    config, traffic = cell_model()
    fam, model = layout.family(config["reference"]), config["model"]
    B, S = int(traffic["batch"]), int(traffic["seq_len"])
    assert fam is ref and (B, S) == (4, 4096)
    assert flops.train_flops_per_token(fam, model, S) == DEEPSEEK["train_flops_per_token"]
    assert flops.scope_flops_per_step(fam, model, scope, B, S) == DEEPSEEK["flops"][scope]
    assert flops.scope_bytes_per_step(fam, model, scope, B, S) == DEEPSEEK["bytes"][scope]
    assert fam.experts_work_per_step(model, B, S) == DEEPSEEK["experts"]


def test_counts_by_hand():
    """One MLA layer with an expert layer at the cell's widths, S = 4096."""
    config, _ = cell_model()
    model = config["model"]
    got = ref.forward_flops_per_token(model, "mla", "moe", 4096)
    # 2 per MLA weight; q.k over 192 and p.v over 128, 16 heads, 4097/2 keys a token
    assert got["attention"] == 2 * 13762560 + 2 * 16 * (192 + 128) * 4097 / 2
    # router 2048*64, shared 3*2048*2816, and 6*8/64 = 0.75 of one expert's 3*2048*1408
    assert got["moe"] == 2 * (2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408)
    assert ref.forward_flops_per_token(model, "mla", "dense_mlp", 4096)["mlp"] == 2 * 3 * 2048 * 10944

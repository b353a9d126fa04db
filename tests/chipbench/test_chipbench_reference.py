"""The benchmark's plain float32 references against the program, at smoke size on the CPU.

Each mixer of the program is run in float32 (its activations follow the
input's dtype), where it must agree with the reference to float32 rounding;
the whole model is compared in the program's own bfloat16, where only the
loss is held to bfloat16 rounding.
"""

import dataclasses
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402
from chipbench.reference import common, layout, transformer, xlstm  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.models import attention as p_attention  # noqa: E402
from repro.models import mlp as p_mlp  # noqa: E402
from repro.models import xlstm as p_xlstm  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.optim import adamw_update as p_adamw_update  # noqa: E402

# float32 through different but equivalent formulas (chunkwise against
# recurrent, a stabiliser against none): a few ulps a step, summed over 32.
F32_TOL = 1e-4


def smoke(arch):
    cfg = get_config(arch, smoke=True)
    return cfg, dataclasses.asdict(cfg)


def block(params, j, name):
    return jax.tree.map(lambda a: a[0], params["layers"]["scan"][f"block{j}"][name])


def close(a, b, tol=F32_TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)) <= tol


@pytest.mark.parametrize("kind,j,ref", [("slstm", 0, xlstm.slstm), ("mlstm", 1, xlstm.mlstm),
                                        ("mlstm", 1, xlstm.mlstm_recurrent)],
                         ids=["slstm", "mlstm_parallel", "mlstm_recurrent"])
def test_xlstm_mixers_match_program_in_float32(kind, j, ref):
    cfg, cd = smoke("xlstm-125m")
    p = block(layout.init_params(xlstm, cd, jax.random.key(1)), j, kind)
    x = jax.random.normal(jax.random.key(2), (2, 32, cfg.d_model))
    prog = getattr(p_xlstm, kind)
    with jax.default_matmul_precision("highest"):
        f_prog = lambda q: jnp.sum(jnp.sin(prog(q, x, cfg)[0]))  # noqa: E731
        f_ref = lambda q: jnp.sum(jnp.sin(ref(q, x, cd)))  # noqa: E731
        assert close(prog(p, x, cfg)[0], ref(p, x, cd))
        g_prog, g_ref = jax.grad(f_prog)(p), jax.grad(f_ref)(p)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref), strict=True):
        assert close(a, b)


def test_mlstm_parallel_form_is_the_recurrent_form():
    """Both forms of the reference, over several blocks of queries and with
    forget gates that decay far (the stabilisers of the two forms differ)."""
    cfg, cd = smoke("xlstm-125m")
    p = block(layout.init_params(xlstm, cd, jax.random.key(5)), 1, "mlstm")
    p = dict(p, wf=p["wf"] * 8.0, wi=p["wi"] * 8.0)
    x = jax.random.normal(jax.random.key(6), (2, 64, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        par = lambda q: xlstm.mlstm(q, x, cd, q_block=16)  # noqa: E731
        rec = lambda q: xlstm.mlstm_recurrent(q, x, cd)  # noqa: E731
        assert close(par(p), rec(p))
        g_par = jax.grad(lambda q: jnp.sum(jnp.sin(par(q))))(p)
        g_rec = jax.grad(lambda q: jnp.sum(jnp.sin(rec(q))))(p)
    for a, b in zip(jax.tree.leaves(g_par), jax.tree.leaves(g_rec), strict=True):
        assert close(a, b)


def test_attention_and_mlp_match_program_in_float32():
    cfg, cd = smoke("granite-3-8b")
    params = layout.init_params(transformer, cd, jax.random.key(1))
    pa, pm = block(params, 0, "attn"), block(params, 0, "mlp")
    x = jax.random.normal(jax.random.key(2), (2, 32, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))
    with jax.default_matmul_precision("highest"):
        assert close(p_attention.attention(pa, x, cfg, pos), transformer.attention(pa, x, cd, q_block=8))
        assert close(p_mlp.mlp(pm, x, act=cfg.act), transformer.mlp(pm, x))


@pytest.mark.parametrize("arch,ref", [("xlstm-125m", xlstm), ("granite-3-8b", transformer)])
def test_model_loss_matches_program(arch, ref):
    """The program in its own bfloat16 against the float32 reference: the
    loss is an average over every token, so bf16 rounding of single
    activations (2**-9 relative) leaves it within 1e-3 relative."""
    cfg, cd = smoke(arch)
    params = layout.init_params(ref, cd, jax.random.key(3))
    model = Model(cfg)
    want = jax.eval_shape(model.init, jax.random.key(0))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    t = jax.random.randint(jax.random.key(4), (2, 33), 0, cfg.vocab)
    batch = {"tokens": t[:, :-1], "labels": t[:, 1:], "loss_mask": jnp.ones((2, 32))}
    prog, _ = model.loss(params, batch)
    with jax.default_matmul_precision("highest"):
        got = ref.loss(params, batch, cd)
    assert abs(float(prog) - float(got)) <= 1e-3 * abs(float(got))


def test_blocked_loss_is_the_plain_loss():
    h = jax.random.normal(jax.random.key(0), (64, 16))
    w = jax.random.normal(jax.random.key(1), (16, 40))
    labels = jax.random.randint(jax.random.key(2), (64,), 0, 40)
    mask = (jnp.arange(64) % 3 != 0).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits = h @ w
        lse = jax.nn.logsumexp(logits, axis=-1)
        nll = lse - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        plain = jnp.sum(nll * mask) / jnp.sum(mask) + common.Z_LOSS * jnp.sum(lse**2 * mask) / jnp.sum(mask)
        assert close(common.lm_loss(h, w, labels, mask, rows=16), plain, 1e-6)


def test_adamw_matches_program():
    params = {"a": jax.random.normal(jax.random.key(0), (8, 4)), "b": jax.random.normal(jax.random.key(1), (4,))}
    grads = jax.tree.map(lambda p: 3.0 * jnp.cos(p), params)  # global norm > 1: clipping is on
    zeros = jax.tree.map(jnp.zeros_like, params)
    opt = {"step": jnp.zeros((), jnp.int32), "m": zeros, "v": zeros}
    lr = common.learning_rate(3, 3e-3, 10, 10**9)
    c = common.ADAMW
    cfg = AdamWConfig(b1=c["b1"], b2=c["b2"], eps=c["eps"], weight_decay=c["weight_decay"], clip_norm=c["clip_norm"])
    assert cfg == AdamWConfig()  # the program's defaults are the configurations' AdamW
    p_prog, o_prog, _ = p_adamw_update(grads, opt, params, lr=lr, cfg=cfg)
    p_ref, m_ref, v_ref, _ = common.adamw_update(params, grads, zeros, zeros, 1, lr)
    for a, b in zip(jax.tree.leaves((p_prog, o_prog["m"], o_prog["v"])), jax.tree.leaves((p_ref, m_ref, v_ref)),
                    strict=True):
        assert close(a, b, 1e-6)


def test_learning_rate_matches_program():
    from repro.optim import cosine_schedule

    prog = cosine_schedule(3e-3, warmup_steps=10, total_steps=100)
    for step in (0, 1, 5, 10, 40, 100, 200):
        assert abs(float(prog(step)) - common.learning_rate(step, 3e-3, 10, 100)) <= 1e-9


#: sha256 (first 16 hex digits) of each leaf's float32 bytes of the granite
#: cell's weights at its family's TINY widths, seed key 20261018, made as the
#: driver makes them (one jitted call), as the parent of the family modules
#: made them.  The benchmark's weights may not move under a change to how the
#: layout is found.
GRANITE_TINY_WEIGHTS = {
    "['embed']['table']": "270bdafbff050edf",
    "['final_norm']['scale']": "177489762c976603",
    "['layers']['scan']['block0']['attn']['wk']": "1cfc1caabd181697",
    "['layers']['scan']['block0']['attn']['wo']": "b28afc71ec251fd0",
    "['layers']['scan']['block0']['attn']['wq']": "7cf21883b50499d1",
    "['layers']['scan']['block0']['attn']['wv']": "4ac3a42cc450b242",
    "['layers']['scan']['block0']['mlp']['wg']": "d19be147d43a956b",
    "['layers']['scan']['block0']['mlp']['wi']": "f860938b68fd3f60",
    "['layers']['scan']['block0']['mlp']['wo']": "691f8526e9f4c3bc",
    "['layers']['scan']['block0']['norm1']['scale']": "f083f4263f2499a1",
    "['layers']['scan']['block0']['norm2']['scale']": "1f670177f6997432",
    "['lm_head']['w']": "f687413d40639c76",
}


def test_granite_weights_are_pinned():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    _, config, _ = harness.resolve(bench, "train.granite-3-8b.s4096")
    fam = layout.family(config["reference"])
    model = dict(config["model"], **fam.TINY)
    params = jax.jit(lambda k: layout.init_params(fam, model, k))(jax.random.key(20261018))
    got = {jax.tree_util.keystr(k): hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == GRANITE_TINY_WEIGHTS

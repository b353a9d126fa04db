"""Device-plane tree tests: HLO parsing, attribution, cost metrics."""

import random

import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    build_device_tree,
    collective_summary,
    parse_hlo_module,
    tree_from_compiled,
)
from repro.core.hlo_tree import (
    _DTYPE_BYTES,
    DEVICE_TREE_SCHEMA,
    HloOp,
    load_device_tree,
    save_device_tree,
)


def compile_fn(fn, *args):
    return jax.jit(fn).lower(*args).compile()


class TestParser:
    def test_parse_simple_module(self):
        text = """HloModule test
ENTRY %main (p0: f32[4,8]) -> f32[4,8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  ROOT %exp = f32[4,8]{1,0} exponential(%p0), metadata={op_name="jit(f)/exp"}
}
"""
        comps = parse_hlo_module(text)
        assert "main" in comps
        ops = comps["main"].ops
        assert ops["exp"].opcode == "exponential"
        assert ops["exp"].op_name == "jit(f)/exp"
        assert ops["exp"].shapes == [("f32", (4, 8))]
        assert ops["exp"].operands == ["p0"]

    def test_parse_tuple_and_trip_count(self):
        text = """HloModule test
%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  ROOT %t = (s32[], f32[8]{0}) tuple(%p)
}
%cond (p2: (s32[], f32[8])) -> pred[] {
  %p2 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}
ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %init = (s32[], f32[8]{0}) tuple(%a)
  %w = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"12"}}
  ROOT %out = f32[8]{0} get-tuple-element(%w), index=1
}
"""
        comps = parse_hlo_module(text)
        w = comps["main"].ops["w"]
        assert w.opcode == "while"
        assert w.trip_count == 12
        assert "body" in w.called and "cond" in w.called

    def test_tpu_loop_and_fused_convolution(self):
        """TPU HLO: tiled layouts, a scan whose trip count is only in its
        condition, and a matmul lowered to a convolution inside a fusion."""
        text = """HloModule tpu
%fused_mm (param_0: bf16[8,768], param_1: bf16[768,256]) -> bf16[8,256] {
  %param_0 = bf16[8,768]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1 = bf16[768,256]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.1 = bf16[8,256]{1,0:T(8,128)(2,1)} convolution(%param_0, %param_1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/layers/while/body/mlp/dot_general"}
}
%body (arg: (s32[], bf16[8,768], bf16[768,256])) -> (s32[], bf16[8,768], bf16[768,256]) {
  %arg = (s32[]{:T(128)}, bf16[8,768]{1,0:T(8,128)(2,1)S(1)}, bf16[768,256]{1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %x = bf16[8,768]{1,0:T(8,128)(2,1)S(1)} get-tuple-element(%arg), index=1
  %w = bf16[768,256]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=2
  %fusion.1 = bf16[8,256]{1,0:T(8,128)(2,1)} fusion(%x, %w), kind=kOutput, calls=%fused_mm
  ROOT %t = (s32[]{:T(128)}, bf16[8,768]{1,0:T(8,128)(2,1)S(1)}, bf16[768,256]{1,0:T(8,128)(2,1)}) tuple(%i, %x, %w)
}
%cond (arg.1: (s32[], bf16[8,768], bf16[768,256])) -> pred[] {
  %constant.3 = s32[]{:T(128)} constant(3)
  %arg.1 = (s32[]{:T(128)}, bf16[8,768]{1,0:T(8,128)(2,1)S(1)}, bf16[768,256]{1,0:T(8,128)(2,1)}) parameter(0)
  %gte = s32[]{:T(128)} get-tuple-element(%arg.1), index=0
  ROOT %lt.1 = pred[]{:T(512)} compare(%gte, %constant.3), direction=LT, metadata={op_name="jit(step)/layers/while/cond/lt"}
}
ENTRY %main (x: bf16[8,768], w: bf16[768,256]) -> bf16[8,768] {
  %x = bf16[8,768]{1,0:T(8,128)(2,1)} parameter(0)
  %w = bf16[768,256]{1,0:T(8,128)(2,1)} parameter(1)
  %zero = s32[]{:T(128)} constant(0)
  %init = (s32[]{:T(128)}, bf16[8,768]{1,0:T(8,128)(2,1)S(1)}, bf16[768,256]{1,0:T(8,128)(2,1)}) tuple(%zero, %x, %w)
  %loop = (s32[]{:T(128)}, bf16[8,768]{1,0:T(8,128)(2,1)S(1)}, bf16[768,256]{1,0:T(8,128)(2,1)}) while(%init), condition=%cond, body=%body
  ROOT %out = bf16[8,768]{1,0:T(8,128)(2,1)} get-tuple-element(%loop), index=1
}
"""
        comps = parse_hlo_module(text)
        assert comps["main"].ops["loop"].trip_count == 3
        assert comps["main"].ops["init"].shapes[1] == ("bf16", (8, 768))
        tree = build_device_tree(text)
        assert tree.total("flops") == 3 * 2 * 8 * 768 * 256
        # The flops sit at the inner convolution's own op_name, not the fusion's.
        leaves = [(tuple(p), n.metrics["flops"]) for p, n in tree.root.walk() if n.metrics.get("flops") and not n.children]
        assert len(leaves) == 1
        path, flops = leaves[0]
        assert flops == tree.total("flops")
        assert "mlp" in path and "fusion" not in path

    def test_fusion_moves_its_slices_not_whole_buffers(self):
        """A fusion that slices one operand and updates another in place
        (a TPU scan body) moves the slice and twice the update."""
        text = """HloModule tpu
%fused (param_0: f32[64,1024], param_1: s32[], param_2: f32[64,1024]) -> f32[64,1024] {
  %param_0 = f32[64,1024]{1,0:T(8,128)} parameter(0)
  %param_1 = s32[]{:T(128)} parameter(1)
  %param_2 = f32[64,1024]{1,0:T(8,128)} parameter(2)
  %zero = s32[]{:T(128)} constant(0)
  %row = f32[1,1024]{1,0:T(1,128)} dynamic-slice(%param_0, %param_1, %zero), dynamic_slice_sizes={1,1024}
  %twice = f32[1,1024]{1,0:T(1,128)} add(%row, %row)
  ROOT %dus = f32[64,1024]{1,0:T(8,128)} dynamic-update-slice(%param_2, %twice, %param_1, %zero)
}
ENTRY %main (x: f32[64,1024], i: s32[], acc: f32[64,1024]) -> f32[64,1024] {
  %x = f32[64,1024]{1,0:T(8,128)} parameter(0)
  %i = s32[]{:T(128)} parameter(1)
  %acc = f32[64,1024]{1,0:T(8,128)} parameter(2)
  ROOT %fusion = f32[64,1024]{1,0:T(8,128)} fusion(%x, %i, %acc), kind=kLoop, calls=%fused
}
"""
        row = 1024 * 4
        # read: the row of x, the index; written: the updated row, twice.
        assert build_device_tree(text).total("bytes") == row + 4 + 2 * row

    def test_real_compiled_module_parses(self):
        def f(x, w):
            with jax.named_scope("mlp"):
                return jax.nn.relu(x @ w).sum()

        comp = compile_fn(f, jnp.ones((8, 16)), jnp.ones((16, 32)))
        comps = parse_hlo_module(comp.as_text())
        assert comps
        all_ops = [op for c in comps.values() for op in c.ops.values()]
        assert any(op.opcode == "dot" for op in all_ops)


class TestAttribution:
    def test_named_scope_paths_in_tree(self):
        def f(x, w1, w2):
            with jax.named_scope("layer0"):
                with jax.named_scope("mlp"):
                    h = jax.nn.relu(x @ w1)
            with jax.named_scope("head"):
                return (h @ w2).sum()

        comp = compile_fn(f, jnp.ones((8, 16)), jnp.ones((16, 32)), jnp.ones((32, 4)))
        tree = tree_from_compiled(comp)
        flat = tree.flatten("flops")
        assert flat.get("mlp", 0) > 0
        assert flat.get("head", 0) > 0

    def test_dot_flops_exact(self):
        def f(x, w):
            return x @ w

        m, k, n = 8, 16, 32
        comp = compile_fn(f, jnp.ones((m, k)), jnp.ones((k, n)))
        tree = tree_from_compiled(comp)
        assert tree.total("flops") == pytest.approx(2 * m * k * n)

    def test_flops_match_xla_cost_analysis(self):
        def f(x, w1, w2):
            return ((x @ w1) @ w2).sum()

        comp = compile_fn(f, jnp.ones((32, 64)), jnp.ones((64, 128)), jnp.ones((128, 16)))
        tree = tree_from_compiled(comp)
        ca = comp.cost_analysis()
        if isinstance(ca, (list, tuple)):  # older jax returns [per-device dict]
            ca = ca[0]
        # Dots dominate; our dot-only count must be within 5% of XLA's total.
        assert tree.total("flops") == pytest.approx(float(ca["flops"]), rel=0.05)

    def test_scan_trip_count_multiplies(self):
        n_layers = 7

        def layer(x, w):
            return jnp.tanh(x @ w)

        def f(x, ws):
            def body(c, w):
                return layer(c, w), None

            y, _ = jax.lax.scan(body, x, ws)
            return y.sum()

        d = 16
        comp = compile_fn(f, jnp.ones((4, d)), jnp.ones((n_layers, d, d)))
        tree = tree_from_compiled(comp)
        got = tree.total("flops")
        want = n_layers * 2 * 4 * d * d
        assert got == pytest.approx(want, rel=0.01)

    def test_bytes_metric_positive_and_sane(self):
        def f(x):
            return (x * 2.0).sum()

        x = jnp.ones((1024, 1024), jnp.float32)
        comp = compile_fn(f, x)
        tree = tree_from_compiled(comp)
        b = tree.total("bytes")
        assert b >= x.size * 4  # must at least read the input
        assert b < 20 * x.size * 4  # and not wildly overcount

    def test_unattributed_ops_bucketed(self):
        text = """HloModule t
ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %c = f32[4]{0} copy(%p0)
}
"""
        tree = build_device_tree(text)
        assert "<unattributed>" in tree.root.children


class TestCollectives:
    def make_sharded(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        if len(jax.devices()) < 2:
            pytest.skip("needs >1 device (run under forced host device count)")
        mesh = jax.make_mesh((2,), ("model",), axis_types=(jax.sharding.AxisType.Auto,))

        def f(x, w):
            return (x @ w).sum()

        xs = jax.ShapeDtypeStruct((8, 16), jnp.float32)
        ws = jax.ShapeDtypeStruct((16, 32), jnp.float32)
        with mesh:
            return (
                jax.jit(
                    f,
                    in_shardings=(
                        NamedSharding(mesh, P(None, "model")),
                        NamedSharding(mesh, P("model", None)),
                    ),
                )
                .lower(xs, ws)
                .compile()
            )

    def test_collective_bytes_counted(self):
        comp = self.make_sharded()
        tree = tree_from_compiled(comp)
        summ = collective_summary(tree)
        # Contracting-dim sharding forces an all-reduce of the f32 partial sums.
        assert summ["total"] > 0
        assert summ.get("all-reduce", 0) > 0

    def test_collective_attribution_under_op_name(self):
        comp = self.make_sharded()
        tree = tree_from_compiled(comp)
        colls = [p for p, n in tree.root.walk() if n.metrics.get("coll_bytes")]
        assert colls  # attributed somewhere under the jit scope, not lost


class TestDtypeBytes:
    @pytest.mark.parametrize("dtype,size", [("bf16", 2), ("f32", 4), ("s8", 1), ("pred", 1), ("f64", 8)])
    def test_table(self, dtype, size):
        assert _DTYPE_BYTES[dtype] == size

    def test_result_bytes_tuple(self):
        op = HloOp("t", "tuple", [("f32", (4, 4)), ("bf16", (8,))], [], None)
        assert op.result_bytes() == 4 * 4 * 4 + 8 * 2


class TestRoundtrip:
    """save_device_tree/load_device_tree must be bit-exact on every metric.

    Property-style: generated modules with *nested* scanned layers (while
    loops carrying known_trip_count) and rng-chosen dims/trip counts, so the
    metric values exercise awkward trip-count-multiplied floats rather than a
    hand-picked happy path.
    """

    @staticmethod
    def _module(t0: int, t1: int, m: int, k: int, n: int, w: int) -> str:
        return f"""HloModule gen
%body1 (p1: (s32[], f32[{w}])) -> (s32[], f32[{w}]) {{
  %p1 = (s32[], f32[{w}]{{0}}) parameter(0)
  %a1 = f32[{m},{k}]{{1,0}} get-tuple-element(%p1), index=1
  %b1 = f32[{k},{n}]{{1,0}} get-tuple-element(%p1), index=1
  %d1 = f32[{m},{n}]{{1,0}} dot(%a1, %b1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="jit(step)/layers/inner/mlp"}}
  %ar1 = f32[{n}]{{0}} all-reduce(%d1), metadata={{op_name="jit(step)/layers/inner/psum"}}
  %ds1 = f32[1,{n}]{{1,0}} dynamic-slice(%d1, %p1), dynamic_slice_sizes={{1,{n}}}, metadata={{op_name="jit(step)/layers/inner/slice"}}
  ROOT %t1 = (s32[], f32[{w}]{{0}}) tuple(%p1)
}}
%cond1 (q1: (s32[], f32[{w}])) -> pred[] {{
  %q1 = (s32[], f32[{w}]{{0}}) parameter(0)
  ROOT %lt1 = pred[] constant(true)
}}
%body0 (p0: (s32[], f32[{w}])) -> (s32[], f32[{w}]) {{
  %p0 = (s32[], f32[{w}]{{0}}) parameter(0)
  %a0 = f32[{m},{k}]{{1,0}} get-tuple-element(%p0), index=1
  %b0 = f32[{k},{n}]{{1,0}} get-tuple-element(%p0), index=1
  %d0 = f32[{m},{n}]{{1,0}} dot(%a0, %b0), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="jit(step)/layers/outer_mlp"}}
  %init1 = (s32[], f32[{w}]{{0}}) tuple(%p0)
  %w1 = (s32[], f32[{w}]{{0}}) while(%init1), condition=%cond1, body=%body1, backend_config={{"known_trip_count":{{"n":"{t1}"}}}}, metadata={{op_name="jit(step)/layers/inner_scan"}}
  ROOT %t0 = (s32[], f32[{w}]{{0}}) tuple(%p0)
}}
%cond0 (q0: (s32[], f32[{w}])) -> pred[] {{
  %q0 = (s32[], f32[{w}]{{0}}) parameter(0)
  ROOT %lt0 = pred[] constant(true)
}}
ENTRY %main (x: f32[{w}]) -> f32[{w}] {{
  %x = f32[{w}]{{0}} parameter(0)
  %init0 = (s32[], f32[{w}]{{0}}) tuple(%x)
  %w0 = (s32[], f32[{w}]{{0}}) while(%init0), condition=%cond0, body=%body0, backend_config={{"known_trip_count":{{"n":"{t0}"}}}}, metadata={{op_name="jit(step)/layers_scan"}}
  ROOT %out = f32[{w}]{{0}} get-tuple-element(%w0), index=1
}}
"""

    @staticmethod
    def _snapshot(tree):
        return {
            tuple(path): (dict(node.metrics), dict(node.self_metrics))
            for path, node in tree.root.walk()
        }

    @pytest.mark.parametrize("seed", range(5))
    def test_save_load_exact(self, seed, tmp_path):
        rng = random.Random(seed)
        t0, t1 = rng.randint(2, 13), rng.randint(2, 9)
        m, k, n = rng.randint(3, 37), rng.randint(3, 37), rng.randint(3, 37)
        tree = build_device_tree(self._module(t0, t1, m, k, n, rng.randint(5, 101)))
        # The generated module must exercise all four metric keys + a per-kind
        # collective counter before the roundtrip assertion means anything.
        root = tree.root.metrics
        for key in ("flops", "bytes", "coll_bytes", "ops"):
            assert root.get(key, 0) > 0, key
        assert root.get("coll_bytes::all-reduce", 0) > 0

        path = str(tmp_path / "device_tree.json")
        save_device_tree(tree, path, meta={"seed": seed})
        loaded = load_device_tree(path)
        assert self._snapshot(loaded) == self._snapshot(tree)  # exact, every key

    def test_nested_trip_counts_multiply_exactly(self):
        base = build_device_tree(self._module(1, 1, 8, 16, 4, 64))
        scaled = build_device_tree(self._module(5, 3, 8, 16, 4, 64))
        bf, sf = base.flatten("flops"), scaled.flatten("flops")
        # inner dot sits under both whiles: x(5*3); outer dot under one: x5
        assert sf["mlp"] == pytest.approx(15 * bf["mlp"], rel=0, abs=0)
        assert sf["outer_mlp"] == pytest.approx(5 * bf["outer_mlp"], rel=0, abs=0)
        bc, sc = base.total("coll_bytes"), scaled.total("coll_bytes")
        assert sc == 15 * bc

    def test_envelope_schema_and_legacy(self, tmp_path):
        import json

        tree = build_device_tree(self._module(2, 2, 4, 4, 4, 8))
        path = str(tmp_path / "device_tree.json")
        save_device_tree(tree, path)
        with open(path) as f:
            doc = json.load(f)
        assert doc["schema"] == DEVICE_TREE_SCHEMA
        # legacy bare-root dumps (pre-envelope) still load
        legacy = str(tmp_path / "legacy.json")
        with open(legacy, "w") as f:
            json.dump(doc["root"], f)
        assert self._snapshot(load_device_tree(legacy)) == self._snapshot(tree)

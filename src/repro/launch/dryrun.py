import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:

* the sharding config is coherent (lower/compile succeeds — sharding
  mismatches, unsupported collectives, and compile-time OOM all fail here);
* the memory plan fits (``compiled.memory_analysis()`` per-device bytes);
* the cost model for §Roofline (``cost_analysis()`` FLOPs/bytes +
  collective bytes parsed from the compiled HLO via the device-plane tree).

NOTE the first two lines of this file: jax locks the device count at first
initialization, so XLA_FLAGS must be set before ANY other import — including
``from repro...``. Do not set this flag globally (tests/benches must see the
real single device).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, get_config, list_archs, shape_applicable  # noqa: E402
from repro.core.hlo_tree import build_device_tree, collective_summary, save_device_tree  # noqa: E402
from repro.core.roofline import PEAKS, V5E_KIND, report_from_artifacts  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_chips  # noqa: E402
from repro.launch.steps import make_serve_step, make_train_step  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.models.modules import abstract_params  # noqa: E402
from repro.optim import AdamWConfig, cosine_schedule  # noqa: E402
from repro.sharding import make_strategy, params_shardings, sharding_ctx  # noqa: E402


def batch_shardings(batch_abs, mesh, batch_axes):
    """Inputs: shard dim 0 (batch) over the data axes; rest replicated."""

    def one(leaf):
        if leaf.shape and leaf.shape[0] % _axes_size(mesh, batch_axes) == 0:
            return NamedSharding(mesh, P(batch_axes, *([None] * (len(leaf.shape) - 1))))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, batch_abs)


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= mesh.shape[a]
    return n


def state_shardings(state_abs, mesh, batch_axes):
    """Decode-state shardings: batch dim over data axes; one wide dim (heads
    preferred, else feature) over 'model'. 'scan'-stacked leaves carry a
    leading layer axis which stays unsharded."""
    model_n = mesh.shape["model"]
    batch_n = _axes_size(mesh, batch_axes)

    def one(path, leaf):
        keys = [getattr(p, "key", "") for p in path]
        dims: list = [None] * len(leaf.shape)
        off = 1 if "scan" in keys else 0  # leading layer-stack axis
        bdim = off
        if len(leaf.shape) > bdim and leaf.shape[bdim] % batch_n == 0:
            dims[bdim] = batch_axes
        # prefer the head axis (rank-4 kv / mlstm-C), else the last wide axis
        prefer = [bdim + 2, bdim + 3, bdim + 1]
        for d in prefer:
            if d < len(leaf.shape) and dims[d] is None and leaf.shape[d] % model_n == 0 and leaf.shape[d] >= model_n:
                dims[d] = "model"
                break
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(one, state_abs)


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    strategy_name: str = "tp_fsdp",
    grad_accum: int = 1,
    remat: str = None,
    chunk_threshold: int = None,
    chunk: int = None,
    moe_impl: str = None,
    attn_cp: bool = False,
    opt_dtype: str = "float32",
    donate: bool = True,
    verbose: bool = True,
    dump_tree: str = None,
) -> dict:
    t0 = time.time()
    cfg = get_config(arch)
    overrides = {}
    if remat is not None:
        overrides["remat"] = remat
    if chunk_threshold is not None:
        overrides["chunk_threshold"] = chunk_threshold
    if chunk is not None:
        overrides["chunk"] = chunk
    if moe_impl is not None:
        overrides["moe_impl"] = moe_impl
    if attn_cp:
        overrides["attn_cp"] = True
    if overrides:
        from dataclasses import replace

        cfg = replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "strategy": strategy_name,
        "grad_accum": grad_accum,
        "overrides": overrides,
    }
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        cell.update(status="skip", reason=why)
        return cell
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh_chips(mesh)
        model = Model(cfg)
        strategy = make_strategy(strategy_name, multi_pod=multi_pod)
        batch_axes = tuple(strategy.act_rules["batch"])
        spec_tree = model.spec()
        params_abs = abstract_params(spec_tree)
        p_sh = params_shardings(spec_tree, strategy, mesh)
        batch_abs = model.input_specs(shape)
        b_sh = batch_shardings(batch_abs, mesh, batch_axes)

        with mesh, sharding_ctx(mesh, strategy.act_rules):
            if shape.kind == "train":
                mdt = jnp.dtype(opt_dtype)
                opt_abs = jax.eval_shape(lambda p: _opt_abstract(p, mdt), params_abs)
                o_sh = {
                    "step": NamedSharding(mesh, P()),
                    "m": p_sh,
                    "v": p_sh,
                }
                step = make_train_step(model, cosine_schedule(3e-4), AdamWConfig(), grad_accum=grad_accum)
                jitted = jax.jit(
                    step,
                    in_shardings=(p_sh, o_sh, b_sh),
                    donate_argnums=(0, 1) if donate else (),
                )
                lowered = jitted.lower(params_abs, opt_abs, batch_abs)
            else:
                if shape.kind == "prefill":
                    def prefill(params, batch):
                        logits, _ = model.forward(params, batch)
                        return jnp.argmax(logits[:, -1], axis=-1)

                    jitted = jax.jit(prefill, in_shardings=(p_sh, b_sh))
                    lowered = jitted.lower(params_abs, batch_abs)
                else:  # decode
                    state_abs = model.abstract_decode_state(shape.global_batch, shape.seq_len)
                    s_sh = state_shardings(state_abs, mesh, batch_axes)
                    step = make_serve_step(model)
                    jitted = jax.jit(
                        step,
                        in_shardings=(p_sh, b_sh, s_sh, NamedSharding(mesh, P())),
                        donate_argnums=(2,) if donate else (),
                    )
                    lowered = jitted.lower(
                        params_abs, batch_abs, state_abs, jax.ShapeDtypeStruct((), jnp.int32)
                    )
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):  # older jax returns [per-device dict]
            ca = ca[0] if ca else {}
        # The production meshes are v5e pods: the artifact is costed for, and
        # says, that kind (the program itself was compiled for CPU devices).
        tree = build_device_tree(compiled.as_text(), step_name=f"{arch}:{shape_name}", device_kind=V5E_KIND)
        colls = collective_summary(tree)
        if dump_tree:
            os.makedirs(os.path.dirname(dump_tree) or ".", exist_ok=True)
            save_device_tree(tree, dump_tree, meta={"arch": arch, "shape": shape_name, "mesh": mesh_name})
        from repro.core.report import breakdown

        component_breakdown = {
            metric: breakdown(tree, level=8, metric=metric, min_share=0.03)[:40]
            for metric in ("flops", "bytes", "coll_bytes")
        }
        rep = report_from_artifacts(
            arch=arch,
            shape=shape_name,
            mesh=mesh_name,
            chips=chips,
            cost_analysis=ca,
            device_tree=tree,
            memory_analysis=ma,
            hw=PEAKS[V5E_KIND],
            model_flops_global=model.model_flops(shape),
        )
        cell.update(
            status="ok",
            chips=chips,
            device_kind=V5E_KIND,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory_analysis={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_bytes_per_device": rep.per_device_hbm_peak,
                "fits_hbm_16g": rep.fits_hbm(),
            },
            cost_analysis={"flops": ca.get("flops", 0.0), "bytes_accessed": ca.get("bytes accessed", 0.0)},
            tree_metrics={"flops": tree.total("flops"), "bytes": tree.total("bytes"), "ops": tree.total("ops")},
            collectives=colls,
            roofline=rep.row(),
            breakdown=component_breakdown,
            n_params=model.n_params,
            n_active_params=model.n_active_params,
        )
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
                  f"(compile {t_compile:.0f}s, dominant={rep.dominant}, "
                  f"t_step={rep.t_step*1e3:.2f}ms, peak={rep.per_device_hbm_peak/2**30:.2f}GiB)")
            print(f"  memory_analysis: {ma}")
            print(f"  cost_analysis: flops={ca.get('flops', 0.0):.3e} bytes={ca.get('bytes accessed', 0.0):.3e}")
            print(f"  collectives: { {k: f'{v:.3e}' for k, v in colls.items()} }")
    except Exception as e:  # noqa: BLE001 — cell failures are data, not crashes
        cell.update(status="fail", error=f"{type(e).__name__}: {e}", trace=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: FAIL {type(e).__name__}: {e}")
    return cell


def _opt_abstract(params_abs, moment_dtype=jnp.float32):
    import jax.numpy as jnp

    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(lambda p: jnp.zeros(p.shape, moment_dtype), params_abs),
        "v": jax.tree.map(lambda p: jnp.zeros(p.shape, moment_dtype), params_abs),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--strategy", default="tp_fsdp")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--chunk-threshold", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--dump-tree", default=None, help="write full device-tree JSON here")
    ap.add_argument("--moe-impl", default=None, choices=["dense", "shard_map"])
    ap.add_argument("--opt-dtype", default="float32", choices=["float32", "bfloat16"])
    ap.add_argument("--attn-cp", action="store_true", help="context-parallel attention q-chunks")
    ap.add_argument("--all", action="store_true", help="run every (arch, shape) cell")
    ap.add_argument("--out", default=None, help="output dir for per-cell JSON")
    args = ap.parse_args()

    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cell = run_cell(
                    arch, shape, mp,
                    strategy_name=args.strategy,
                    grad_accum=args.grad_accum,
                    remat=args.remat,
                    chunk_threshold=args.chunk_threshold,
                    chunk=args.chunk,
                    moe_impl=args.moe_impl,
                    attn_cp=args.attn_cp,
                    opt_dtype=args.opt_dtype,
                    dump_tree=args.dump_tree,
                )
                results.append(cell)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    mesh_tag = "2x16x16" if mp else "16x16"
                    fn = f"{arch}__{shape}__{mesh_tag}__{args.strategy}"
                    if args.grad_accum > 1:
                        fn += f"__ga{args.grad_accum}"
                    if args.remat:
                        fn += f"__remat-{args.remat}"
                    if args.chunk_threshold is not None:
                        fn += f"__ct{args.chunk_threshold}"
                    if args.moe_impl:
                        fn += f"__moe-{args.moe_impl}"
                    if args.opt_dtype != "float32":
                        fn += f"__opt-{args.opt_dtype}"
                    if args.attn_cp:
                        fn += "__cp"
                    with open(os.path.join(args.out, fn + ".json"), "w") as f:
                        json.dump(cell, f, indent=1)
    n_ok = sum(1 for c in results if c["status"] == "ok")
    n_skip = sum(1 for c in results if c["status"] == "skip")
    n_fail = sum(1 for c in results if c["status"] == "fail")
    print(f"\n[dryrun] done: {n_ok} ok, {n_skip} skip(by-rule), {n_fail} fail")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

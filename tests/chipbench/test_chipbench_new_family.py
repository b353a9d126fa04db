"""A model family arrives as new files only.

To a copy of the benchmark (``BENCHMARK.json`` and its ``paths``) the test
adds, from ``data/new-family/``, what a later configuration brings: its
configuration file, its traffic file, its family module and its entries of
``BENCHMARK.json``.  The family's stack has a dense first layer wider than
the others (``first_dense``, ``dense_d_ff``), a scanned pattern of two blocks
and a remainder, which no shipped family lays out.  Run as a script from the
root of such a copy (``python tests/chipbench/test_chipbench_new_family.py
<cell>``), this file resolves the cell there, builds its weights against the
program's tree, reads its ``mfu.train`` and drives a tiny run of it on the CPU
through the shipped harness tests' ``tiny_run``; it prints what it found as
one JSON line.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "new-family")


def _files(root) -> dict[str, str]:
    out = {}
    for d, dirs, names in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for n in names:
            p = os.path.join(d, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def add_family(root) -> list[str]:
    """Add the fixture's files and entries to the benchmark at ``root``; -> its cells."""
    with open(os.path.join(FIXTURE, "entries.json")) as f:
        entries = json.load(f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for key in ("configs", "workloads"):
        bench[key] += entries[key]
    with open(path, "w") as f:
        json.dump(bench, f, indent=2)
    for c in entries["configs"]:
        config = os.path.join(FIXTURE, f"{c['name']}.json")
        shutil.copy(config, os.path.join(root, c["file"]))
        with open(config) as f:
            reference = json.load(f)["reference"]
        shutil.copy(os.path.join(FIXTURE, f"{reference}.py"), os.path.join(root, "chipbench", "reference"))
    for w in entries["workloads"]:
        shutil.copy(os.path.join(FIXTURE, f"{w['traffic']}.json"), os.path.join(root, "chipbench", "workloads"))
    return [w["name"] for w in entries["workloads"]]


def test_a_family_arrives_as_new_files_only(tmp_path):
    root = tmp_path / "bench"
    root.mkdir()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        shipped_bench = json.load(f)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for p in shipped_bench["paths"]:
        shutil.copytree(os.path.join(REPO, p), root / p, ignore=shutil.ignore_patterns("__pycache__"))
    shipped = _files(root)
    (cell,) = add_family(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, os.path.join("tests", "chipbench", os.path.basename(__file__)), cell],
                       cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])

    assert got["family_file"] == os.path.join(str(root), "chipbench", "reference", "dense_prefix.py")
    assert got["tree_matches_program"], got
    assert got["layers"] == {"prefix": ["layer0"], "remainder": ["layer3"], "scan": ["block0", "block1"]}
    # d 32, 2 query heads and 1 kv head of 16, V 128, S 32: the head 2*32*128;
    # attention 2 * (32*2*16*2 + 32*1*16*2) a layer and 2*2*2*16 * 33/2 for the
    # scores and values; MLP 2 * 3*32*96 in the dense layer and 2 * 3*32*64 in
    # each of the three others; training 3x.  Over 64 tokens a second at 1e12.
    per_token = 3 * (2 * 32 * 128 + 4 * (2 * 3072 + 64 * 33) + 2 * 3 * 32 * 96 + 3 * 2 * 3 * 32 * 64)
    assert got["mfu.train"] == 100.0 * per_token * 64 / 1e12
    line = got["line"]
    assert line["correct"] and line["attempted"] > 0, line["checks"]

    after = _files(root)
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    for key, value in shipped_bench.items():  # the shipped entries stay as they were
        assert (bench[key][: len(value)] if isinstance(value, list) else bench[key]) == value, key
    changed = [p for p, h in shipped.items() if p != "BENCHMARK.json" and after.get(p) != h]
    assert not changed, changed


def check(cell: str) -> dict:
    """What a new family gives in the benchmark at the current directory."""
    import jax

    from chipbench import harness
    from chipbench.drivers.train import model_config
    from chipbench.readings import Readings
    from chipbench.reference import layout
    from chipbench.trace import Trace
    from repro.models import Model
    from test_chipbench_harness import tiny_run

    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    c, config, traffic = harness.resolve(bench, cell)
    fam = layout.family(config["reference"])
    model = dict(config["model"], **fam.TINY)
    key = jax.random.key(7)
    want = jax.eval_shape(Model(model_config(model)).init, key)
    got = jax.eval_shape(lambda k: layout.init_params(fam, model, k), key)
    same = jax.tree.structure(want) == jax.tree.structure(got) and all(
        (a.shape, a.dtype) == (b.shape, b.dtype) for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got), strict=True))
    m = Readings(Trace(), steps=1, tokens=64, window_s=1.0, model=model, reference=config["reference"],
                 traffic={"batch": 2, "seq_len": 32}, peaks={"flops": 1e12, "hbm_bytes_per_s": 1e11})
    mfu = harness.load_module(harness.metric_path("mfu.train"), "chipbench_metric_mfu_train").read(m)
    return {"family_file": fam.__file__, "tree_matches_program": same,
            "layers": {g: sorted(t) for g, t in got["layers"].items()}, "mfu.train": mfu, "line": tiny_run(cell)}


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    print(json.dumps(check(sys.argv[1])), flush=True)

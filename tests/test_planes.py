"""Unified host+device plane tests: annotation, serving, CLI, timeline gating.

Covers the merge layer (``core/planes.py``), the ``?plane=`` query plane, the
CLI ``--plane`` flag, and the acceptance contract that merged-plane annotation
metrics survive the timeline seal -> decode -> diff roundtrip and can gate a
``profilerd check`` run.
"""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import pytest

from repro.core import CallTree, EpochMeta, TimelineReader, TimelineWriter, share_regressions
from repro.core.export import export_tree, from_folded, to_folded, to_speedscope
from repro.core.hlo_tree import build_device_tree, save_device_tree
from repro.core.roofline import PEAKS, V5E_KIND, peaks_for
from repro.core.planes import (
    DOMINANT_PREFIX,
    HLO_PREFIX,
    OCCUPANCY,
    PLANES,
    TERM_PREFIX,
    PlaneError,
    annotate_tree,
    default_metric,
    dominant_term,
    missing_device_hint,
    roofline_note,
    select_plane,
)

SRC_ROOT = os.path.join(os.path.dirname(__file__), "..", "src")

# Hand-written compiled-HLO text whose op_name paths mirror the host stacks
# below (scores/gate_proj are compute-heavy dots, top_p is a pure-traffic
# slice, lm_head carries an all-reduce -> three distinct dominant terms).
HLO_TEXT = """HloModule m
ENTRY %main (p0: f32[4096,4096], p1: f32[4096,4096], p2: f32[4096,4096]) -> f32[4096,4096] {
  %p0 = f32[4096,4096]{1,0} parameter(0)
  %p1 = f32[4096,4096]{1,0} parameter(1)
  %p2 = f32[4096,4096]{1,0} parameter(2)
  %scores = f32[4096,4096]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(serve_step)/model/attention/scores"}
  %context = f32[4096,4096]{1,0} dot(%scores, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(serve_step)/model/attention/context"}
  %gate = f32[4096,4096]{1,0} dot(%scores, %context), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(serve_step)/model/mlp/gate_proj"}
  %hs = f32[64,64]{1,0} dynamic-slice(%gate, %p0), dynamic_slice_sizes={64,64}, metadata={op_name="jit(serve_step)/model/lm_head"}
  %head = f32[64,64]{1,0} dot(%hs, %hs), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(serve_step)/model/lm_head"}
  %ar = f32[4096,4096]{1,0} all-reduce(%p2), metadata={op_name="jit(serve_step)/model/lm_head"}
  %tp = f32[1,64]{1,0} dynamic-slice(%gate, %p0), dynamic_slice_sizes={1,64}, metadata={op_name="jit(serve_step)/sampler/top_p"}
  ROOT %out = f32[4096,4096]{1,0} copy(%ar), metadata={op_name="jit(serve_step)/out"}
}
"""


def device_tree() -> CallTree:
    return build_device_tree(HLO_TEXT, device_kind=V5E_KIND)


def host_tree() -> CallTree:
    """A daemon-shaped host tree: frames carry spool origin prefixes."""
    t = CallTree()
    stacks = [
        (["thread::MainThread", "py::serve_step", "py::model", "py::attention", "py::scores"], 40),
        (["thread::MainThread", "py::serve_step", "py::model", "py::attention", "py::context"], 10),
        (["thread::MainThread", "py::serve_step", "py::model", "py::mlp", "py::gate_proj"], 30),
        (["thread::MainThread", "py::serve_step", "py::model", "py::lm_head"], 15),
        (["thread::MainThread", "py::serve_step", "py::sampler", "py::top_p"], 5),
    ]
    for frames, n in stacks:
        for _ in range(n):
            t.add_stack(frames)
    return t


def _descend(tree: CallTree, *names):
    node = tree.root
    for n in names:
        node = node.children[n]
    return node


def _http_get(url: str) -> tuple[int, str]:
    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


class TestAnnotate:
    def test_origin_prefixes_match_device_paths(self):
        merged = annotate_tree(host_tree(), device_tree())
        scores = _descend(
            merged, "thread::MainThread", "py::serve_step", "py::model", "py::attention", "py::scores"
        )
        dev_scores = _descend(device_tree(), "jit(serve_step)", "model", "attention", "scores")
        assert scores.metrics[HLO_PREFIX + "flops"] == dev_scores.total("flops")
        assert scores.metrics[OCCUPANCY] > 0

    def test_root_occupancy_is_one(self):
        merged = annotate_tree(host_tree(), device_tree())
        assert merged.root.metrics[OCCUPANCY] == pytest.approx(1.0)

    def test_unmatched_glue_frames_inherit_child_sums(self):
        merged = annotate_tree(host_tree(), device_tree())
        main = _descend(merged, "thread::MainThread")
        # thread::MainThread matches nothing on the device plane but must
        # aggregate its matched descendants (monotone inclusive metrics).
        child_flops = sum(c.metrics.get(HLO_PREFIX + "flops", 0) for c in main.children.values())
        assert main.metrics[HLO_PREFIX + "flops"] == pytest.approx(child_flops)
        assert main.metrics[HLO_PREFIX + "flops"] > 0

    def test_dominant_terms_by_workload_shape(self):
        merged = annotate_tree(host_tree(), device_tree())
        pre = ("thread::MainThread", "py::serve_step")
        scores = _descend(merged, *pre, "py::model", "py::attention", "py::scores")
        top_p = _descend(merged, *pre, "py::sampler", "py::top_p")
        lm_head = _descend(merged, *pre, "py::model", "py::lm_head")
        assert dominant_term(scores.metrics) == "compute"  # dot-only node
        assert dominant_term(top_p.metrics) == "memory"  # pure-slice node
        assert dominant_term(lm_head.metrics) == "collective"  # all-reduce
        # exactly one dominant::<term> key per annotated node
        for node in (scores, top_p, lm_head):
            assert sum(1 for k in node.metrics if k.startswith(DOMINANT_PREFIX)) == 1

    def test_annotations_survive_json_roundtrip(self):
        merged = annotate_tree(host_tree(), device_tree())
        back = CallTree.from_json(merged.to_json())
        for (path, node), (bpath, bnode) in zip(merged.root.walk(), back.root.walk(), strict=True):
            assert tuple(path) == tuple(bpath)
            assert dict(node.metrics) == dict(bnode.metrics)

    def test_host_tree_not_mutated(self):
        host = host_tree()
        before = host.to_json()
        annotate_tree(host, device_tree())
        assert host.to_json() == before


class TestPeaks:
    def test_known_kind_resolves_to_its_entry(self):
        hw = peaks_for("TPU v5 lite")
        assert hw is PEAKS[V5E_KIND]
        assert (hw.peak_flops, hw.hbm_bw, hw.hbm_bytes) == (197e12, 819e9, 16e9)
        assert roofline_note(device_tree()) is None

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", None])
    def test_kind_without_entry_gets_no_roofline_terms(self, kind):
        device = build_device_tree(HLO_TEXT, device_kind=kind)
        merged = annotate_tree(host_tree(), device)
        keys = {k for _p, n in merged.root.walk() for k in n.metrics}
        assert HLO_PREFIX + "flops" in keys  # the cost counters still graft
        assert not [k for k in keys if k.startswith((TERM_PREFIX, DOMINANT_PREFIX)) or k == OCCUPANCY]
        assert peaks_for(kind) is None
        assert repr(kind) in roofline_note(device)

    def test_kind_travels_with_the_artifact(self, tmp_path):
        from repro.core.hlo_tree import load_device_tree

        path = str(tmp_path / "device_tree.json")
        save_device_tree(build_device_tree(HLO_TEXT, device_kind="cpu"), path, meta={"arch": "x"})
        with open(path) as f:
            assert json.load(f)["meta"] == {"arch": "x", "device_kind": "cpu"}
        assert load_device_tree(path).device_kind == "cpu"


class TestSelectPlane:
    def test_host_passthrough(self):
        host = host_tree()
        assert select_plane(host, None, "host") is host

    def test_unknown_plane_is_value_error(self):
        with pytest.raises(ValueError, match="unknown plane"):
            select_plane(host_tree(), None, "bogus")

    def test_missing_device_artifact_raises_with_remedy(self):
        for plane in ("device", "merged"):
            with pytest.raises(PlaneError, match="device_tree.json"):
                select_plane(host_tree(), None, plane, profile="/some/profile")
        hint = missing_device_hint("/some/profile")
        assert "dryrun" in hint and "/some/profile" in hint

    def test_device_default_metric_is_flops(self):
        assert default_metric("device", None) == "flops"
        assert default_metric("device", "bytes") == "bytes"
        assert default_metric("merged", None) is None
        assert default_metric("host", None) is None


class TestServerPlanes:
    @pytest.fixture
    def profile_dir(self, tmp_path):
        d = tmp_path / "prof"
        d.mkdir()
        (d / "tree.json").write_text(host_tree().to_json())
        return d

    def _serve(self, path):
        from repro.profilerd.server import OfflineSource, ProfileServer

        return ProfileServer(OfflineSource(str(path))).start()

    def test_plane_404_without_artifact_has_remedy_hint(self, profile_dir):
        server = self._serve(profile_dir)
        try:
            for plane in ("device", "merged"):
                code, body = _http_get(server.url + f"/tree?plane={plane}")
                assert code == 404
                assert "device_tree.json" in body  # remedy hint, not a bare 404
            code, body = _http_get(server.url + "/diff?plane=merged")
            assert code in (400, 404)  # no baseline param -> 400; plane checked too
        finally:
            server.stop()

    def test_unknown_plane_is_400(self, profile_dir):
        server = self._serve(profile_dir)
        try:
            code, body = _http_get(server.url + "/tree?plane=bogus")
            assert code == 400
            assert "plane" in body
        finally:
            server.stop()

    def test_all_planes_served_with_artifact(self, profile_dir):
        from repro.analysis.static_tree import save_static_tree

        save_device_tree(device_tree(), str(profile_dir / "device_tree.json"))
        static = CallTree()
        static.add_stack(["mod::pkg", "repro::fn"], metrics={"defs": 1.0})
        save_static_tree(static, str(profile_dir / "static_tree.json"))
        server = self._serve(profile_dir)
        try:
            for plane in PLANES:
                code, body = _http_get(server.url + f"/tree?plane={plane}&fmt=json")
                assert code == 200, (plane, body)
            code, body = _http_get(server.url + "/tree?plane=merged&fmt=json")
            merged = CallTree.from_json(body)
            occs = [n.metrics.get(OCCUPANCY, 0) for _p, n in merged.root.walk()]
            assert max(occs) == pytest.approx(1.0)
            code, body = _http_get(server.url + "/tree?plane=device&fmt=folded")
            assert code == 200 and "scores" in body
        finally:
            server.stop()

    def test_merged_html_carries_roofline_legend(self, profile_dir):
        save_device_tree(device_tree(), str(profile_dir / "device_tree.json"))
        server = self._serve(profile_dir)
        try:
            code, html = _http_get(server.url + "/tree?plane=merged&fmt=html")
            assert code == 200
            for term in ("compute", "memory", "collective"):
                assert term in html
        finally:
            server.stop()


    def test_merged_plane_without_peaks_says_why(self, profile_dir):
        save_device_tree(build_device_tree(HLO_TEXT, device_kind="cpu"), str(profile_dir / "device_tree.json"))
        server = self._serve(profile_dir)
        try:
            code, html = _http_get(server.url + "/tree?plane=merged&fmt=html")
            assert code == 200
            assert "no roofline terms" in html
        finally:
            server.stop()


class TestExportRoundtrip:
    def test_merged_folded_roundtrip(self):
        merged = annotate_tree(host_tree(), device_tree())
        folded = to_folded(merged, OCCUPANCY)
        back = from_folded(folded, OCCUPANCY)
        # folded carries self-values; totals must agree to float precision
        assert back.total(OCCUPANCY) == pytest.approx(merged.total(OCCUPANCY))
        assert back.flatten(OCCUPANCY)["py::scores"] == pytest.approx(
            merged.flatten(OCCUPANCY)["py::scores"]
        )

    def test_merged_speedscope_uses_annotation_metric(self):
        merged = annotate_tree(host_tree(), device_tree())
        doc = to_speedscope(merged, OCCUPANCY, name="merged")
        assert doc["profiles"], "speedscope document has no profiles"
        assert doc["profiles"][0]["endValue"] > 0
        frames = [f["name"] for f in doc["shared"]["frames"]]
        assert any("scores" in f for f in frames)

    def test_merged_html_export_self_contained(self):
        merged = annotate_tree(host_tree(), device_tree())
        html = export_tree(merged, fmt="html", roofline=True)
        assert "<html" in html.lower()
        assert "src=\"http" not in html and "href=\"http" not in html  # no CDN deps
        for term in ("compute", "memory", "collective"):
            assert term in html


class TestTimelineSealRoundtrip:
    """Acceptance: annotations survive seal -> decode -> diff, and gate check."""

    def _seal(self, tmp_path, merged):
        tdir = str(tmp_path / "timeline")
        w = TimelineWriter(tdir)
        w.append_full(merged, EpochMeta(0, kind=0))
        delta = annotate_tree(host_tree(), device_tree()).diff(CallTree())
        w.append_delta(delta, EpochMeta(1))
        w.close()
        return tdir

    def test_seal_decode_preserves_annotations(self, tmp_path):
        merged = annotate_tree(host_tree(), device_tree())
        tdir = self._seal(tmp_path, merged)
        epochs = list(TimelineReader(tdir).epochs())
        assert len(epochs) == 2
        _meta, _window, cum = epochs[-1]
        flat = cum.flatten(OCCUPANCY)
        assert flat["py::scores"] == pytest.approx(2 * merged.flatten(OCCUPANCY)["py::scores"])
        assert cum.total(HLO_PREFIX + "flops") > 0

    def test_diff_and_share_regression_gate_on_device_metric(self, tmp_path):
        base = annotate_tree(host_tree(), device_tree())
        # a "regressed" run: the recompiled program doubles the scores matmul,
        # so scores' share of the roofline step time grows
        extra = (
            '  %scores2 = f32[4096,4096]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, '
            'rhs_contracting_dims={0}, metadata={op_name="jit(serve_step)/model/attention/scores"}\n'
        )
        worse_device = build_device_tree(HLO_TEXT.replace("  %context", extra + "  %context"), device_kind=V5E_KIND)
        worse = annotate_tree(host_tree(), worse_device)
        sc = ("thread::MainThread", "py::serve_step", "py::model", "py::attention", "py::scores")
        assert _descend(worse, *sc).metrics[OCCUPANCY] > _descend(base, *sc).metrics[OCCUPANCY]
        regs = share_regressions(base, worse, metric=OCCUPANCY, tolerance=0.01, self_only=False)
        assert any("scores" in name for name, *_rest in regs)


class TestCLIPlanes:
    def _run(self, *argv, cwd=None):
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        return subprocess.run(
            [sys.executable, "-m", "repro.profilerd", *argv],
            env=env, capture_output=True, text=True, timeout=120, cwd=cwd,
        )

    @pytest.fixture
    def host_only(self, tmp_path):
        d = tmp_path / "hostonly"
        d.mkdir()
        (d / "tree.json").write_text(host_tree().to_json())
        return str(d)

    @pytest.fixture
    def with_device(self, tmp_path):
        d = tmp_path / "full"
        d.mkdir()
        (d / "tree.json").write_text(host_tree().to_json())
        save_device_tree(device_tree(), str(d / "device_tree.json"))
        return str(d)

    def test_export_device_plane_without_artifact_exits_4(self, host_only, tmp_path):
        r = self._run(
            "export", host_only, "--plane", "device",
            "--fmt", "folded", "--out", str(tmp_path / "o.folded"),
        )
        assert r.returncode == 4, (r.stdout, r.stderr)
        assert "device_tree.json" in (r.stdout + r.stderr)

    def test_export_merged_folded_roundtrips(self, with_device, tmp_path):
        out = str(tmp_path / "m.folded")
        r = self._run(
            "export", with_device, "--plane", "merged",
            "--fmt", "folded", "--metric", OCCUPANCY, "--out", out,
        )
        assert r.returncode == 0, (r.stdout, r.stderr)
        back = from_folded(open(out).read(), OCCUPANCY)
        merged = annotate_tree(host_tree(), device_tree())
        assert back.total(OCCUPANCY) == pytest.approx(merged.total(OCCUPANCY))

    def test_merged_plane_without_peaks_says_why(self, tmp_path):
        d = tmp_path / "cpu"
        d.mkdir()
        (d / "tree.json").write_text(host_tree().to_json())
        save_device_tree(build_device_tree(HLO_TEXT, device_kind="cpu"), str(d / "device_tree.json"))
        r = self._run(
            "export", str(d), "--plane", "merged", "--fmt", "folded",
            "--metric", HLO_PREFIX + "flops", "--out", str(tmp_path / "m.folded"),
        )
        assert r.returncode == 0, (r.stdout, r.stderr)
        assert "no roofline terms" in r.stderr and "'cpu'" in r.stderr

    def test_check_gates_on_device_plane_share(self, with_device):
        r = self._run(
            "check", with_device, "--baseline", with_device,
            "--plane", "merged", "--metric", OCCUPANCY,
        )
        assert r.returncode == 0, (r.stdout, r.stderr)

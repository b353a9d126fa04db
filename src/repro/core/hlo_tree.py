"""Device-plane call-tree: component attribution of the compiled XLA program.

The paper's insight is that the simulator's call-stack reflects the simulated
architecture. On TPU the "simulated architecture" is the compiled XLA program
executing the model: the host cannot sample it, but every HLO instruction
carries ``metadata={op_name="jit(step)/<module>/<submodule>/<op>"}`` — the
``jax.named_scope`` call-path under which it was traced. That path *is* the
call-stack of the compiled program, and we merge it into the very same
:class:`~repro.core.calltree.CallTree`, with cost-model metrics as counters:

* ``flops``      — matmul/conv FLOPs (2 * prod(out_dims) * prod(contract_dims));
* ``bytes``      — memory traffic at fusion boundaries (operands + result; a
                   post-fusion instruction is one kernel, so its boundary
                   traffic approximates HBM traffic);
* ``coll_bytes`` — operand bytes of every collective instruction
                   (all-gather / all-reduce / reduce-scatter / all-to-all /
                   collective-permute), the §Roofline collective term;
* ``ops``        — instruction count (dominance denominators for the detector).

``while`` bodies (``lax.scan`` over layers) are multiplied by their trip
count, so a scanned 94-layer stack is attributed at full cost: the CPU
backend records it as ``known_trip_count`` in ``backend_config``; the TPU
backend does not, so it is read from the loop condition (``i < N``, the form
every ``lax.scan``/``fori_loop`` lowers to, counting from 0 by 1).  The TPU
backend also lowers every matmul to a ``convolution`` inside a ``fusion``;
fusions are one kernel for ``bytes``/``ops``, but their inner dots and
convolutions are visited for ``flops``, attributed to the inner op's own
``op_name``.  All shapes in post-SPMD HLO are per-device shard shapes, so
every metric here is **per device** — consistent with
``compiled.cost_analysis()``.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field

from .calltree import CallTree

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# Fusion-optimistic traffic model: only ops that stay HBM-visible on TPU are
# charged bytes. Standalone elementwise/broadcast/reshape ops fuse into their
# producers/consumers on TPU (the CPU backend leaves many unfused, which would
# wildly overstate the memory term), so they are NOT in this set.
_TRAFFIC_OPS = {
    "dot",
    "convolution",
    "fusion",
    "custom-call",
    "copy",
    "copy-start",
    "transpose",
    "reduce",
    "reduce-window",
    "sort",
    "gather",
    "scatter",
    "dynamic-slice",
    "dynamic-update-slice",
    "pad",
    "concatenate",
    "slice",
    "select-and-scatter",
    "cholesky",
    "triangular-solve",
    "fft",
    *COLLECTIVE_OPS,
}

_DTYPE_BYTES = {
    "pred": 1,
    "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    "token": 0,
    "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_HEAD_RE = re.compile(r"^\s*(?P<root>ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*")
# TPU layouts carry tiling and memory space: `bf16[8,128]{1,0:T(8,128)(2,1)S(1)}`.
_ARRAY_TYPE_RE = re.compile(r"[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?")
_OPCODE_RE = re.compile(r"\s*(?P<opcode>[\w\-]+)\(")
_COMP_HEADER_RE = re.compile(r"^(?P<entry>ENTRY\s+)?%?(?P<name>[\w.\-]+)\s+\(.*\)\s*->\s*.+\{\s*$")
_METADATA_RE = re.compile(r'op_name="([^"]+)"')
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_DIM_LABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
_WINDOW_RE = re.compile(r"window=\{([^}]*)\}")


def _split_type(text: str) -> tuple[str, str] | None:
    """Split ``<type> <opcode>(...`` into the result type and the remainder.

    Tuple types nest parentheses (TPU tiling ``T(8,128)`` inside each
    element's layout) and embed ``/*index=N*/`` comments, so they are
    matched by depth rather than by a regex.
    """
    if not text.startswith("("):
        m = _ARRAY_TYPE_RE.match(text)
        return (m.group(0), text[m.end():]) if m else None
    depth = 0
    for i, ch in enumerate(text):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return text[: i + 1], text[i + 1:]
    return None


@dataclass
class HloOp:
    name: str
    opcode: str
    shapes: list[tuple[str, tuple[int, ...]]]  # result (flattened if tuple)
    operands: list[str]
    op_name: str | None
    trip_count: int = 1
    called: list[str] = field(default_factory=list)
    attrs: str = ""
    args: str = ""  # raw operand text (a constant's literal)

    def result_bytes(self) -> int:
        total = 0
        for dtype, dims in self.shapes:
            n = 1
            for d in dims:
                n *= d
            total += n * _DTYPE_BYTES.get(dtype, 4)
        return total


@dataclass
class HloComputation:
    name: str
    ops: dict[str, HloOp] = field(default_factory=dict)
    order: list[str] = field(default_factory=list)
    root: str | None = None


def _parse_shapes(type_str: str) -> list[tuple[str, tuple[int, ...]]]:
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dims = tuple(int(x) for x in m.group(2).split(",") if x != "")
        out.append((m.group(1), dims))
    return out


def parse_hlo_module(text: str) -> dict[str, HloComputation]:
    """Parse post-optimization HLO text into computations with a symbol table."""
    comps: dict[str, HloComputation] = {}
    current: HloComputation | None = None
    entry_name: str | None = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        if current is None:
            m = _COMP_HEADER_RE.match(line)
            if m and not line.startswith("HloModule"):
                current = HloComputation(m.group("name"))
                if m.group("entry"):
                    entry_name = current.name
            continue
        if stripped == "}":
            comps[current.name] = current
            current = None
            continue
        head = _OP_HEAD_RE.match(line)
        split = _split_type(line[head.end():]) if head else None
        m = _OPCODE_RE.match(split[1]) if split else None
        if not m:
            continue
        type_str = split[0]
        rest = split[1][m.end():]
        # Operand list ends at the first unnested ')'.
        depth = 0
        end = len(rest)
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                if depth == 0:
                    end = i
                    break
                depth -= 1
        operand_str, attrs = rest[:end], rest[end + 1:]
        if "%" in operand_str:
            # Typed operand lists (`dot(f32[8,16]{1,0} %Arg_0.1, ...)`): only
            # %-prefixed tokens are operand names; the rest is dtype/layout
            # noise that would otherwise shadow operand 0 and zero out the
            # dot-flops / traffic attribution.
            operands = re.findall(r"%([\w.\-]+)", operand_str)
        else:
            operands = re.findall(r"([\w.\-]+)", operand_str)
            # Keep only tokens that look like op names (filter literals like "0").
            operands = [o for o in operands if not re.fullmatch(r"[0-9.eE+\-]+", o)]
        mmeta = _METADATA_RE.search(attrs)
        mtrip = _TRIP_RE.search(attrs)
        called = _CALLS_RE.findall(attrs)
        op = HloOp(
            name=head.group("name"),
            opcode=m.group("opcode"),
            shapes=_parse_shapes(type_str),
            operands=operands,
            op_name=mmeta.group(1) if mmeta else None,
            trip_count=int(mtrip.group(1)) if mtrip else 1,
            called=called,
            attrs=attrs,
            args=operand_str,
        )
        current.ops[op.name] = op
        current.order.append(op.name)
        if head.group("root"):
            current.root = op.name
    for comp in comps.values():
        for op in comp.ops.values():
            if op.opcode == "while" and not _TRIP_RE.search(op.attrs):
                op.trip_count = _condition_trip_count(comps, op)
    if entry_name is not None:
        comps["__entry__"] = comps[entry_name]
    return comps


def _condition_trip_count(comps: dict[str, HloComputation], op: HloOp) -> int:
    """Trip count of a ``while`` whose condition is ``i < N`` for a constant N.

    ``lax.scan`` and ``fori_loop`` count from 0 by 1, so N is the trip count.
    Any other condition shape is unknown and counts once.
    """
    m = _COND_RE.search(op.attrs)
    cond = comps.get(m.group(1)) if m else None
    root = cond.ops.get(cond.root) if cond is not None and cond.root else None
    if root is None or root.opcode != "compare" or "direction=LT" not in root.attrs:
        return 1
    bound = cond.ops.get(root.operands[1]) if len(root.operands) == 2 else None
    if bound is None or bound.opcode != "constant" or not bound.args.isdigit():
        return 1
    return max(int(bound.args), 1)


def _dot_flops(op: HloOp, comp: HloComputation) -> float:
    """2 * prod(output dims) * prod(lhs contracting dim sizes)."""
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.attrs)
    if not m or not op.operands:
        return 0.0
    lhs = comp.ops.get(op.operands[0])
    if lhs is None or not lhs.shapes:
        return 0.0
    lhs_dims = lhs.shapes[0][1]
    contract = 1
    for idx in (int(x) for x in m.group(1).split(",") if x):
        if idx < len(lhs_dims):
            contract *= lhs_dims[idx]
    out_elems = 1
    for _, dims in op.shapes[:1]:
        for d in dims:
            out_elems *= d
    return 2.0 * out_elems * contract


def _window_dims(window: str, key: str, n: int, default: str) -> list[str]:
    m = re.search(rf"(?:^|\s){key}=(\S+)", window)
    return m.group(1).split("x") if m else [default] * n


def _valid_taps(n_in: int, k: int, n_out: int, stride: int, pad_lo: int, lhs_dil: int, rhs_dil: int) -> int:
    """(output, window) position pairs of one spatial dim that read a real
    input element — not padding, not a hole left by ``lhs_dilate``."""
    last = (n_in - 1) * lhs_dil
    total = 0
    for kk in range(k):
        off = kk * rhs_dil - pad_lo  # input position = o * stride + off
        if lhs_dil == 1:
            lo = max(0, -(off // stride))
            hi = min(n_out - 1, (last - off) // stride)
            total += max(0, hi - lo + 1)
        else:
            total += sum(
                1 for o in range(n_out) if 0 <= o * stride + off <= last and (o * stride + off) % lhs_dil == 0
            )
    return total


def _conv_flops(op: HloOp, comp: HloComputation) -> float:
    """2 * batch * output features * input features * valid window taps.

    The TPU backend lowers every matmul to a convolution, often with batch
    dims turned into padded spatial dims (``window={size=1x4 pad=0_0x3_3}``)
    or base-dilated ones for weight gradients; only taps that read a real
    input element are counted, as XLA's own cost analysis does.
    """
    if len(op.operands) < 2 or not op.shapes:
        return 0.0
    lhs, rhs = comp.ops.get(op.operands[0]), comp.ops.get(op.operands[1])
    labels = _DIM_LABELS_RE.search(op.attrs)
    if labels is None or lhs is None or rhs is None or not lhs.shapes or not rhs.shapes:
        return 0.0
    (ll, rl, ol), ls, rs, os_ = labels.groups(), lhs.shapes[0][1], rhs.shapes[0][1], op.shapes[0][1]
    if (len(ll), len(rl), len(ol)) != (len(ls), len(rs), len(os_)):
        return 0.0
    window = _WINDOW_RE.search(op.attrs)
    w = window.group(1) if window else ""
    n_sp = sum(c.isdigit() for c in ol)
    strides = _window_dims(w, "stride", n_sp, "1")
    pads = _window_dims(w, "pad", n_sp, "0_0")
    lhs_dil = _window_dims(w, "lhs_dilate", n_sp, "1")
    rhs_dil = _window_dims(w, "rhs_dilate", n_sp, "1")
    macs = os_[ol.index("b")] * os_[ol.index("f")] * rs[rl.index("i")]
    for j in range(n_sp):
        d = str(j)
        macs *= _valid_taps(
            ls[ll.index(d)], rs[rl.index(d)], os_[ol.index(d)],
            int(strides[j]), int(pads[j].split("_")[0]), int(lhs_dil[j]), int(rhs_dil[j]),
        )
    return 2.0 * macs


def _op_flops(op: HloOp, comp: HloComputation) -> float:
    if op.opcode == "dot":
        return _dot_flops(op, comp)
    if op.opcode == "convolution":
        return _conv_flops(op, comp)
    return 0.0


_SLICING_OPS = ("dynamic-slice", "slice", "gather")


def _fusion_bytes(op: HloOp, comp: HloComputation, fused: HloComputation) -> int:
    """HBM traffic of one fusion: what it reads of each operand, plus what it writes.

    The TPU backend fuses the slices and in-place updates of a scan body
    into its fusions.  An operand the fused computation only slices moves
    its slices, and a buffer it updates in place (``dynamic-update-slice``)
    moves twice the update, as for the standalone ops above; charging whole
    buffers instead would overstate a time scan's traffic by its trip count.
    """
    users: dict[str, list[HloOp]] = {}
    for inner in fused.ops.values():
        for o in inner.operands:
            users.setdefault(o, []).append(inner)

    def uses(name: str) -> list[tuple[HloOp, str]]:  # (user, operand it reads), bitcasts seen through
        out = []
        for u in users.get(name, []):
            out += uses(u.name) if u.opcode == "bitcast" else [(u, name)]
        return out

    def producer(name: str | None) -> HloOp | None:
        inner = fused.ops.get(name or "")
        while inner is not None and inner.opcode == "bitcast" and inner.operands:
            inner = fused.ops.get(inner.operands[0])
        return inner

    params = {int(p.args): p for p in fused.ops.values() if p.opcode == "parameter" and p.args.isdigit()}
    moved = 0
    for i, o in enumerate(op.operands):
        src = comp.ops.get(o)
        if src is None:
            continue
        reads = uses(params[i].name) if i in params else []
        if reads and all(
            u.opcode in _SLICING_OPS or (u.opcode == "dynamic-update-slice" and u.operands[:1] == [via])
            for u, via in reads
        ):
            moved += sum(u.result_bytes() for u, _via in reads if u.opcode in _SLICING_OPS)
        else:
            moved += src.result_bytes()
    root = fused.ops.get(fused.root or "")
    if root is None:
        return moved + op.result_bytes()
    for name in root.operands if root.opcode == "tuple" else [root.name]:
        out = producer(name)
        if out is not None and out.opcode == "dynamic-update-slice" and len(out.operands) > 1:
            upd = fused.ops.get(out.operands[1])
            moved += 2 * (upd.result_bytes() if upd is not None else 0)
        elif out is not None:
            moved += out.result_bytes()
    return moved


class DeviceTree(CallTree):
    """A device-plane tree and the ``device_kind`` its program was compiled for.

    The kind (``jax.Device.device_kind``, e.g. ``"TPU v5 lite"``) selects the
    peaks the merged plane is costed with (:mod:`repro.core.roofline`); None
    means the tree does not say, and it is costed with none.
    """

    def __init__(self, root=None, device_kind: str | None = None):
        super().__init__(root)
        self.device_kind = device_kind


def build_device_tree(
    hlo_text: str,
    *,
    entry: str | None = None,
    step_name: str | None = None,
    device_kind: str | None = None,
) -> DeviceTree:
    """Build the device-plane tree from compiled HLO text."""
    comps = parse_hlo_module(hlo_text)
    if not comps:
        return DeviceTree(device_kind=device_kind)
    if entry is None:
        if "__entry__" in comps:
            entry = comps["__entry__"].name
        else:
            # Fallback: the computation no other computation calls.
            called_names = {c for comp in comps.values() for op in comp.ops.values() for c in op.called}
            candidates = [n for n in comps if n != "__entry__" and n not in called_names]
            entry = candidates[-1] if candidates else next(iter(comps))
    tree = DeviceTree(device_kind=device_kind)

    def op_path(op: HloOp) -> list[str]:
        if op.op_name:
            frames = [f for f in op.op_name.split("/") if f]
            if step_name and frames and frames[0].startswith("jit("):
                frames[0] = step_name
            return frames + [op.opcode]
        return ["<unattributed>", op.opcode]

    def visit(comp_name: str, multiplier: float, seen: tuple[str, ...]) -> None:
        comp = comps.get(comp_name)
        if comp is None or comp_name in seen:
            return
        for name in comp.order:
            op = comp.ops[name]
            metrics = {"ops": 1.0 * multiplier}
            if op.opcode in ("dot", "convolution"):
                metrics["flops"] = _op_flops(op, comp) * multiplier
            if op.opcode in _TRAFFIC_OPS:
                # In-place semantics for indexed ops (TPU aliases while-loop
                # buffers; charging the full operand per iteration would be a
                # CPU-backend artifact): slice/gather move ~2x the slice;
                # dynamic-update-slice/scatter move ~2x the update operand;
                # in-loop copies are CPU aliasing artifacts and are skipped.
                if op.opcode in ("dynamic-slice", "gather"):
                    metrics["bytes"] = 2 * op.result_bytes() * multiplier
                elif op.opcode in ("dynamic-update-slice", "scatter"):
                    upd_idx = 1 if op.opcode == "dynamic-update-slice" else 2
                    upd = comp.ops.get(op.operands[upd_idx]) if len(op.operands) > upd_idx else None
                    moved = upd.result_bytes() if upd is not None else op.result_bytes()
                    metrics["bytes"] = 2 * moved * multiplier
                elif op.opcode == "copy":
                    if multiplier <= 1:
                        metrics["bytes"] = 2 * op.result_bytes() * multiplier
                elif op.opcode == "fusion" and op.called and op.called[0] in comps:
                    metrics["bytes"] = _fusion_bytes(op, comp, comps[op.called[0]]) * multiplier
                else:
                    operand_bytes = 0
                    for o in op.operands:
                        src = comp.ops.get(o)
                        if src is not None:
                            operand_bytes += src.result_bytes()
                    metrics["bytes"] = (op.result_bytes() + operand_bytes) * multiplier
            if op.opcode in COLLECTIVE_OPS:
                operand_bytes = 0
                for o in op.operands:
                    src = comp.ops.get(o)
                    if src is not None:
                        operand_bytes += src.result_bytes()
                metrics["coll_bytes"] = operand_bytes * multiplier
                metrics[f"coll_bytes::{op.opcode}"] = operand_bytes * multiplier
            tree.add_stack(op_path(op), metrics)
            if op.opcode == "while":
                body = _BODY_RE.search(op.attrs)
                if body:
                    visit(body.group(1), multiplier * op.trip_count, seen + (comp_name,))
            elif op.opcode in ("call", "conditional", "async-start"):
                for c in op.called:
                    visit(c, multiplier, seen + (comp_name,))
            elif op.opcode == "fusion":
                # One fusion == one kernel: its ops and boundary traffic are
                # counted above; only the matmuls inside add flops.
                for c in op.called:
                    visit_fused(c, multiplier, op_path(op), seen + (comp_name,))

    def visit_fused(comp_name: str, multiplier: float, outer: list[str], seen: tuple[str, ...]) -> None:
        comp = comps.get(comp_name)
        if comp is None or comp_name in seen:
            return
        for name in comp.order:
            op = comp.ops[name]
            flops = _op_flops(op, comp)
            if flops:
                tree.add_stack(op_path(op) if op.op_name else outer, {"flops": flops * multiplier})
            if op.opcode == "fusion":
                for c in op.called:
                    visit_fused(c, multiplier, outer, seen + (comp_name,))

    visit(entry, 1.0, ())
    return tree


def collective_summary(tree: CallTree) -> dict[str, float]:
    """Total collective bytes per collective kind + overall (per device)."""
    out: dict[str, float] = {"total": tree.total("coll_bytes")}
    for k, v in tree.root.metrics.items():
        if k.startswith("coll_bytes::"):
            out[k.split("::", 1)[1]] = v
    return out


def tree_from_compiled(compiled, **kw) -> DeviceTree:
    """Convenience: build the device tree straight from a jax compiled object."""
    return build_device_tree(compiled.as_text(), **kw)


DEVICE_TREE_SCHEMA = "repro-device-tree/v1"


def save_device_tree(tree: DeviceTree, path: str, *, meta: dict | None = None) -> None:
    """Persist a device-plane tree as a versioned ``device_tree.json`` artifact.

    The tree's ``device_kind`` is written into ``meta`` beside the caller's
    keys, so the artifact states the device it is to be costed for.

    The write is atomic (tmp + rename): daemons and servers discover this file
    lazily beside a profile that is still being written.  JSON float encoding
    is ``repr``-based, so every metric value — including ``while``
    trip-count-multiplied flops and per-kind ``coll_bytes::*`` counters —
    roundtrips bit-exactly through :func:`load_device_tree`.
    """
    doc: dict = {"schema": DEVICE_TREE_SCHEMA, "root": tree.root.to_dict()}
    meta = dict(meta or {})
    if tree.device_kind:
        meta["device_kind"] = tree.device_kind
    if meta:
        doc["meta"] = meta
    tmp = f"{path}.tmp.{id(doc)}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


def load_device_tree(path: str) -> DeviceTree:
    """Load a ``device_tree.json`` (versioned envelope or legacy bare root)."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a device tree artifact")
    meta: dict = {}
    if "schema" in doc:
        if doc["schema"] != DEVICE_TREE_SCHEMA:
            raise ValueError(f"{path}: unsupported device tree schema {doc['schema']!r}")
        root = doc.get("root")
        meta = doc.get("meta") or {}
    else:  # legacy: a bare CallTree.to_json() dump
        root = doc
    if not isinstance(root, dict) or "name" not in root:
        raise ValueError(f"{path}: device tree artifact has no root node")
    from .calltree import CallNode

    return DeviceTree(CallNode.from_dict(root), device_kind=meta.get("device_kind"))

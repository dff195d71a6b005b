"""Textual parsers over lowered / compiled XLA programs.

Reference analogue: none — DeepSpeed has no compiler artifact to parse; its
collectives are imperative NCCL calls and the only audit trail is a wire
sniffer (comms_logger). Here every step is a compiled HLO module whose text
names every collective with its shape, every input/output buffer alias
(donation), and every dtype conversion — so lints can be plain parsers.

Three program representations matter (analysis/program.py produces them):

- **optimized HLO** (``compiled.as_text()``): post-GSPMD, post-fusion. The
  collectives that will actually hit the ICI live here, as do the
  ``input_output_alias`` entries that realize buffer donation.
- **pre-optimization HLO** (``lowered.as_text(dialect="hlo")``): still
  carries explicit ``sharding={...}`` annotations — the replication scan
  reads these.
- **StableHLO** (``lowered.as_text()``): per-argument ``tf.aliasing_output``
  and ``mhlo.sharding`` attributes.
"""

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# HLO primitive byte widths (token/opaque types are skipped).
ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "i8": 1, "ui8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

# `all-reduce(` / `all-gather-start(` — requires the open paren so operand
# references (`%all-reduce.16`) and op_name metadata (underscored) don't match
_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(dtype: str, dims_csv: str) -> int:
    """Bytes of one HLO shape token, e.g. ("f32", "2,32,32") -> 8192."""
    n = 1
    for d in dims_csv.split(","):
        if d:
            n *= int(d)
    return n * ITEMSIZE.get(dtype, 0)


def result_bytes(result_text: str) -> int:
    """Total bytes of an op's result type text — handles tuples
    ``(f32[16]{0}, f32[16]{0})`` and plain ``f32[2,32]{1,0}``."""
    return sum(shape_bytes(dt, dims)
               for dt, dims in _SHAPE_RE.findall(result_text))


@dataclass
class CollectiveOp:
    kind: str            # all-reduce | all-gather | ...
    nbytes: int          # result bytes (sum over tuple elements)
    line: str            # the defining HLO line (trimmed)
    is_async: bool = False


def _collective_nbytes(result_text: str, is_async: bool) -> int:
    """Result bytes of one collective definition. ``-start`` ops return a
    tuple wrapping the in-flight operand alongside the result (plus u32
    contexts for permutes), so for those the op size is the LARGEST tuple
    element, not the sum — summing would double-count every async
    collective. Plain variadic ops (an all-reduce over N grad buffers) do
    sum their elements. The ONE place this rule lives: parse_collectives
    and parse_overlap both price ops through it, so the collective census
    and the overlap census can never disagree on sizes."""
    sizes = [shape_bytes(dt, dims)
             for dt, dims in _SHAPE_RE.findall(result_text)]
    if not sizes:
        return 0
    return max(sizes) if is_async and len(sizes) > 1 else sum(sizes)


def parse_collectives(optimized_hlo: str) -> List[CollectiveOp]:
    """Every collective op in a compiled module, with result byte sizes.

    Async pairs count once (the ``-start`` carries the shape; the ``-done``
    is skipped). Ops inside fusions/while bodies appear in the text and are
    counted — an op in a scanned loop body is ONE static site.
    """
    out = []
    for line in optimized_hlo.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m or m.group(2) == "-done":
            continue
        head = line[:m.start()]
        if "=" not in head:
            continue  # operand continuation line, not a definition
        is_async = m.group(2) == "-start"
        nbytes = _collective_nbytes(head.split("=", 1)[1], is_async)
        out.append(CollectiveOp(kind=m.group(1), nbytes=nbytes,
                                line=line.strip()[:240], is_async=is_async))
    return out


def collective_census(ops: List[CollectiveOp],
                      min_bytes: int = 0) -> Dict[str, Dict[str, int]]:
    """Aggregate: {kind: {"count": n, "bytes": total}} for ops >= min_bytes."""
    census: Dict[str, Dict[str, int]] = {}
    for op in ops:
        if op.nbytes < min_bytes:
            continue
        c = census.setdefault(op.kind, {"count": 0, "bytes": 0})
        c["count"] += 1
        c["bytes"] += op.nbytes
    return census


# --------------------------------------------------------------------------
# Overlap classification (scheduled HLO)
# --------------------------------------------------------------------------

@dataclass
class OverlapOp:
    """One collective, classified against the scheduled instruction order."""
    kind: str
    nbytes: int
    line: str
    computation: str = ""
    is_async: bool = False     # lowered as a start/done pair at all
    overlapped: bool = False   # async AND compute scheduled between the pair
    gap_ops: int = 0           # heavyweight ops between start and done


# ops that represent real device work between a start/done pair; everything
# else (gtes, bitcasts, copies, parameters) is bookkeeping that the
# latency-hiding scheduler can place anywhere for free. The result type may
# be a parenthesized TUPLE (multi-output kOutput fusions, every while loop)
# — the first alternative covers those.
_COMPUTE_OP_RE = re.compile(
    r"=\s*(?:\([^()=]*\)|[\w\[\],{}\s]*)\s(fusion|dot|convolution|while|"
    r"conditional|custom-call|reduce|reduce-window|sort|scatter|gather|"
    r"select-and-scatter|cholesky|triangular-solve|rng|pad|transpose|"
    r"concatenate)\(")

# the '%' sigil is optional: some XLA dump styles print instruction names
# without it — the done-matcher below uses boundary-anchored search so a
# sigil-less name cannot substring-match a longer one
_RESULT_VAR_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=")


def parse_overlap(optimized_hlo: str) -> List[OverlapOp]:
    """Classify every collective in a (scheduled) compiled module as
    overlapped or exposed.

    XLA's latency-hiding scheduler emits asynchronous collectives as
    ``-start``/``-done`` pairs; the module text after scheduling lists
    instructions in schedule order, so a pair with real compute between the
    two halves is *overlapped* (the wire runs under that compute) and a
    pair scheduled back-to-back is *exposed* latency. Synchronous
    collectives (no ``-start``) block by construction and are always
    exposed — which is also what every collective looks like on backends
    that never async-lower (CPU test meshes): the overlap gate is therefore
    opt-in (``analysis.max_exposed_collectives``).
    """
    out: List[OverlapOp] = []
    computation = ""
    # per-computation: open start var -> (index into out, compute count)
    open_async: Dict[str, Tuple[int, int]] = {}
    compute_seen = 0
    for line in optimized_hlo.splitlines():
        if line and not line.startswith(" "):
            m = _COMPUTATION_HEADER_RE.match(line)
            if m:
                computation = m.group(2)
                open_async = {}
                compute_seen = 0
            continue
        cm = _COLLECTIVE_RE.search(line)
        if cm is None:
            if _COMPUTE_OP_RE.search(line):
                compute_seen += 1
            continue
        head = line[:cm.start()]
        if "=" not in head:
            continue  # operand continuation, not a definition
        kind, suffix = cm.group(1), cm.group(2)
        if suffix == "-done":
            # match the start by the operand var it consumes
            # (boundary-anchored: a name must not substring-match a longer
            # one, with or without the '%' sigil)
            done = None
            for var, (idx, started_at) in list(open_async.items()):
                if re.search(r"(?<![\w.\-])" + re.escape(var)
                             + r"(?![\w.\-])", line):
                    done = var
                    break
            if done is not None:
                idx, started_at = open_async.pop(done)
                gap = compute_seen - started_at
                out[idx].gap_ops = gap
                out[idx].overlapped = gap > 0
            continue
        is_async = suffix == "-start"
        nbytes = _collective_nbytes(head.split("=", 1)[1], is_async)
        op = OverlapOp(kind=kind, nbytes=nbytes, line=line.strip()[:240],
                       computation=computation, is_async=is_async)
        out.append(op)
        if is_async:
            vm = _RESULT_VAR_RE.match(line)
            if vm:
                open_async[vm.group(1)] = (len(out) - 1, compute_seen)
    return out


def overlap_summary(ops: List[OverlapOp],
                    min_bytes: int = 0) -> Dict[str, Dict[str, int]]:
    """Aggregate {overlapped|exposed: {count, bytes}} over ops >= min_bytes."""
    summary = {"overlapped": {"count": 0, "bytes": 0},
               "exposed": {"count": 0, "bytes": 0}}
    for op in ops:
        if op.nbytes < min_bytes:
            continue
        bucket = summary["overlapped" if op.overlapped else "exposed"]
        bucket["count"] += 1
        bucket["bytes"] += op.nbytes
    return summary


# --------------------------------------------------------------------------
# Donation (input/output buffer aliasing)
# --------------------------------------------------------------------------

_ALIAS_BLOCK_RE = re.compile(r"input_output_alias=\{")
_ALIAS_ENTRY_RE = re.compile(
    r"\{[\d,\s]*\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(?:may-alias|must-alias)\)")


def parse_donated_params(optimized_hlo: str) -> List[int]:
    """Entry-parameter numbers that alias an output buffer (i.e. whose
    donation XLA actually honored). Parsed from the module header's
    ``input_output_alias={ {out}: (param, {path}, may-alias), ... }``."""
    m = _ALIAS_BLOCK_RE.search(optimized_hlo)
    if not m:
        return []
    # the alias map lives on the HloModule header line
    header = optimized_hlo[m.end():optimized_hlo.index("\n", m.end())]
    return sorted({int(p) for p in _ALIAS_ENTRY_RE.findall(header)})


_ARG_DECL_RE = re.compile(r"%arg(\d+)\s*:")


def parse_aliased_args_stablehlo(stablehlo: str) -> List[int]:
    """Argument positions carrying ``tf.aliasing_output`` in StableHLO text —
    the donation view *before* XLA decides what it can honor.

    Attribution is per-argument: the text is sliced between consecutive
    ``%argN:`` declarations so a later argument's attribute dict (which may
    contain commas and quoted braces) is never charged to an earlier one.
    """
    decls = list(_ARG_DECL_RE.finditer(stablehlo))
    out = set()
    for i, m in enumerate(decls):
        end = decls[i + 1].start() if i + 1 < len(decls) else len(stablehlo)
        segment = stablehlo[m.end():end]
        # the last arg's slice runs into the body; attrs end at the result
        # arrow, and tf.aliasing_output only ever appears in the signature
        arrow = segment.find("->")
        if arrow != -1:
            segment = segment[:arrow]
        if "tf.aliasing_output" in segment:
            out.add(int(m.group(1)))
    return sorted(out)


# --------------------------------------------------------------------------
# Dtype promotion
# --------------------------------------------------------------------------

@dataclass
class ConvertOp:
    to_dtype: str
    from_dtype: str
    nbytes: int          # bytes of the widened result
    shape: str           # e.g. "f32[4,16,64]"
    line: str


_CONVERT_RE = re.compile(
    r"^\s+(ROOT\s+)?%?[\w.\-]+\s*=\s*(f32|f64)\[([\d,]*)\][^ ]*\s+"
    r"convert\((?:(\w+)\[[^ ]*\s+)?%?([\w.\-]+)\)")
_DEF_DTYPE_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+)\[")
_COMPUTATION_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\()")


def parse_upcasts(hlo_text: str, min_bytes: int = 0) -> List[ConvertOp]:
    """Widening converts (bf16/f16 -> f32/f64) with result bytes >=
    min_bytes, in optimized HLO.

    Only converts whose RESULT is a buffer count: one at the top level
    (entry / while-body / conditional computations), or one that is the
    ROOT of a fusion (0.9's CPU pipeline wraps a lone convert in a
    ``%wrapped_convert_computation``: the fusion's output IS the widened
    copy). A convert in the middle of a ``%fused_computation`` body is
    elementwise inside one kernel and never materializes the f32 buffer —
    flagging it would indict every fused softmax/grad cast a bf16 model
    intends. The operand's dtype is read off the line where the text
    carries it and off the operand's definition where it does not (0.9
    prints operands by name only).
    """
    out = []
    in_fusion = False
    narrow: Dict[str, str] = {}   # instruction name -> "bf16" | "f16"
    for line in hlo_text.splitlines():
        if not line.startswith(" "):  # computation header at column 0
            m = _COMPUTATION_HEADER_RE.match(line)
            if m:
                in_fusion = ("fused_" in m.group(2)
                             or m.group(2).startswith("wrapped_"))
            continue
        d = _DEF_DTYPE_RE.match(line)
        if d and d.group(2) in ("bf16", "f16"):
            narrow[d.group(1)] = d.group(2)
        if " convert(" not in line:
            continue
        m = _CONVERT_RE.match(line)
        if not m or (in_fusion and not m.group(1)):
            continue
        _, to_dt, dims, inline_dt, operand = m.groups()
        from_dt = inline_dt or narrow.get(operand)
        if from_dt not in ("bf16", "f16"):
            continue
        nb = shape_bytes(to_dt, dims)
        if nb < min_bytes:
            continue
        out.append(ConvertOp(to_dtype=to_dt, from_dtype=from_dt, nbytes=nb,
                             shape=f"{to_dt}[{dims}]",
                             line=line.strip()[:240]))
    return out


# --------------------------------------------------------------------------
# Static peak-HBM liveness (scheduled HLO)
# --------------------------------------------------------------------------
#
# ``compiled.as_text()`` of a compiled module carries ``is_scheduled=true``:
# the instruction order IS the schedule, so def/last-use over that order is a
# faithful live-range model. Each top-level instruction allocates its result
# bytes; view-like ops (gte/tuple/bitcast/while/...-done/dynamic-update-slice)
# alias their operands instead of allocating — the same ops XLA's buffer
# assignment treats as in-place updates or pointer bookkeeping. While/
# conditional bodies contribute their own internal temp peak at the call site
# (the carry is charged once, at the caller). Entry parameters are caller-
# owned and live for the whole program; a donated output (input_output_alias)
# writes into its parameter's buffer instead of allocating a second one —
# which is exactly why a missed donation shows up here as double memory.
# The estimate is cross-checkable against ``compiled.memory_analysis()``
# where the backend provides one (analysis/program.py records it in meta).

# ops whose result is a view/in-place update of an operand — no new buffer.
# (`-done` halves of async pairs land here via the suffix check below.)
_ALIAS_OPS = frozenset((
    "get-tuple-element", "tuple", "bitcast", "while", "optimization-barrier",
    "dynamic-update-slice", "add-dependency", "after-all",
))

_INSTR_RE = re.compile(r"^\s+(ROOT\s+)?(%?[\w.\-]+)\s*=\s*(.*)$")
# first lowercase word directly followed by '(' after the result type
_OPCODE_RE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
# operand refs: %var not preceded by '=' (excludes attr refs like body=%b)
_OPERAND_RE = re.compile(r"(?<![=\w])%([\w.\-]+)")
_CALLED_RE = re.compile(
    r"(?:body|true_computation|false_computation|to_apply)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_PARAM_NUM_RE = re.compile(r"parameter\((\d+)\)")
# header alias entries WITH the output index: {out}: (param, {path}, kind)
_ALIAS_PAIR_RE = re.compile(
    r"\{(\d+)[\d,\s]*\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*"
    r"(?:may-alias|must-alias)\)")


def shape_key(result_text: str) -> str:
    """Normalized "dtype[dims]" of a (non-tuple) result type, or ""."""
    m = _SHAPE_RE.search(result_text)
    return f"{m.group(1)}[{m.group(2)}]" if m else ""


@dataclass
class EntryParam:
    """One ENTRY-computation parameter of the compiled (post-SPMD) module —
    its shape is the PER-DEVICE shard, not the logical array."""
    number: int
    var: str
    dtype: str
    dims: str
    nbytes: int


def parse_entry_params(optimized_hlo: str) -> List[EntryParam]:
    """Entry parameters with their per-device shapes, sorted by number."""
    comps, entry = _split_computations(optimized_hlo)
    out = []
    for line in comps.get(entry, ()):
        if " parameter(" not in line:
            continue
        pm = _PARAM_NUM_RE.search(line)
        m = _INSTR_RE.match(line)
        if not pm or not m:
            continue
        rhs = m.group(3)
        sm = _SHAPE_RE.search(rhs)
        dtype, dims = (sm.group(1), sm.group(2)) if sm else ("", "")
        out.append(EntryParam(number=int(pm.group(1)),
                              var=m.group(2).lstrip("%"),
                              dtype=dtype, dims=dims,
                              nbytes=shape_bytes(dtype, dims)))
    return sorted(out, key=lambda p: p.number)


@dataclass
class _Buffer:
    """One allocated buffer in one computation's schedule."""
    var: str
    nbytes: int
    cls: str
    first: int
    last: int
    line: str
    is_param: bool = False


@dataclass
class MemoryEstimate:
    """Static peak-HBM model of one scheduled module."""
    peak_bytes: int = 0
    peak_index: int = 0            # entry instruction index of the peak
    # live bytes per class AT the peak point (body peaks included)
    breakdown: Dict[str, int] = field(default_factory=dict)
    # total entry-parameter bytes per class (per-device, post-SPMD)
    param_bytes: Dict[str, int] = field(default_factory=dict)
    # largest live buffers at the peak: (bytes, class, line)
    largest: List[Tuple[int, str, str]] = field(default_factory=list)
    # activation bytes carried across the fwd/bwd boundary (-1 = no
    # backward-stamped instruction found in the entry computation)
    boundary_index: int = -1
    boundary_bytes: int = 0


def _split_computations(text: str) -> Tuple[Dict[str, List[str]], str]:
    """{computation_name: [instruction lines]}, entry computation name."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[List[str]] = None
    entry = ""
    for line in text.splitlines():
        if line and not line.startswith(" ") and not line.startswith("}"):
            m = _COMPUTATION_HEADER_RE.match(line)
            if m and "{" in line:
                name = m.group(2)
                if line.startswith("ENTRY"):
                    entry = name
                cur = comps.setdefault(name, [])
            continue
        if cur is not None and line.strip().startswith(("%", "ROOT")):
            cur.append(line)
    return comps, entry


def _strip_attrs(rhs: str) -> str:
    """Drop metadata/backend_config payloads before operand scanning."""
    for marker in (", metadata={", ", backend_config="):
        k = rhs.find(marker)
        if k != -1:
            rhs = rhs[:k]
    return rhs


class _Liveness:
    """One liveness analysis over a parsed module: computation map, memoized
    per-body temp peaks, and the shape->class temp classifier."""

    def __init__(self, comps: Dict[str, List[str]],
                 temp_class_shapes: Optional[Dict[str, str]] = None):
        self.comps = comps
        self.temp_shapes = temp_class_shapes or {}
        self._body_peak: Dict[str, Tuple[int, Dict[str, int]]] = {}

    # -- one computation scan ---------------------------------------------
    def _scan(self, lines: List[str],
              param_classes: Optional[Dict[int, str]]):
        """Def/last-use over one computation's scheduled instructions.

        Returns (buffers: {var: _Buffer}, body_at: {idx: (bytes, breakdown)},
        param_var: {param_number: var}, root: (idx, out_vars) | None,
        boundary: index of the first backward-stamped instruction that reads
        a forward temporary | -1, n_instr).
        param_classes None = body computation: parameters are caller-owned
        views and contribute nothing here.
        """
        bufs: Dict[str, _Buffer] = {}
        # var -> ("ref", v) | ("tuple", (v...)) | ("elt", tuple_var, index)
        # — element-level aliasing matters: a gte selecting ONE element of
        # a fat while carry must not keep every carry buffer alive
        alias: Dict[str, Tuple] = {}
        body_at: Dict[int, Tuple[int, Dict[str, int]]] = {}
        param_var: Dict[int, str] = {}
        root = None
        boundary = -1
        bwd_vars = set()
        i = 0

        def roots(var: str, _depth: int = 0) -> List[str]:
            if var in bufs:
                return [var]
            a = alias.get(var)
            if a is None or _depth > 64:
                return []
            if a[0] == "ref":
                return roots(a[1], _depth + 1)
            if a[0] == "tuple":
                out: List[str] = []
                for v in a[1]:
                    out.extend(roots(v, _depth + 1))
                return out
            # ("elt", tv, k): chase refs until a tuple structure resolves,
            # then select element k; anything opaque falls back to coarse
            tv, k = a[1], a[2]
            cur = tv
            for _ in range(64):
                if cur in bufs:
                    return [cur]   # materialized tuple buffer
                aa = alias.get(cur)
                if aa is None:
                    return []
                if aa[0] == "ref":
                    cur = aa[1]
                    continue
                if aa[0] == "tuple":
                    elems = aa[1]
                    if k < len(elems):
                        return roots(elems[k], _depth + 1)
                    return roots(cur, _depth + 1)
                return roots(cur, _depth + 1)   # nested elt: coarse
            return []

        for line in lines:
            m = _INSTR_RE.match(line)
            if not m:
                continue
            is_root = bool(m.group(1))
            var, rhs = m.group(2).lstrip("%"), m.group(3)
            is_bwd = bool(_BWD_MARK_RE.search(line))
            if is_bwd:
                bwd_vars.add(var)
            stripped = _strip_attrs(rhs)
            om = _OPCODE_RE.search(stripped)
            opcode = om.group(1) if om else ""
            type_text = stripped[:om.start()] if om else stripped
            operands = tuple(_OPERAND_RE.findall(stripped))
            for op_var in operands:
                for r in roots(op_var):
                    bufs[r].last = max(bufs[r].last, i)
                    # the backward STARTS where it first reads a forward
                    # temporary: backward-stamped leaves (partition-id,
                    # constants, the zero accumulators broadcast from them)
                    # are scheduled at the top of the entry computation
                    if is_bwd and boundary < 0 and r not in bwd_vars \
                            and not bufs[r].is_param:
                        boundary = i
            if opcode == "parameter":
                if param_classes is not None:
                    pm = _PARAM_NUM_RE.search(stripped)
                    num = int(pm.group(1)) if pm else -1
                    bufs[var] = _Buffer(
                        var=var, nbytes=result_bytes(type_text),
                        cls=param_classes.get(num, "params"),
                        first=0, last=i, line=line.strip()[:200],
                        is_param=True)
                    param_var[num] = var
                else:
                    # body carry: owned by the caller
                    alias[var] = ("tuple", ())
            elif opcode in _ALIAS_OPS or opcode.endswith("-done"):
                # view of the operand(s): dynamic-update-slice updates in
                # place; while reuses its carry; tuple/gte are pointers
                if opcode == "tuple":
                    alias[var] = ("tuple", operands)
                elif opcode == "get-tuple-element":
                    im = re.search(r"index=(\d+)", stripped)
                    alias[var] = ("elt", operands[0] if operands else "",
                                  int(im.group(1)) if im else 0)
                elif operands:
                    # while/dus/barrier/done: view of the first operand
                    alias[var] = ("ref", operands[0])
                else:
                    alias[var] = ("tuple", ())
            else:
                bufs[var] = _Buffer(
                    var=var, nbytes=result_bytes(type_text),
                    cls=self.temp_shapes.get(shape_key(type_text),
                                             "activations"),
                    first=i, last=i, line=line.strip()[:200])
            if opcode in ("while", "conditional", "call"):
                # bodies run at this instruction; conditions/reducers are
                # scalar math and peak at ~0, so max() lands on the body
                peaks = [self.body_peak(nm)
                         for cm in _CALLED_RE.finditer(rhs)
                         for nm in ([cm.group(1)] if cm.group(1)
                                    else re.findall(r"%?([\w.\-]+)",
                                                    cm.group(2) or ""))
                         if nm in self.comps]
                if peaks:
                    body_at[i] = max(peaks, key=lambda p: p[0])
            if is_root:
                out_vars = (list(operands) if opcode == "tuple" else [var])
                root = (i, out_vars)
            i += 1

        if root is not None:
            for v in root[1]:
                for r in roots(v):
                    bufs[r].last = i
        for v in param_var.values():
            bufs[v].last = i   # caller-owned: resident for the whole step
        return bufs, body_at, param_var, root, boundary, i

    # -- body peaks --------------------------------------------------------
    def body_peak(self, name: str) -> Tuple[int, Dict[str, int]]:
        """Internal temp peak of a non-entry computation (its carry is
        charged at the call site)."""
        if name in self._body_peak:
            return self._body_peak[name]
        self._body_peak[name] = (0, {})   # cycle guard
        if name in self.comps:
            est = self._sweep(self.comps[name], param_classes=None)
            self._body_peak[name] = (est.peak_bytes, est.breakdown)
        return self._body_peak[name]

    # -- peak sweep --------------------------------------------------------
    def _sweep(self, lines: List[str],
               param_classes: Optional[Dict[int, str]],
               alias_pairs: Tuple[Tuple[int, int], ...] = ()
               ) -> MemoryEstimate:
        bufs, body_at, param_var, root, boundary, n = self._scan(
            lines, param_classes)
        if root is not None and param_classes is not None:
            # donated outputs write into their parameter's buffer: the
            # producing op is not a second allocation
            pvars = set(param_var.values())
            for out_idx, pnum in alias_pairs:
                if out_idx < len(root[1]) and pnum in param_var:
                    for b in bufs.values():
                        if b.var == root[1][out_idx].lstrip("%") \
                                and b.var not in pvars:
                            b.nbytes = 0
        elif root is not None:
            # while/conditional BODY: XLA requires the body root to share
            # the carry's shape/layout and buffer-assigns them in place —
            # the updated-carry producers are not second allocations (this
            # is what keeps a fused K-step program's peak ~1x one step's:
            # the inter-step state stays in the carry slot)
            for v in root[1]:
                b = bufs.get(v.lstrip("%"))
                if b is not None:
                    b.nbytes = 0

        est = MemoryEstimate()
        if param_classes is not None:
            for b in bufs.values():
                if b.is_param:
                    est.param_bytes[b.cls] = \
                        est.param_bytes.get(b.cls, 0) + b.nbytes

        # one O(n) sweep finds the peak index; the per-class breakdown and
        # largest-buffer list are reconstructed in a single linear pass at
        # that index afterwards (rebuilding them inside the sweep is
        # quadratic on the forward ramp of a real pod's module, where
        # almost every allocation raises the running peak)
        delta: Dict[int, int] = {}
        for b in bufs.values():
            delta[b.first] = delta.get(b.first, 0) + b.nbytes
            delta[b.last + 1] = delta.get(b.last + 1, 0) - b.nbytes
        live = 0
        for i in range(n + 1):
            live += delta.get(i, 0)
            body_b = body_at.get(i, (0, {}))[0]
            if live + body_b > est.peak_bytes:
                est.peak_bytes = live + body_b
                est.peak_index = i
        i_peak = est.peak_index
        at_peak = [b for b in bufs.values() if b.first <= i_peak <= b.last]
        bd: Dict[str, int] = {}
        for b in at_peak:
            bd[b.cls] = bd.get(b.cls, 0) + b.nbytes
        for c, by in body_at.get(i_peak, (0, {}))[1].items():
            bd[c] = bd.get(c, 0) + by
        est.breakdown = bd
        est.largest = sorted(((b.nbytes, b.cls, b.line)
                              for b in at_peak if b.nbytes),
                             key=lambda t: -t[0])[:8]

        est.boundary_index = boundary
        if boundary >= 0:
            est.boundary_bytes = sum(
                b.nbytes for b in bufs.values()
                if not b.is_param and b.cls == "activations"
                and b.first < boundary <= b.last)
        return est


def estimate_peak_hbm(optimized_hlo: str,
                      param_classes: Optional[Dict[int, str]] = None,
                      temp_class_shapes: Optional[Dict[str, str]] = None
                      ) -> MemoryEstimate:
    """Static peak-HBM estimate of one scheduled module.

    param_classes: entry-param number -> class ("params"/"opt"/...);
    unmapped params default to "params".
    temp_class_shapes: normalized "dtype[dims]" -> class for temporaries
    whose shape provenance is known (state-shaped temps are grads);
    unmatched temps are "activations".
    """
    comps, entry = _split_computations(optimized_hlo)
    if not entry:
        return MemoryEstimate()
    header_end = optimized_hlo.find("\n")
    header = optimized_hlo[:header_end] if header_end != -1 else optimized_hlo
    pairs: Tuple[Tuple[int, int], ...] = ()
    if _ALIAS_BLOCK_RE.search(header):
        pairs = tuple((int(o), int(p))
                      for o, p in _ALIAS_PAIR_RE.findall(header))
    lv = _Liveness(comps, temp_class_shapes)
    return lv._sweep(comps[entry], param_classes=param_classes or {},
                     alias_pairs=pairs)


# --------------------------------------------------------------------------
# Remat census (scheduled HLO + jax metadata)
# --------------------------------------------------------------------------

# jax.checkpoint regions stamp recomputed ops with /rematted_computation/ in
# their op_name metadata; autodiff backward ops carry transpose(jvp(...)).
_REMAT_MARK_RE = re.compile(r'op_name="[^"]*rematted_computation[^"]*"')
_BWD_MARK_RE = re.compile(r'op_name="[^"]*transpose\(jvp[^"]*"')


def parse_remat_census(optimized_hlo: str) -> Dict[str, int]:
    """{"remat_ops": recomputed-in-backward ops, "bwd_ops": ops stamped as
    autodiff transpose, "total_ops": all metadata-carrying ops} over the
    whole module text (fusion bodies included — remat survives fusion in
    the metadata)."""
    return {"remat_ops": len(_REMAT_MARK_RE.findall(optimized_hlo)),
            "bwd_ops": len(_BWD_MARK_RE.findall(optimized_hlo)),
            "total_ops": optimized_hlo.count('op_name="')}


# --------------------------------------------------------------------------
# SPMD partitioner warnings (involuntary full rematerialization)
# --------------------------------------------------------------------------

_SPMD_WARN_RE = re.compile(
    r"from sharding (\{[^}]*\}[^ ]*) to (\{[^}]*\}[^ ]*) without")
_SPMD_OP_RE = re.compile(
    r"HLO operation:\s*(%?[\w.\-]+)\s*=\s*(\w+\[[\d,]*\])")
_SPMD_SRC_RE = re.compile(r'source_file="([^"]+)"\s+source_line=(\d+)')
_SPMD_OPNAME_RE = re.compile(r'op_name="([^"]+)"')
# broadcast/iota fed only by scalars ("f32[]", "s32[]") or nothing:
# re-materializing one costs zero HBM traffic and zero meaningful flops
_SPMD_TRIVIAL_RE = re.compile(
    r"=\s*\w+\[[\d,]*\][^ ]*\s+(?:broadcast|iota|constant)"
    r"(?:\(\s*(?:\w+\[\]\S*\s*%?[\w.\-]+\s*,?\s*)*\))?[,\s]")


def parse_spmd_remat_warning(line: str) -> Dict[str, object]:
    """Structure one spmd_partitioner.cc 'Involuntary full
    rematerialization' log line into a machine-readable diagnosis.

    Sets ``trivial: True`` when the rematted op is a broadcast/iota/constant
    whose operands are all scalars — recomputing those is free (no HBM reads,
    no flops), so the fallback is benign and gates should not fire on it."""
    out: Dict[str, object] = {"raw": line.strip()[:500]}
    m = _SPMD_WARN_RE.search(line)
    if m:
        out["from_sharding"], out["to_sharding"] = m.group(1), m.group(2)
    m = _SPMD_OP_RE.search(line)
    if m:
        out["op"], out["shape"] = m.group(1), m.group(2)
        sm = _SHAPE_RE.search(m.group(2))
        if sm:
            out["nbytes"] = shape_bytes(sm.group(1), sm.group(2))
    if _SPMD_TRIVIAL_RE.search(line):
        out["trivial"] = True
    m = _SPMD_SRC_RE.search(line)
    if m:
        out["source_file"], out["source_line"] = m.group(1), int(m.group(2))
    m = _SPMD_OPNAME_RE.search(line)
    if m:
        out["op_name"] = m.group(1)
    return out


# --------------------------------------------------------------------------
# Replication scan (absorbed from utils/hlo_check.replicated_tensor_bytes)
# --------------------------------------------------------------------------

# HLO:        sharding={replicated}
# StableHLO:  mhlo.sharding = "{replicated}"
_REPLICATED_RE = re.compile(
    r'sharding\s*=\s*(?:"?\{replicated\}"?|\{\{replicated\}\})')
# anchored on '=' so only the RESULT shape is charged — matching operand
# shapes would bill a big sharded input to a tiny replicated result.
# int8 is in scope alongside floats: weight-only-quantized decode keeps
# its matmul weights as s8 payloads in HBM (ISSUE 17), and a replicated
# int8 weight stack wastes HBM exactly like a replicated float one
_FLOAT_SHAPE_RE = re.compile(r"=\s*(f32|bf16|f16|f64|s8|u8)\[([\d,]+)\]")
_FLOAT_SHAPE_ST_RE = re.compile(r"tensor<([\dx]+)x(f32|bf16|f16|f64|i8|ui8)>")


def replicated_tensor_bytes(hlo_text: str,
                            min_bytes: int = 1 << 20) -> List[Tuple[int, str]]:
    """Scan HLO (or StableHLO) text for explicitly replicated float tensors
    larger than min_bytes. Returns (bytes, line) tuples, largest first.

    Complements the runtime SPMD-warning capture (analysis.program): the
    warning catches the partitioner's resharding *fallback*; this catches ops
    that were *assigned* a replicated sharding for activation-sized tensors.
    """
    out = []
    for line in hlo_text.splitlines():
        if not _REPLICATED_RE.search(line):
            continue
        nbytes = 0
        m = _FLOAT_SHAPE_RE.search(line)
        if m:
            nbytes = shape_bytes(m.group(1), m.group(2))
        else:
            st = _FLOAT_SHAPE_ST_RE.search(line)
            if st:
                dims, dt = st.groups()
                nbytes = shape_bytes(dt, dims.replace("x", ","))
        if nbytes >= min_bytes:
            out.append((nbytes, line.strip()[:200]))
    return sorted(out, key=lambda t: -t[0])

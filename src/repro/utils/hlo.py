"""HLO text analysis: FLOPs / bytes / collective-traffic for the roofline.

XLA's ``compiled.cost_analysis()`` counts a while-loop body ONCE, so any
scan-over-layers model under-reports flops and bytes by ~num_layers. All
three roofline terms therefore come from walking ``compiled.as_text()``
ourselves:

  * ``collective_bytes`` — wire bytes of every all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute;
  * ``hlo_cost`` — dot/convolution FLOPs plus "bytes accessed" (operand +
    result bytes of every materialization-boundary op, i.e. post-fusion
    instructions; fusion internals are on-chip and not counted);

both multiplying ops inside while bodies by the loop trip count.

Trip counts are recovered from the loop condition: XLA canonical while
conditions compare the induction variable against a constant; we take the
largest integer constant compared in the condition computation. This is a
heuristic (documented in DESIGN.md §8) validated by tests against known
scan lengths.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def xla_cost_analysis(compiled) -> dict:
    """XLA's own cost analysis of ``compiled`` (a dict of named counters)."""
    return dict(compiled.cost_analysis())

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_RE = re.compile(r"^\s*%?([\w\.\-]+)\s*(?:\([^)]*\))?\s*->", re.M)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    """computation name -> lines."""
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in hlo.splitlines():
        m = re.match(r"^\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->", line)
        if m and ("{" in line or line.rstrip().endswith("{")):
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)
    return comps


def _while_bodies_with_trips(hlo: str, comps) -> Dict[str, int]:
    """body computation name -> trip count.

    Primary source: XLA's ``backend_config={"known_trip_count":{"n":N}}``
    annotation on the while op; fallback: the largest integer constant in
    the loop-condition computation (canonical scan loops compare the
    induction variable against the length)."""
    out: Dict[str, int] = {}
    for line in hlo.splitlines():
        m = re.search(
            r"while\(.*?condition=%?([\w\.\-]+).*?body=%?([\w\.\-]+)", line)
        if not m:
            continue
        cond, body = m.group(1), m.group(2)
        kt = re.search(r'known_trip_count[":{\s]+n["\s:]+"?(\d+)', line)
        if kt:
            out[body] = int(kt.group(1))
            continue
        trip = 1
        for cline in comps.get(cond, []):
            for c in re.finditer(r"constant\((\d+)\)", cline):
                trip = max(trip, int(c.group(1)))
        out[body] = trip
    return out


def _called_by(comps) -> Dict[str, List[str]]:
    """computation -> computations it calls (body/branches/called comps)."""
    calls = defaultdict(list)
    names = set(comps)
    for name, lines in comps.items():
        for line in lines:
            for m in re.finditer(r"(?:body|condition|to_apply|branch_computations=\{[^}]*)"
                                 r"=?%?([\w\.\-]+)", line):
                if m.group(1) in names:
                    calls[name].append(m.group(1))
    return calls


def collective_bytes(hlo: str) -> Dict[str, float]:
    """Returns {collective_kind: bytes, "total": bytes} with while-loop
    multipliers applied and CPU-backend precision-simulation fusions
    counted at semantic width (see _roundtrip_factor)."""
    comps = _split_computations(hlo)
    trips = _while_bodies_with_trips(hlo, comps)
    calls = _called_by(comps)
    parsed = {name: _parse_computation(lines) for name, lines in comps.items()}
    factors = _semantic_factors(parsed)

    # propagate multipliers: a computation called from a while body inherits
    # the body's trip count (one level of nesting handled transitively)
    mult: Dict[str, float] = defaultdict(lambda: 1.0)
    for body, t in trips.items():
        stack = [(body, float(t))]
        seen = set()
        while stack:
            name, m = stack.pop()
            if name in seen:
                continue
            seen.add(name)
            mult[name] = max(mult[name], m)
            for child in calls.get(name, []):
                child_t = trips.get(child, 1)
                stack.append((child, m * child_t))

    out: Dict[str, float] = defaultdict(float)
    for name, lines in comps.items():
        m = mult[name]
        for line in lines:
            for kind in COLLECTIVES:
                if not re.search(rf"\s{re.escape(kind)}(-start)?\(", line):
                    continue
                # scheduled HLO: '%x = f32[a,b]{layout} all-gather(%y), ...'
                # operands are bare refs; take the RESULT shape and convert
                # to approximate per-device wire bytes via the group size.
                mm = re.search(rf"=\s*(.+?)\s+{re.escape(kind)}(?:-start)?\(",
                               line)
                b = _shape_bytes(mm.group(1)) if mm else 0
                # semantic width: a collective fed by a bf16->f32 roundtrip
                # fusion moves bf16 on real (TPU/GPU) hardware
                om = re.search(rf"{re.escape(kind)}(?:-start)?\(%([\w\.\-]+)",
                               line)
                if om and om.group(1) in factors:
                    b *= factors[om.group(1)]
                g = _group_size(line)
                if kind == "all-reduce":
                    wire = 2.0 * b * (g - 1) / max(g, 1)
                elif kind == "all-gather":
                    wire = b * (g - 1) / max(g, 1)
                elif kind == "reduce-scatter":
                    wire = b * (g - 1)            # result is 1/g of operand
                elif kind == "all-to-all":
                    wire = b * (g - 1) / max(g, 1)
                else:                              # collective-permute
                    wire = b
                out[kind] += wire * m
                out["count_" + kind] += m
                break
    out["total"] = sum(v for k, v in out.items() if k in COLLECTIVES)
    return dict(out)


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


# ---------------------------------------------------------------------------
# full FLOPs / bytes walk (while-trip-count aware)
# ---------------------------------------------------------------------------

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+?)\s+"
                       r"([\w\-]+)\((.*)\)(.*)$")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_ENTRY_RE = re.compile(r"^\s*ENTRY\s+%?([\w\.\-]+)", re.M)
_DIMS_RE = re.compile(r"\[([\d,]*)\]")

# ops whose operands/results live in registers after fusion — not memory
_NO_BYTES = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "while", "conditional", "call", "after-all", "add-dependency",
             "domain", "partition-id", "replica-id", "iota", "fusion-marker"}


def _shape_dims(shape_str: str) -> List[int]:
    m = _DIMS_RE.search(shape_str)
    if not m or not m.group(1):
        return []
    return [int(d) for d in m.group(1).split(",") if d]


def _parse_computation(lines: List[str]):
    """-> (symbol table name->shape str, instruction tuples)."""
    symbols: Dict[str, str] = {}
    instrs = []
    for line in lines:
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, shape, op, operands, attrs = m.groups()
        symbols[name] = shape
        instrs.append((name, shape, op, operands, attrs, line))
    return symbols, instrs


# lhs operand of a dot: either 'dot(%name, ...' (bare refs) or the typed
# form current XLA prints, 'dot(f32[128,256]{1,0} %name, ...' — capture the
# optional inline shape and the name
_DOT_LHS_RE = re.compile(
    r"dot\(\s*(?:(\w+\[[\d,]*\](?:\{[\d,]*\})?)\s+)?%?([\w\.\-]+)")


def _dot_flops(shape: str, line: str, symbols: Dict[str, str]) -> float:
    """2 * result_elems * prod(lhs contracting dims)."""
    res_elems = 1
    for d in _shape_dims(shape):
        res_elems *= d
    mo = _DOT_LHS_RE.search(line)
    if not mo:
        return 0.0
    # inline operand shape (typed operands) beats the symbol table; with
    # bare refs the shape comes from the producing instruction
    lhs_shape = mo.group(1) or symbols.get(mo.group(2), "")
    lhs_dims = _shape_dims(lhs_shape)
    k = 1
    mc = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
    if mc and mc.group(1):
        for idx in mc.group(1).split(","):
            i = int(idx)
            if i < len(lhs_dims):
                k *= lhs_dims[i]
    return 2.0 * res_elems * k


def _conv_flops(shape: str, operands: str, symbols: Dict[str, str]) -> float:
    """2 * result_elems * kernel_elems / out_features (approximation)."""
    res_elems = 1
    for d in _shape_dims(shape):
        res_elems *= d
    ops = _OPERAND_RE.findall(operands)
    if len(ops) < 2:
        return 0.0
    k_dims = _shape_dims(symbols.get(ops[1], ""))
    k_elems = 1
    for d in k_dims:
        k_elems *= d
    out_feat = k_dims[-1] if k_dims else 1
    return 2.0 * res_elems * k_elems / max(out_feat, 1)


def _operand_names(operands: str) -> List[str]:
    # operand list ends at the first ')' — attrs follow
    head = operands.split(")", 1)[0]
    return _OPERAND_RE.findall(head)


_DTYPE_RE = re.compile(r"(\w+)\[")


def _elem_width(shape_str: str) -> int:
    m = _DTYPE_RE.search(shape_str)
    return _DTYPE_BYTES.get(m.group(1), 0) if m else 0


def _roundtrip_factor(called) -> float:
    """XLA:CPU simulates bf16 compute by f32 round-trips: the fused
    computation contains ``convert(narrow)`` followed by ``convert`` back to
    the root's wide dtype (often mixed with slice/bitcast/copy ops, e.g.
    scan-layer weight fetch: dynamic-slice -> bf16 -> f32 -> bitcast). On
    TPU the value stays at the narrow width, so tensors produced by such
    fusions are counted at their SEMANTIC width (factor = narrow/wide)."""
    if called is None:
        return 1.0
    _, cinstrs = called
    if not cinstrs:
        return 1.0
    root_w = 0
    conv_widths = []
    for (n, sh, op, opr, at, line) in cinstrs:
        if line.lstrip().startswith("ROOT"):
            root_w = _elem_width(sh)
        if op == "convert":
            conv_widths.append(_elem_width(sh))
    if not root_w or not conv_widths:
        return 1.0
    narrow = min(conv_widths)
    # a true round-trip: something was narrowed below the root width AND
    # converted back up to it inside the same fusion
    if 0 < narrow < root_w and any(w == root_w for w in conv_widths):
        return narrow / root_w
    return 1.0


def _semantic_factors(parsed) -> Dict[str, float]:
    """instruction name -> semantic width factor, per convert-roundtrip
    fusion anywhere in the module (instruction names are module-unique)."""
    factors: Dict[str, float] = {}
    for name, (symbols, instrs) in parsed.items():
        for (iname, shape, op, operands, attrs, line) in instrs:
            if op != "fusion":
                continue
            mm = re.search(r"calls=%?([\w\.\-]+)", line)
            if not mm:
                continue
            f = _roundtrip_factor(parsed.get(mm.group(1)))
            if f < 1.0:
                factors[iname] = f
    return factors


def _instr_bytes(shape: str, operands: str, symbols: Dict[str, str],
                 factors: Optional[Dict[str, float]] = None,
                 own: str = "") -> float:
    factors = factors or {}
    b = _shape_bytes(shape) * factors.get(own, 1.0)
    for o in _operand_names(operands):
        b += _shape_bytes(symbols.get(o, "")) * factors.get(o, 1.0)
    return float(b)


def _fusion_bytes(shape: str, operands: str, symbols: Dict[str, str],
                  called: Optional[Tuple[Dict[str, str], list]],
                  factors: Optional[Dict[str, float]] = None,
                  own: str = "") -> float:
    """Bytes accessed at a fusion boundary.

    Scan-over-layers fusions take full stacked arrays but only touch a
    dynamic-slice per iteration; counting the full operand would overstate
    the loop's traffic by the trip count. Parameters consumed exclusively by
    dynamic-slice count their slice bytes; parameters consumed exclusively
    as the target of dynamic-update-slice count the update bytes (in-place
    write); a DUS root likewise counts the update, not the full buffer."""
    factors = factors or {}
    if called is None:
        return _instr_bytes(shape, operands, symbols, factors, own)
    csyms, cinstrs = called
    onames = _operand_names(operands)
    # map parameter name -> index, and find each parameter's consumers
    params = {}
    consumers = defaultdict(list)
    root_op = None
    for (n, sh, op, opr, at, line) in cinstrs:
        if op == "parameter":
            mi = re.search(r"parameter\((\d+)\)", line)
            params[n] = (sh, int(mi.group(1)) if mi else -1)
        for o in _operand_names(opr):
            consumers[o].append((op, sh, opr))
        if line.lstrip().startswith("ROOT") or " ROOT " in line:
            root_op = (op, sh, opr)

    total = 0.0
    for pname, (pshape, pidx) in params.items():
        oname = onames[pidx] if 0 <= pidx < len(onames) else ""
        f = factors.get(oname, 1.0)
        cons = consumers.get(pname, [])
        if cons and all(c[0] in ("dynamic-slice", "slice") for c in cons):
            total += f * sum(_shape_bytes(c[1]) for c in cons)
        elif cons and all(
                c[0] == "dynamic-update-slice"
                and _operand_names(c[2])[:1] == [pname] for c in cons):
            # in-place update target: read/write only the update window
            for c in cons:
                upd = _operand_names(c[2])
                if len(upd) > 1:
                    total += f * _shape_bytes(csyms.get(upd[1], ""))
        else:
            total += f * _shape_bytes(pshape)
    f_own = factors.get(own, 1.0)
    # result bytes: a DUS root writes only the update window
    if root_op and root_op[0] == "dynamic-update-slice":
        upd = _operand_names(root_op[2])
        total += f_own * (_shape_bytes(csyms.get(upd[1], ""))
                          if len(upd) > 1 else _shape_bytes(shape))
    else:
        total += f_own * _shape_bytes(shape)
    return total


def hlo_cost(hlo: str) -> Dict[str, float]:
    """{"flops", "bytes", "dot_flops", "instr_count"} from a post-SPMD HLO
    module text, with while-loop bodies multiplied by their trip counts.

    Semantics match XLA's per-instruction cost analysis on post-fusion HLO:
    every instruction reads its operands and writes its result to memory;
    fusion internals are free (flops inside fusions ARE counted)."""
    comps = _split_computations(hlo)
    trips = _while_bodies_with_trips(hlo, comps)
    parsed = {name: _parse_computation(lines) for name, lines in comps.items()}
    factors = _semantic_factors(parsed)
    # propagate semantic width through shape-preserving ops (collectives,
    # copies): a collective of a roundtrip-fusion output is narrow too
    for _ in range(2):
        for name, (symbols, instrs) in parsed.items():
            for (iname, shape, op, operands, attrs, line) in instrs:
                if iname in factors:
                    continue
                if op in ("copy", "bitcast", "reshape", "transpose") or \
                        any(op.startswith(c) for c in COLLECTIVES):
                    ons = _operand_names(operands)
                    if ons and all(o in factors for o in ons):
                        factors[iname] = factors[ons[0]]
    em = _ENTRY_RE.search(hlo)
    entry = em.group(1) if em else next(iter(comps), None)

    # map while-op line -> (cond, body) for per-callsite trip attribution
    def cost_of(name: str, depth: int = 0) -> Tuple[float, float]:
        if name not in parsed or depth > 12:
            return (0.0, 0.0)
        symbols, instrs = parsed[name]
        flops = 0.0
        bytes_ = 0.0
        for iname, shape, op, operands, attrs, line in instrs:
            if op == "dot":
                flops += _dot_flops(shape, line, symbols)
                bytes_ += _instr_bytes(shape, operands, symbols, factors, iname)
            elif op == "convolution":
                flops += _conv_flops(shape, operands, symbols)
                bytes_ += _instr_bytes(shape, operands, symbols, factors, iname)
            elif op == "while":
                mm = re.search(r"condition=%?([\w\.\-]+).*?body=%?([\w\.\-]+)",
                               line)
                if mm:
                    cond, body = mm.groups()
                    t = trips.get(body, 1)
                    f_b, b_b = cost_of(body, depth + 1)
                    f_c, b_c = cost_of(cond, depth + 1)
                    flops += t * (f_b + f_c)
                    bytes_ += t * (b_b + b_c)
            elif op == "conditional":
                for bc in re.findall(r"branch_computations=\{([^}]*)\}", line):
                    branch_costs = [cost_of(b.strip().lstrip("%"), depth + 1)
                                    for b in bc.split(",")]
                    if branch_costs:
                        flops += max(c[0] for c in branch_costs)
                        bytes_ += max(c[1] for c in branch_costs)
            elif op == "call":
                mm = re.search(r"to_apply=%?([\w\.\-]+)", line)
                if mm:
                    f_c, b_c = cost_of(mm.group(1), depth + 1)
                    flops += f_c
                    bytes_ += b_c
                bytes_ += _instr_bytes(shape, operands, symbols, factors, iname)
            elif op == "fusion":
                # internals are on-chip; count dot flops inside, bytes at
                # the fusion boundary only (slice-aware for scan patterns)
                mm = re.search(r"calls=%?([\w\.\-]+)", line)
                called = parsed.get(mm.group(1)) if mm else None
                if mm:
                    f_c, _ = cost_of(mm.group(1), depth + 1)
                    flops += f_c
                bytes_ += _fusion_bytes(shape, operands, symbols, called,
                                        factors, iname)
            elif op in ("slice", "dynamic-slice"):
                # reads only the window it produces
                bytes_ += 2.0 * _shape_bytes(shape)
            elif op == "dynamic-update-slice":
                # in-place window write: read + write the update only
                upd = _operand_names(operands)
                ub = (_shape_bytes(symbols.get(upd[1], ""))
                      if len(upd) > 1 else _shape_bytes(shape))
                bytes_ += 2.0 * ub
            elif op in _NO_BYTES:
                continue
            else:
                bytes_ += _instr_bytes(shape, operands, symbols, factors, iname)
        return flops, bytes_

    # memoize via simple cache keyed by name (trip-independent)
    cache: Dict[str, Tuple[float, float]] = {}
    orig = cost_of

    def cost_cached(name: str, depth: int = 0) -> Tuple[float, float]:
        if name in cache:
            return cache[name]
        r = orig(name, depth)
        cache[name] = r
        return r

    cost_of = cost_cached  # noqa: F811 — recursion goes through the cache
    flops, bytes_ = cost_of(entry) if entry else (0.0, 0.0)
    return {"flops": flops, "bytes": bytes_}


def top_bytes(hlo: str, n: int = 20):
    """The heaviest instructions by bytes x loop-trips — the §Perf profile
    (what to look at first when the memory roofline term dominates)."""
    comps = _split_computations(hlo)
    trips = _while_bodies_with_trips(hlo, comps)
    calls = _called_by(comps)
    parsed = {name: _parse_computation(lines) for name, lines in comps.items()}
    factors = _semantic_factors(parsed)
    mult: Dict[str, float] = defaultdict(lambda: 1.0)
    for body, t in trips.items():
        stack = [(body, float(t))]
        seen = set()
        while stack:
            nm, m = stack.pop()
            if nm in seen:
                continue
            seen.add(nm)
            mult[nm] = max(mult[nm], m)
            for ch in calls.get(nm, []):
                stack.append((ch, m * trips.get(ch, 1)))
    rows = []
    for name, (symbols, instrs) in parsed.items():
        m = mult[name]
        for (iname, shape, op, operands, attrs, line) in instrs:
            if op in _NO_BYTES or op in ("while",):
                continue
            if op == "fusion":
                mm = re.search(r"calls=%?([\w\.\-]+)", line)
                b = _fusion_bytes(shape, operands, symbols,
                                  parsed.get(mm.group(1)) if mm else None,
                                  factors, iname)
            elif op in ("slice", "dynamic-slice"):
                b = 2.0 * _shape_bytes(shape)
            else:
                b = _instr_bytes(shape, operands, symbols, factors, iname)
            rows.append((b * m, op, shape.split("{")[0][:60], m, name[:40]))
    rows.sort(reverse=True)
    return rows[:n]

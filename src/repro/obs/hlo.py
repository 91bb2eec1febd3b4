"""Compiled-executor introspection: FLOPs, bytes, peak memory, collectives.

The host-side pillars (spans/retrace/ledger) say what the *driver* did;
this module says what the *compiled program* is — straight from XLA's own
analyses of the memoized executors, never from running anything:

  * ``cost_analysis``   — compiler-estimated FLOPs and bytes accessed;
  * ``memory_analysis`` — argument/output/temp/alias buffer sizes, folded
    into the same analytic peak the dryrun harness reports
    (arg + out + temp − alias);
  * a structured **collective census** over the HLO text — per collective
    kind (all-reduce / all-gather / reduce-scatter / all-to-all /
    collective-permute): occurrence count, operand bytes, and replica
    group sizes. The census is what turns PR 4's "``all-reduce`` appears
    in the HLO" string assert into "exactly one client-axis all-reduce,
    spanning all client shards" — and gives the mesh-regression
    investigation per-collective numbers.

`op_scopes` maps each top-level instruction of a compiled module (what a
device trace shows as an op) to the program phase it belongs to: the
innermost of the round step's named scopes (`SCOPES`) in its metadata.

Everything here is AOT: `analyze_executor` lowers the executor's own
program for the shapes the driver actually dispatched (specs captured
before donation) under `retrace.suspended()`, so the compile-watermark
pins stay exact and the run's numerics are untouched. Results surface as
`RunResult.cost_stats`, the `bench_engine/v3` per-engine breakdown, and
`dryrun --cost`.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro.obs import retrace  # noqa: F401  (re-exported context for callers)

# dtype byte widths for HLO shape strings (mirrors the roofline parser —
# benchmarks cross-check the two against each other)
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_CUSTOM_CALL_RE = re.compile(r'custom_call_target="([^"]+)"')
# literal groups {{0,1},{2,3}} / {} or iota form [groups,size]<=[n]
_GROUPS_RE = re.compile(
    r"replica_groups=(\{(?:\{[^}]*\},?)*\}|\[[^\]]*\](?:<=\[[^\]]*\])?)")


def _shape_bytes(shape_str: str) -> float:
    nbytes = 0.0
    for sm in _SHAPE_RE.finditer(shape_str):
        dt, dims = sm.group(1), sm.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _DTYPE_BYTES[dt]
    return nbytes


def _group_sizes(raw: str) -> List[int]:
    """Participant count per replica group from the HLO attribute text."""
    if raw.startswith("["):             # iota form: [groups,size]<=[n]
        dims = [int(x) for x in raw[1:raw.index("]")].split(",") if x]
        if len(dims) == 2:
            return [dims[1]] * dims[0]
        if len(dims) == 1:
            return dims
        return []
    inner = raw.strip("{}")
    if not inner:
        return []
    return [len([t for t in grp.split(",") if t.strip()])
            for grp in inner.split("},{")]


def collective_census(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """Structured census of collectives in a per-device HLO module.

    Returns ``{op: {"count", "bytes", "group_sizes"}}`` where `bytes` sums
    output-shape operand bytes over occurrences (the roofline link-bytes
    convention) and `group_sizes` lists each occurrence's replica-group
    width (empty when the op carries no replica_groups attribute, e.g.
    collective-permute's source-target pairs). `-start` variants count as
    the base op; their `-done` halves carry no '=shape op(' pattern, so
    nothing is double-counted.
    """
    census: Dict[str, Dict[str, Any]] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        ent = census.setdefault(
            op, {"count": 0, "bytes": 0.0, "group_sizes": []})
        ent["count"] += 1
        ent["bytes"] += _shape_bytes(shape_str)
        gm = _GROUPS_RE.search(line)
        if gm:
            ent["group_sizes"].extend(_group_sizes(gm.group(1)))
    return census


def custom_call_census(hlo_text: str) -> Dict[str, int]:
    """Occurrences of each custom-call target in an HLO module. On a TPU,
    every Pallas (Mosaic) kernel is a ``tpu_custom_call``; the XLA
    fallbacks add none."""
    census: Dict[str, int] = {}
    for m in _CUSTOM_CALL_RE.finditer(hlo_text):
        census[m.group(1)] = census.get(m.group(1), 0) + 1
    return census


# the round step's named scopes (core/zo, core/pairzero, models/transformer)
SCOPES = ("theta_plus", "theta_minus", "theta_update", "forward_blocks",
          "lm_head_ce", "ota_aggregate")
_COMPUTATION_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# computations whose instructions run as ops of their own: loop bodies and
# conditions, conditional branches, called computations
_CONTROL_RE = re.compile(
    r"(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")
_CALL_RE = re.compile(r"to_apply=%?([\w.\-]+)")
_WRAPPED_RE = re.compile(r"^[\w\-]+\((.*)\)$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
# opcodes that run nothing on the device: they keep their own scope
_NO_WORK = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")


def opcode(rhs: str) -> str:
    """The opcode of an instruction's right-hand side (`shape opcode(...)`),
    skipping a tuple shape's parentheses."""
    i = 0
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        i += 1
    rest = rhs[i:].lstrip()
    if i == 0:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return rest.split("(", 1)[0].strip()


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of `SCOPES` in an op_name path, or None. Transform
    wrappers (`vmap(theta_plus)`, `jvp(...)`) are looked through."""
    for part in reversed(op_name.split("/")):
        m = _WRAPPED_RE.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED_RE.match(part)
        if part in SCOPES:
            return part
    return None


def computations(hlo_text: str
                 ) -> Tuple[Dict[str, List[Tuple[str, str]]], Optional[str]]:
    """({computation: [(instruction, right-hand side)]}, entry name) of a
    module's text."""
    comps: Dict[str, List[Tuple[str, str]]] = {}
    entry = None
    current: Optional[List[Tuple[str, str]]] = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m and not line.startswith((" ", "\t")):
            current = comps.setdefault(m.group(2), [])
            if m.group(1):
                entry = m.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION_RE.match(line)
        if m and current is not None:
            current.append((m.group(1), m.group(2)))
    return comps, entry


def op_name(rhs: str) -> Optional[str]:
    """The `op_name` metadata of an instruction's right-hand side."""
    m = _OP_NAME_RE.search(rhs)
    return m.group(1) if m else None


def _inherit(insts: List[Tuple[str, str]],
             scopes: Dict[str, Optional[str]]) -> None:
    """Give each instruction of one computation that does work but has no
    metadata (the copies the compiler inserts for layouts and loop
    carries) the scope of its first scoped operand, else of its first
    scoped user."""
    local = {name for name, _ in insts}
    operands = {name: [o for o in _OPERAND_RE.findall(rhs.split("),")[0])
                       if o in local and o != name]
                for name, rhs in insts}
    users: Dict[str, List[str]] = {name: [] for name in local}
    for name, ops in operands.items():
        for o in ops:
            users[o].append(name)
    bare = [name for name, rhs in insts
            if op_name(rhs) is None and opcode(rhs) not in _NO_WORK]
    changed = True
    while changed:
        changed = False
        for name in bare:
            if scopes[name] is None:
                near = [scopes[o] for o in operands[name] + users[name]]
                scope = next((x for x in near if x is not None), None)
                if scope is not None:
                    scopes[name] = scope
                    changed = True


def op_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """{instruction name: scope} for every top-level instruction of a
    compiled module's text (`compiled.as_text()`): those of the entry
    computation and of the loop bodies, conditions, branches and called
    computations reachable from it — not those inside fusions or
    reducers. The scope is `scope_of` the instruction's `op_name`
    metadata, None where it has none of `SCOPES`; an instruction with no
    metadata at all takes its operand's scope, or its user's."""
    comps, entry = computations(hlo_text)
    out: Dict[str, Optional[str]] = {}
    todo = [entry] if entry is not None else []
    seen = set(todo)
    while todo:
        comp = comps.get(todo.pop(), [])
        for name, rhs in comp:
            op = opcode(rhs)
            meta = op_name(rhs)
            out[name] = scope_of(meta) if meta else None
            called = []
            for cm in _CONTROL_RE.finditer(rhs):
                if cm.group(1):
                    called.append(cm.group(1))
                else:
                    called.extend(c.strip().lstrip("%")
                                  for c in cm.group(2).split(","))
            if op == "call":
                called.extend(_CALL_RE.findall(rhs))
            for c in called:
                if c and c not in seen:
                    seen.add(c)
                    todo.append(c)
        _inherit(comp, out)
    return out


@dataclass
class CostStats:
    """XLA's own account of one compiled program (per-device numbers)."""

    flops: float = 0.0              # cost_analysis "flops"
    bytes_accessed: float = 0.0     # cost_analysis "bytes accessed"
    argument_bytes: int = 0         # memory_analysis buffer classes
    output_bytes: int = 0
    temp_bytes: int = 0
    alias_bytes: int = 0
    peak_bytes: int = 0             # arg + out + temp − alias
    generated_code_bytes: int = 0
    collectives: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    custom_calls: Dict[str, int] = field(default_factory=dict)

    @property
    def collective_bytes(self) -> float:
        """Total operand bytes over every collective occurrence."""
        return float(sum(e["bytes"] for e in self.collectives.values()))

    def to_dict(self) -> Dict[str, Any]:
        """JSON-ready view (what RunResult/bench artifacts record)."""
        return {
            "flops": self.flops, "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes, "alias_bytes": self.alias_bytes,
            "peak_bytes": self.peak_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "collective_bytes": self.collective_bytes,
            "collectives": self.collectives,
            "custom_calls": self.custom_calls,
        }


def describe(stats, indent: str = "  ") -> str:
    """Human-readable block for a CostStats (or its dict) — dryrun
    --cost, logs."""
    if hasattr(stats, "to_dict"):
        stats = stats.to_dict()
    lines = [
        f"{indent}flops            {stats['flops']:.3e}",
        f"{indent}bytes accessed   {stats['bytes_accessed']:.3e}",
        f"{indent}peak bytes       {stats['peak_bytes']:,}"
        f"  (arg {stats['argument_bytes']:,} + out {stats['output_bytes']:,}"
        f" + temp {stats['temp_bytes']:,} - alias {stats['alias_bytes']:,})",
    ]
    colls = stats.get("collectives") or {}
    if not colls:
        lines.append(f"{indent}collectives      none")
    for op, ent in sorted(colls.items()):
        gs = ent.get("group_sizes") or []
        lines.append(
            f"{indent}{op:<16} x{ent['count']}  {ent['bytes']:.3e} B"
            + (f"  groups={gs}" if gs else ""))
    return "\n".join(lines)


def analyze_compiled(compiled) -> CostStats:
    """Read cost/memory/collective analyses off an already-compiled
    executable (`jit(f).lower(...).compile()`); never executes it."""
    ca = compiled.cost_analysis() or {}
    stats = CostStats(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)))
    try:
        mem = compiled.memory_analysis()
    except Exception:                    # backend without memory stats
        mem = None
    if mem is not None:
        stats.argument_bytes = int(
            getattr(mem, "argument_size_in_bytes", 0) or 0)
        stats.output_bytes = int(
            getattr(mem, "output_size_in_bytes", 0) or 0)
        stats.temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
        stats.alias_bytes = int(getattr(mem, "alias_size_in_bytes", 0) or 0)
        stats.generated_code_bytes = int(
            getattr(mem, "generated_code_size_in_bytes", 0) or 0)
        stats.peak_bytes = (stats.argument_bytes + stats.output_bytes
                            + stats.temp_bytes - stats.alias_bytes)
    try:
        hlo = compiled.as_text()
    except Exception:                    # text unavailable on some backends
        hlo = ""
    stats.collectives = collective_census(hlo)
    stats.custom_calls = custom_call_census(hlo)
    return stats


def specs_of(tree) -> Any:
    """ShapeDtypeStruct tree mirroring `tree`'s shapes/dtypes/shardings —
    capture this BEFORE dispatch so donation can't invalidate the args."""
    def spec(a):
        sh = getattr(a, "sharding", None)
        try:
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
        except TypeError:                # leaves without device sharding
            return jax.ShapeDtypeStruct(a.shape, a.dtype)
    return jax.tree_util.tree_map(spec, tree)


def analyze_executor(executor, carry_spec, ctl_spec, batch_spec) -> CostStats:
    """Cost/memory/collective stats for the program `executor` would run
    on stacks of these shapes. Duck-typed over `aot_compiled` (both
    LoopExecutor and ScanExecutor expose it), so the caller — fedsim's
    driver, benchmarks — stays engine-agnostic. Compile-only; memoized on
    the executor per shape signature."""
    compiled = executor.aot_compiled(carry_spec, ctl_spec, batch_spec)
    return analyze_compiled(compiled)

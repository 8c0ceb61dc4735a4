"""Where a program's device time goes, by the scopes the program enters.

Two halves, both stdlib-only like the rest of the package.

**Entering a scope.** `device_scope(name)` is `jax.named_scope(name)`
that also records `name` among the scopes this process knows: the name
reaches the compiled HLO's `op_name` metadata exactly as before, and
the account below learns it at trace time, from the one line in the
model that knows it (as `models/stats.py` `sow_stat` does for a
counter). No list of scope names is kept anywhere else.

**The account.** Pure functions on the plain trace form that
`perfbench/trace.py` documents, `{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns, stats], ...]}]}]}`
(`plain_trace` brings a `jax.profiler.ProfileData` into it; the fourth
entry of an event holds the stats the account reads and may be
missing):

- per device and per PROGRAM: an op of the "XLA Ops" line goes to the
  "XLA Modules" event that holds its start, so a trace of a run gives
  one account for `jit_update_step` and one for `jit_step`. A CPU
  profile has neither line: its ops are events on the runtime's
  thread lines whose stats name their module and run, and a run's
  first start to its last end stands for the module event (`layout`
  `host_threads`; several threads run a program's ops at once there,
  so no residual is taken);
- an op's SELF time: a `while`, `conditional` or `call` holds its
  body's ops on the same line, so self = length - children;
- op -> `op_name` from the compiled program's text, by instruction
  name (`read_program_text`): no event of either layout carries its
  path in its stats (jax 0.9.0; a CPU's carry `hlo_op`, `hlo_module`,
  `run_id`, a TPU's device events their timing alone);
- scope = the innermost KNOWN scope of that path, each component read
  through its `jvp(..)` / `transpose(..)` wrappers; phase `backward`
  under a `transpose(`, `rematerialised` where the path also passes
  `rematted_computation` (the second forward a backward runs), else
  `forward`;
- an op with no scope of its own is `unscoped`, and listed again
  (`unscoped_under`) under the scope of the innermost op that holds it
  in time, where one has a scope; else, where the compiler made the
  op and gave it no path (a relayout's `copy`, the `copy-done` of a
  prefetch), under the scope of the value it moves: its first operand
  with a path, else its first user; else under `none`;
- a row: ms a step, share of the step, by phase, by op kind (the
  instruction's name less its numbering; a custom call by its
  kernel's name) with calls a step, and `inclusive_ms` (the row with
  the scopes entered inside it);
- totals: `steps`, `module_ms` (mean module event), `period_ms` (mean
  distance of consecutive module starts: what the frames see),
  `sum_self_ms`, `residual_pct` = their difference over `module_ms`,
  and `overlap_pct` = the time of ops that run while an op that is no
  `while` / `conditional` / `call` does (0 in every trace of a v5e
  read so far); `AccountError` where either passes `max_residual_pct`
  (overlapping streams, a line the account does not know). The
  program in flight when the trace began is left out: its module
  event is cut short (what made the benchmark's traced step read 2%
  under the frames' in some runs, PERF.md section 7).
"""

import bisect
import collections
import re
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
UNSCOPED = "unscoped"
NO_LOOP = "none"
PHASES = ("forward", "backward", "rematerialised")
# Stats of an event the account reads: a CPU profile's op names its
# instruction, module and run there; a TPU's device events carry none
# of the three, and neither carries the op's path (jax 0.9.0).
KEPT_STATS = ("hlo_op", "hlo_module", "run_id")
# Ops that hold other ops of their line in time.
HOLDERS = ("while", "conditional", "call")
# A program's first op starts 7-9 us after its module event does (a
# v5e, PR 51); a first event of a plane whose op starts under 1 us
# after it was in flight when the trace began.
LAUNCH_NS = 1000.0
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap|remat|checkpoint)\((.*)\)$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ([^\n]*)", re.M)
_REFERENCE = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)

_known = set()


class AccountError(ValueError):
    """The ops' self times do not add up to the program's time."""


def device_scope(name: str):
    """`jax.named_scope(name)`, the name noted for the account. The
    package imports nothing heavy (beastlint's IMPORT-PURITY); a scope
    is entered while jax traces, so the module is there."""
    _known.add(name)
    return sys.modules["jax"].named_scope(name)


def known_device_scopes() -> frozenset:
    """The names `device_scope` was entered under in this process."""
    return frozenset(_known)


# --- from a profile to the plain form -----------------------------------------


def instruction_name(event_name: str) -> str:
    """`fusion.3` of `%fusion.3 = f32[8]{0} fusion(...)`: the profiler
    labels a device op with the whole HLO instruction."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def plain_trace(profile) -> Dict:
    """The planes the account reads of a `jax.profiler.ProfileData`
    (or anything with its `planes` / `lines` / `events` / `stats`)."""
    planes = []
    for plane in profile.planes:
        on_device = DEVICE_PLANE.match(plane.name) is not None
        lines = []
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for event in line.events:
                stats = {
                    key: value for key, value in event.stats
                    if key in KEPT_STATS
                }
                if not on_device and "hlo_module" not in stats:
                    continue
                name = event.name
                if line.name == OPS_LINE:
                    name = instruction_name(name)
                events.append([
                    name, float(event.start_ns), float(event.duration_ns),
                    stats,
                ])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


class ProgramText(NamedTuple):
    """What the account reads of a compiled program's text."""

    module: str
    op_names: Dict[str, str]  # instruction -> its own `op_name`
    # An instruction the compiler gave no `op_name` -> the path of the
    # value it moves: its first operand's, else its first user's.
    moves: Dict[str, str]


_NO_TEXT = ProgramText("", {}, {})


def read_program_text(text: str) -> ProgramText:
    """`compiled.as_text()` -> what a device op is joined on by its
    instruction's name, where the profile's events carry no path."""
    module = _MODULE.search(text)
    op_names: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    for match in _INSTRUCTION.finditer(text):
        name, rest = match.group(1), match.group(2)
        if name in operands:
            continue
        operands[name] = _REFERENCE.findall(rest)
        path = _OP_NAME.search(rest)
        if path is not None:
            op_names[name] = path.group(1)
    users: Dict[str, List[str]] = collections.defaultdict(list)
    for name, refs in operands.items():
        for ref in refs:
            users[ref].append(name)

    def reach(name: str, edges, depth: int = 8) -> str:
        """The first path along `edges`, through ops without one (a
        parameter's `op_name` is its argument's name, no path)."""
        for other in edges.get(name, ()):
            if "/" in op_names.get(other, ""):
                return op_names[other]
            if depth and other in operands and other not in op_names:
                found = reach(other, edges, depth - 1)
                if found:
                    return found
        return ""

    moves = {}
    for name in operands:
        if name not in op_names:
            path = reach(name, operands) or reach(name, users)
            if path:
                moves[name] = path
    return ProgramText(module.group(1) if module else "", op_names, moves)


# --- a path's scope and phase ------------------------------------------------------


def _bare(component: str) -> str:
    while True:
        match = _WRAPPER.match(component)
        if match is None:
            return component
        component = match.group(1)


def scopes_of(op_name: str, known: Iterable[str]) -> List[str]:
    """The known scopes a path passes, outermost first."""
    return [
        bare for bare in map(_bare, op_name.split("/")) if bare in known
    ]


def phase_of(op_name: str) -> str:
    if "transpose(" not in op_name:
        return "forward"
    if "/rematted_computation/" in op_name:
        return "rematerialised"
    return "backward"


def _opcode(instruction: str) -> str:
    """`fusion` of `fusion.310`, `broadcast` of `broadcast.4.clone`."""
    return instruction.split(".", 1)[0]


def op_kind(instruction: str, op_name: str) -> str:
    """The instruction's name less its numbering; a custom call by the
    last component of its path, the kernel's name."""
    kind = _opcode(instruction)
    if kind.startswith("custom-call") and op_name:
        return _bare(op_name.rsplit("/", 1)[-1])
    return kind


# --- the account ---------------------------------------------------------------------


def _line_events(plane: Dict, name: str) -> List:
    return [
        event for line in plane["lines"] if line["name"] == name
        for event in line["events"]
    ]


def _stats(event) -> Dict:
    return event[3] if len(event) > 3 and event[3] else {}


def _nest(events: List) -> Tuple[List[float], List[int]]:
    """(self time, index of the holder or -1) of every event of one
    line, the events sorted by start, a holder before what it holds."""
    selfs = [event[2] for event in events]
    parents = [-1] * len(events)
    stack: List[Tuple[float, int]] = []  # (end, index)
    for i, event in enumerate(events):
        start = event[1]
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            parents[i] = stack[-1][1]
            selfs[stack[-1][1]] -= event[2]
        stack.append((start + event[2], i))
    return selfs, parents


def _sorted(events: Iterable) -> List:
    return sorted(events, key=lambda event: (event[1], -event[2]))


class _Program(NamedTuple):
    """A program's events on one plane: `ops` all of the plane's, in
    `_nest`'s order with their self times and holders; `indices` those
    of this program; `modules` its module events, sorted."""

    name: str
    modules: List
    ops: List
    selfs: List[float]
    parents: List[int]
    indices: List[int]


def _device_programs(plane: Dict) -> List[_Program]:
    """A device plane's programs: an op belongs to the module event
    that holds its start; the program in flight when the trace began
    is left out, its cut event with its ops."""
    modules = _sorted(_line_events(plane, MODULES_LINE))
    ops = _sorted(_line_events(plane, OPS_LINE))
    selfs, parents = _nest(ops)
    starts = [module[1] for module in modules]
    found: Dict[str, _Program] = {}
    for module in modules:
        name = module[0].split("(")[0]
        found.setdefault(
            name, _Program(name, [], ops, selfs, parents, [])
        ).modules.append(module)
    for i, op in enumerate(ops):
        at = bisect.bisect_right(starts, op[1]) - 1
        if at >= 0 and op[1] < modules[at][1] + modules[at][2]:
            found[modules[at][0].split("(")[0]].indices.append(i)
    if modules and ops and ops[0][1] - modules[0][1] < LAUNCH_NS:
        # The trace began inside the plane's first program: its event
        # starts with the first op the profiler saw, not with the
        # program, and reads short (342.05 ms among 23 of 383.9).
        cut = found[modules[0][0].split("(")[0]]
        end = modules[0][1] + modules[0][2]
        cut.modules.remove(modules[0])
        cut.indices[:] = [i for i in cut.indices if ops[i][1] >= end]
    return [program for program in found.values() if program.modules]


def _thread_programs(plane: Dict) -> List[_Program]:
    """The same of a CPU profile's plane: ops on the runtime's thread
    lines, their module and run in their stats; a run's span stands
    for its module event."""
    ops, selfs, parents = [], [], []
    for line in plane["lines"]:
        events = _sorted(
            event for event in line["events"]
            if "hlo_module" in _stats(event)
        )
        line_selfs, line_parents = _nest(events)
        parents.extend(
            -1 if parent < 0 else parent + len(ops)
            for parent in line_parents
        )
        selfs.extend(line_selfs)
        ops.extend(events)
    runs: Dict[Tuple[str, object], List[int]] = collections.defaultdict(list)
    for i, op in enumerate(ops):
        stats = _stats(op)
        runs[(stats["hlo_module"], stats.get("run_id"))].append(i)
    found: Dict[str, _Program] = {}
    for (name, _), indices in runs.items():
        start = min(ops[i][1] for i in indices)
        end = max(ops[i][1] + ops[i][2] for i in indices)
        program = found.setdefault(
            name, _Program(name, [], ops, selfs, parents, [])
        )
        program.modules.append([name, start, end - start])
        program.indices.extend(indices)
    for program in found.values():
        program.modules.sort(key=lambda module: module[1])
    return list(found.values())


def _new_row() -> Dict:
    return {
        "ns": 0.0, "inclusive_ns": 0.0,
        "phases": dict.fromkeys(PHASES, 0.0),
        "ops": collections.defaultdict(lambda: [0.0, 0]),
    }


def _finish_ops(ops: Dict, steps: int, top: int) -> Dict:
    ranked = sorted(ops.items(), key=lambda item: -item[1][0])[:top]
    return {
        kind: {"ms": ns / steps / 1e6, "calls": calls / steps}
        for kind, (ns, calls) in ranked
    }


def _charge(row: Dict, kind: str, own: float) -> None:
    row["ns"] += own
    row["ops"][kind][0] += own
    row["ops"][kind][1] += 1


def account_program(
    program: _Program, text: ProgramText, known: Iterable[str],
    residual: bool = True, max_residual_pct: Optional[float] = 1.0,
    idle_allowed: bool = False, top_ops: int = 8,
) -> Dict:
    """One program's account on one device (the module's docstring).
    `idle_allowed`, a residual above zero is reported and not refused:
    a short program (an act step) leaves the device idle between its
    ops; ops counted twice are refused all the same."""
    known = frozenset(known)
    _, modules, ops, selfs, parents, indices = program
    steps = len(modules)
    module_ns = sum(module[2] for module in modules)
    starts = [module[1] for module in modules]
    paths: Dict[int, str] = {}

    def path(i: int) -> str:
        if i not in paths:
            paths[i] = text.op_names.get(
                _stats(ops[i]).get("hlo_op") or ops[i][0], ""
            )
        return paths[i]

    rows: Dict[str, Dict] = collections.defaultdict(_new_row)
    under: Dict[str, Dict] = collections.defaultdict(_new_row)
    loose: Dict[str, float] = collections.defaultdict(float)
    sum_self = overlap = 0.0
    for i in indices:
        own, name = selfs[i], path(i)
        sum_self += own
        if parents[i] >= 0 and _opcode(ops[parents[i]][0]) not in HOLDERS:
            overlap += ops[i][2]
        entered = scopes_of(name, known)
        kind = op_kind(ops[i][0], name)
        row = rows[entered[-1] if entered else UNSCOPED]
        _charge(row, kind, own)
        row["phases"][phase_of(name)] += own
        for scope in set(entered):
            rows[scope]["inclusive_ns"] += own
        if entered:
            continue
        row["inclusive_ns"] += own
        holder, held_by = parents[i], NO_LOOP
        while holder >= 0:
            around = scopes_of(path(holder), known)
            if around:
                held_by = around[-1]
                break
            holder = parents[holder]
        if held_by == NO_LOOP and not name:
            moved = scopes_of(text.moves.get(ops[i][0], ""), known)
            held_by = moved[-1] if moved else NO_LOOP
        _charge(under[held_by], kind, own)
        if held_by == NO_LOOP:
            loose[name or "(no op_name) " + kind] += own
    residual_pct = overlap_pct = None
    if residual and module_ns:
        residual_pct = 100.0 * (module_ns - sum_self) / module_ns
        overlap_pct = 100.0 * overlap / module_ns
        worst = max(
            -residual_pct if idle_allowed else abs(residual_pct),
            overlap_pct,
        )
        if max_residual_pct is not None and worst > max_residual_pct:
            raise AccountError(
                f"{program.name}: the ops' self times are {sum_self / 1e6:.3f} "
                f"ms of the module events' {module_ns / 1e6:.3f} "
                f"(residual {residual_pct:.2f}%), and {overlap / 1e6:.3f} "
                f"ms run while an op that is no {' / '.join(HOLDERS)} "
                f"does (overlap {overlap_pct:.2f}%); over "
                f"{max_residual_pct}%: overlapping streams, or a line "
                "the account does not know"
            )

    def per_step(ns: float) -> float:
        return ns / steps / 1e6

    step_ns = module_ns if residual else sum_self
    scopes = {
        scope: {
            "ms": per_step(row["ns"]),
            "share_pct": 100.0 * row["ns"] / step_ns if step_ns else 0.0,
            "inclusive_ms": per_step(row["inclusive_ns"]),
            **{
                phase + "_ms": per_step(row["phases"][phase])
                for phase in PHASES
            },
            "ops": _finish_ops(row["ops"], steps, top_ops),
        }
        for scope, row in sorted(
            rows.items(), key=lambda item: -item[1]["ns"]
        )
    }
    return {
        "steps": steps,
        "module_ms": per_step(module_ns),
        "period_ms": (
            (starts[-1] - starts[0]) / (steps - 1) / 1e6
            if steps > 1 else None
        ),
        "sum_self_ms": per_step(sum_self),
        "residual_pct": residual_pct,
        "overlap_pct": overlap_pct,
        "scopes": scopes,
        "unscoped_under": {
            scope: {
                "ms": per_step(row["ns"]),
                "ops": _finish_ops(row["ops"], steps, top_ops),
            }
            for scope, row in sorted(
                under.items(), key=lambda item: -item[1]["ns"]
            )
        },
        "unscoped_paths": [
            [name, per_step(ns)] for name, ns in sorted(
                loose.items(), key=lambda item: -item[1]
            )[:top_ops]
        ],
    }


def account(
    trace: Dict, texts: Iterable[ProgramText] = (),
    scopes: Optional[Iterable[str]] = None,
    counters: Optional[Dict[str, float]] = None,
    max_residual_pct: Optional[float] = 1.0, strict: bool = True,
) -> Dict:
    """{"programs": [one account a device and program], "counters"}.

    `texts` are the compiled programs' (`read_program_text`; a program
    without one is joined on its events' stats alone), `scopes` the
    known names (by default those `device_scope` noted in this
    process), `counters` the update's sown stats of the same steps,
    passed through. Not `strict` (a run's own shutdown, every program
    of the run in the trace): idle time inside a short program is
    reported and not refused, and a program whose ops are counted
    twice is reported by its `error` while the others go on.
    """
    by_module = {text.module: text for text in texts}
    known = known_device_scopes() if scopes is None else frozenset(scopes)
    programs = []
    for plane in trace["planes"]:
        on_device = DEVICE_PLANE.match(plane["name"]) is not None
        found = (_device_programs if on_device else _thread_programs)(plane)
        for program in found:
            if not program.indices:
                continue
            entry = {
                "device": plane["name"],
                "layout": "device" if on_device else "host_threads",
                "program": program.name,
            }
            try:
                entry.update(account_program(
                    program, by_module.get(program.name, _NO_TEXT), known,
                    residual=on_device, max_residual_pct=max_residual_pct,
                    idle_allowed=not strict,
                ))
            except AccountError as e:
                if strict:
                    raise
                entry["error"] = str(e)
            programs.append(entry)
    if not programs:
        raise AccountError(
            "the trace holds no program's ops: no device plane with an "
            f"{OPS_LINE!r} line and no host event with an `hlo_module`"
        )
    return {"programs": programs, "counters": dict(counters or {})}


# --- the table -------------------------------------------------------------------------


def _ops_cell(ops: Dict, n: int = 3) -> str:
    return ", ".join(
        f"{kind} {entry['calls']:g}x {entry['ms']:.2f}"
        for kind, entry in list(ops.items())[:n]
    )


def render(report: Dict) -> str:
    """The account as text: one table a program, the counters last."""
    out = []
    for entry in report["programs"]:
        if "error" in entry:
            out.append(f"{entry['program']} on {entry['device']}: "
                       f"{entry['error']}")
            continue
        period = entry["period_ms"]
        residual = entry["residual_pct"]
        out.append(
            f"{entry['program']} on {entry['device']}: "
            f"{entry['steps']} steps, module {entry['module_ms']:.3f} ms"
            + (f", period {period:.3f} ms" if period is not None else "")
            + f", ops' self times {entry['sum_self_ms']:.3f} ms"
            + (f", residual {residual:.3f}%" if residual is not None else "")
        )
        out.append(
            f"  {'scope':<22}{'ms':>9}{'share %':>9}{'forward':>9}"
            f"{'backward':>9}{'remat.':>9}{'incl. ms':>10}  op kinds "
            "(calls a step, ms)"
        )
        for scope, row in entry["scopes"].items():
            out.append(
                f"  {scope:<22}{row['ms']:>9.3f}{row['share_pct']:>9.2f}"
                f"{row['forward_ms']:>9.3f}{row['backward_ms']:>9.3f}"
                f"{row['rematerialised_ms']:>9.3f}"
                f"{row['inclusive_ms']:>10.3f}  {_ops_cell(row['ops'])}"
            )
        for scope, row in entry["unscoped_under"].items():
            out.append(
                f"  {UNSCOPED + ', under ' + scope:<31}{row['ms']:>9.3f}"
                f"  {_ops_cell(row['ops'], 4)}"
            )
        for name, ms in entry["unscoped_paths"]:
            out.append(f"    {ms:>9.3f}  {name}")
    if report["counters"]:
        out.append("counters of the same steps:")
        out.extend(
            f"  {name:<40}{value:>16,.6g}"
            for name, value in sorted(report["counters"].items())
        )
    return "\n".join(out)

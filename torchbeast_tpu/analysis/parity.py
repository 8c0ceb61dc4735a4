"""beastlint repo-level rules: cross-language wire parity and cross-driver
flag parity.

Both rules are TEXTUAL: the C++ headers are parsed with regexes scoped to
the specific declaration shapes this repo uses (constexpr tag constants,
the DType enum, the itemsize switch), and the Python side is parsed from
the AST without importing it. That keeps the analyzer runnable in an image
with no compiler and no jax/numpy — and means a parity break fails lint in
the same run that would have shipped it, instead of waiting for the
cross-language fuzz tests to execute both stacks.
"""

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import config
from .engine import FileContext, Finding, load_context

# ---------------------------------------------------------------------------
# C++ parsing helpers


def _fold_cpp_int(expr: str) -> Optional[int]:
    """Evaluate `256ull * 1024 * 1024`-style constant expressions."""
    cleaned = re.sub(r"(?i)(?<=\d)(ull|ll|ul|u|l)\b", "", expr)
    cleaned = cleaned.replace("'", "")  # C++14 digit separators
    if not re.fullmatch(r"[0-9xXa-fA-F\s*+\-()<>]+", cleaned):
        return None
    try:
        return int(eval(cleaned, {"__builtins__": {}}, {}))  # noqa: S307
    except (SyntaxError, NameError, ValueError, TypeError,
            ArithmeticError):
        # Unparseable constant expression -> None; callers treat an
        # unresolved anchor as its own parity finding, so nothing is
        # silently swallowed here.
        return None


def _norm_tag(name: str) -> str:
    """Case/underscore-insensitive tag identity: TAG_NP_SCALAR (py) and
    kTagNpScalar (C++) both normalize to NPSCALAR."""
    return name.upper().replace("_", "")


def parse_cpp_tags(wire_h: str) -> Dict[str, int]:
    """kTagArray = 0x01 -> {'ARRAY': 1}."""
    out: Dict[str, int] = {}
    for m in re.finditer(
        r"constexpr\s+uint8_t\s+kTag(\w+)\s*=\s*(0[xX][0-9a-fA-F]+|\d+)\s*;",
        wire_h,
    ):
        out[_norm_tag(m.group(1))] = int(m.group(2), 0)
    return out


def parse_cpp_max_frame(src: str) -> Optional[int]:
    m = re.search(
        r"constexpr\s+size_t\s+kMaxFrameBytes\s*=\s*([^;]+);", src
    )
    return _fold_cpp_int(m.group(1)) if m else None


def parse_cpp_dtype_enum(array_h: str) -> Dict[str, int]:
    """enum class DType entries -> {'kU8': 0, ...}."""
    m = re.search(
        r"enum\s+class\s+DType\s*:\s*uint8_t\s*\{(.*?)\};", array_h,
        re.DOTALL,
    )
    if not m:
        return {}
    out: Dict[str, int] = {}
    for entry in re.finditer(r"(k\w+)\s*=\s*(\d+)", m.group(1)):
        out[entry.group(1)] = int(entry.group(2))
    return out


def parse_cpp_ring(shm_h: str) -> Dict[str, Optional[int]]:
    """csrc/shm.h ring-layout constants -> canonical names. Missing
    pieces parse to None (the checker turns that into a finding)."""
    out: Dict[str, Optional[int]] = {}

    def const(cpp_name: str):
        m = re.search(
            r"constexpr\s+(?:size_t|uint32_t|uint8_t)\s+" + cpp_name +
            r"\s*=\s*(0[xX][0-9a-fA-F]+|\d+)",
            shm_h,
        )
        return int(m.group(1), 0) if m else None

    out["header_bytes"] = const("kRingHeaderBytes")
    out["head_word"] = const("kRingHeadWord")
    out["tail_word"] = const("kRingTailWord")
    out["capacity_word"] = const("kRingCapacityWord")
    out["waiting_word"] = const("kRingWaitingWord")
    out["wrap_marker"] = const("kRingWrapMarker")
    out["inline_marker"] = const("kRingInlineMarker")
    out["doorbell_wake"] = const("kDoorbellWake")
    out["doorbell_inline"] = const("kDoorbellInline")
    # Ring-eligibility cap: `max_frame_bytes() ... return capacity_ / D - S`.
    m = re.search(
        r"max_frame_bytes\s*\(\s*\)\s*const\s*\{\s*return\s+capacity_\s*/"
        r"\s*(\d+)\s*-\s*(\d+)\s*;",
        shm_h,
    )
    out["eligibility_divisor"] = int(m.group(1)) if m else None
    out["eligibility_slack"] = int(m.group(2)) if m else None
    return out


def parse_py_ring(tree: ast.Module) -> Dict[str, Optional[int]]:
    """runtime/transport.py ring-layout facts -> the same canonical
    names as parse_cpp_ring (ShmRing class attributes, the module-level
    doorbell bytes, and max_frame_bytes' capacity//D - S expression)."""
    out: Dict[str, Optional[int]] = {key: None for key in (
        "header_bytes", "head_word", "tail_word", "capacity_word",
        "waiting_word", "wrap_marker", "inline_marker", "doorbell_wake",
        "doorbell_inline", "eligibility_divisor", "eligibility_slack",
    )}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target = node.targets[0]
            if isinstance(target, ast.Name) and isinstance(
                node.value, ast.Constant
            ) and isinstance(node.value.value, bytes) and len(
                node.value.value
            ) == 1:
                if target.id == "_DOORBELL_WAKE":
                    out["doorbell_wake"] = node.value.value[0]
                elif target.id == "_DOORBELL_INLINE":
                    out["doorbell_inline"] = node.value.value[0]
    ring_cls = next(
        (n for n in ast.walk(tree)
         if isinstance(n, ast.ClassDef) and n.name == "ShmRing"),
        None,
    )
    if ring_cls is None:
        return out
    for node in ring_cls.body:
        if isinstance(node, ast.Assign):
            targets = node.targets[0]
            if isinstance(targets, ast.Name):
                value = _fold_py_int(node.value)
                name = {
                    "HEADER_BYTES": "header_bytes",
                    "_WRAP": "wrap_marker",
                    "_INLINE": "inline_marker",
                }.get(targets.id)
                if name is not None and value is not None:
                    out[name] = value
            elif isinstance(targets, ast.Tuple) and isinstance(
                node.value, ast.Tuple
            ):
                # `_HEAD, _TAIL, _CAP, _WAITING = 0, 1, 2, 3`
                names = {
                    "_HEAD": "head_word", "_TAIL": "tail_word",
                    "_CAP": "capacity_word", "_WAITING": "waiting_word",
                }
                for elt, val in zip(targets.elts, node.value.elts):
                    if isinstance(elt, ast.Name) and elt.id in names:
                        folded = _fold_py_int(val)
                        if folded is not None:
                            out[names[elt.id]] = folded
        elif isinstance(node, ast.FunctionDef) and (
            node.name == "max_frame_bytes"
        ):
            # `return self._capacity // D - S`
            for ret in ast.walk(node):
                if not isinstance(ret, ast.Return):
                    continue
                expr = ret.value
                if (
                    isinstance(expr, ast.BinOp)
                    and isinstance(expr.op, ast.Sub)
                    and isinstance(expr.left, ast.BinOp)
                    and isinstance(expr.left.op, ast.FloorDiv)
                ):
                    out["eligibility_divisor"] = _fold_py_int(
                        expr.left.right
                    )
                    out["eligibility_slack"] = _fold_py_int(expr.right)
    return out


def parse_cpp_itemsizes(array_h: str) -> Dict[str, int]:
    """The itemsize() switch -> {'kU8': 1, ...}."""
    m = re.search(
        r"inline\s+size_t\s+itemsize\s*\(.*?\)\s*\{(.*?)\n\}", array_h,
        re.DOTALL,
    )
    if not m:
        return {}
    out: Dict[str, int] = {}
    pending: List[str] = []
    for line in m.group(1).splitlines():
        case = re.search(r"case\s+DType::(k\w+)\s*:", line)
        if case:
            pending.append(case.group(1))
        ret = re.search(r"return\s+(\d+)\s*;", line)
        if ret and pending:
            for name in pending:
                out[name] = int(ret.group(1))
            pending = []
    return out


# ---------------------------------------------------------------------------
# Python (AST) parsing helpers


def _fold_py_int(node: ast.AST) -> Optional[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.BinOp):
        left = _fold_py_int(node.left)
        right = _fold_py_int(node.right)
        if left is None or right is None:
            return None
        if isinstance(node.op, ast.Mult):
            return left * right
        if isinstance(node.op, ast.Add):
            return left + right
        if isinstance(node.op, ast.Sub):
            return left - right
        if isinstance(node.op, ast.Pow):
            return left ** right
        if isinstance(node.op, ast.LShift):
            return left << right
    return None


def _np_dtype_name(call: ast.AST) -> Optional[str]:
    """np.dtype(np.uint8) / np.dtype(_bfloat16) -> numpy dtype name."""
    if not (
        isinstance(call, ast.Call)
        and isinstance(call.func, ast.Attribute)
        and call.func.attr == "dtype"
        and call.args
    ):
        return None
    arg = call.args[0]
    if isinstance(arg, ast.Attribute):
        name = arg.attr
    elif isinstance(arg, ast.Name):
        name = arg.id
    else:
        return None
    name = name.lstrip("_")
    return {"bool_": "bool"}.get(name, name)


def parse_py_wire(tree: ast.Module) -> Tuple[
    Dict[str, int], Optional[int], Dict[str, int]
]:
    """(TAG_* map, DEFAULT_MAX_FRAME_BYTES, dtype-name -> code)."""
    tags: Dict[str, int] = {}
    max_frame: Optional[int] = None
    codes: Dict[str, int] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        if isinstance(target, ast.Name):
            if target.id.startswith("TAG_"):
                value = _fold_py_int(node.value)
                if value is not None:
                    tags[_norm_tag(target.id[4:])] = value
            elif target.id == "DEFAULT_MAX_FRAME_BYTES":
                max_frame = _fold_py_int(node.value)
            elif target.id == "_DTYPE_CODES" and isinstance(
                node.value, ast.Dict
            ):
                for k, v in zip(node.value.keys, node.value.values):
                    name = _np_dtype_name(k)
                    code = _fold_py_int(v)
                    if name is not None and code is not None:
                        codes[name] = code
        elif isinstance(target, ast.Subscript):
            # _DTYPE_CODES[np.dtype(_bfloat16)] = 12 (the guarded
            # ml_dtypes registration).
            base = target.value
            if isinstance(base, ast.Name) and base.id == "_DTYPE_CODES":
                key = target.slice
                name = _np_dtype_name(key)
                code = _fold_py_int(node.value)
                if name is not None and code is not None:
                    codes[name] = code
    return tags, max_frame, codes


def _find_add_argument_default(
    tree: ast.Module, flag: str
) -> Tuple[Optional[ast.AST], Optional[int]]:
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "add_argument"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and node.args[0].value == flag
        ):
            for kw in node.keywords:
                if kw.arg == "default":
                    return kw.value, node.lineno
            return None, node.lineno
    return None, None


# ---------------------------------------------------------------------------
# WIRE-PARITY


def check_wire_parity(
    py_ctx: FileContext,
    wire_h: str,
    array_h: str,
    client_h: str,
    poly_ctx: Optional[FileContext],
) -> List[Finding]:
    findings: List[Finding] = []
    path = py_ctx.path

    def finding(line: int, msg: str, at: str = ""):
        findings.append(Finding("WIRE-PARITY", at or path, line, msg))

    tags_py, max_frame_py, codes_py = parse_py_wire(py_ctx.tree)
    tags_cpp = parse_cpp_tags(wire_h)
    max_frame_cpp = parse_cpp_max_frame(wire_h)
    enum_cpp = parse_cpp_dtype_enum(array_h)
    sizes_cpp = parse_cpp_itemsizes(array_h)

    # Parse failures are findings, not silence: an unparseable header
    # means the contract is no longer being checked.
    if not tags_py or not codes_py or max_frame_py is None:
        finding(1, "could not parse TAG_*/_DTYPE_CODES/"
                   "DEFAULT_MAX_FRAME_BYTES from runtime/wire.py — "
                   "WIRE-PARITY cannot verify the codec")
        return findings
    if not tags_cpp or not enum_cpp or not sizes_cpp:
        finding(1, "could not parse kTag*/DType/itemsize from csrc "
                   "headers — WIRE-PARITY cannot verify the codec")
        return findings

    # 1. Frame tag constants.
    for name in sorted(tags_py.keys() | tags_cpp.keys()):
        py_v, cpp_v = tags_py.get(name), tags_cpp.get(name)
        if py_v is None:
            finding(1, f"csrc/wire.h defines kTag{name.title()}={cpp_v} "
                       "but wire.py has no matching TAG_ constant")
        elif cpp_v is None:
            finding(1, f"wire.py defines TAG_{name}={py_v} but "
                       "csrc/wire.h has no matching kTag constant")
        elif py_v != cpp_v:
            finding(1, f"frame tag {name}: wire.py says {py_v:#x}, "
                       f"csrc/wire.h says {cpp_v:#x}")

    # 2. Dtype code table (both directions) + itemsize ground truth.
    codes_cpp: Dict[str, int] = {}
    for cpp_name, code in enum_cpp.items():
        np_name = config.CPP_DTYPE_TO_NUMPY.get(cpp_name)
        if np_name is None:
            finding(1, f"csrc/array.h DType::{cpp_name} has no numpy "
                       "mapping in analysis/config.py "
                       "CPP_DTYPE_TO_NUMPY — add one")
            continue
        codes_cpp[np_name] = code
    for name in sorted(codes_py.keys() | codes_cpp.keys()):
        py_c, cpp_c = codes_py.get(name), codes_cpp.get(name)
        if py_c is None:
            finding(1, f"dtype {name!r} (code {cpp_c}) exists in "
                       "csrc/array.h but not in wire.py _DTYPE_CODES")
        elif cpp_c is None:
            finding(1, f"dtype {name!r} (code {py_c}) exists in wire.py "
                       "_DTYPE_CODES but not in csrc/array.h DType")
        elif py_c != cpp_c:
            finding(1, f"dtype {name!r}: wire.py code {py_c} != "
                       f"csrc/array.h code {cpp_c}")
        expected = config.DTYPE_ITEMSIZE.get(name)
        if expected is None and (py_c is not None or cpp_c is not None):
            finding(1, f"dtype {name!r} missing from "
                       "analysis/config.py DTYPE_ITEMSIZE ground truth")
    for cpp_name, size in sizes_cpp.items():
        np_name = config.CPP_DTYPE_TO_NUMPY.get(cpp_name)
        expected = config.DTYPE_ITEMSIZE.get(np_name or "")
        if expected is not None and size != expected:
            finding(1, f"csrc/array.h itemsize({cpp_name}) = {size}, "
                       f"expected {expected} for {np_name}")
    for cpp_name in enum_cpp:
        if cpp_name not in sizes_cpp:
            finding(1, f"csrc/array.h itemsize() has no case for "
                       f"DType::{cpp_name} — decoding that code throws")

    # 3. Max frame bytes: wire.py default == csrc constant, and the C++
    # frame reader actually enforces it.
    if max_frame_cpp is None:
        finding(1, "could not parse kMaxFrameBytes from csrc/wire.h")
    elif max_frame_cpp != max_frame_py:
        finding(1, f"DEFAULT_MAX_FRAME_BYTES={max_frame_py} (wire.py) != "
                   f"kMaxFrameBytes={max_frame_cpp} (csrc/wire.h)")
    if client_h and "kMaxFrameBytes" not in client_h:
        finding(1, "csrc/client.h never references kMaxFrameBytes — the "
                   "C++ frame reader is not enforcing the frame bound")

    # 4. The driver flag default must resolve to the same constant.
    if poly_ctx is not None:
        default, line = _find_add_argument_default(
            poly_ctx.tree, "--max_frame_bytes"
        )
        if line is None:
            finding(1, "polybeast.py no longer defines --max_frame_bytes",
                    at=poly_ctx.path)
        elif isinstance(default, ast.Constant):
            if default.value != max_frame_py:
                finding(line, f"--max_frame_bytes default {default.value} "
                              f"!= wire.DEFAULT_MAX_FRAME_BYTES "
                              f"{max_frame_py}", at=poly_ctx.path)
        elif default is None or (
            not isinstance(default, ast.Attribute)
            or default.attr != "DEFAULT_MAX_FRAME_BYTES"
        ):
            finding(line or 1, "--max_frame_bytes default should be "
                               "wire.DEFAULT_MAX_FRAME_BYTES (or its "
                               "literal value) so py/C++ stay in lockstep",
                    at=poly_ctx.path)
    return findings


# Human-readable labels for the ring-layout contract fields.
_RING_FIELD_LABELS = {
    "header_bytes": "ring header size (ShmRing.HEADER_BYTES / "
                    "kRingHeaderBytes)",
    "head_word": "head counter word index (_HEAD / kRingHeadWord)",
    "tail_word": "tail counter word index (_TAIL / kRingTailWord)",
    "capacity_word": "capacity word index (_CAP / kRingCapacityWord)",
    "waiting_word": "waiting-flag word index (_WAITING / kRingWaitingWord)",
    "wrap_marker": "wrap marker (_WRAP / kRingWrapMarker)",
    "inline_marker": "inline marker (_INLINE / kRingInlineMarker)",
    "doorbell_wake": "doorbell WAKE byte (_DOORBELL_WAKE / kDoorbellWake)",
    "doorbell_inline": "doorbell INLINE byte (_DOORBELL_INLINE / "
                       "kDoorbellInline)",
    "eligibility_divisor": "ring-eligibility cap divisor "
                           "(max_frame_bytes: capacity // D - S)",
    "eligibility_slack": "ring-eligibility cap slack "
                         "(max_frame_bytes: capacity // D - S)",
}


def check_ring_parity(
    transport_ctx: FileContext, shm_h: str
) -> List[Finding]:
    """WIRE-PARITY (shm ring layout): a Python env server and a C++
    actor loop attach the SAME SharedMemory segments, so the header
    word layout, in-ring wrap/inline markers, doorbell control bytes,
    and the capacity//2-4 ring-eligibility cap must match byte for
    byte. Unparseable side = finding, not silence."""
    findings: List[Finding] = []
    path = transport_ctx.path

    def finding(msg: str):
        findings.append(Finding("WIRE-PARITY", path, 1, msg))

    ring_py = parse_py_ring(transport_ctx.tree)
    ring_cpp = parse_cpp_ring(shm_h)
    if all(v is None for v in ring_py.values()):
        finding("could not parse the ShmRing layout (HEADER_BYTES/"
                "_WRAP/_INLINE/word indices/doorbell bytes) from "
                "runtime/transport.py — WIRE-PARITY cannot verify the "
                "shm ring contract")
        return findings
    if all(v is None for v in ring_cpp.values()):
        finding("could not parse the ring layout (kRing*/kDoorbell* "
                "constants, max_frame_bytes) from csrc/shm.h — "
                "WIRE-PARITY cannot verify the shm ring contract")
        return findings
    for key, label in _RING_FIELD_LABELS.items():
        py_v, cpp_v = ring_py.get(key), ring_cpp.get(key)
        if py_v is None:
            finding(f"shm ring {label}: missing/unparseable on the "
                    f"Python side (csrc/shm.h says {cpp_v})")
        elif cpp_v is None:
            finding(f"shm ring {label}: missing/unparseable on the C++ "
                    f"side (transport.py says {py_v})")
        elif py_v != cpp_v:
            finding(f"shm ring {label}: transport.py says {py_v:#x}, "
                    f"csrc/shm.h says {cpp_v:#x}")
    return findings


class WireParityRule:
    """WIRE-PARITY: runtime/wire.py == csrc/ on tags, dtypes, frame
    bound — and runtime/transport.py == csrc/shm.h on the shm ring
    layout."""

    name = "WIRE-PARITY"

    def check_repo(
        self, root: str, contexts: Sequence[FileContext]
    ) -> List[Finding]:
        by_path = {ctx.path: ctx for ctx in contexts}
        py_ctx = by_path.get(config.WIRE_PY)
        if py_ctx is None:
            return []  # partial scan (explicit paths): parity not in scope

        def read(rel: str) -> str:
            p = os.path.join(root, rel)
            try:
                with open(p, encoding="utf-8", errors="replace") as f:
                    return f.read()
            except OSError:
                return ""

        wire_h = read(config.WIRE_H)
        array_h = read(config.ARRAY_H)
        client_h = read(config.CLIENT_H)
        if not wire_h or not array_h:
            return [
                Finding(
                    self.name, config.WIRE_PY, 1,
                    "csrc/wire.h or csrc/array.h missing — the C++ side "
                    "of the wire contract is gone",
                )
            ]
        findings = check_wire_parity(
            py_ctx, wire_h, array_h, client_h,
            by_path.get(config.POLYBEAST_PY),
        )
        # The shm ring layout contract (ISSUE 9 satellite): checked
        # whenever transport.py is in scope.
        transport_ctx = by_path.get(config.TRANSPORT_PY)
        if transport_ctx is not None:
            shm_h = read(config.SHM_H)
            if not shm_h:
                findings.append(Finding(
                    self.name, config.TRANSPORT_PY, 1,
                    "csrc/shm.h missing — the C++ side of the shm ring "
                    "contract is gone",
                ))
            else:
                findings.extend(check_ring_parity(transport_ctx, shm_h))
        return findings


# ---------------------------------------------------------------------------
# ROUTE-PARITY


# Human-readable labels for the splitmix64 contract fields.
_SPLITMIX_FIELD_LABELS = {
    "gamma": "splitmix64 gamma increment (kSplitMix64Gamma)",
    "mul1": "splitmix64 first multiplier (kSplitMix64Mul1)",
    "mul2": "splitmix64 second multiplier (kSplitMix64Mul2)",
    "shift1": "splitmix64 first xor-shift (kSplitMix64Shift1)",
    "shift2": "splitmix64 second xor-shift (kSplitMix64Shift2)",
    "shift3": "splitmix64 final xor-shift (kSplitMix64Shift3)",
}


def parse_py_splitmix(tree: ast.Module) -> Dict[str, Optional[int]]:
    """runtime/placement.py `_mix64` -> canonical splitmix64 fields.

    Constants are classified by operator context, not position: the Add
    operand is the gamma increment, RShift operands are the xor-shifts
    in statement order, Mult operands the multipliers. The
    `& 0xFFFFFFFFFFFFFFFF` masks are Python-only wrap emulation (C++
    uint64_t wraps natively) and are ignored (BitAnd)."""
    out: Dict[str, Optional[int]] = {
        key: None for key in _SPLITMIX_FIELD_LABELS
    }
    fn = next(
        (n for n in ast.walk(tree)
         if isinstance(n, ast.FunctionDef) and n.name == "_mix64"),
        None,
    )
    if fn is None:
        return out
    shifts: List[int] = []
    muls: List[int] = []
    for stmt in fn.body:  # statement order == finalizer stage order
        for node in ast.walk(stmt):
            if not isinstance(node, ast.BinOp):
                continue
            value = _fold_py_int(node.right)
            if value is None:
                continue
            if isinstance(node.op, ast.Add) and out["gamma"] is None:
                out["gamma"] = value
            elif isinstance(node.op, ast.RShift):
                shifts.append(value)
            elif isinstance(node.op, ast.Mult):
                muls.append(value)
    for i, value in enumerate(shifts[:3]):
        out[f"shift{i + 1}"] = value
    for i, value in enumerate(muls[:2]):
        out[f"mul{i + 1}"] = value
    return out


def parse_cpp_routing(
    routing_h: str,
) -> Tuple[Dict[str, Optional[int]], Optional[str]]:
    """csrc/routing.h -> (splitmix64 fields, slice-series prefix)."""
    names = {
        "Gamma": "gamma", "Mul1": "mul1", "Mul2": "mul2",
        "Shift1": "shift1", "Shift2": "shift2", "Shift3": "shift3",
    }
    out: Dict[str, Optional[int]] = {
        key: None for key in _SPLITMIX_FIELD_LABELS
    }
    for m in re.finditer(
        r"constexpr\s+(?:uint64_t|int)\s+kSplitMix64(\w+)\s*=\s*"
        r"(0[xX][0-9a-fA-F]+|\d+)(?:[uU]?[lL]{0,2})\s*;",
        routing_h,
    ):
        key = names.get(m.group(1))
        if key is not None:
            out[key] = int(m.group(2), 0)
    prefix_m = re.search(
        r"constexpr\s+const\s+char\s+kSliceSeriesPrefix\[\]\s*=\s*"
        r'"([^"]*)"',
        routing_h,
    )
    return out, (prefix_m.group(1) if prefix_m else None)


def _py_string_prefixes(tree: ast.Module) -> List[str]:
    """Every literal string prefix in the module: plain str constants
    verbatim, f-strings contribute their leading constant fragment
    (`f"inference.slice.{i}.depth"` -> "inference.slice.")."""
    out: List[str] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            out.append(node.value)
        elif isinstance(node, ast.JoinedStr) and node.values:
            head = node.values[0]
            if isinstance(head, ast.Constant) and isinstance(
                head.value, str
            ):
                out.append(head.value)
    return out


def check_route_parity(
    placement_ctx: FileContext,
    routing_h: str,
    series_ctxs: Sequence[FileContext],
) -> List[Finding]:
    """ROUTE-PARITY: the slot->slice hash and the per-slice telemetry
    namespace agree across languages. Both sides check against the
    SPLITMIX64_SPEC ground truth (a wrong constant on either side is a
    finding even if the other side drifted in lockstep); the series
    prefix pins csrc/routing.h kSliceSeriesPrefix AND every Python
    emitter to config.SLICE_SERIES_PREFIX. Unparseable side = finding,
    not silence."""
    findings: List[Finding] = []

    def finding(path: str, msg: str):
        findings.append(Finding("ROUTE-PARITY", path, 1, msg))

    mix_py = parse_py_splitmix(placement_ctx.tree)
    mix_cpp, prefix_cpp = parse_cpp_routing(routing_h)
    if all(v is None for v in mix_py.values()):
        finding(placement_ctx.path,
                "could not parse the _mix64 splitmix64 finalizer from "
                "runtime/placement.py — ROUTE-PARITY cannot verify the "
                "slot->slice hash")
        return findings
    if all(v is None for v in mix_cpp.values()):
        finding(config.ROUTING_H,
                "could not parse the kSplitMix64* constants from "
                "csrc/routing.h — ROUTE-PARITY cannot verify the "
                "slot->slice hash")
        return findings
    for key, label in _SPLITMIX_FIELD_LABELS.items():
        spec = config.SPLITMIX64_SPEC[key]
        py_v, cpp_v = mix_py.get(key), mix_cpp.get(key)
        if py_v is None:
            finding(placement_ctx.path,
                    f"{label}: missing/unparseable in placement._mix64 "
                    f"(spec says {spec:#x})")
        elif py_v != spec:
            finding(placement_ctx.path,
                    f"{label}: placement._mix64 uses {py_v:#x}, the "
                    f"pinned spec (analysis/config.py) says {spec:#x} — "
                    "a drifted hash remaps every slot's slice")
        if cpp_v is None:
            finding(config.ROUTING_H,
                    f"{label}: missing/unparseable in csrc/routing.h "
                    f"(spec says {spec:#x})")
        elif cpp_v != spec:
            finding(config.ROUTING_H,
                    f"{label}: csrc/routing.h says {cpp_v:#x}, the "
                    f"pinned spec (analysis/config.py) says {spec:#x} — "
                    "native and Python pools would route the same slot "
                    "to different slices")
    # The per-slice telemetry namespace.
    want = config.SLICE_SERIES_PREFIX
    if prefix_cpp is None:
        finding(config.ROUTING_H,
                "could not parse kSliceSeriesPrefix from csrc/routing.h "
                f"— expected the pinned prefix {want!r}")
    elif prefix_cpp != want:
        finding(config.ROUTING_H,
                f"kSliceSeriesPrefix is {prefix_cpp!r}, the pinned "
                f"per-slice series prefix is {want!r}")
    for ctx in series_ctxs:
        strings = _py_string_prefixes(ctx.tree)
        if not any(s.startswith(want) for s in strings):
            finding(ctx.path,
                    f"no telemetry series under the pinned per-slice "
                    f"prefix {want!r} — the per-slice schema emitter "
                    "moved or renamed its series")
    return findings


class RouteParityRule:
    """ROUTE-PARITY: runtime/placement.py == csrc/routing.h on the
    splitmix64 slot->slice hash, and every per-slice telemetry emitter
    uses the pinned `inference.slice.` namespace."""

    name = "ROUTE-PARITY"

    def check_repo(
        self, root: str, contexts: Sequence[FileContext]
    ) -> List[Finding]:
        by_path = {ctx.path: ctx for ctx in contexts}
        placement_ctx = by_path.get(config.PLACEMENT_PY)
        if placement_ctx is None:
            return []  # partial scan (explicit paths): parity not in scope
        routing_path = os.path.join(root, config.ROUTING_H)
        try:
            with open(routing_path, encoding="utf-8",
                      errors="replace") as f:
                routing_h = f.read()
        except OSError:
            routing_h = ""
        if not routing_h:
            return [
                Finding(
                    self.name, config.PLACEMENT_PY, 1,
                    "csrc/routing.h missing — the C++ side of the "
                    "slot->slice routing contract is gone",
                )
            ]
        series_ctxs = [
            by_path[p] for p in config.SLICE_SERIES_FILES if p in by_path
        ]
        return check_route_parity(placement_ctx, routing_h, series_ctxs)


# ---------------------------------------------------------------------------
# FLAG-PARITY


def _collect_flags(ctx: FileContext) -> Dict[str, dict]:
    """--flag -> {type, default, action, line} (unparsed expr text)."""
    out: Dict[str, dict] = {}
    for node in ast.walk(ctx.tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "add_argument"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
            and node.args[0].value.startswith("--")
        ):
            continue
        spec = {"type": "", "default": "", "action": "", "line": node.lineno}
        for kw in node.keywords:
            if kw.arg in ("type", "default", "action"):
                spec[kw.arg] = ast.unparse(kw.value)
        # Normalize cross-module constant spellings so
        # `wire.DEFAULT_MAX_FRAME_BYTES` == `DEFAULT_MAX_FRAME_BYTES` —
        # but only for identifier chains (a float literal like `0.1`
        # must not lose its integer part).
        if re.fullmatch(r"[A-Za-z_][\w.]*", spec["default"] or ""):
            spec["default"] = spec["default"].split(".")[-1]
        out[node.args[0].value] = spec
    return out


def check_flag_parity(
    ctx_a: FileContext, ctx_b: FileContext
) -> List[Finding]:
    """Shared flags must agree on type, default, and action. Findings
    anchor at the SECOND file's add_argument line (one finding per flag),
    so one inline suppression there exempts an intentional divergence."""
    flags_a = _collect_flags(ctx_a)
    flags_b = _collect_flags(ctx_b)
    findings: List[Finding] = []
    for flag in sorted(flags_a.keys() & flags_b.keys()):
        a, b = flags_a[flag], flags_b[flag]
        diffs = [
            f"{field} {a[field] or '<unset>'!r} (in {ctx_a.path}) vs "
            f"{b[field] or '<unset>'!r}"
            for field in ("type", "default", "action")
            if a[field] != b[field]
        ]
        if diffs:
            findings.append(
                Finding(
                    "FLAG-PARITY", ctx_b.path, b["line"],
                    f"flag {flag} diverges between drivers: "
                    + "; ".join(diffs),
                )
            )
    return findings


class FlagParityRule:
    """FLAG-PARITY: flags shared across driver pairs agree on type+default."""

    name = "FLAG-PARITY"

    def check_repo(
        self, root: str, contexts: Sequence[FileContext]
    ) -> List[Finding]:
        by_path = {ctx.path: ctx for ctx in contexts}
        findings: List[Finding] = []
        for path_a, path_b in config.FLAG_PARITY_GROUPS:
            ctx_b = by_path.get(path_b)
            if ctx_b is None:
                continue  # partial scan: this pair not in scope
            # The anchor is read even when the scan did not name it.
            ctx_a = by_path.get(path_a) or load_context(
                os.path.join(root, path_a), root
            )
            if ctx_a is not None:
                findings.extend(check_flag_parity(ctx_a, ctx_b))
        return findings


REPO_RULES = [WireParityRule(), RouteParityRule(), FlagParityRule()]

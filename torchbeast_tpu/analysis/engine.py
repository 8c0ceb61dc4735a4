"""beastlint engine: file discovery, annotation/suppression parsing,
baseline mechanics, and the rule runner.

The engine is deliberately stdlib-only (`ast` + `tokenize` + `json`): the
analyzer must run in CI images without jax/numpy installed, and must never
import the code it analyzes (a stray import could execute device-touching
module bodies). Rules receive a parsed `FileContext` and return `Finding`s;
repo-level rules (wire/flag parity) receive every context at once.

Annotation grammar (all live in comments, so the runtime never sees them):

    # beastlint: disable=RULE[,RULE2]  <reason>   suppress findings on this
                                                  line (trailing) or the next
                                                  line (standalone comment)
    # beastlint: hot                              on/above a `def`: function
                                                  is an acting/learning hot
                                                  path (HOTPATH-SYNC applies)
    # beastlint: hot-module                       whole module is hot
    # beastlint: holds self._lock                 on/above a `def`: method is
                                                  documented as called with
                                                  the lock already held
    # guarded-by: self._lock                      trailing `self.attr = ...`:
                                                  attr may only be touched
                                                  under `with self._lock`
                                                  (LOCK-DISCIPLINE)

Suppressions without a reason are themselves findings (SUPPRESS-REASON):
the whole point of an inline disable is the recorded justification.

Baseline: a committed JSON list of finding fingerprints (rule + path +
message, line-insensitive so pure code motion doesn't churn it). `--ci`
fails on any finding not in the baseline. The repo's committed baseline is
EMPTY — new debt needs an inline, reasoned suppression, not a baseline
entry.
"""

import ast
import dataclasses
import hashlib
import io
import json
import os
import re
import time
import tokenize
from typing import Dict, List, Optional, Sequence, Set, Tuple

# Directories never scanned (build outputs, VCS metadata, vendored eggs).
SKIP_DIRS = {
    ".git",
    "build",
    "dist",
    "__pycache__",
    ".eggs",
    ".pytest_cache",
    "node_modules",
    # A builder's copies of the tree for chip comparisons (.gitignore):
    # never linted as the tree.
    ".scratch",
}

_DISABLE_RE = re.compile(
    r"#\s*beastlint:\s*disable=([A-Za-z0-9_,\-]+)\s*(.*)$"
)
_HOT_RE = re.compile(r"#\s*beastlint:\s*hot\s*$")
_HOT_MODULE_RE = re.compile(r"#\s*beastlint:\s*hot-module\b")
_HOLDS_RE = re.compile(r"#\s*beastlint:\s*holds\s+(\S+)")
_GUARDED_RE = re.compile(r"#\s*guarded-by:\s*(\S+)")


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative, posix separators
    line: int
    message: str

    @property
    def fingerprint(self) -> str:
        """Line-insensitive identity: stable across pure code motion."""
        digest = hashlib.sha256(
            f"{self.rule}|{self.path}|{self.message}".encode()
        ).hexdigest()
        return digest[:16]

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.message}"

    def as_dict(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "fingerprint": self.fingerprint,
        }


@dataclasses.dataclass
class Suppression:
    line: int  # line the comment sits on
    rules: Optional[Set[str]]  # None = all rules
    reason: str
    standalone: bool  # comment-only line: also covers the next line
    used: bool = False


class FileContext:
    """One parsed source file plus its beastlint annotations."""

    # C++ sources load as analysis.cxx.CxxFileContext (is_cxx=True);
    # file rules only see Python contexts, repo rules see both.
    is_cxx = False

    def __init__(self, path: str, source: str, abspath: str = ""):
        self.path = path.replace(os.sep, "/")
        self.abspath = abspath or path
        self.source = source
        self.tree = ast.parse(source)
        # line -> raw comment text (including '#').
        self.comments: Dict[int, str] = {}
        # line -> True when the line holds ONLY a comment.
        self._comment_only: Dict[int, bool] = {}
        self._scan_comments(source)

        self.suppressions: List[Suppression] = []
        self.hot_module = False
        self._hot_lines: Set[int] = set()
        self._holds: Dict[int, str] = {}
        self.guarded_annotations: Dict[int, str] = {}
        self._parse_annotations()

    def _scan_comments(self, source: str) -> None:
        try:
            tokens = tokenize.generate_tokens(io.StringIO(source).readline)
            prev_row_has_code: Dict[int, bool] = {}
            for tok in tokens:
                if tok.type == tokenize.COMMENT:
                    row = tok.start[0]
                    self.comments[row] = tok.string
                    self._comment_only[row] = not prev_row_has_code.get(
                        row, False
                    )
                elif tok.type not in (
                    tokenize.NL,
                    tokenize.NEWLINE,
                    tokenize.INDENT,
                    tokenize.DEDENT,
                    tokenize.ENDMARKER,
                ):
                    for row in range(tok.start[0], tok.end[0] + 1):
                        prev_row_has_code[row] = True
        except tokenize.TokenError:
            pass

    def _parse_annotations(self) -> None:
        for line, text in self.comments.items():
            m = _DISABLE_RE.search(text)
            if m:
                rules_text, reason = m.group(1), m.group(2).strip()
                names = {
                    r.strip() for r in rules_text.split(",") if r.strip()
                }
                self.suppressions.append(
                    Suppression(
                        line=line,
                        rules=None if "all" in names else names,
                        reason=reason,
                        standalone=self._comment_only.get(line, False),
                    )
                )
                continue
            if _HOT_MODULE_RE.search(text):
                self.hot_module = True
            elif _HOT_RE.search(text):
                self._hot_lines.add(line)
            m = _HOLDS_RE.search(text)
            if m:
                self._holds[line] = m.group(1)
            m = _GUARDED_RE.search(text)
            if m:
                self.guarded_annotations[line] = m.group(1)

    # -- annotation queries -------------------------------------------------

    def is_hot_def(self, node: ast.AST) -> bool:
        """A def annotated `# beastlint: hot` on its line, the line above,
        or above its first decorator."""
        if self.hot_module:
            return True
        first = getattr(node, "lineno", 0)
        decorators = getattr(node, "decorator_list", [])
        if decorators:
            first = min(first, min(d.lineno for d in decorators))
        for line in range(first - 1, getattr(node, "lineno", 0) + 1):
            if line in self._hot_lines:
                return True
        return False

    def comment_only(self, line: int) -> bool:
        """True when `line` holds only a comment (no code)."""
        return self._comment_only.get(line, False)

    def holds_annotation(self, node: ast.AST) -> Optional[str]:
        first = getattr(node, "lineno", 0)
        for line in (first - 1, first):
            if line in self._holds:
                return self._holds[line]
        return None

    # -- suppression application -------------------------------------------

    def suppression_for(self, finding: Finding) -> Optional[Suppression]:
        for sup in self.suppressions:
            covered = {sup.line}
            if sup.standalone:
                covered.add(sup.line + 1)
            if finding.line not in covered:
                continue
            if sup.rules is None or finding.rule in sup.rules:
                return sup
        return None


# C++ sources the frontend (analysis/cxx.py) lexes; the C++ rules
# (GIL-DISCIPLINE, ATOMIC-ORDER, CXX-LOCK-DISCIPLINE) run over these.
CXX_SUFFIXES = (".h", ".hpp", ".cc", ".cpp")


def discover_files(paths: Sequence[str], root: str) -> List[str]:
    """Expand files/directories into a sorted list of .py and C++
    (.h/.cc) sources."""
    suffixes = (".py",) + CXX_SUFFIXES
    out: Set[str] = set()
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(ap) and ap.endswith(suffixes):
            out.add(os.path.abspath(ap))
        elif os.path.isdir(ap):
            for dirpath, dirnames, filenames in os.walk(ap):
                dirnames[:] = [
                    d for d in dirnames
                    if d not in SKIP_DIRS and not d.endswith(".egg-info")
                ]
                for fn in filenames:
                    if fn.endswith(suffixes):
                        out.add(os.path.abspath(os.path.join(dirpath, fn)))
    return sorted(out)


def load_context(abspath: str, root: str) -> Optional[FileContext]:
    rel = os.path.relpath(abspath, root)
    try:
        with open(abspath, "r", encoding="utf-8", errors="replace") as f:
            source = f.read()
        if abspath.endswith(CXX_SUFFIXES):
            from . import cxx  # local import: engine stays ast-only

            return cxx.CxxFileContext(rel, source, abspath=abspath)
        return FileContext(rel, source, abspath=abspath)
    except (SyntaxError, ValueError, OSError):
        return None


@dataclasses.dataclass
class Report:
    findings: List[Finding]
    suppressed: List[Tuple[Finding, Suppression]]
    baselined: List[Finding]
    files_scanned: int
    elapsed_s: float = 0.0
    # Wall-clock per rule name, seconds (file rules summed across
    # contexts) — scripts/lint.sh prints these so a new whole-tree scan
    # cannot silently regress the CI budget.
    rule_timings: Dict[str, float] = dataclasses.field(
        default_factory=dict
    )

    def as_dict(self) -> dict:
        return {
            "findings": [f.as_dict() for f in self.findings],
            "suppressed": [
                {**f.as_dict(), "reason": s.reason}
                for f, s in self.suppressed
            ],
            "baselined": [f.as_dict() for f in self.baselined],
            "files_scanned": self.files_scanned,
            "elapsed_s": self.elapsed_s,
            "rule_timings": {
                name: round(t, 4)
                for name, t in sorted(self.rule_timings.items())
            },
        }


def load_baseline(path: Optional[str]) -> Set[str]:
    if not path or not os.path.isfile(path):
        return set()
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict):
        data = data.get("fingerprints", [])
    return {str(fp) for fp in data}


def write_baseline(path: str, findings: Sequence[Finding]) -> None:
    fingerprints = sorted({f.fingerprint for f in findings})
    with open(path, "w") as f:
        json.dump({"fingerprints": fingerprints}, f, indent=2)
        f.write("\n")


def run_rules(
    contexts: Sequence[FileContext],
    file_rules,
    repo_rules,
    root: str,
    baseline: Set[str] = frozenset(),
    known_rules: Optional[Set[str]] = None,
    only_paths: Optional[Set[str]] = None,
) -> Report:
    """Run every rule, apply suppressions and the baseline.

    `only_paths` filters FINDINGS (and suppression hygiene) to a file
    subset while every rule still sees the full context set — the
    `--diff` mode: the whole-program graph and parity anchors need the
    repo, the gate only cares about the changed files.

    Suppression hygiene is enforced here, not per-rule: a reasonless
    suppression, or one naming an unknown rule, is a SUPPRESS-REASON
    finding anchored at the comment (these cannot themselves be
    suppressed — that would be a hole in the gate).
    """
    raw: List[Finding] = []
    timings: Dict[str, float] = {}
    ctx_by_path: Dict[str, FileContext] = {}
    for ctx in contexts:
        ctx_by_path[ctx.path] = ctx
        if ctx.is_cxx:
            continue  # Python file rules; C++ rules are repo rules
        for rule in file_rules:
            t0 = time.perf_counter()
            raw.extend(rule.check(ctx))
            timings[rule.name] = (
                timings.get(rule.name, 0.0) + time.perf_counter() - t0
            )
    for rule in repo_rules:
        t0 = time.perf_counter()
        raw.extend(rule.check_repo(root, contexts))
        timings[rule.name] = (
            timings.get(rule.name, 0.0) + time.perf_counter() - t0
        )
    if only_paths is not None:
        raw = [f for f in raw if f.path in only_paths]

    findings: List[Finding] = []
    suppressed: List[Tuple[Finding, Suppression]] = []
    baselined: List[Finding] = []
    for f in raw:
        ctx = ctx_by_path.get(f.path)
        sup = ctx.suppression_for(f) if ctx is not None else None
        if sup is not None:
            sup.used = True
            suppressed.append((f, sup))
        elif f.fingerprint in baseline:
            baselined.append(f)
        else:
            findings.append(f)

    all_rules = known_rules or set()
    for ctx in contexts:
        if only_paths is not None and ctx.path not in only_paths:
            continue
        for sup in ctx.suppressions:
            if not sup.reason:
                findings.append(
                    Finding(
                        "SUPPRESS-REASON",
                        ctx.path,
                        sup.line,
                        "beastlint suppression without a reason "
                        "(write `# beastlint: disable=RULE  <why>`)",
                    )
                )
            if sup.rules and all_rules:
                for name in sorted(sup.rules - all_rules):
                    findings.append(
                        Finding(
                            "SUPPRESS-REASON",
                            ctx.path,
                            sup.line,
                            f"suppression names unknown rule {name!r}",
                        )
                    )
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return Report(
        findings=findings,
        suppressed=suppressed,
        baselined=baselined,
        files_scanned=len(contexts),
        rule_timings=timings,
    )


def repo_root() -> str:
    """The repository root: two levels above this package."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

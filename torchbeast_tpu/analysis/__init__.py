"""beastlint — repo-native static analysis for torchbeast_tpu.

`python -m torchbeast_tpu.analysis [--json] [--ci] [paths...]` runs the
rule set over the repo (default: the whole tree) and fails CI at the
offending file:line. The rules encode the repo's real runtime contracts:

    HOTPATH-SYNC     no implicit device->host syncs in annotated hot paths
    JIT-HAZARD       no jit/scan construction in loops, no unhashable
                     static args, no immediately-invoked jit
    DONATE-USE       no reads of consume-once staged buffers after dispatch
    IMPORT-PURITY    per-package import allowlists (telemetry/, analysis/)
    LOCK-DISCIPLINE  `# guarded-by:` attributes only touched under their
                     lock; no bare .acquire() without try/finally
    EXCEPT-SWALLOW   broad except bodies on runtime/ + resilience/ paths
                     re-raise, log, or count the failure (no silent
                     swallows on the failure-handling layers)
    WIRE-PARITY      runtime/wire.py == csrc/{wire,array,client}.h on the
                     dtype table, frame tags, and kMaxFrameBytes
    FLAG-PARITY      flags a script re-declares beside polybeast's
                     (polybeast_env, chaos_run) agree
                     on default and type

Whole-program concurrency rules (ISSUE 7) ride the module -> call ->
thread-root graph in analysis/graph.py plus the per-function sync
summaries in analysis/summaries.py:

    RACE                cross-thread-root attribute conflicts with no
                        common lock (guards inferred from observed
                        `with self._lock:` dominance; `# guarded-by`
                        annotations become cross-checked assertions)
    LOCK-ORDER          lock-acquisition ordering cycles across roots +
                        non-reentrant re-acquisition self-deadlocks
    HOTPATH-SYNC-XPROC  interprocedural HOTPATH-SYNC: helpers that
                        host-convert tainted params flag at every hot
                        call site; device-returning helpers taint
                        their callers

Cross-language C++ rules (ISSUE 10) ride the stdlib-only C++ frontend
in analysis/cxx.py (lexer + extractor over csrc/*.h|*.cc — no libclang)
and the protocol spec in analysis/protocol.py (whose exhaustive model
checker runs as `--check-protocol`):

    GIL-DISCIPLINE       CPython API calls in the binding layer only
                         with the GIL held; no blocking calls (waits,
                         recvs, queue dequeues — direct or via the
                         may-block call summary) while holding it;
                         acquire/release pairing balanced
    ATOMIC-ORDER         shm ring header words only through the
                         designated atomic accessors with the documented
                         memory orders (C++) / named offsets (Python);
                         both languages' access sequences conform to the
                         model-checked protocol spec
    CXX-LOCK-DISCIPLINE  `// guarded-by: mu_` members only touched under
                         an RAII guard, plus cross-root conflicts over
                         std::thread spawn sites and Python-facing entry
                         methods (the C++ half of PR 7's thread graph)

Distributed-systems rules (ISSUE 20) ride the control-plane extractors
in analysis/fleetrules.py and the fleet protocol spec in
analysis/fleetproto.py (whose exhaustive model checker runs as
`--check-fleet`):

    FLEET-MSG-PARITY         every fleet control-plane send site (dict
                             literals with a "type" key into
                             _send/_broadcast) has a receiving-role
                             handler arm and the field sets agree, per
                             role (lead vs remote); handled types must
                             be sent by someone
    FLEET-TIMEOUT-DISCIPLINE every blocking control-plane operation
                             under fleet/ (accept, recv, dial,
                             cond/event wait, join) is under a deadline
                             or carries an explicit
                             `# unbounded-by-design: <why>` annotation
                             (the reader threads' EOF-side loss
                             detection, stated in the source)
    TELEMETRY-SCHEMA         the repo-wide series registry: naming
                             grammar (`layer.noun[_noun]`; the
                             `host<r>.` fold prefix reserved to the
                             lead's telemetry folder), one instrument
                             kind per name, and every series the chaos
                             verdicts / telemetry tests consume has an
                             emitter

See README "Static analysis" for the suppression syntax and how to add a
rule. The package is stdlib-only by contract (enforced by its own
IMPORT-PURITY entry).
"""

from .engine import (  # noqa: F401
    FileContext,
    Finding,
    Report,
    Suppression,
    discover_files,
    load_baseline,
    load_context,
    repo_root,
    run_rules,
    write_baseline,
)
from .cxxrules import CXX_RULES  # noqa: F401
from .fleetrules import FLEET_RULES  # noqa: F401
from .parity import REPO_RULES as PARITY_RULES  # noqa: F401
from .rules import CONCURRENCY_RULES, FILE_RULES  # noqa: F401

# Repo-level rules: cross-language/cross-driver parity, the
# whole-program concurrency rules (which share one Program model per
# run via graph.get_program's cache), the C++ concurrency rules over
# the analysis/cxx.py frontend contexts, and the distributed-systems
# rules over the fleet control plane + telemetry registry.
REPO_RULES = (
    list(PARITY_RULES) + list(CONCURRENCY_RULES) + list(CXX_RULES)
    + list(FLEET_RULES)
)

ALL_RULE_NAMES = (
    {r.name for r in FILE_RULES}
    | {r.name for r in REPO_RULES}
    | {"SUPPRESS-REASON"}
)


def analyze_source(source: str, path: str = "snippet.py", rules=None):
    """Lint a source string (fixture tests / selftest). Suppression and
    hygiene mechanics apply exactly as in a real run."""
    ctx = FileContext(path, source)
    report = run_rules(
        [ctx],
        rules if rules is not None else FILE_RULES,
        [],
        root="/",
        known_rules=ALL_RULE_NAMES,
    )
    return report


def analyze_sources(sources, repo_rules=None):
    """Lint a {path: source} program (multi-module fixtures): file rules
    per context plus the repo rules (concurrency rules by default) over
    the whole set."""
    contexts = [FileContext(path, src) for path, src in sources.items()]
    return run_rules(
        contexts,
        FILE_RULES,
        repo_rules if repo_rules is not None else list(CONCURRENCY_RULES),
        root="/",
        known_rules=ALL_RULE_NAMES,
    )


def analyze_cxx_sources(sources, repo_rules=None):
    """Lint a {path: source} fixture program through the C++ frontend:
    .h/.cc paths load as CxxFileContext, .py paths as FileContext, and
    the C++ rules (by default) run over the whole set — the selftest /
    test harness entry for GIL-DISCIPLINE, ATOMIC-ORDER, and
    CXX-LOCK-DISCIPLINE fixtures."""
    from . import cxx

    contexts = [
        cxx.CxxFileContext(path, src)
        if path.endswith((".h", ".hpp", ".cc", ".cpp"))
        else FileContext(path, src)
        for path, src in sources.items()
    ]
    return run_rules(
        contexts,
        [],
        repo_rules if repo_rules is not None else list(CXX_RULES),
        root="/",
        known_rules=ALL_RULE_NAMES,
    )


def analyze_paths(paths, root=None, baseline_path=None, only_paths=None):
    """Lint files/directories on disk with the full rule set.

    `only_paths` (repo-relative, posix) restricts FINDINGS to those
    files while the program graph and parity anchors still come from the
    full `paths` scan — the `--diff` mode's contract."""
    root = root or repo_root()
    files = discover_files(paths, root)
    contexts = [c for c in (load_context(f, root) for f in files) if c]
    baseline = load_baseline(baseline_path)
    return run_rules(
        contexts,
        FILE_RULES,
        REPO_RULES,
        root=root,
        baseline=baseline,
        known_rules=ALL_RULE_NAMES,
        only_paths=only_paths,
    )

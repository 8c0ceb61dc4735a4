"""beastlint repo configuration: which contracts bind which paths.

This file is the declarative half of the analyzer — rules read it, the
repo edits it. Everything here is data, so adding a package to a purity
contract or a flag to the parity exemptions is a one-line diff reviewed
like any other contract change.
"""

# Per-package banned top-level imports (IMPORT-PURITY). Keys are
# repo-relative directory prefixes; values are module roots that must
# never be imported anywhere under that prefix.
#
# telemetry/: stdlib-only so instrumentation can never introduce a device
# sync (replaces the PR 2 source-pin test as the single source of truth).
# analysis/: the linter itself must run in a bare-CI image and must never
# import the runtime it analyzes.
_HEAVY = (
    "jax",
    "jaxlib",
    "numpy",
    "np",
    "torch",
    "optax",
    "ml_dtypes",
    "chex",
    "flax",
    "tensorflow",
)
PURITY = {
    "torchbeast_tpu/telemetry": _HEAVY,
    "torchbeast_tpu/analysis": _HEAVY + ("torchbeast_tpu",),
}

# EXCEPT-SWALLOW scope: path prefixes where a broad `except:` /
# `except Exception:` / `except BaseException:` body must re-raise,
# log, or count the failure. These are the pipeline's failure-handling
# layers — a silent swallow here is exactly how a DEGRADED run hides
# (ISSUE 6). telemetry/ and analysis/ joined in ISSUE 10: a swallowed
# exporter failure silently drops observability, and a swallowed
# analyzer failure silently stops checking a contract. Other packages
# stay out of scope: broad-but-silent guards in benches/tests are
# noise, not hidden outages.
EXCEPT_SWALLOW_PATHS = (
    "torchbeast_tpu/runtime",
    "torchbeast_tpu/resilience",
    "torchbeast_tpu/telemetry",
    "torchbeast_tpu/analysis",
)

# WIRE-PARITY anchors: the Python codec and its C++ mirrors.
WIRE_PY = "torchbeast_tpu/runtime/wire.py"
WIRE_H = "csrc/wire.h"
ARRAY_H = "csrc/array.h"
CLIENT_H = "csrc/client.h"
POLYBEAST_PY = "torchbeast_tpu/polybeast.py"
# The shm ring layout contract (ISSUE 9): a Python env server and a C++
# actor attach the SAME segments, so the header word layout, in-ring
# markers, doorbell bytes, and the ring-eligibility cap must agree.
TRANSPORT_PY = "torchbeast_tpu/runtime/transport.py"
SHM_H = "csrc/shm.h"

# C++ DType enumerator -> numpy dtype name (the dtype table's rosetta
# stone; WIRE-PARITY fails if either side has a code the other lacks).
CPP_DTYPE_TO_NUMPY = {
    "kU8": "uint8",
    "kI8": "int8",
    "kI32": "int32",
    "kI64": "int64",
    "kF32": "float32",
    "kF64": "float64",
    "kBool": "bool",
    "kU16": "uint16",
    "kI16": "int16",
    "kU32": "uint32",
    "kU64": "uint64",
    "kF16": "float16",
    "kBF16": "bfloat16",
}

# Ground-truth itemsizes (bytes) per wire dtype: both languages' tables
# are checked against this, so a wrong size on either side is a finding
# even when the two sides agree with each other.
DTYPE_ITEMSIZE = {
    "uint8": 1,
    "int8": 1,
    "bool": 1,
    "uint16": 2,
    "int16": 2,
    "float16": 2,
    "bfloat16": 2,
    "int32": 4,
    "uint32": 4,
    "float32": 4,
    "int64": 8,
    "uint64": 8,
    "float64": 8,
}

# ROUTE-PARITY anchors (ISSUE 16): the static slot->slice hash runs in
# BOTH languages — runtime/placement.py `_mix64` for the Python pool and
# csrc/routing.h `splitmix64` for the native one. The same slot MUST
# land on the same slice either way (slot tables never migrate between
# devices), so the splitmix64 finalizer constants are pinned against
# the ground-truth spec below on both sides. The per-slice telemetry
# namespace ("inference.slice.<i>.*") is part of the same contract:
# dashboards and the capacity bench read one schema regardless of
# which language routed the request.
PLACEMENT_PY = "torchbeast_tpu/runtime/placement.py"
ROUTING_H = "csrc/routing.h"
# Python emitters of the per-slice series (both must build names under
# SLICE_SERIES_PREFIX): the Python serving plane and the native
# telemetry folder.
SLICE_SERIES_FILES = (
    "torchbeast_tpu/parallel/sebulba.py",
    "torchbeast_tpu/runtime/native.py",
)

# splitmix64 finalizer ground truth (Vigna's constants): both languages
# are checked against THIS, so a wrong constant on either side is a
# finding even when the two sides agree with each other.
SPLITMIX64_SPEC = {
    "gamma": 0x9E3779B97F4A7C15,
    "mul1": 0xBF58476D1CE4E5B9,
    "mul2": 0x94D049BB133111EB,
    "shift1": 30,
    "shift2": 27,
    "shift3": 31,
}

# The per-slice telemetry namespace: csrc/routing.h kSliceSeriesPrefix
# and every Python series builder must use exactly this prefix.
SLICE_SERIES_PREFIX = "inference.slice."

# FLAG-PARITY groups: (anchor, second file). The flags the second file
# re-declares must agree on type, default and action with the anchor's.
# polybeast's parser is two anchors: its own file, and learner_setup.py,
# where the learner flags are declared once for every driver (so no
# driver pair is left to compare). Intentional divergences carry inline
# suppressions at the add_argument site (with the reason), not entries
# here; findings anchor in the SECOND file.
_POLYBEAST_PARSER = (
    "torchbeast_tpu/polybeast.py",
    "torchbeast_tpu/learner_setup.py",
)
FLAG_PARITY_GROUPS = tuple(
    (anchor, second)
    for second in (
        # The env-server group driver shares its address/supervision
        # flags with the learner driver (polybeast spawns
        # ServerSupervisor from the same knobs).
        "torchbeast_tpu/polybeast_env.py",
        # The chaos harness builds polybeast flag lists
        # programmatically; the flags it re-declares for itself must
        # not silently drift from the driver's meaning.
        "scripts/chaos_run.py",
    )
    for anchor in _POLYBEAST_PARSER
)

# Whole-program concurrency analysis scope (RACE / LOCK-ORDER /
# HOTPATH-SYNC-XPROC, analysis/graph.py): the module/call/thread-root
# graphs are built from — and findings restricted to — these prefixes.
# tests/ and benchmarks/ stay out: their ad-hoc threads would add roots
# that exist only for one test's lifetime.
CONCURRENCY_PATHS = (
    "torchbeast_tpu",
    "scripts",
)

# Module-level functions treated as driver main-thread roots wherever
# they appear inside CONCURRENCY_PATHS (the driver main loops of
# polybeast/monobeast/anakin/polybeast_env/chaos_run).
THREAD_ROOT_FUNCTIONS = ("main", "train", "cli")

# ---------------------------------------------------------------------
# C++ analysis scope (ISSUE 10, analysis/cxx.py + cxxrules.py).

# GIL-DISCIPLINE: files whose CPython API calls must be dominated by a
# GIL acquire (in-function or via the call summary) and whose GIL-held
# regions must not make blocking calls (waits, socket recvs, queue
# dequeues). pymodule.cc is the binding layer; actor_pool.h hosts the
# slot hooks' call sites (its threads run GIL-free by design, so a
# CPython call appearing there without an acquire is a bug by
# construction); chaos.h hosts the FaultHooks entry points the Python
# chaos thread drives through pymodule (ISSUE 12) — same contract: any
# CPython call landing there without an acquire is a bug.
GIL_FILES = (
    "csrc/pymodule.cc",
    "csrc/actor_pool.h",
    "csrc/chaos.h",
)

# CXX-LOCK-DISCIPLINE / cross-root conflict scope: every C++ source the
# frontend lexes. Classes are in conflict scope only when they own a
# mutex or one of their methods is a thread-spawn target — same
# "you lock because you share" heuristic as the Python RACE rule.
CXX_PATHS = ("csrc",)

# ATOMIC-ORDER: the required memory order at the KEY publish/Dekker
# sites of csrc/shm.h, keyed by (function, word, op). Sites not listed
# only need an EXPLICIT order through the designated accessor; listed
# sites must use exactly this one (weakening the publish to relaxed is
# a lost-wakeup, not a style choice).
ATOMIC_ORDER_REQUIRED = {
    ("write_frame", "head", "store"): "release",
    ("write_inline_marker", "head", "store"): "release",
    ("release", "tail", "store"): "release",
    ("set_waiting", "waiting", "store"): "seq_cst",
    ("has_frame", "head", "load"): "acquire",
    ("reader_waiting", "waiting", "load"): "acquire",
    ("read_frame", "head", "load"): "acquire",
    ("wait_free", "tail", "load"): "acquire",
}

# Shared by HOTPATH-SYNC (intraprocedural) and HOTPATH-SYNC-XPROC
# (summary-based): jax.* namespaces that do HOST work (rooted there does
# not make a value device-resident), and calls whose RESULT is host data
# regardless of their arguments (`jax.device_get` is the explicit fetch
# the findings recommend, so its result must never re-taint).
HOST_JAX_NAMESPACES = ("tree_util", "tree", "dtypes", "typing")
HOST_RETURNING_CALLS = ("jax.device_get",)

# ---------------------------------------------------------------------
# Distributed-systems analysis tier (ISSUE 20, analysis/fleetrules.py +
# fleetproto.py).

# FLEET-MSG-PARITY anchor: the one file that speaks the fleet
# control-plane dict protocol. The rule extracts every send site
# (dict literals with a "type" key flowing into _send/_broadcast) and
# every handler arm, then cross-checks types and field sets per role.
FLEET_COORDINATOR = "torchbeast_tpu/fleet/coordinator.py"
# The payload-carrying senders the extractor follows. `_send`'s first
# argument is the destination rank (a literal 0 means "to the lead");
# `_broadcast` fans out lead -> remotes.
FLEET_SEND_FUNCS = ("_send", "_broadcast")
# Role assignment for handler arms found OUTSIDE the shared `_handle` /
# `_reader` dispatch (which both roles run): the lead-only accept loop
# handles "hello"; anything in the remote-only dial path is remote.
FLEET_LEAD_FUNCS = ("_start_lead",)
FLEET_REMOTE_FUNCS = ("_start_remote",)
# Fields every control-plane message may carry without a reader: "type"
# is consumed by the dispatch itself, and "rank" is the sender identity
# (verified once at hello, implied by the connection thereafter).
FLEET_MSG_STANDARD_FIELDS = ("type", "rank")

# FLEET-TIMEOUT-DISCIPLINE scope: path prefixes where every blocking
# control-plane operation (accept, recv, dial, condition/event wait,
# join) must be under a deadline or carry an explicit
# `# unbounded-by-design: <why>` annotation.
FLEET_TIMEOUT_PATHS = ("torchbeast_tpu/fleet",)
# Dial helpers that bound their own retry loop ONLY when a deadline is
# passed; calling them without one is an unbounded dial.
FLEET_DIAL_FUNCS = ("dial_transport", "connect_transport")

# TELEMETRY-SCHEMA scope: where series registrations
# (reg.counter/gauge/histogram with a literal or f-string name) are
# collected from. tests/ stays out: fixture registries use throwaway
# names by design.
TELEMETRY_SCAN_PATHS = ("torchbeast_tpu", "scripts", "benchmarks")
# The `host<r>.` fold prefix is reserved to the lead's telemetry folder
# (NativeTelemetryFolder): any other emitter would collide with the
# folded remote series and corrupt fleet dashboards.
TELEMETRY_FOLD_FILES = ("torchbeast_tpu/runtime/native.py",)
# Files whose series READS are schema commitments: the chaos harness'
# verdict counters and the telemetry test suite's snapshot assertions.
# A name consumed here that no scanned code emits is drift (a rename
# that silently turned the verdict/assert into a no-op).
TELEMETRY_CONSUMER_FILES = (
    "scripts/chaos_run.py",
    "tests/test_telemetry.py",
)
# The consumed-but-never-emitted check only runs when the scan plainly
# covers the whole tree (partial scans would see a truncated emitter
# set and flag everything): this sentinel file must be in scope.
TELEMETRY_SENTINEL_FILE = "torchbeast_tpu/telemetry/metrics.py"

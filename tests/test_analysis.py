"""beastlint (torchbeast_tpu/analysis): per-rule fixtures, suppression +
baseline mechanics, the cross-language/cross-driver parity rules run in
anger against the real repo, and the tier-1 CI gate itself.

The gate test at the bottom IS the contract from ISSUE 5: `python -m
torchbeast_tpu.analysis --ci` exits 0 on the repo with an EMPTY committed
baseline — new findings are fixed or suppressed inline with a reason,
never grandfathered.
"""

import json
import os
import re
import resource
import shutil
import subprocess
import sys
import time

import pytest

from torchbeast_tpu import analysis
from torchbeast_tpu.analysis import config as lint_config
from torchbeast_tpu.analysis.engine import FileContext
from torchbeast_tpu.analysis.parity import (
    FlagParityRule,
    WireParityRule,
    check_flag_parity,
    check_ring_parity,
    check_route_parity,
    check_wire_parity,
)
from torchbeast_tpu.analysis.selftest import run_selftest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(report, name):
    return [f for f in report.findings if f.rule == name]


# ---------------------------------------------------------------------------
# HOTPATH-SYNC


class TestHotpathSync:
    def test_item_flagged_in_hot_function(self):
        src = (
            "import jax.numpy as jnp\n"
            "# beastlint: hot\n"
            "def act(env):\n"
            "    logits = jnp.tanh(env)\n"
            "    return logits.item()\n"
        )
        found = _rules(analysis.analyze_source(src), "HOTPATH-SYNC")
        assert len(found) == 1 and found[0].line == 5

    def test_cold_function_not_flagged(self):
        src = (
            "import jax.numpy as jnp\n"
            "def helper(env):\n"
            "    return jnp.tanh(env).item()\n"
        )
        assert not _rules(analysis.analyze_source(src), "HOTPATH-SYNC")

    def test_hot_module_marks_every_function(self):
        src = (
            "# beastlint: hot-module\n"
            "import jax.numpy as jnp\n"
            "def act(env):\n"
            "    x = jnp.tanh(env)\n"
            "    return float(x)\n"
        )
        assert _rules(analysis.analyze_source(src), "HOTPATH-SYNC")

    def test_taint_propagates_through_derived_names(self):
        src = (
            "import jax.numpy as jnp\n"
            "import numpy as np\n"
            "# beastlint: hot\n"
            "def act(env):\n"
            "    x = jnp.tanh(env)\n"
            "    y = x * 2\n"
            "    return np.asarray(y)\n"
        )
        found = _rules(analysis.analyze_source(src), "HOTPATH-SYNC")
        assert len(found) == 1 and found[0].line == 7

    def test_host_conversions_clean(self):
        """int()/np.asarray on untainted host values never flag — a
        pure-host module (wire.py) can be hot-annotated for free."""
        src = (
            "# beastlint: hot-module\n"
            "import numpy as np\n"
            "def encode(value, batch_dim):\n"
            "    rows = int(np.asarray(value).shape[batch_dim])\n"
            "    return rows\n"
        )
        assert not analysis.analyze_source(src).findings

    def test_jax_tree_util_is_host_side(self):
        """jax.tree_util does pytree plumbing on host: bool() over its
        result is not a device sync (regression: state_table._leaves)."""
        src = (
            "# beastlint: hot-module\n"
            "import jax\n"
            "def has_leaves(tree):\n"
            "    return bool(jax.tree_util.tree_leaves(tree))\n"
        )
        assert not analysis.analyze_source(src).findings

    def test_device_get_result_is_host(self):
        """The fix the rule recommends must itself pass: a value fetched
        via explicit jax.device_get is host-resident, so converting it
        does not re-flag."""
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "# beastlint: hot\n"
            "def act(env):\n"
            "    logits = jnp.tanh(env)\n"
            "    host = jax.device_get(logits)\n"
            "    return float(host)\n"
        )
        assert not analysis.analyze_source(src).findings

    def test_print_flagged_in_hot_path(self):
        src = (
            "# beastlint: hot\n"
            "def act(env):\n"
            "    print(env)\n"
            "    return env\n"
        )
        found = _rules(analysis.analyze_source(src), "HOTPATH-SYNC")
        assert len(found) == 1 and "print" in found[0].message


# ---------------------------------------------------------------------------
# JIT-HAZARD


class TestJitHazard:
    def test_jit_in_loop_flagged(self):
        src = (
            "import jax\n"
            "def train(fs, x):\n"
            "    for f in fs:\n"
            "        x = jax.jit(f)(x)\n"
            "    return x\n"
        )
        found = _rules(analysis.analyze_source(src), "JIT-HAZARD")
        # Both hazards: construction in a loop AND immediately-invoked.
        assert len(found) == 2 and all(f.line == 4 for f in found)

    def test_hoisted_jit_clean(self):
        src = (
            "import jax\n"
            "def train(f, xs):\n"
            "    step = jax.jit(f)\n"
            "    for x in xs:\n"
            "        x = step(x)\n"
            "    return x\n"
        )
        assert not _rules(analysis.analyze_source(src), "JIT-HAZARD")

    def test_scan_in_loop_flagged(self):
        src = (
            "from jax import lax\n"
            "def roll(body, carries, xs):\n"
            "    outs = []\n"
            "    while carries:\n"
            "        outs.append(lax.scan(body, carries.pop(), xs))\n"
            "    return outs\n"
        )
        found = _rules(analysis.analyze_source(src), "JIT-HAZARD")
        assert len(found) == 1 and "scan" in found[0].message

    def test_unhashable_static_default(self):
        src = (
            "import jax\n"
            "def f(x, cfg=[1, 2]):\n"
            "    return x\n"
            "g = jax.jit(f, static_argnums=(1,))\n"
        )
        found = _rules(analysis.analyze_source(src), "JIT-HAZARD")
        assert len(found) == 1 and "unhashable" in found[0].message

    def test_hashable_static_default_clean(self):
        src = (
            "import jax\n"
            "def f(x, cfg=(1, 2)):\n"
            "    return x\n"
            "g = jax.jit(f, static_argnums=(1,))\n"
        )
        assert not _rules(analysis.analyze_source(src), "JIT-HAZARD")


# ---------------------------------------------------------------------------
# DONATE-USE


class TestDonateUse:
    def test_read_after_wrapped_call_flagged(self):
        src = (
            "def drive(update, p, o, batch, state):\n"
            "    step = consume_staged_inputs(update)\n"
            "    out = step(p, o, batch, state)\n"
            "    return out, batch.mean()\n"
        )
        found = _rules(analysis.analyze_source(src), "DONATE-USE")
        assert len(found) == 1 and found[0].line == 4

    def test_read_in_either_branch_flagged(self):
        src = (
            "def drive(x, cond):\n"
            "    x.delete()\n"
            "    if cond:\n"
            "        return 0\n"
            "    return x.shape\n"
        )
        found = _rules(analysis.analyze_source(src), "DONATE-USE")
        assert len(found) == 1 and found[0].line == 5

    def test_rebinding_clears_consumption(self):
        src = (
            "def drive(update, p, o, batch, state, queue):\n"
            "    step = consume_staged_inputs(update)\n"
            "    out = step(p, o, batch, state)\n"
            "    batch = queue.get()\n"
            "    return out, batch.mean()\n"
        )
        assert not _rules(analysis.analyze_source(src), "DONATE-USE")

    def test_loop_back_edge_read_flagged(self):
        src = (
            "def drive(items):\n"
            "    staged = None\n"
            "    for item in items:\n"
            "        use(staged)\n"
            "        staged = stage(item)\n"
            "        staged.delete()\n"
        )
        found = _rules(analysis.analyze_source(src), "DONATE-USE")
        assert len(found) == 1 and found[0].line == 4

    def test_for_target_rebinds_each_iteration(self):
        """Regression: `for leaf in ...: leaf.delete()` is the
        consume-once idiom itself (learner.consume_staged_inputs), not
        a use-after-free — the loop target rebinds per iteration."""
        src = (
            "def consume(leaves):\n"
            "    for leaf in leaves:\n"
            "        if not leaf.is_deleted():\n"
            "            leaf.delete()\n"
        )
        assert not _rules(analysis.analyze_source(src), "DONATE-USE")

    def test_factory_with_donate_batch_true_consumes(self):
        src = (
            "def drive(model, opt, hp, p, o, batch, state):\n"
            "    step = make_update_superstep(\n"
            "        model, opt, hp, 4, donate_batch=True\n"
            "    )\n"
            "    out = step(p, o, batch, state)\n"
            "    return out, state.shape\n"
        )
        found = _rules(analysis.analyze_source(src), "DONATE-USE")
        assert len(found) == 1 and "state" in found[0].message


# ---------------------------------------------------------------------------
# IMPORT-PURITY


class TestExceptSwallow:
    _PATH = "torchbeast_tpu/runtime/fixture.py"

    def test_silent_pass_flagged(self):
        report = analysis.analyze_source(
            "try:\n    f()\nexcept Exception:\n    pass\n",
            path=self._PATH,
        )
        assert _rules(report, "EXCEPT-SWALLOW")

    def test_bare_except_return_flagged(self):
        report = analysis.analyze_source(
            "def g():\n    try:\n        f()\n"
            "    except:\n        return None\n",
            path=self._PATH,
        )
        assert _rules(report, "EXCEPT-SWALLOW")

    def test_baseexception_in_tuple_flagged(self):
        report = analysis.analyze_source(
            "try:\n    f()\nexcept (ValueError, BaseException):\n"
            "    x = 1\n",
            path=self._PATH,
        )
        assert _rules(report, "EXCEPT-SWALLOW")

    def test_logging_clean(self):
        report = analysis.analyze_source(
            "try:\n    f()\nexcept Exception:\n"
            "    log.exception('boom')\n",
            path=self._PATH,
        )
        assert not _rules(report, "EXCEPT-SWALLOW")

    def test_reraise_clean(self):
        report = analysis.analyze_source(
            "try:\n    f()\nexcept BaseException:\n"
            "    cleanup()\n    raise\n",
            path=self._PATH,
        )
        assert not _rules(report, "EXCEPT-SWALLOW")

    def test_counter_clean(self):
        report = analysis.analyze_source(
            "try:\n    f()\nexcept Exception:\n    errors.inc()\n",
            path=self._PATH,
        )
        assert not _rules(report, "EXCEPT-SWALLOW")

    def test_promise_fail_clean(self):
        report = analysis.analyze_source(
            "try:\n    f()\nexcept Exception as e:\n"
            "    batch.fail(e)\n",
            path=self._PATH,
        )
        assert not _rules(report, "EXCEPT-SWALLOW")

    def test_narrow_handler_out_of_contract(self):
        report = analysis.analyze_source(
            "try:\n    f()\nexcept OSError:\n    pass\n",
            path=self._PATH,
        )
        assert not _rules(report, "EXCEPT-SWALLOW")

    def test_outside_scoped_paths_unconstrained(self):
        report = analysis.analyze_source(
            "try:\n    f()\nexcept Exception:\n    pass\n",
            path="scripts/fixture.py",
        )
        assert not _rules(report, "EXCEPT-SWALLOW")

    def test_log_in_nested_def_does_not_credit_handler(self):
        """A log call inside a nested def doesn't run as part of the
        handler — defining a logging callback is still a swallow at
        handler time."""
        report = analysis.analyze_source(
            "try:\n    f()\nexcept Exception:\n"
            "    def cb():\n        log.exception('later')\n"
            "    register(cb)\n",
            path=self._PATH,
        )
        assert _rules(report, "EXCEPT-SWALLOW")

    def test_resilience_path_in_scope(self):
        report = analysis.analyze_source(
            "try:\n    f()\nexcept Exception:\n    pass\n",
            path="torchbeast_tpu/resilience/fixture.py",
        )
        assert _rules(report, "EXCEPT-SWALLOW")

    def test_real_runtime_and_resilience_clean(self):
        """The burn-down contract: the real failure-handling layers
        carry no silent broad swallows (and the baseline stays empty)."""
        report = analysis.analyze_paths(
            list(lint_config.EXCEPT_SWALLOW_PATHS), root=REPO
        )
        assert not _rules(report, "EXCEPT-SWALLOW"), [
            f.render() for f in report.findings
        ]


class TestImportPurity:
    def test_numpy_in_telemetry_flagged(self):
        report = analysis.analyze_source(
            "import numpy as np\n",
            path="torchbeast_tpu/telemetry/fixture.py",
        )
        assert _rules(report, "IMPORT-PURITY")

    def test_function_local_import_flagged(self):
        report = analysis.analyze_source(
            "def f():\n    import jax\n    return jax\n",
            path="torchbeast_tpu/telemetry/fixture.py",
        )
        assert _rules(report, "IMPORT-PURITY")

    def test_stdlib_clean(self):
        report = analysis.analyze_source(
            "import json\nimport threading\n",
            path="torchbeast_tpu/telemetry/fixture.py",
        )
        assert not report.findings

    def test_outside_contract_dirs_unconstrained(self):
        report = analysis.analyze_source(
            "import numpy as np\n", path="torchbeast_tpu/learner.py"
        )
        assert not _rules(report, "IMPORT-PURITY")

    def test_real_telemetry_package_is_pure(self):
        """The single source of truth for the PR 2 stdlib-only pin:
        the analyzer's IMPORT-PURITY rule over the real package (the
        hand-rolled regex test in test_telemetry.py is replaced by
        this)."""
        report = analysis.analyze_paths(
            ["torchbeast_tpu/telemetry", "torchbeast_tpu/analysis"],
            root=REPO,
        )
        assert not _rules(report, "IMPORT-PURITY"), [
            f.render() for f in report.findings
        ]


# ---------------------------------------------------------------------------
# LOCK-DISCIPLINE


class TestLockDiscipline:
    GUARDED = (
        "import threading\n"
        "class Q:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._not_empty = threading.Condition(self._lock)\n"
        "        self._items = []  # guarded-by: self._lock\n"
    )

    def test_unlocked_access_flagged(self):
        src = self.GUARDED + (
            "    def size(self):\n"
            "        return len(self._items)\n"
        )
        found = _rules(analysis.analyze_source(src), "LOCK-DISCIPLINE")
        assert len(found) == 1 and found[0].line == 8

    def test_with_lock_clean(self):
        src = self.GUARDED + (
            "    def size(self):\n"
            "        with self._lock:\n"
            "            return len(self._items)\n"
        )
        assert not analysis.analyze_source(src).findings

    def test_condition_acquires_underlying_lock(self):
        src = self.GUARDED + (
            "    def pop(self):\n"
            "        with self._not_empty:\n"
            "            return self._items.pop()\n"
        )
        assert not analysis.analyze_source(src).findings

    def test_holds_annotation_exempts_helper(self):
        src = self.GUARDED + (
            "    # beastlint: holds self._lock\n"
            "    def _drain_locked(self):\n"
            "        self._items.clear()\n"
        )
        assert not analysis.analyze_source(src).findings

    def test_access_inside_except_handler_with_lock(self):
        """Regression: a `with self._lock` nested in try/except must
        still count as holding the lock (actor_pool reconnect path)."""
        src = self.GUARDED + (
            "    def run(self):\n"
            "        while True:\n"
            "            try:\n"
            "                return 1\n"
            "            except OSError:\n"
            "                with self._lock:\n"
            "                    self._items.append(1)\n"
        )
        assert not analysis.analyze_source(src).findings

    def test_annassign_guarded_attr_enforced(self):
        """Regression: `self._x: Dict[...] = {}  # guarded-by: ...`
        (an AnnAssign, the MetricsRegistry._instruments form) must
        register the guard, not silently drop it."""
        src = (
            "import threading\n"
            "from typing import Dict\n"
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._table: Dict[str, int] = {}"
            "  # guarded-by: self._lock\n"
            "    def get(self, k):\n"
            "        return self._table.get(k)\n"
        )
        found = _rules(analysis.analyze_source(src), "LOCK-DISCIPLINE")
        assert len(found) == 1 and "_table" in found[0].message

    def test_bare_acquire_flagged(self):
        src = (
            "def f(lock, work):\n"
            "    lock.acquire()\n"
            "    work()\n"
            "    lock.release()\n"
        )
        found = _rules(analysis.analyze_source(src), "LOCK-DISCIPLINE")
        assert len(found) == 1 and found[0].line == 2

    def test_acquire_with_try_finally_clean(self):
        src = (
            "def f(lock, work):\n"
            "    lock.acquire()\n"
            "    try:\n"
            "        work()\n"
            "    finally:\n"
            "        lock.release()\n"
        )
        assert not analysis.analyze_source(src).findings


# ---------------------------------------------------------------------------
# Parity rules, fixtures + in anger


class TestWireParity:
    WIRE_PY = (
        "import numpy as np\n"
        "TAG_ARRAY = 0x01\n"
        "DEFAULT_MAX_FRAME_BYTES = 16 * 1024\n"
        "_DTYPE_CODES = {np.dtype(np.uint8): 0}\n"
    )
    WIRE_H = (
        "constexpr uint8_t kTagArray = 0x01;\n"
        "constexpr size_t kMaxFrameBytes = 16ull * 1024;\n"
    )
    ARRAY_H = (
        "enum class DType : uint8_t {\n  kU8 = 0,\n};\n"
        "inline size_t itemsize(DType dtype) {\n"
        "  switch (dtype) {\n    case DType::kU8:\n      return 1;\n"
        "  }\n  throw 1;\n}\n"
    )
    CLIENT_H = "if (length > wire::kMaxFrameBytes) throw;\n"

    def _ctx(self, src):
        return FileContext("torchbeast_tpu/runtime/wire.py", src)

    def test_matched_tables_clean(self):
        assert not check_wire_parity(
            self._ctx(self.WIRE_PY), self.WIRE_H, self.ARRAY_H,
            self.CLIENT_H, None,
        )

    def test_dtype_code_drift_flagged(self):
        drifted = self.ARRAY_H.replace("kU8 = 0", "kU8 = 3")
        found = check_wire_parity(
            self._ctx(self.WIRE_PY), self.WIRE_H, drifted,
            self.CLIENT_H, None,
        )
        assert any("uint8" in f.message for f in found)

    def test_max_frame_drift_flagged(self):
        drifted = self.WIRE_H.replace("16ull", "8ull")
        found = check_wire_parity(
            self._ctx(self.WIRE_PY), drifted, self.ARRAY_H,
            self.CLIENT_H, None,
        )
        assert any("kMaxFrameBytes" in f.message for f in found)

    def test_itemsize_drift_flagged(self):
        drifted = self.ARRAY_H.replace("return 1;", "return 2;")
        found = check_wire_parity(
            self._ctx(self.WIRE_PY), self.WIRE_H, drifted,
            self.CLIENT_H, None,
        )
        assert any("itemsize" in f.message for f in found)

    def test_unenforced_frame_bound_flagged(self):
        found = check_wire_parity(
            self._ctx(self.WIRE_PY), self.WIRE_H, self.ARRAY_H,
            "// no bound check here\n", None,
        )
        assert any("client.h" in f.message for f in found)

    def test_multiword_tag_names_normalized(self):
        """TAG_NP_SCALAR (py) and kTagNpScalar (C++) are the same tag:
        underscore/case differences must not read as drift."""
        py = self._ctx(
            self.WIRE_PY + "TAG_NP_SCALAR = 0x09\n"
        )
        wire_h = self.WIRE_H + (
            "constexpr uint8_t kTagNpScalar = 0x09;\n"
        )
        assert not check_wire_parity(
            py, wire_h, self.ARRAY_H, self.CLIENT_H, None
        )

    def test_real_repo_in_anger(self):
        """The satellite: the dtype table (incl. bf16 code 12),
        --max_frame_bytes default, and frame tags agree between
        runtime/wire.py and csrc/ RIGHT NOW."""
        report = analysis.analyze_paths(
            [lint_config.WIRE_PY, lint_config.POLYBEAST_PY], root=REPO
        )
        found = _rules(report, "WIRE-PARITY")
        assert not found, [f.render() for f in found]
        # And the parse actually saw the full table (13 dtypes incl.
        # bfloat16=12; 9 tags incl. SNAPSHOT=9), not an empty dict
        # vacuously matching.
        from torchbeast_tpu.analysis.parity import parse_py_wire

        ctx = analysis.load_context(
            os.path.join(REPO, lint_config.WIRE_PY), REPO
        )
        tags, max_frame, codes = parse_py_wire(ctx.tree)
        assert codes.get("bfloat16") == 12 and len(codes) == 13
        assert max_frame == 256 * 1024 * 1024
        assert tags["ARRAY"] == 1 and tags["SNAPSHOT"] == 9
        assert len(tags) == 9


class TestRingParity:
    """WIRE-PARITY's shm ring-layout arm (ISSUE 9 satellite): the drift
    check PR 5 flagged as missing — header word layout, wrap/inline
    markers, doorbell bytes, and the capacity//2-4 eligibility cap
    pinned py<->C++, with unparseable sides surfacing as findings."""

    TRANSPORT_PY = (
        '_DOORBELL_WAKE = b"\\x01"\n'
        '_DOORBELL_INLINE = b"\\x02"\n'
        "class ShmRing:\n"
        "    HEADER_BYTES = 64\n"
        "    _WRAP = 0xFFFFFFFF\n"
        "    _INLINE = 0xFFFFFFFE\n"
        "    _HEAD, _TAIL, _CAP, _WAITING = 0, 1, 2, 3\n"
        "    def max_frame_bytes(self):\n"
        "        return self._capacity // 2 - 4\n"
    )
    SHM_H = (
        "constexpr size_t kRingHeaderBytes = 64;\n"
        "constexpr size_t kRingHeadWord = 0;\n"
        "constexpr size_t kRingTailWord = 1;\n"
        "constexpr size_t kRingCapacityWord = 2;\n"
        "constexpr size_t kRingWaitingWord = 3;\n"
        "constexpr uint32_t kRingWrapMarker = 0xFFFFFFFF;\n"
        "constexpr uint32_t kRingInlineMarker = 0xFFFFFFFE;\n"
        "constexpr uint8_t kDoorbellWake = 0x01;\n"
        "constexpr uint8_t kDoorbellInline = 0x02;\n"
        "size_t max_frame_bytes() const { return capacity_ / 2 - 4; }\n"
    )

    def _ctx(self, src):
        return FileContext("torchbeast_tpu/runtime/transport.py", src)

    def test_matched_layout_clean(self):
        assert not check_ring_parity(self._ctx(self.TRANSPORT_PY),
                                     self.SHM_H)

    def test_cpp_marker_drift_flagged(self):
        drifted = self.SHM_H.replace(
            "kRingInlineMarker = 0xFFFFFFFE", "kRingInlineMarker = 0xFFFFFFFD"
        )
        found = check_ring_parity(self._ctx(self.TRANSPORT_PY), drifted)
        assert any("inline marker" in f.message for f in found)
        assert all(f.rule == "WIRE-PARITY" for f in found)

    def test_py_header_drift_flagged(self):
        drifted = self.TRANSPORT_PY.replace(
            "HEADER_BYTES = 64", "HEADER_BYTES = 32"
        )
        found = check_ring_parity(self._ctx(drifted), self.SHM_H)
        assert any("header size" in f.message for f in found)

    def test_word_index_drift_flagged(self):
        drifted = self.TRANSPORT_PY.replace(
            "_HEAD, _TAIL, _CAP, _WAITING = 0, 1, 2, 3",
            "_HEAD, _TAIL, _CAP, _WAITING = 0, 2, 1, 3",
        )
        found = check_ring_parity(self._ctx(drifted), self.SHM_H)
        assert any("tail counter" in f.message for f in found)
        assert any("capacity word" in f.message for f in found)

    def test_eligibility_cap_drift_flagged(self):
        drifted = self.SHM_H.replace(
            "capacity_ / 2 - 4", "capacity_ / 4 - 8"
        )
        found = check_ring_parity(self._ctx(self.TRANSPORT_PY), drifted)
        assert any("eligibility" in f.message for f in found)

    def test_doorbell_byte_drift_flagged(self):
        drifted = self.TRANSPORT_PY.replace(
            '_DOORBELL_WAKE = b"\\x01"', '_DOORBELL_WAKE = b"\\x03"'
        )
        found = check_ring_parity(self._ctx(drifted), self.SHM_H)
        assert any("WAKE byte" in f.message for f in found)

    def test_unparseable_side_is_a_finding_not_silence(self):
        found = check_ring_parity(
            self._ctx("x = 1\n"), self.SHM_H
        )
        assert found and any("cannot verify" in f.message for f in found)
        found = check_ring_parity(
            self._ctx(self.TRANSPORT_PY), "// nothing here\n"
        )
        assert found and any("cannot verify" in f.message for f in found)

    def test_partially_unparseable_field_is_flagged(self):
        drifted = self.SHM_H.replace(
            "constexpr uint8_t kDoorbellWake = 0x01;\n", ""
        )
        found = check_ring_parity(self._ctx(self.TRANSPORT_PY), drifted)
        assert any(
            "WAKE byte" in f.message and "C++ side" in f.message
            for f in found
        )

    def test_real_repo_in_anger(self):
        """transport.py and csrc/shm.h agree RIGHT NOW, and the parse
        saw every field (no vacuous None==None matches)."""
        report = analysis.analyze_paths(
            [lint_config.TRANSPORT_PY], root=REPO
        )
        found = _rules(report, "WIRE-PARITY")
        assert not found, [f.render() for f in found]
        from torchbeast_tpu.analysis.parity import (
            parse_cpp_ring,
            parse_py_ring,
        )

        ctx = analysis.load_context(
            os.path.join(REPO, lint_config.TRANSPORT_PY), REPO
        )
        ring_py = parse_py_ring(ctx.tree)
        with open(os.path.join(REPO, lint_config.SHM_H)) as f:
            ring_cpp = parse_cpp_ring(f.read())
        assert None not in ring_py.values(), ring_py
        assert None not in ring_cpp.values(), ring_cpp
        assert ring_py == ring_cpp
        assert ring_py["header_bytes"] == 64
        assert ring_py["eligibility_divisor"] == 2
        assert ring_py["eligibility_slack"] == 4


class TestRouteParity:
    """ROUTE-PARITY (ISSUE 16): the splitmix64 slot->slice hash and the
    per-slice telemetry namespace pinned Python<->C++ against the
    ground-truth spec, drift injected in BOTH directions."""

    PLACEMENT_PY = (
        "def _mix64(x):\n"
        "    x = (x + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF\n"
        "    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9)"
        " & 0xFFFFFFFFFFFFFFFF\n"
        "    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB)"
        " & 0xFFFFFFFFFFFFFFFF\n"
        "    return x ^ (x >> 31)\n"
    )
    ROUTING_H = (
        "constexpr uint64_t kSplitMix64Gamma = 0x9E3779B97F4A7C15ULL;\n"
        "constexpr uint64_t kSplitMix64Mul1 = 0xBF58476D1CE4E5B9ULL;\n"
        "constexpr uint64_t kSplitMix64Mul2 = 0x94D049BB133111EBULL;\n"
        "constexpr int kSplitMix64Shift1 = 30;\n"
        "constexpr int kSplitMix64Shift2 = 27;\n"
        "constexpr int kSplitMix64Shift3 = 31;\n"
        'constexpr const char kSliceSeriesPrefix[] = "inference.slice.";\n'
    )
    SERIES_PY = (
        "def series(i):\n"
        '    return f"inference.slice.{i}.requests"\n'
    )

    def _ctx(self, src, path=lint_config.PLACEMENT_PY):
        return FileContext(path, src)

    def _series(self, src=None):
        return [self._ctx(src or self.SERIES_PY,
                          lint_config.SLICE_SERIES_FILES[0])]

    def test_matched_sides_clean(self):
        assert not check_route_parity(
            self._ctx(self.PLACEMENT_PY), self.ROUTING_H, self._series()
        )

    def test_cpp_constant_drift_flagged(self):
        drifted = self.ROUTING_H.replace(
            "kSplitMix64Mul1 = 0xBF58476D1CE4E5B9ULL",
            "kSplitMix64Mul1 = 0xBF58476D1CE4E5B8ULL",
        )
        found = check_route_parity(
            self._ctx(self.PLACEMENT_PY), drifted, self._series()
        )
        assert any(
            "first multiplier" in f.message and "routing.h" in f.path
            for f in found
        )
        assert all(f.rule == "ROUTE-PARITY" for f in found)

    def test_py_shift_drift_flagged(self):
        drifted = self.PLACEMENT_PY.replace("x >> 30", "x >> 29")
        found = check_route_parity(
            self._ctx(drifted), self.ROUTING_H, self._series()
        )
        assert any(
            "first xor-shift" in f.message
            and f.path == lint_config.PLACEMENT_PY
            for f in found
        )

    def test_py_gamma_drift_flagged(self):
        drifted = self.PLACEMENT_PY.replace(
            "x + 0x9E3779B97F4A7C15", "x + 0x9E3779B97F4A7C16"
        )
        found = check_route_parity(
            self._ctx(drifted), self.ROUTING_H, self._series()
        )
        assert any("gamma" in f.message for f in found)

    def test_lockstep_drift_still_flagged(self):
        """Both sides drifting TOGETHER is still a finding: the check
        is against the pinned spec, not mutual agreement (a lockstep
        rewrite silently remaps every deployed slot assignment)."""
        py = self.PLACEMENT_PY.replace("x >> 27", "x >> 26")
        cpp = self.ROUTING_H.replace("Shift2 = 27", "Shift2 = 26")
        found = check_route_parity(self._ctx(py), cpp, self._series())
        assert any(f.path == lint_config.PLACEMENT_PY for f in found)
        assert any(f.path == lint_config.ROUTING_H for f in found)

    def test_cpp_series_prefix_drift_flagged(self):
        drifted = self.ROUTING_H.replace(
            '"inference.slice."', '"inference.slices."'
        )
        found = check_route_parity(
            self._ctx(self.PLACEMENT_PY), drifted, self._series()
        )
        assert any("kSliceSeriesPrefix" in f.message for f in found)

    def test_py_series_rename_flagged(self):
        renamed = self.SERIES_PY.replace("inference.slice.", "infer.sl.")
        found = check_route_parity(
            self._ctx(self.PLACEMENT_PY), self.ROUTING_H,
            self._series(renamed),
        )
        assert any("pinned per-slice prefix" in f.message for f in found)

    def test_unparseable_side_is_a_finding_not_silence(self):
        found = check_route_parity(
            self._ctx("x = 1\n"), self.ROUTING_H, self._series()
        )
        assert found and any("cannot verify" in f.message for f in found)
        found = check_route_parity(
            self._ctx(self.PLACEMENT_PY), "// nothing\n", self._series()
        )
        assert found and any("cannot verify" in f.message for f in found)

    def test_real_repo_in_anger(self):
        """placement.py, csrc/routing.h, and both per-slice series
        emitters agree RIGHT NOW — and the parse saw every field (no
        vacuous None==None matches)."""
        report = analysis.analyze_paths(
            [lint_config.PLACEMENT_PY, *lint_config.SLICE_SERIES_FILES],
            root=REPO,
        )
        found = _rules(report, "ROUTE-PARITY")
        assert not found, [f.render() for f in found]
        from torchbeast_tpu.analysis.parity import (
            parse_cpp_routing,
            parse_py_splitmix,
        )

        ctx = analysis.load_context(
            os.path.join(REPO, lint_config.PLACEMENT_PY), REPO
        )
        mix_py = parse_py_splitmix(ctx.tree)
        with open(os.path.join(REPO, lint_config.ROUTING_H)) as f:
            mix_cpp, prefix = parse_cpp_routing(f.read())
        assert None not in mix_py.values(), mix_py
        assert mix_py == mix_cpp == lint_config.SPLITMIX64_SPEC
        assert prefix == lint_config.SLICE_SERIES_PREFIX

    def test_native_hash_matches_python_in_anger(self):
        """The executable ground truth behind the textual pin: the C++
        extension's splitmix64 IS placement._mix64 (when the native
        runtime is built)."""
        core = pytest.importorskip("_tbt_core")
        from torchbeast_tpu.runtime.placement import _mix64

        for slot in (0, 1, 7, 63, 255, 2**31, -1):
            assert core.splitmix64(slot) == _mix64(slot & (2**64 - 1))
        for n in (1, 2, 3, 8):
            for slot in range(64):
                assert core.slice_for_slot(
                    slot=slot, n_slices=n
                ) == _mix64(slot) % n


class TestFlagParity:
    def test_default_drift_flagged_at_second_file(self):
        a = FileContext(
            "a.py",
            'p.add_argument("--batch_size", type=int, default=8)\n',
        )
        b = FileContext(
            "b.py",
            'p.add_argument("--batch_size", type=int, default=16)\n',
        )
        found = check_flag_parity(a, b)
        assert len(found) == 1 and found[0].path == "b.py"

    def test_qualified_constant_spelling_normalized(self):
        a = FileContext(
            "a.py",
            'p.add_argument("--m", type=int, default=DEFAULT_MAX)\n',
        )
        b = FileContext(
            "b.py",
            'p.add_argument("--m", type=int, default=wire.DEFAULT_MAX)\n',
        )
        assert not check_flag_parity(a, b)

    def test_float_defaults_compared_exactly(self):
        a = FileContext(
            "a.py", 'p.add_argument("--lr", type=float, default=8.5)\n'
        )
        b = FileContext(
            "b.py", 'p.add_argument("--lr", type=float, default=7.5)\n'
        )
        assert len(check_flag_parity(a, b)) == 1

    def _assert_declared_once_and_drift_caught(self, drifts):
        """Each shared learner flag is declared ONCE, in
        learner_setup.py (neither driver's file repeats it), and a
        script that re-declares it beside polybeast's parser with a
        drifted default is CAUGHT against learner_setup.py, the second
        anchor of polybeast's groups — the parity net still covers it
        where it now lives."""
        def read(*parts):
            with open(os.path.join(REPO, *parts)) as f:
                return f.read()

        setup = FileContext(
            "torchbeast_tpu/learner_setup.py",
            read("torchbeast_tpu", "learner_setup.py"),
        )
        poly = FileContext(
            "torchbeast_tpu/polybeast.py",
            read("torchbeast_tpu", "polybeast.py"),
        )
        mono_src = read("torchbeast_tpu", "monobeast.py")
        for flag, (orig, drifted_frag) in drifts.items():
            assert orig in setup.source, flag
            assert f'"{flag}"' not in poly.source, flag
            assert f'"{flag}"' not in mono_src, flag
            same = FileContext(
                "scripts/s.py", f"p.add_argument({orig})\n"
            )
            assert not check_flag_parity(setup, same)
            assert not check_flag_parity(poly, same)
            drifted = FileContext(
                "scripts/s.py", f"p.add_argument({drifted_frag})\n"
            )
            found = check_flag_parity(setup, drifted)
            assert any(flag in f.message for f in found), (
                flag, [f.message for f in found],
            )
            assert all(f.path == "scripts/s.py" for f in found)

    def test_issue13_flags_present_and_drift_caught(self):
        """Three shared learner flags (--remat, --superstep_k,
        --hbm_budget_gb)."""
        self._assert_declared_once_and_drift_caught({
            "--remat": (
                '"--remat", default=None',
                '"--remat", default="all"',
            ),
            "--superstep_k": (
                '"--superstep_k", type=int, default=1',
                '"--superstep_k", type=int, default=4',
            ),
            "--hbm_budget_gb": (
                '"--hbm_budget_gb", type=float, default=0.0',
                '"--hbm_budget_gb", type=float, default=15.75',
            ),
        })

    def test_issue18_flags_present_and_drift_caught(self):
        """The three ISSUE 18 shared IMPACT flags (--impact_clip,
        --replay_reuse, --target_refresh_updates)."""
        self._assert_declared_once_and_drift_caught({
            "--impact_clip": (
                '"--impact_clip", type=float, default=0.2',
                '"--impact_clip", type=float, default=0.3',
            ),
            "--replay_reuse": (
                '"--replay_reuse", type=int, default=1',
                '"--replay_reuse", type=int, default=2',
            ),
            "--target_refresh_updates": (
                '"--target_refresh_updates", type=int, default=8',
                '"--target_refresh_updates", type=int, default=80',
            ),
        })


# ---------------------------------------------------------------------------
# Suppression + baseline mechanics


class TestSuppressionMechanics:
    HOT_ITEM = (
        "import jax.numpy as jnp\n"
        "# beastlint: hot\n"
        "def act(env):\n"
        "    x = jnp.tanh(env)\n"
        "    return x.item(){}\n"
    )

    def test_trailing_suppression_with_reason(self):
        src = self.HOT_ITEM.format(
            "  # beastlint: disable=HOTPATH-SYNC  boundary fetch"
        )
        report = analysis.analyze_source(src)
        assert not report.findings
        assert len(report.suppressed) == 1
        assert report.suppressed[0][1].reason == "boundary fetch"

    def test_standalone_suppression_covers_next_line(self):
        src = (
            "import jax.numpy as jnp\n"
            "# beastlint: hot\n"
            "def act(env):\n"
            "    x = jnp.tanh(env)\n"
            "    # beastlint: disable=HOTPATH-SYNC  boundary fetch\n"
            "    return x.item()\n"
        )
        report = analysis.analyze_source(src)
        assert not report.findings and len(report.suppressed) == 1

    def test_reasonless_suppression_is_a_finding(self):
        src = self.HOT_ITEM.format("  # beastlint: disable=HOTPATH-SYNC")
        report = analysis.analyze_source(src)
        assert _rules(report, "SUPPRESS-REASON")

    def test_unknown_rule_in_suppression_is_a_finding(self):
        src = "x = 1  # beastlint: disable=NO-SUCH-RULE  whatever\n"
        report = analysis.analyze_source(src)
        found = _rules(report, "SUPPRESS-REASON")
        assert len(found) == 1 and "NO-SUCH-RULE" in found[0].message

    def test_wrong_rule_does_not_suppress(self):
        src = self.HOT_ITEM.format(
            "  # beastlint: disable=JIT-HAZARD  wrong rule"
        )
        report = analysis.analyze_source(src)
        assert _rules(report, "HOTPATH-SYNC")


class TestBaselineMechanics:
    def test_fingerprint_is_line_insensitive(self):
        src1 = (
            "# beastlint: hot\n"
            "def act(env):\n"
            "    return env.item()\n"
        )
        src2 = "\n\n" + src1  # pure code motion
        f1 = analysis.analyze_source(src1).findings[0]
        f2 = analysis.analyze_source(src2).findings[0]
        assert f1.line != f2.line
        assert f1.fingerprint == f2.fingerprint

    def test_write_then_load_roundtrip(self, tmp_path):
        src = (
            "# beastlint: hot\n"
            "def act(env):\n"
            "    return env.item()\n"
        )
        findings = analysis.analyze_source(src).findings
        path = str(tmp_path / "baseline.json")
        analysis.write_baseline(path, findings)
        loaded = analysis.load_baseline(path)
        assert loaded == {f.fingerprint for f in findings}

    def test_committed_baseline_is_empty(self):
        with open(os.path.join(REPO, ".beastlint-baseline.json")) as f:
            data = json.load(f)
        assert data == {"fingerprints": []}


# ---------------------------------------------------------------------------
# Selftest + the tier-1 CI gate


class TestSelftestAndGate:
    def test_selftest_in_process(self):
        verdict = run_selftest()
        assert verdict["ok"], verdict
        assert set(verdict["rules"]) == {
            "HOTPATH-SYNC", "JIT-HAZARD", "DONATE-USE", "IMPORT-PURITY",
            "LOCK-DISCIPLINE", "EXCEPT-SWALLOW", "WIRE-PARITY",
            "ROUTE-PARITY", "FLAG-PARITY", "RACE", "LOCK-ORDER",
            "HOTPATH-SYNC-XPROC", "GIL-DISCIPLINE", "ATOMIC-ORDER",
            "CXX-LOCK-DISCIPLINE", "FLEET-MSG-PARITY",
            "FLEET-TIMEOUT-DISCIPLINE", "TELEMETRY-SCHEMA",
        }
        for name, checks in verdict["rules"].items():
            assert checks["positive"] and checks["clean"], (name, checks)
            assert checks["isolated"], (name, checks)

    def test_list_rules_shows_all_eighteen(self):
        """The 11 -> 14 -> 15 -> 18 rule invariant (ISSUE 10;
        ROUTE-PARITY joined in ISSUE 16; the fleet tier in ISSUE 20):
        every registered rule appears in --list-rules, and every listed
        rule has a selftest fixture pair (the selftest set and the
        registry agree)."""
        proc = subprocess.run(
            [sys.executable, "-m", "torchbeast_tpu.analysis",
             "--list-rules"],
            capture_output=True, text=True, cwd=REPO, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        listed = {
            line.split()[0] for line in proc.stdout.splitlines() if line
        }
        assert len(listed) == 18, sorted(listed)
        verdict = run_selftest()
        assert listed == set(verdict["rules"]), (
            listed ^ set(verdict["rules"])
        )

    @staticmethod
    def _run_the_gate():
        """`python -m torchbeast_tpu.analysis --ci --json` exits 0 on
        the repo: (its report, the seconds of its own CPU, the wall)."""
        t0 = time.monotonic()
        before = resource.getrusage(resource.RUSAGE_CHILDREN)
        proc = subprocess.run(
            [sys.executable, "-m", "torchbeast_tpu.analysis",
             "--ci", "--json"],
            capture_output=True, text=True, cwd=REPO, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        after = resource.getrusage(resource.RUSAGE_CHILDREN)
        cpu_s = (after.ru_utime + after.ru_stime) - (
            before.ru_utime + before.ru_stime
        )
        wall = time.monotonic() - t0
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        return report, cpu_s, wall

    def test_ci_gate_clean(self):
        """THE acceptance gate (ISSUE 5; re-pinned by ISSUE 7 with the
        whole-program graph layer and by ISSUE 10 with the C++ frontend
        active): `python -m torchbeast_tpu.analysis --ci` exits 0 on the
        repo (empty baseline, reasoned suppressions only, concurrency +
        C++ rules running). Its clock is `test_ci_gate_budget`'s."""
        report, _, _ = self._run_the_gate()
        assert report["findings"] == [] and report["ci"] == "PASS"
        assert report["files_scanned"] > 100
        # Every surviving suppression carries a reason (the engine also
        # enforces this as SUPPRESS-REASON findings — belt and braces).
        assert all(s["reason"] for s in report["suppressed"])
        # The RACE and CXX-LOCK-DISCIPLINE burn-down suppressions prove
        # that the graph layer and the C++ frontend both ran.
        assert any(
            s["rule"] == "RACE" for s in report["suppressed"]
        ), "concurrency rules did not run in the gate"
        assert any(
            s["rule"] == "CXX-LOCK-DISCIPLINE" for s in report["suppressed"]
        ), "C++ rules did not run in the gate"

    @pytest.mark.slow
    def test_ci_gate_budget(self):
        """ISSUE 10 acceptance: the gate in under 20 s repo-wide WITH
        the graph layer AND the C++ frontend, as 20 s of the child's own
        CPU (one process, one thread). `slow`: for a run that has the
        machine to itself; beside five busy workers the child's CPU
        clock reads what they do to its caches (21.4 s in tier-1 against
        11.1 s alone, PR 56's tree). `scripts/lint.sh --timing` prints
        the clock a rule."""
        report, cpu_s, wall = self._run_the_gate()
        assert cpu_s < 20, (cpu_s, report["elapsed_s"])
        assert wall < 90  # import + scan, generous for a loaded sandbox

    def test_pyproject_packages_complete(self):
        """Every torchbeast_tpu.* subpackage on disk is in pyproject's
        packages list (ISSUE 10 satellite: resilience/ shipped
        unimportable from a wheel for four PRs because the list is
        maintained by hand — this pin makes the next new package fail
        CI instead)."""
        with open(os.path.join(REPO, "pyproject.toml")) as f:
            toml = f.read()
        m = re.search(r"packages\s*=\s*\[(.*?)\]", toml, re.DOTALL)
        assert m, "packages list missing from pyproject.toml"
        declared = set(re.findall(r'"([\w.]+)"', m.group(1)))
        pkg_root = os.path.join(REPO, "torchbeast_tpu")
        on_disk = {"torchbeast_tpu"}
        for entry in sorted(os.listdir(pkg_root)):
            full = os.path.join(pkg_root, entry)
            if os.path.isdir(full) and os.path.isfile(
                os.path.join(full, "__init__.py")
            ):
                on_disk.add(f"torchbeast_tpu.{entry}")
        assert declared == on_disk, (
            f"pyproject packages drift: missing {on_disk - declared}, "
            f"stale {declared - on_disk}"
        )

    def test_cli_exits_nonzero_on_findings(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(
            "# beastlint: hot\n"
            "def act(env):\n"
            "    return env.item()\n"
        )
        proc = subprocess.run(
            [sys.executable, "-m", "torchbeast_tpu.analysis",
             str(bad), "--json"],
            capture_output=True, text=True, cwd=REPO, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        assert report["findings"][0]["rule"] == "HOTPATH-SYNC"


# ---------------------------------------------------------------------------
# Sanitizer wiring (slow: compiles C++)


@pytest.mark.slow
class TestSanitizerWiring:
    @pytest.fixture(autouse=True)
    def _need_toolchain(self):
        if shutil.which("g++") is None:
            pytest.skip("no g++ toolchain")

    def _run_sanitized(self, sanitizer):
        proc = subprocess.run(
            ["bash", "scripts/build_native.sh",
             f"--sanitize={sanitizer}", "--filter=wire"],
            capture_output=True, text=True, cwd=REPO, timeout=300,
        )
        if proc.returncode != 0 and (
            "cannot find" in proc.stderr
            or "unrecognized" in proc.stderr
            or "Shadow memory" in proc.stderr
        ):
            pytest.skip(
                f"{sanitizer} sanitizer unavailable in this toolchain/"
                f"sandbox: {proc.stderr.strip().splitlines()[-1]}"
            )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "FILTERED NATIVE CORE TESTS PASSED" in proc.stdout

    def test_asan_wire_smoke(self):
        self._run_sanitized("address")

    def test_ubsan_wire_smoke(self):
        self._run_sanitized("undefined")

    def _run_sanitized_filter(self, sanitizer, filt):
        proc = subprocess.run(
            ["bash", "scripts/build_native.sh",
             f"--sanitize={sanitizer}", f"--filter={filt}"],
            capture_output=True, text=True, cwd=REPO, timeout=600,
        )
        if proc.returncode != 0 and (
            "cannot find" in proc.stderr
            or "unrecognized" in proc.stderr
            or "Shadow memory" in proc.stderr
            or "unsupported" in proc.stderr.lower()
        ):
            pytest.skip(
                f"{sanitizer} sanitizer unavailable in this toolchain/"
                f"sandbox: {proc.stderr.strip().splitlines()[-1]}"
            )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "FILTERED NATIVE CORE TESTS PASSED" in proc.stdout
        # TSan reports land on stderr; rc is already non-zero when any
        # race fires, but pin the absence explicitly so a future
        # `halt_on_error=0` env can't mask one.
        assert "ThreadSanitizer" not in proc.stderr, proc.stderr

    def test_tsan_queue_suites(self):
        """ISSUE 7 satellite: the C++ BatchingQueue suites (incl. the
        multi-producer stress test) run clean under ThreadSanitizer."""
        self._run_sanitized_filter("thread", "queue")

    def test_tsan_batcher_suites(self):
        """The batching/dynamic-batcher suites under TSan. Regression
        for the csrc/queues.h timed wait: a steady_clock wait_until
        lowers to pthread_cond_clockwait, which this toolchain's TSan
        does not intercept — the old code produced ~90 bogus
        double-lock/race reports on this exact suite."""
        self._run_sanitized_filter("thread", "atch")

"""Telemetry core (ISSUE 2): histogram bucket/merge/percentile
properties, per-thread shard merge under concurrent writers, snapshot
delta correctness, span lifecycle, exporters — and the transfer-guard
test pinning that instrumentation adds ZERO device syncs on the acting
hot path. All CPU-backend tier-1."""

import http.client
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from torchbeast_tpu import telemetry
from torchbeast_tpu.telemetry import export as export_mod
from torchbeast_tpu.telemetry import trace as trace_mod
from torchbeast_tpu.telemetry.metrics import (
    BUCKET_GROWTH,
    MetricsRegistry,
    bucket_bounds,
    bucket_index,
    bucket_representative,
)
from torchbeast_tpu.telemetry.trace import Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestHistogram:
    def test_bucket_geometry(self):
        # Every positive value lands in the bucket whose (lower, upper]
        # bounds contain it, and the representative is within one
        # half-bucket (~9% relative) of the value.
        for v in (1e-8, 1e-3, 0.5, 1.0, 7.3, 1234.5):
            i = bucket_index(v)
            lower, upper = bucket_bounds(i)
            assert lower < v <= upper, (v, i, lower, upper)
            rep = bucket_representative(i)
            assert abs(rep - v) / v <= (BUCKET_GROWTH - 1), (v, rep)
        # Underflow bucket: zero and negatives.
        assert bucket_index(0.0) == 0
        assert bucket_index(-5.0) == 0
        assert bucket_representative(0) == 0.0

    def test_moments_exact_and_percentiles_bounded(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        values = [i / 1000.0 for i in range(1, 1001)]  # 1ms..1s
        for v in values:
            h.observe(v)
        assert h.count == 1000
        assert h.mean == pytest.approx(np.mean(values))
        assert h.std == pytest.approx(np.std(values), rel=1e-9)
        for q in (0.5, 0.95, 0.99):
            true = float(np.quantile(values, q))
            est = h.percentile(q)
            assert abs(est - true) / true < 0.10, (q, est, true)

    def test_stats_bucket_sum_matches_count(self):
        reg = MetricsRegistry()
        h = reg.histogram("x")
        for v in (0.0, 1e-12, 0.001, 0.001, 5.0):
            h.observe(v)
        stats = h.stats()
        assert sum(stats["buckets"].values()) == stats["count"] == 5
        assert stats["min"] == 0.0 and stats["max"] == 5.0

    def test_concurrent_writers_merge(self):
        """Per-thread shard merge: N threads hammer one histogram; the
        merged moments/buckets account for every sample."""
        reg = MetricsRegistry()
        h = reg.histogram("concurrent")
        N, K = 8, 5000
        barrier = threading.Barrier(N)

        def writer(seed):
            barrier.wait()
            for i in range(K):
                h.observe((seed + 1) * 0.001 + i * 1e-7)

        threads = [
            threading.Thread(target=writer, args=(t,)) for t in range(N)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert h.count == N * K
        stats = h.stats()
        assert sum(stats["buckets"].values()) == N * K

    def test_dead_thread_shards_fold_into_retired(self):
        """Short-lived writer threads (env-server connection churn)
        must not grow the shard list forever: registration folds dead
        threads' shards into a retired aggregate, losing nothing."""
        reg = MetricsRegistry()
        h = reg.histogram("churn")
        c = reg.counter("churn_count")

        def one_shot(i):
            h.observe(0.001 * (i + 1))
            c.inc(2)

        for wave in range(5):
            threads = [
                threading.Thread(target=one_shot, args=(i,))
                for i in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
        # Trigger compaction from a fresh (live) writer.
        h.observe(1.0)
        c.inc(1)
        assert h.num_shards() <= 9  # bounded by live threads, not 40
        assert c.num_shards() <= 9
        assert h.count == 41
        assert c.value() == 81.0
        assert h.stats()["max"] == 1.0
        assert h.stats()["min"] == pytest.approx(0.001)

    def test_counter_concurrent_shards(self):
        reg = MetricsRegistry()
        c = reg.counter("hits")
        N, K = 8, 20000
        barrier = threading.Barrier(N)

        def writer():
            barrier.wait()
            for _ in range(K):
                c.inc()

        threads = [threading.Thread(target=writer) for _ in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        # Exact despite no hot-path lock: each thread owns its shard
        # (registration may already have folded early-finishing
        # threads' shards into the retired total, so the live-shard
        # count is only bounded above).
        assert c.value() == N * K
        assert 1 <= c.num_shards() <= N

    def test_type_collision_rejected(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")


class TestSnapshotDeltaMerge:
    def test_delta_counters_and_gauges(self):
        reg = MetricsRegistry()
        reg.counter("c").inc(10)
        reg.gauge("g").set(1.0)
        snap0 = telemetry.snapshot(reg)
        reg.counter("c").inc(7)
        reg.gauge("g").set(4.0)
        reg.counter("new").inc(2)  # appears only after snap0
        snap1 = telemetry.snapshot(reg)
        d = telemetry.delta(snap1, snap0)
        assert d["counters"]["c"] == 7.0
        assert d["counters"]["new"] == 2.0
        assert d["gauges"]["g"] == 4.0  # gauges: current value
        assert d["interval_s"] >= 0.0
        assert telemetry.validate_snapshot(d) == []

    def test_delta_histogram_is_interval_only(self):
        """The delta's percentiles reflect ONLY the interval's samples
        (the whole point: attribute a slow window, not the whole run)."""
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        for _ in range(1000):
            h.observe(0.001)  # old regime: 1ms
        snap0 = telemetry.snapshot(reg)
        for _ in range(100):
            h.observe(1.0)  # new regime: 1s
        snap1 = telemetry.snapshot(reg)
        d = telemetry.delta(snap1, snap0)["histograms"]["lat"]
        assert d["count"] == 100
        assert sum(d["buckets"].values()) == 100
        # Interval p50 is ~1s; the cumulative p50 would be ~1ms.
        assert 0.9 <= d["p50"] <= 1.1
        assert d["mean"] == pytest.approx(1.0)
        cumulative = snap1["histograms"]["lat"]
        assert cumulative["p50"] <= 0.0011

    def test_merge_inverts_delta(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        for v in (0.01, 0.02, 0.03):
            h.observe(v)
        snap0 = telemetry.snapshot(reg)
        for v in (0.5, 0.6):
            h.observe(v)
        snap1 = telemetry.snapshot(reg)
        d = telemetry.delta(snap1, snap0)
        back = telemetry.merge_snapshots(snap0, d)
        hb = back["histograms"]["lat"]
        h1 = snap1["histograms"]["lat"]
        assert hb["count"] == h1["count"] == 5
        assert hb["buckets"] == h1["buckets"]
        assert hb["total"] == pytest.approx(h1["total"])
        assert back["counters"] == snap1["counters"]

    def test_merge_one_sided_histogram_keeps_extremes(self):
        """Regression: merging snapshots where a histogram exists in
        only ONE side must not absorb the empty side's 0.0 min/max
        placeholders."""
        ra, rb = MetricsRegistry(), MetricsRegistry()
        ra.histogram("only_a").observe(5.0)
        rb.histogram("only_b").observe(-2.0)
        merged = telemetry.merge_snapshots(
            telemetry.snapshot(ra), telemetry.snapshot(rb)
        )
        assert merged["histograms"]["only_a"]["min"] == 5.0
        assert merged["histograms"]["only_b"]["max"] == -2.0
        assert telemetry.validate_snapshot(merged) == []

    def test_merge_unions_gauges(self):
        """Regression: merge is a union — gauges present only in the
        second snapshot (another process's registry, e.g. an env
        server's) must survive; first argument wins on collision."""
        ra, rb = MetricsRegistry(), MetricsRegistry()
        ra.gauge("shared").set(1.0)
        ra.gauge("only_a").set(2.0)
        rb.gauge("shared").set(9.0)
        rb.gauge("only_b").set(3.0)
        merged = telemetry.merge_snapshots(
            telemetry.snapshot(ra), telemetry.snapshot(rb)
        )
        assert merged["gauges"] == {
            "shared": 1.0, "only_a": 2.0, "only_b": 3.0,
        }

    def test_validate_catches_drift(self):
        snap = telemetry.snapshot(MetricsRegistry())
        assert telemetry.validate_snapshot(snap) == []
        bad = dict(snap)
        bad.pop("histograms")
        assert any(
            "histograms" in p for p in telemetry.validate_snapshot(bad)
        )
        bad2 = json.loads(json.dumps(snap))
        bad2["histograms"]["h"] = {"count": 3, "buckets": {"1": 1}}
        probs = telemetry.validate_snapshot(bad2)
        assert any("missing" in p for p in probs)
        assert any("bucket sum" in p for p in probs)


class TestSpans:
    def test_nested_spans(self):
        tr = Tracer()
        with tr.span("outer", cat="test"):
            with tr.span("inner", cat="test"):
                pass
        events = tr.events()
        by_name = {e["name"]: e for e in events}
        assert set(by_name) == {"outer", "inner"}
        outer, inner = by_name["outer"], by_name["inner"]
        # Chrome "X" nesting by containment: inner within outer.
        assert outer["ts"] <= inner["ts"]
        assert (
            inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
        )
        assert outer["ph"] == "X" and inner["ph"] == "X"

    def test_orphaned_span_tracked_not_exported(self, tmp_path):
        tr = Tracer()
        token = tr.begin("never_ends")
        assert tr.open_count() == 1
        done = tr.begin("ends")
        assert tr.end(done) is True
        assert tr.open_count() == 1
        path = str(tmp_path / "trace.json")
        n = tr.export_chrome(path)
        doc = json.loads(open(path).read())
        names = {e["name"] for e in doc["traceEvents"]}
        assert "ends" in names and "never_ends" not in names
        assert n == len(doc["traceEvents"])
        assert doc["otherData"]["open_spans_dropped"] == 1
        # Late end still works and clears the orphan; double-end no-ops.
        assert tr.end(token) is True
        assert tr.end(token) is False
        assert tr.open_count() == 0

    def test_stage_trace_emits_per_stage_spans(self):
        tr = Tracer()
        st = tr.stage("req", actor=3)
        st.stamp("enqueue")
        st.stamp("batch")
        st.stamp("reply")
        st.finish()
        st.finish()  # idempotent
        names = [e["name"] for e in tr.events()]
        assert names == [
            "req.enqueue", "req.batch", "req.reply", "req",
        ]
        total = next(e for e in tr.events() if e["name"] == "req")
        parts = [e for e in tr.events() if e["name"] != "req"]
        assert total["dur"] == pytest.approx(
            sum(p["dur"] for p in parts), abs=1.0
        )
        assert all(e["args"] == {"actor": 3} for e in tr.events())

    def test_ring_buffer_bounded(self):
        tr = Tracer(max_events=10)
        for i in range(100):
            tr.add_complete(f"e{i}", "t", 0.0, 1.0)
        events = tr.events()
        assert len(events) == 10
        assert events[0]["name"] == "e90"  # oldest dropped


class TestSpanThreadCpu:
    """Every span observes `<name>_cpu_s`, the calling thread's own
    CPU clock, beside `<name>_s` (ISSUE 36): read in one pass of
    CPU_SAMPLE_EVERY, credited that many times over."""

    EVERY = trace_mod.CPU_SAMPLE_EVERY

    @staticmethod
    def _span(name):
        reg = MetricsRegistry()
        tr = Tracer(registry=reg, record=False)
        return tr.span(name), reg

    def test_a_sleep_costs_wall_and_no_cpu(self):
        span, reg = self._span("stage.sleep")
        for _ in range(self.EVERY):
            with span:
                time.sleep(0.02)
        wall = reg.histogram("stage.sleep_s").merged()
        cpu = reg.histogram("stage.sleep_cpu_s").merged()
        assert wall.count == cpu.count == self.EVERY
        assert 0.02 * self.EVERY <= wall.total < 0.5 * self.EVERY
        assert cpu.total < 0.005 * self.EVERY

    def test_a_spin_costs_as_much_cpu_as_wall(self):
        """The pass whose CPU is read spins until the thread's own
        clock has moved 20 ms: the span reads those 20 ms, however long
        a loaded host made the pass last, and never more than its
        wall time; on a host with a core to spare the two agree."""
        span, reg = self._span("stage.spin")
        wall_h = reg.histogram("stage.spin_s")
        cpu_h = reg.histogram("stage.spin_cpu_s")
        ratios = []
        for _ in range(3 * self.EVERY):
            wall0, cpu0 = wall_h.merged().total, cpu_h.merged()
            with span:
                end = time.thread_time() + 0.02
                while time.thread_time() < end:
                    pass
            cpu1 = cpu_h.merged()
            if cpu1.count == cpu0.count:
                continue  # not a pass that reads the clock
            assert cpu1.count == cpu0.count + self.EVERY
            read = (cpu1.total - cpu0.total) / self.EVERY
            wall = wall_h.merged().total - wall0
            assert 0.02 <= read < 0.03 and read <= wall + 1e-3
            ratios.append(read / wall)
        assert len(ratios) == 3
        # Five other test workers may share this host's cores: only
        # with one to spare does a spin's wall time equal its CPU time.
        if os.getloadavg()[0] < len(os.sched_getaffinity(0)) - 1:
            assert max(ratios) >= 0.8, ratios

    def test_one_pass_in_sixteen_is_read_and_stands_for_sixteen(self):
        span, reg = self._span("stage.pass")
        for _ in range(3 * self.EVERY):
            with span:
                pass
        wall = reg.histogram("stage.pass_s").merged()
        cpu = reg.histogram("stage.pass_cpu_s").merged()
        assert wall.count == cpu.count == 3 * self.EVERY
        assert all(n % self.EVERY == 0 for n in cpu.buckets.values())
        assert cpu.min <= cpu.total / cpu.count <= cpu.max < 0.01
        # The span counts its passes, whichever thread makes them: a
        # native actor thread's Python state lasts one slot hook, so
        # here every pass is a new thread's first.
        def one_pass():
            with span:
                pass

        for _ in range(self.EVERY):
            other = threading.Thread(target=one_pass)
            other.start()
            other.join()
        assert reg.histogram("stage.pass_cpu_s").count == 4 * self.EVERY

    def test_a_nested_span_and_its_parent_each_read_their_own_clock(self):
        reg = MetricsRegistry()
        tr = Tracer(registry=reg, record=False)
        outer, inner = tr.span("stage.outer"), tr.span("stage.inner")
        # Made one after the other, they read the clock on different
        # passes: the parent's reading holds no child's clock reads.
        assert outer._passes != inner._passes
        for _ in range(self.EVERY):
            with outer:
                with inner:
                    time.sleep(0.01)
                end = time.thread_time() + 0.01
                while time.thread_time() < end:
                    pass
        outer_cpu = reg.histogram("stage.outer_cpu_s").merged()
        inner_cpu = reg.histogram("stage.inner_cpu_s").merged()
        assert outer_cpu.count == inner_cpu.count == self.EVERY
        assert inner_cpu.total / self.EVERY < 0.005
        assert 0.01 <= outer_cpu.total / self.EVERY < 0.02
        assert reg.histogram("stage.outer_s").merged().total >= (
            0.02 * self.EVERY
        )

    def test_a_disabled_tracer_observes_neither(self):
        tr = telemetry.get_tracer()
        span = tr.span("gate_test.cpu_span")
        reg = telemetry.get_registry()
        wall = reg.histogram("gate_test.cpu_span_s")
        cpu = reg.histogram("gate_test.cpu_span_cpu_s")
        telemetry.set_enabled(False)
        try:
            for _ in range(self.EVERY):
                with span:
                    pass
            assert (wall.count, cpu.count) == (0, 0)
        finally:
            telemetry.set_enabled(True)
        for _ in range(self.EVERY):
            with span:
                pass
        assert (wall.count, cpu.count) == (self.EVERY, self.EVERY)

    def test_a_given_histogram_keeps_its_name_and_gains_the_cpu_one(self):
        """utils/prof.Timings hands its sections' older names in."""
        reg = MetricsRegistry()
        tr = Tracer(registry=reg, record=False)
        span = tr.span(
            "learner.learn", histogram=reg.histogram("learner.learn")
        )
        for _ in range(self.EVERY):
            with span:
                pass
        assert reg.histogram("learner.learn").count == self.EVERY
        assert reg.histogram("learner.learn_cpu_s").count == self.EVERY
        assert "learner.learn_s" not in reg.instruments()
        # A tracer without a registry observes nothing of its own.
        bare = Tracer(record=False).span("x.y")
        assert bare.histogram is None and bare.cpu_histogram is None
        with bare:
            pass


# /proc as a thread ledger reads it, recorded: six tasks of process
# 4242 (clock ticks of 10 ms; schedstat: on-CPU ns, run-queue ns,
# timeslices) and an env-server listener 500 with one forked child.
_STAT = (
    "{tid} ({comm}) S 1 4242 4242 0 -1 4194560 1 0 0 0 {utime} {stime} "
    "0 0 20 0 6 0 100 1000000 100 18446744073709551615 0 0 0 0 0 0 0 0 "
    "0 0 0 0 17 3 0 0 0 0 0 0 0 0 0 0 0 0 0\n"
)
# tid: (python name or None, comm, utime, stime, on_cpu_ns, wait_ns)
_TASKS_T0 = {
    4242: ("MainThread", "python3", 150, 50, 2_000_000_000, 40_000_000),
    4250: ("inference-0", "python3", 300, 100, 4_000_000_000, 900_000_000),
    4251: ("inference-0-replier", "python3", 200, 50, 2_500_000_000,
           600_000_000),
    4252: ("learner", "python3", 90, 10, 1_000_000_000, 10_000_000),
    4260: (None, "tbt-actor", 40, 20, 600_000_000, 300_000_000),
    4270: (None, "tf_XLATfrtCpuCl", 10, 0, 100_000_000, 5_000_000),
}
# 2.5 s later: the launcher ran 1.5 s and waited 0.5 s for a core, the
# replier 0.75 / 0.25, the actor 0.1 / 0.2; the learner thread is gone
# and tid 4280 (a new prefetch thread, 0.3 s of CPU) has appeared.
_TASKS_T1 = {
    4242: ("MainThread", "python3", 160, 50, 2_100_000_000, 45_000_000),
    4250: ("inference-0", "python3", 420, 130, 5_500_000_000,
           1_400_000_000),
    4251: ("inference-0-replier", "python3", 260, 65, 3_250_000_000,
           850_000_000),
    4260: (None, "tbt-actor", 45, 25, 700_000_000, 500_000_000),
    4270: (None, "tf_XLATfrtCpuCl", 10, 0, 100_000_000, 5_000_000),
    4280: ("device-prefetch", "python3", 25, 5, 300_000_000, 1_000_000),
}


class _FakeThread:
    def __init__(self, native_id, name):
        self.native_id, self.name = native_id, name


def _write_proc(root, pid, tasks, schedstat=True, children=None):
    task_dir = root / str(pid) / "task"
    if task_dir.exists():
        import shutil

        shutil.rmtree(task_dir)
    for tid, (_, comm, utime, stime, on_cpu, waited) in tasks.items():
        d = task_dir / str(tid)
        d.mkdir(parents=True)
        text = _STAT.format(tid=tid, comm=comm, utime=utime, stime=stime)
        (d / "stat").write_text(text)
        (d / "comm").write_text(comm + "\n")
        if schedstat:
            (d / "schedstat").write_text(f"{on_cpu} {waited} 1234\n")
        if children is not None:
            listed = children.get(tid, [])
            (d / "children").write_text(" ".join(map(str, listed)))
    (root / str(pid) / "stat").write_text(
        _STAT.format(tid=pid, comm="python3", utime=0, stime=0)
    )


def _threads_of(tasks):
    return lambda: [
        _FakeThread(tid, name) for tid, (name, *_) in tasks.items()
        if name is not None
    ]


class TestThreadLedger:
    """telemetry/heartbeat.py ThreadLedger on recorded /proc text."""

    def _counters(self, reg):
        return {
            name: inst.value() for name, inst in reg.instruments().items()
        }

    def test_roles(self):
        from torchbeast_tpu.telemetry.heartbeat import thread_role

        assert thread_role("inference-0", "python3") == "launcher"
        assert thread_role("inference-0-replier", "python3") == "replier"
        assert thread_role("inference.slice.1-0-replier", "x") == "replier"
        assert thread_role("learner", "python3") == "learner"
        assert thread_role("learner-watchdog", "python3") == "python_other"
        assert thread_role("device-prefetch", "python3") == "prefetch"
        assert thread_role("MainThread", "python3") == "python_other"
        assert thread_role("telemetry-heartbeat", "x") == "python_other"
        assert thread_role(None, "tbt-actor") == "actors"
        assert thread_role(None, "tf_XLATfrtCpuCl") == "native_other"

    def test_deltas_by_role_between_two_samples(self, tmp_path):
        from torchbeast_tpu.telemetry.heartbeat import ThreadLedger

        state = {"tasks": _TASKS_T0}
        _write_proc(tmp_path, 4242, _TASKS_T0, children={})
        reg = MetricsRegistry()
        ledger = ThreadLedger(
            reg, proc_root=str(tmp_path), pid=4242,
            threads=lambda: _threads_of(state["tasks"])(),
        )
        assert ledger.has_schedstat
        ledger.fold()
        first = self._counters(reg)
        # The first fold credits all a task has used since it began.
        assert first["host.cpu_s.launcher"] == pytest.approx(4.0)
        assert first["host.cpu_s.learner"] == pytest.approx(1.0)
        assert first["host.cpu_s.prefetch"] == 0.0
        assert first["host.run_delay_s.replier"] == pytest.approx(0.6)

        state["tasks"] = _TASKS_T1
        _write_proc(tmp_path, 4242, _TASKS_T1, children={})
        ledger.fold()
        now = self._counters(reg)
        grew = {k: now[k] - first[k] for k in now}
        want = {
            "host.cpu_s.launcher": 1.5, "host.run_delay_s.launcher": 0.5,
            "host.cpu_s.replier": 0.75, "host.run_delay_s.replier": 0.25,
            "host.cpu_s.actors": 0.1, "host.run_delay_s.actors": 0.2,
            "host.cpu_s.python_other": 0.1,
            "host.run_delay_s.python_other": 0.005,
            # Idle between the samples.
            "host.cpu_s.native_other": 0.0,
            "host.run_delay_s.native_other": 0.0,
            # New since the last fold: all it has used.
            "host.cpu_s.prefetch": 0.3,
            "host.run_delay_s.prefetch": 0.001,
            # Gone between the samples: nothing more, and no error.
            "host.cpu_s.learner": 0.0, "host.run_delay_s.learner": 0.0,
        }
        assert set(grew) == set(want)
        for name, value in want.items():
            assert grew[name] == pytest.approx(value, abs=1e-9), name

    def test_a_fold_too_soon_after_another_is_skipped(self, tmp_path):
        from torchbeast_tpu.telemetry.heartbeat import ThreadLedger

        _write_proc(tmp_path, 4242, _TASKS_T0, children={})
        reg = MetricsRegistry()
        ledger = ThreadLedger(
            reg, proc_root=str(tmp_path), pid=4242,
            threads=_threads_of(_TASKS_T1),
        )
        ledger.fold()
        _write_proc(tmp_path, 4242, _TASKS_T1, children={})
        ledger.fold(min_interval_s=60.0)  # DriverTelemetry.write's way
        assert reg.counter("host.cpu_s.launcher").value() == (
            pytest.approx(4.0)
        )
        ledger.fold()  # the native folder's: always
        assert reg.counter("host.cpu_s.launcher").value() == (
            pytest.approx(5.5)
        )

    def test_a_kernel_without_schedstat(self, tmp_path):
        """CPU comes from stat's utime + stime, in clock ticks; the
        run-delay counters are not registered at all."""
        from torchbeast_tpu.telemetry.heartbeat import ThreadLedger

        tick = 1.0 / os.sysconf("SC_CLK_TCK")
        state = {"tasks": _TASKS_T0}
        _write_proc(tmp_path, 4242, _TASKS_T0, schedstat=False, children={})
        reg = MetricsRegistry()
        ledger = ThreadLedger(
            reg, proc_root=str(tmp_path), pid=4242,
            threads=lambda: _threads_of(state["tasks"])(),
        )
        assert not ledger.has_schedstat
        ledger.watch(lambda: [])
        ledger.fold()
        state["tasks"] = _TASKS_T1
        _write_proc(tmp_path, 4242, _TASKS_T1, schedstat=False, children={})
        ledger.fold()
        counters = self._counters(reg)
        assert not [k for k in counters if "run_delay" in k]
        assert counters["host.cpu_s.launcher"] == pytest.approx(550 * tick)
        assert counters["host.cpu_s.replier"] == pytest.approx(325 * tick)
        assert counters["host.cpu_s.actors"] == pytest.approx(70 * tick)
        assert counters["host.cpu_s.native_other"] == pytest.approx(
            10 * tick
        )
        assert counters["host.cpu_s.env_servers"] == 0.0

    @pytest.mark.parametrize("children_file", [True, False])
    def test_watched_processes_and_what_they_forked(
        self, tmp_path, children_file
    ):
        """The env servers: a listener (500) and the stream child it
        forked (501, which has a thread of its own), found through
        task/<tid>/children or, on a kernel without it, by ppid."""
        from torchbeast_tpu.telemetry.heartbeat import ThreadLedger

        def proc(pid, ppid, tasks, children):
            _write_proc(
                tmp_path, pid, tasks,
                children=children if children_file else None,
            )
            stat = _STAT.format(tid=pid, comm="python3", utime=0, stime=0)
            (tmp_path / str(pid) / "stat").write_text(
                stat.replace(" S 1 ", f" S {ppid} ", 1)
            )

        me = {4242: ("MainThread", "python3", 0, 0, 0, 0)}
        idle = ("x", "python3", 1, 0, 10_000_000, 1_000_000)
        proc(4242, 1, me, {})
        proc(500, 4242, {500: idle}, {500: [501]})
        proc(501, 500, {
            501: ("x", "python3", 100, 20, 1_200_000_000, 400_000_000),
            502: ("x", "python3", 5, 0, 50_000_000, 0),
        }, {})
        # Not the supervisor's, nobody's child here: never counted.
        proc(900, 1, {900: ("x", "python3", 999, 0, 9_000_000_000, 0)}, {})
        reg = MetricsRegistry()
        ledger = ThreadLedger(
            reg, proc_root=str(tmp_path), pid=4242, threads=_threads_of(me),
        )
        ledger.watch(lambda: [500, None, 777])  # unstarted, and gone
        ledger.fold()
        assert reg.counter("host.cpu_s.env_servers").value() == (
            pytest.approx(1.26)
        )
        assert reg.counter("host.run_delay_s.env_servers").value() == (
            pytest.approx(0.401)
        )
        # The child ends; the listener goes on.
        import shutil

        shutil.rmtree(tmp_path / "501")
        proc(500, 4242, {500: ("x", "python3", 2, 0, 20_000_000, 1_000_000)},
             {500: []})
        ledger.fold()
        assert reg.counter("host.cpu_s.env_servers").value() == (
            pytest.approx(1.27)
        )

    def test_on_this_process(self):
        """The live /proc: a thread that spins is credited with about
        what it spun, under the role its name gives it."""
        from torchbeast_tpu.telemetry.heartbeat import ThreadLedger

        reg = MetricsRegistry()
        ledger = ThreadLedger(reg)
        stop = threading.Event()

        def spin():
            while not stop.is_set():
                pass

        thread = threading.Thread(target=spin, name="learner", daemon=True)
        thread.start()
        try:
            ledger.fold()
            before = reg.counter("host.cpu_s.learner").value()
            used, began = time.thread_time(), time.monotonic()
            time.sleep(0.3)
            ledger.fold()
            lasted = time.monotonic() - began
        finally:
            stop.set()
            thread.join()
        grew = reg.counter("host.cpu_s.learner").value() - before
        # It ran whenever this thread slept (and shared the GIL with
        # nobody else): a loaded host may have kept it off a core, or
        # this thread asleep for longer than it asked.
        assert 0.02 <= grew <= lasted + 0.05
        assert time.thread_time() - used < 0.1
        assert reg.counter("host.cpu_s.python_other").value() > 0.0


    def test_a_native_thread_that_ran_python_keeps_its_native_role(self):
        """A foreign thread that has run Python code (an actor thread inside
        a slot hook) stands in threading.enumerate() as a _DummyThread; the
        ledger goes by its `comm` all the same."""
        import ctypes

        from torchbeast_tpu.telemetry.heartbeat import ThreadLedger
        seen = {}
        release = threading.Event()

        @ctypes.CFUNCTYPE(ctypes.c_void_p, ctypes.c_void_p)
        def body(_):
            # Python code on a thread Python did not start.
            seen["thread"] = threading.current_thread()
            end = time.thread_time() + 0.05
            while time.thread_time() < end:
                pass
            seen["spun"] = True
            release.wait(10)
            return None

        libc = ctypes.CDLL(None, use_errno=True)
        libc.pthread_create.argtypes = [
            ctypes.POINTER(ctypes.c_ulong), ctypes.c_void_p, type(body),
            ctypes.c_void_p,
        ]
        libc.pthread_create.restype = ctypes.c_int
        libc.pthread_setname_np.argtypes = [ctypes.c_ulong, ctypes.c_char_p]
        libc.pthread_setname_np.restype = ctypes.c_int
        libc.pthread_join.argtypes = [ctypes.c_ulong, ctypes.c_void_p]
        libc.pthread_join.restype = ctypes.c_int
        handle = ctypes.c_ulong()
        assert libc.pthread_create(
            ctypes.byref(handle), None, body, None
        ) == 0
        try:
            assert libc.pthread_setname_np(handle, b"tbt-actor") == 0
            deadline = time.monotonic() + 10
            while "spun" not in seen and time.monotonic() < deadline:
                time.sleep(0.01)
            assert isinstance(seen["thread"], threading._DummyThread)
            assert seen["thread"] in threading.enumerate()
            registry = MetricsRegistry()
            ThreadLedger(registry).fold()
            assert registry.counter("host.cpu_s.actors").value() >= 0.04
        finally:
            release.set()
            libc.pthread_join(handle, None)


class TestEnabledGate:
    def test_disabled_global_instruments_noop(self):
        reg = telemetry.get_registry()
        c = reg.counter("gate_test.count")
        h = reg.histogram("gate_test.lat")
        tr = telemetry.get_tracer()
        before_c, before_h = c.value(), h.count
        before_e = len(tr.events())
        telemetry.set_enabled(False)
        try:
            c.inc(5)
            h.observe(1.0)
            with tr.span("gate_test.span"):
                pass
            assert tr.stage("gate_test.req") is None
            assert c.value() == before_c
            assert h.count == before_h
            assert len(tr.events()) == before_e
            # Private registries ignore the gate (Timings contract).
            private = MetricsRegistry()
            private.counter("x").inc()
            assert private.counter("x").value() == 1.0
        finally:
            telemetry.set_enabled(True)
        c.inc(1)
        assert c.value() == before_c + 1


class TestExporters:
    def test_jsonl_exporter(self, tmp_path):
        path = str(tmp_path / "telemetry.jsonl")
        reg = MetricsRegistry()
        reg.counter("c").inc(2)
        exporter = telemetry.JsonLinesExporter(
            path, registry=reg, static={"driver": "test"}
        )
        exporter.write(extra={"step": 1})
        reg.counter("c").inc(1)
        exporter.write(extra={"step": 2})
        lines = telemetry.read_jsonl(path)
        assert len(lines) == 2
        assert [ln["step"] for ln in lines] == [1, 2]
        assert all(ln["driver"] == "test" for ln in lines)
        assert lines[1]["counters"]["c"] == 3.0
        assert all(telemetry.validate_snapshot(ln) == [] for ln in lines)

    def test_read_jsonl_skips_torn_lines(self, tmp_path):
        path = tmp_path / "t.jsonl"
        path.write_text('{"a": 1}\n{"torn...\n{"b": 2}\n')
        assert telemetry.read_jsonl(str(path)) == [{"a": 1}, {"b": 2}]
        assert telemetry.read_jsonl(str(tmp_path / "missing")) == []

    def test_prometheus_endpoint(self):
        reg = MetricsRegistry()
        reg.counter("wire.bytes_up").inc(42)
        reg.gauge("queue.depth").set(3)
        reg.histogram("lat_s").observe(0.25)
        server = telemetry.PrometheusServer(
            reg, port=0, host="127.0.0.1"
        ).start()
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", server.port, timeout=10
            )
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            assert resp.status == 200
            body = resp.read().decode()
            assert "# TYPE wire_bytes_up counter" in body
            assert "wire_bytes_up 42.0" in body
            assert "queue_depth 3.0" in body
            assert 'lat_s{quantile="0.5"}' in body
            assert "lat_s_count 1" in body
            conn.request("GET", "/nope")
            assert conn.getresponse().status == 404
        finally:
            server.stop()

    def test_telemetry_block_schema(self):
        """The shape every bench artifact embeds (tier-1 pin: schema
        drift in the shared constructor fails HERE, not at chip-measure
        time)."""
        reg = MetricsRegistry()
        reg.histogram("inference.batch_size").observe(8)
        prev = telemetry.snapshot(reg)
        reg.histogram("inference.batch_size").observe(16)
        block = export_mod.telemetry_block(prev=prev, registry=reg)
        assert set(block) == {"enabled", "snapshot"}
        assert isinstance(block["enabled"], bool)
        assert telemetry.validate_snapshot(block["snapshot"]) == []
        h = block["snapshot"]["histograms"]["inference.batch_size"]
        assert h["count"] == 1  # delta: only the post-prev observation

    def test_selftest_cli(self, tmp_path):
        proc = subprocess.run(
            [
                sys.executable, "-m", "torchbeast_tpu.telemetry",
                "--selftest", "--out", str(tmp_path / "t.jsonl"),
            ],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "PYTHONPATH": REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")},
            cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        verdict = json.loads(proc.stdout.strip().splitlines()[-1])
        assert verdict["ok"] is True
        assert all(verdict["checks"].values()), verdict["checks"]


class TestHotPathPurity:
    def test_telemetry_modules_import_no_jax_numpy(self):
        """The telemetry package must stay stdlib-only: a jax/numpy
        import would put device-touching code one refactor away from
        the acting hot path. The contract's single source of truth is
        beastlint's IMPORT-PURITY rule (analysis/config.py PURITY);
        this test just runs that rule over the real package, so the
        banned-module list can never drift from what CI enforces."""
        from torchbeast_tpu import analysis

        report = analysis.analyze_paths(
            ["torchbeast_tpu/telemetry"], root=REPO
        )
        purity = [
            f for f in report.findings if f.rule == "IMPORT-PURITY"
        ]
        assert not purity, [f.render() for f in purity]

    def test_instrumented_hot_path_zero_device_syncs(self):
        """Transfer-guard pin: a full instrumented acting unroll —
        DeviceStateTable steps (which now carry telemetry) plus every
        telemetry op the runtime uses around them — under
        jax.transfer_guard("disallow"). Any implicit transfer a metric/
        span might introduce would raise."""
        import jax
        import jax.numpy as jnp

        from torchbeast_tpu.runtime.inference import (
            pad_advance,
            pad_slots,
            pad_to,
        )
        from torchbeast_tpu.runtime.state_table import DeviceStateTable

        H = 4

        def act(ctx, env, state):
            new = state["h"] + 1.0
            return {"out": env["frame"] + state["h"]}, {"h": new}

        table = DeviceStateTable(
            {"h": jnp.zeros((1, 1, H))}, num_slots=4, act_fn=act,
            batch_dim=1,
        )
        env = pad_to(
            {"frame": np.ones((1, 2, H), np.float32)}, 4, batch_dim=1
        )
        slots = pad_slots(np.asarray([0, 1]), 4, table.trash_slot)
        advance = pad_advance(np.asarray([True, True]), 4)
        # Warm compiles outside the guard (compilation may transfer
        # constants; the guarded property is the per-step hot path).
        out = table.step(slots, advance, env)
        table.fetch(out, 2)
        table.read_slot(0)

        reg = telemetry.get_registry()
        tracer = telemetry.get_tracer()
        # All three sinks on, as in a driver run with --trace_path.
        tracer.set_annotation_factory(jax.profiler.TraceAnnotation)
        tracer.set_recording(True)
        hot_step = tracer.span("hot.step", cat="test")
        try:
            with jax.transfer_guard("disallow"):
                for _ in range(5):
                    with hot_step:
                        out = table.step(slots, advance, env)
                        fetched = table.fetch(out, 2)
                    reg.counter("hot.steps").inc()
                    reg.histogram("hot.lat_s").observe(0.001)
                    reg.gauge("hot.depth").set(1)
                    st = tracer.stage("hot.req")
                    st.stamp("reply")
                    st.finish()
                table.read_slot(0)
        finally:
            tracer.set_annotation_factory(None)
            tracer.set_recording(False)
            tracer.clear()
        assert np.asarray(fetched["out"]).shape == (1, 2, H)
        assert reg.counter("hot.steps").value() >= 5


class TestTimingsShim:
    def test_timings_feed_registry_histograms(self):
        """utils/prof.Timings is a shim over telemetry histograms: the
        same sections expose p50/p95 through the registry snapshot."""
        from torchbeast_tpu.utils import Timings

        reg = MetricsRegistry()
        t = Timings(registry=reg, prefix="driver.")
        for _ in range(20):
            with t.section("collect"):
                pass
            with t.section("learn"):
                pass
        assert set(t.means()) == {"collect", "learn"}  # unprefixed API
        snap = telemetry.snapshot(reg)
        assert {"driver.collect", "driver.learn"} <= set(
            snap["histograms"]
        )
        # beastlint: disable=TELEMETRY-SCHEMA  prof.Timings composes its series names at runtime (prefix + section) — the emitter is real but statically invisible
        h = snap["histograms"]["driver.collect"]
        assert h["count"] == 20
        assert h["p95"] >= h["p50"] >= 0.0
        assert t.histogram("collect").percentile(0.5) == h["p50"]

    def test_timings_private_registry_ignores_gate(self):
        from torchbeast_tpu.utils import Timings

        telemetry.set_enabled(False)
        try:
            t = Timings()  # private registry: --no_telemetry unaffected
            with t.section("a"):
                pass
            assert t.means()["a"] >= 0.0
            assert t.histogram("a").count == 1
        finally:
            telemetry.set_enabled(True)


class TestQueueInstrumentation:
    def test_batching_queue_series(self):
        from torchbeast_tpu.runtime.queues import BatchingQueue

        q = BatchingQueue(
            batch_dim=0, minimum_batch_size=1,
            telemetry_name="tq_test_queue",
        )
        q.enqueue({"x": np.ones((2, 3))})
        q.enqueue({"x": np.ones((1, 3))})
        reg = telemetry.get_registry()
        assert reg.gauge("tq_test_queue.depth").value() == 2.0
        assert reg.counter("tq_test_queue.items_in").value() >= 2.0
        batch, payloads = q.dequeue_many()
        assert reg.gauge("tq_test_queue.depth").value() == 0.0
        h = reg.histogram("tq_test_queue.batch_size")
        assert h.count >= 1
        assert h.percentile(0.5) == pytest.approx(3.0, rel=0.1)

    def test_dynamic_batcher_request_wait_and_traces(self):
        from torchbeast_tpu.runtime.queues import DynamicBatcher

        batcher = DynamicBatcher(
            batch_dim=1, minimum_batch_size=1, maximum_batch_size=4,
            timeout_ms=10, telemetry_name="tq_test_batcher",
        )
        # A private tracer: the process tracer keeps Chrome events
        # only once a driver was given --trace_path.
        tracer = Tracer()
        trace = tracer.stage("tq_test.request")

        def consumer():
            for batch in batcher:
                batch.set_outputs(
                    {"y": np.asarray(batch.get_inputs()["x"]) * 2}
                )

        t = threading.Thread(target=consumer, daemon=True)
        t.start()
        out = batcher.compute({"x": np.ones((1, 2))}, trace=trace)
        np.testing.assert_array_equal(out["y"], 2 * np.ones((1, 2)))
        batcher.close()
        t.join(timeout=10)
        reg = telemetry.get_registry()
        assert reg.histogram("tq_test_batcher.request_wait_s").count >= 1
        # The request trace was stamped through enqueue -> batch ->
        # reply and finished by the Batch.
        names = {e["name"] for e in tracer.events()}
        assert {
            "tq_test.request.enqueue",
            "tq_test.request.batch",
            "tq_test.request.reply",
        } <= names

"""The poly driver's process hygiene and its window rule."""

import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from perfbench.drivers import poly


def test_steady_rule():
    assert poly.steady([100, 105, 95, 102, 98], 0.1)
    assert not poly.steady([100, 105, 80, 102, 98], 0.1)
    assert not poly.steady([0, 0, 0], 0.1)


def test_per_second_uses_the_samples_own_clock():
    # Samples every 0.4 s of a counter rising by 1000/s: each rate is
    # exact although no sample falls on a whole second.
    samples = [(0.4 * i, 400.0 * i) for i in range(20)]
    rates = poly.per_second(samples, 1.0, 6.0)
    assert len(rates) == 5
    assert rates == pytest.approx([1000.0] * 5)


def test_per_second_sees_a_stall():
    samples = [(0.1 * i, 100.0 * min(i, 20)) for i in range(50)]
    rates = poly.per_second(samples, 0.0, 4.0)
    assert rates[0] == pytest.approx(1000.0)
    assert rates[-1] == 0.0


def test_hist_delta():
    before = {"count": 2, "total": 1.0, "buckets": {5: 2}}
    after = {"count": 5, "total": 4.0, "buckets": {5: 3, 7: 2}}
    assert poly._hist_delta(after, before) == {
        "count": 3, "total": 3.0, "buckets": {"5": 1, "7": 2},
    }


LEADER = textwrap.dedent("""
    import os, subprocess, sys, time
    sys.path.insert(0, {root!r})
    from perfbench.drivers import poly
    pgid = poly.lead_new_group()
    assert pgid == os.getpid()
    kids = [subprocess.Popen([sys.executable, "-c",
            "import signal,time; signal.signal(signal.SIGTERM, signal.SIG_IGN); time.sleep(600)"
            if i else "import time; time.sleep(600)"]) for i in range(2)]
    time.sleep(0.5)
    print("members", len(poly.group_members(pgid, (os.getpid(),))), flush=True)
    killed = poly.kill_group(pgid, grace_s=1.0)
    for k in kids:
        k.wait(timeout=10)
    print("killed", len(killed), "left",
          len(poly.group_members(pgid, (os.getpid(),))), flush=True)
""")


def test_kill_group_leaves_no_child_alive():
    """A leader of a new group with two children, one of which ignores
    SIGTERM: after kill_group neither is alive, and the leader is."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    proc = subprocess.run(
        [sys.executable, "-c", LEADER.format(root=root)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "members 2" in proc.stdout
    assert "killed 2 left 0" in proc.stdout


def test_a_tagged_process_of_an_earlier_run_is_found():
    env = dict(os.environ, **{poly.RUN_TAG: "12345"})
    child = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(600)"], env=env
    )
    try:
        deadline = time.monotonic() + 10
        while child.pid not in poly.tagged_processes():
            assert time.monotonic() < deadline
            time.sleep(0.05)
        assert child.pid not in poly.tagged_processes(exclude=(child.pid,))
    finally:
        child.send_signal(signal.SIGKILL)
        child.wait(timeout=10)
    assert child.pid not in poly.tagged_processes()


def test_run_refuses_to_start_beside_an_earlier_run(monkeypatch):
    monkeypatch.setattr(poly, "tagged_processes", lambda exclude=(): [4242])
    with pytest.raises(RuntimeError, match="earlier poly run are alive"):
        poly.run(None, 0, 1.0, False, [], None)


def test_shm_segments_lists_only_the_programs_rings(tmp_path, monkeypatch):
    monkeypatch.setattr(poly, "SHM_DIR", str(tmp_path))
    (tmp_path / "tbtring_1_2").write_text("")
    (tmp_path / "other").write_text("")
    assert poly.shm_segments() == {str(tmp_path / "tbtring_1_2")}

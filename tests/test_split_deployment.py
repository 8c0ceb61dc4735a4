"""Split deployment: the learner and the env-server group run as SEPARATE
OS process trees connected only by TCP — the cross-machine topology
(reference polybeast_env.py:61-77 launches the env group on its own
machine; polybeast_learner.py:436-444 is the learner that dials it;
BASELINE config 5's shape). The env group is launched through its REAL
CLI (`python -m torchbeast_tpu.polybeast_env`), the learner runs with
--no_start_servers, trains to completion, then RESUMES from its
checkpoint against the same still-running servers — the env group's
lifetime is fully decoupled from the learner's, which is the point of
the split."""

import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from torchbeast_tpu import polybeast

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NUM_SERVERS = 2


def _free_port_base(n: int) -> int:
    """A base port with n consecutive free TCP ports (best-effort: bind
    them all, then release — the env CLI rebinds right after)."""
    for _ in range(50):
        socks = []
        try:
            s0 = socket.socket()
            s0.bind(("127.0.0.1", 0))
            base = s0.getsockname()[1]
            socks.append(s0)
            if base + n >= 65535:
                continue
            for i in range(1, n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("could not find a free port range")


def _wait_ports(ports, want_open, timeout_s=60.0):
    """Until every port matches `want_open` (True = accepting, False =
    closed), or timeout. Returns True on success."""
    deadline = time.monotonic() + timeout_s
    remaining = set(ports)
    while remaining and time.monotonic() < deadline:
        for p in list(remaining):
            with socket.socket() as s:
                s.settimeout(0.5)
                try:
                    s.connect(("127.0.0.1", p))
                    is_open = True
                except OSError:
                    is_open = False
            if is_open == want_open:
                remaining.discard(p)
        if remaining:
            time.sleep(0.3)
    return not remaining


def _wait_listening(ports, timeout_s=60.0):
    return _wait_ports(ports, want_open=True, timeout_s=timeout_s)


def _launch_group(base_port):
    """The env group through its REAL CLI, as a separate process tree.
    stdout goes to DEVNULL: nothing reads the pipe, and a filled pipe
    would block the launcher's logging during teardown."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",  # the env CLI must never claim a device
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    return subprocess.Popen(
        [
            sys.executable, "-m", "torchbeast_tpu.polybeast_env",
            "--env", "Mock",
            "--num_servers", str(NUM_SERVERS),
            "--pipes_basename", f"127.0.0.1:{base_port}",
        ],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )


def _stop_group(group):
    """terminate -> bounded wait -> kill escalation (the launcher's own
    SIGTERM reap joins its children for up to ~20 s worst-case)."""
    group.terminate()
    try:
        group.wait(timeout=30)
    except subprocess.TimeoutExpired:
        group.kill()
        group.wait(timeout=10)


def _learner_flags(tmp_path, base_port, total_steps):
    return polybeast.make_parser().parse_args([
        "--env", "Mock",
        "--no_start_servers",
        "--num_servers", str(NUM_SERVERS),
        "--batch_size", "2",
        "--unroll_length", "5",
        "--total_steps", str(total_steps),
        "--savedir", str(tmp_path),
        "--xpid", "split-tcp",
        "--model", "shallow",
        "--pipes_basename", f"127.0.0.1:{base_port}",
        "--num_inference_threads", "1",
        "--max_inference_batch_size", "4",
        "--checkpoint_interval_s", "100000",
    ])


def test_env_group_cli_sigterm_reaps_its_servers():
    """Killing the group launcher must take its server children with it.
    SIGTERM used to bypass the CLI's finally (Python's default handler
    skips finally/atexit), orphaning daemonic servers that kept their
    ports open forever — every run of the split test leaked a pair.
    The CLI now converts SIGTERM to SystemExit so its reap runs; the
    observable contract is that the ports STOP accepting."""
    base_port = _free_port_base(NUM_SERVERS)
    group = _launch_group(base_port)
    ports = [base_port + i for i in range(NUM_SERVERS)]
    try:
        assert _wait_listening(ports), "group never came up"
        _stop_group(group)
        # Orphaned servers would keep accepting; reaped ones close.
        assert _wait_ports(ports, want_open=False, timeout_s=30), (
            "ports still accepting after SIGTERM — the group leaked "
            "orphaned server children"
        )
    finally:
        if group.poll() is None:
            group.kill()
            group.wait(timeout=10)


def test_split_deployment_external_tcp_servers_train_and_resume(
    tmp_path, caplog
):
    base_port = _free_port_base(NUM_SERVERS)
    group = _launch_group(base_port)
    try:
        assert _wait_listening(
            [base_port + i for i in range(NUM_SERVERS)]
        ), "env-server group never came up on its TCP ports"

        # Phase 1: train to completion against the external group.
        stats = polybeast.train(_learner_flags(tmp_path, base_port, 60))
        assert stats["step"] >= 60
        assert np.isfinite(stats["total_loss"])
        ckpt = tmp_path / "split-tcp" / "model.ckpt"
        assert ckpt.exists()

        # The env group must have been untouched by learner shutdown:
        # it belongs to a different machine in the real topology.
        assert group.poll() is None, "env group died with the learner"

        # Phase 2: a NEW learner process-equivalent resumes from the
        # checkpoint against the same still-running servers and trains
        # further (each reconnect gets a fresh env stream server-side).
        import logging

        with caplog.at_level(logging.INFO, logger="torchbeast_tpu"):
            stats = polybeast.train(
                _learner_flags(tmp_path, base_port, 120)
            )
        assert any("Resuming" in r.message for r in caplog.records), (
            "phase 2 trained from scratch instead of resuming"
        )
        assert stats["step"] >= 120
        assert np.isfinite(stats["total_loss"])
    finally:
        _stop_group(group)

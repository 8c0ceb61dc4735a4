"""Does the system start, compile and step on the attached TPU?

    python chip_smoke.py              # one chip: six phases, in order
    python chip_smoke.py --chips 4    # four chips: the two multi-chip phases only

One process holds the chip: this script initialises the JAX backend and
calls the drivers' own `main(flags)` in-process, with flags built by
their own parsers — what `python -m torchbeast_tpu.monobeast ...` runs.
The only children are the native build (no JAX) and the drivers' env
workers / env servers, which are started pinned to the CPU platform
(torchbeast_tpu/utils/spawn.py). Everything runs at the flagship width:
deep ResNet + LSTM, uint8 84x84x4 frames, unroll 80, batch 32; only the
number of updates is small. Weights and batches come from --seed.

Each phase prints one JSON line (name, seconds, compile seconds, compile
cache requests/hits, what was checked). A phase that fails prints its
error and the script exits non-zero at once; nothing turns a failed
phase into a pass. The last line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`,
or `{"ok": false, ...}` with a non-zero exit code — which is what a
machine without a TPU gets from the first phase.

Savedirs, sockets and the native build all live under --out (default
chiprun_out/chip_smoke in the checkout); each run replaces what the
previous one left there. The phase functions take their sizes as
arguments so tests/test_chip_smoke.py can run them tiny on the CPU.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

T, B = 80, 32  # the flagship unroll and batch (BASELINE.md)
ENV = "tbt/MiniAtari-v0"
FLAGSHIP_ARGV = ["--env", ENV, "--model", "deep", "--use_lstm"]

# First-step loss, chip vs the CPU backend, same params and batch. TPU
# f32 convs and matmuls run bf16 passes at default precision, so "f32"
# agrees with the CPU to bf16 rounding accumulated over the net, not to
# f32 rounding.
CPU_PARITY_RTOL = {"f32": 2e-2, "bf16_train": 5e-2}
# DP over four chips vs one chip, same params and batch. The loss and
# the global gradient norm are big sums that only reassociate (the norm
# is what a wrong all-reduce would move). The parameter UPDATE is held
# to a relative L2 bound instead of elementwise: RMSprop's first step is
# lr * g / (0.1|g| + 0.01), which turns a reassociated near-zero
# gradient element into a visibly different step (2.3e-4 max on the
# v5e, against steps of up to 1.2e-2).
DP_LOSS_RTOL = DP_GRAD_NORM_RTOL = 1e-3
DP_UPDATE_REL_L2 = 5e-2


class CompileMeter:
    """Counts what JAX's monitoring events say about compilation: the
    backend-compile seconds, and the persistent cache's requests/hits."""

    def __init__(self):
        import jax

        self.reset()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )

    def reset(self):
        self.compile_seconds = 0.0
        self.cache_requests = 0
        self.cache_hits = 0

    def _on_event(self, event, **kwargs):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _on_duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_seconds += duration

    def report(self):
        return {
            "compile_seconds": round(self.compile_seconds, 2),
            "cache": {
                "requests": self.cache_requests,
                "hits": self.cache_hits,
            },
        }


def _emit(obj):
    print(json.dumps(obj), flush=True)


def _fresh_dir(*parts):
    path = os.path.join(*parts)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def _check(cond, message):
    # Not `assert`: the checks must hold under `python -O` too.
    if not cond:
        raise RuntimeError(message)


def _final_counters(savedir, xpid, before):
    """Counters of a run's final telemetry line, less what the process-
    wide registry already held when the run began."""
    from torchbeast_tpu.telemetry import read_jsonl

    path = os.path.join(savedir, xpid, "telemetry.jsonl")
    lines = read_jsonl(path)
    _check(lines and lines[-1].get("final") is True,
           f"{path}: no final line")
    return {
        k: v - before.get(k, 0.0)
        for k, v in lines[-1]["counters"].items()
    }


def _counters_now():
    from torchbeast_tpu.telemetry.export import snapshot

    return dict(snapshot()["counters"])


# ---------------------------------------------------------------- phases


def phase_device(chips):
    import jax

    devices = jax.devices()
    first = devices[0]
    _check(
        first.platform == "tpu",
        f"no TPU: jax.devices() reports platform {first.platform!r}",
    )
    _check(
        len(devices) == chips,
        f"--chips {chips} but jax.devices() reports {len(devices)}",
    )
    from torchbeast_tpu.utils.xla_cache import use_compile_cache

    return {
        "platform": first.platform,
        "device_kind": first.device_kind,
        "count": len(devices),
        "bytes_limit": first.memory_stats()["bytes_limit"],
        "compile_cache_dir": use_compile_cache(),
    }


def phase_native_build(out):
    """Build `_tbt_core` from csrc/ + setup.py into `out` — not in
    place, so neither a pre-built .so nor build/ can stand in — then
    import that build and check its API version."""
    build = _fresh_dir(out, "native")
    proc = subprocess.run(
        [
            sys.executable, "setup.py", "build_ext", "--force",
            "--build-lib", build,
            "--build-temp", os.path.join(build, "tmp"),
        ],
        cwd=REPO, capture_output=True, text=True,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout[-4000:] + proc.stderr[-4000:])
        raise RuntimeError(f"native build failed (rc={proc.returncode})")
    sys.path.insert(0, build)
    from torchbeast_tpu.runtime import native

    core = native.import_native()
    _check(core is not None, "built _tbt_core does not import")
    _check(
        os.path.dirname(os.path.abspath(core.__file__)) == build,
        f"imported {core.__file__}, not the build in {build}",
    )
    reason = native.gap_reason(core)
    _check(reason is None, reason)
    return {
        "module": os.path.relpath(core.__file__, out),
        "api_version": core.API_VERSION,
        "required_api_version": native.REQUIRED_API_VERSION,
    }


def build_flagship_learner(argv, t, b, seed=0):
    """The flagship learner the way the drivers build it — monobeast's
    parser, hparams, precision and model plumbing — from extra `argv`.
    Returns (flags, hp, model, params, optimizer, staged) where
    staged(t, b) is a seeded host batch and initial agent state, cast
    as the driver stages them. tests/test_chip_compile.py compiles this
    same construction for a described chip."""
    import __graft_entry__
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu import monobeast
    from torchbeast_tpu import precision as precision_lib

    flags = monobeast.make_parser().parse_args(
        FLAGSHIP_ARGV
        + ["--unroll_length", str(t), "--batch_size", str(b),
           "--seed", str(seed)]
        + argv
    )
    num_actions, frame_shape = 6, (84, 84, 4)
    prec = precision_lib.resolve_flags(flags)
    hp = monobeast.hparams_from_flags(flags)
    model, params = monobeast._init_model_and_params(
        flags, num_actions, b, frame_shape
    )

    def staged(t_, b_):
        batch = __graft_entry__._make_batch(
            t_, b_, num_actions, frame_shape, seed=seed
        )
        return (
            precision_lib.cast_batch(batch, prec.batch_dtype),
            precision_lib.cast_batch(
                model.initial_state(b_), prec.batch_dtype
            ),
        )

    return (
        flags, hp, model, params, learner_lib.make_optimizer(hp), staged
    )


def _learner_variant(argv, t, b, steps, seed):
    """Run the flagship update step `steps` times on one fixed batch.
    Returns what phase_learner compares."""
    import jax

    from torchbeast_tpu import learner as learner_lib

    _, hp, model, params, optimizer, staged = build_flagship_learner(
        argv, t, b, seed
    )
    params_host = jax.device_get(params)
    update_step = learner_lib.make_update_step(model, optimizer, hp)
    init = jax.jit(optimizer.init)
    opt_state = init(params)
    batch, state = jax.device_put(staged(t, b))
    memory = update_step.lower(
        params, opt_state, batch, state
    ).compile().memory_analysis()
    losses = []
    for _ in range(steps):
        params, opt_state, stats = update_step(
            params, opt_state, batch, state
        )
        losses.append(float(stats["total_loss"]))
    moved = max(
        float(np.max(np.abs(
            np.asarray(new, np.float32) - np.asarray(old, np.float32)
        )))
        for new, old in zip(
            jax.tree_util.tree_leaves(jax.device_get(params)),
            jax.tree_util.tree_leaves(params_host),
        )
    )
    _check(np.all(np.isfinite(losses)), f"non-finite losses {losses}")
    _check(
        losses[-1] < losses[0],
        f"loss did not fall on a fixed batch: {losses}",
    )
    _check(moved > 0, "parameters did not change")
    _check(
        update_step._cache_size() == 1,
        f"update step traced {update_step._cache_size()} times",
    )
    return {
        "params_host": params_host, "staged": staged, "losses": losses,
        "init": init,
        # The same step undonated, for the device-vs-CPU comparison.
        "body": jax.jit(learner_lib.update_body(model, optimizer, hp)),
        "report": {
            "losses": losses,
            "max_param_change": moved,
            "memory_analysis": {
                "temp_bytes": memory.temp_size_in_bytes,
                "argument_bytes": memory.argument_size_in_bytes,
                "output_bytes": memory.output_size_in_bytes,
                "alias_bytes": memory.alias_size_in_bytes,
            },
        },
    }


def _first_step_loss(run, device, t, b):
    """The variant's first update on `device`, from its initial params
    and a (t, b) batch: where the arguments live is where it runs."""
    import jax

    params = jax.device_put(run["params_host"], device)
    batch, state = jax.device_put(run["staged"](t, b), device)
    stats = run["body"](params, run["init"](params), batch, state)[2]
    return float(stats["total_loss"])


def phase_learner(t=T, b=B, steps=4, ref_t=8, ref_b=4, seed=0):
    """learner.make_update_step at the flagship batch, f32 and
    bf16_train: each variant's first-step loss is compared with the
    same step on the CPU backend at a batch the CPU can take."""
    import jax

    cpu = jax.devices("cpu")[0]
    checked = {}
    for name in ("f32", "bf16_train"):
        argv = [] if name == "f32" else ["--precision", name]
        run = _learner_variant(argv, t, b, steps, seed)
        chip_loss = _first_step_loss(run, jax.devices()[0], ref_t, ref_b)
        cpu_loss = _first_step_loss(run, cpu, ref_t, ref_b)
        rel = abs(chip_loss - cpu_loss) / abs(cpu_loss)
        _check(
            rel <= CPU_PARITY_RTOL[name],
            f"{name}: first-step loss {chip_loss} vs CPU {cpu_loss} "
            f"(rel {rel:.3g} > {CPU_PARITY_RTOL[name]})",
        )
        run["report"]["cpu_parity"] = {
            "t": ref_t, "b": ref_b, "device_loss": chip_loss,
            "cpu_loss": cpu_loss, "rel_diff": rel,
            "rtol": CPU_PARITY_RTOL[name],
        }
        checked[name] = run["report"]
    return checked


def phase_mono(out, t=T, b=B, updates=3, seed=0, test_episodes=2):
    """monobeast through its parser and main(): process env workers,
    then its checkpoint reloaded by --mode test."""
    from torchbeast_tpu import monobeast

    savedir = _fresh_dir(out, "mono")
    argv = FLAGSHIP_ARGV + [
        "--unroll_length", str(t), "--batch_size", str(b),
        "--num_actors", str(b),
        "--total_steps", str(updates * t * b),
        "--savedir", savedir, "--xpid", "run", "--seed", str(seed),
    ]
    before = _counters_now()
    stats = monobeast.main(monobeast.make_parser().parse_args(argv))
    counters = _final_counters(savedir, "run", before)
    _check(
        counters["learner.updates"] >= updates,
        f"{counters['learner.updates']} learner updates < {updates}",
    )
    _check(
        np.isfinite(stats["total_loss"]), f"loss {stats['total_loss']}"
    )
    checkpoint = os.path.join(savedir, "run", "model.ckpt")
    _check(os.path.exists(checkpoint), f"no checkpoint at {checkpoint}")
    returns = monobeast.main(monobeast.make_parser().parse_args(
        argv + ["--mode", "test",
                "--num_test_episodes", str(test_episodes)]
    ))
    _check(
        len(returns) == test_episodes and np.all(np.isfinite(returns)),
        f"test-mode returns {returns}",
    )
    return {
        "learner_updates": counters["learner.updates"],
        "step": stats["step"],
        "total_loss": float(stats["total_loss"]),
        "env_restarts": counters.get("recovery.env_restarts", 0.0),
        "checkpoint_reloaded": True,
        "test_returns": returns,
    }


def _poly_argv(savedir, t, b, updates, seed, actors, servers):
    return FLAGSHIP_ARGV + [
        "--unroll_length", str(t), "--batch_size", str(b),
        "--num_actors", str(actors), "--num_servers", str(servers),
        "--native_runtime",
        "--pipes_basename", f"shm:{savedir}/pipes",
        "--total_steps", str(updates * t * b),
        "--savedir", savedir, "--xpid", "run", "--seed", str(seed),
    ]


def _check_poly_run(stats, counters, updates):
    _check(stats["health"] == "HEALTHY", f"health {stats['health']}")
    _check(
        counters["learner.updates"] >= updates,
        f"{counters['learner.updates']} learner updates < {updates}",
    )
    # Central serving counts `inference.batches`; under --device_split
    # each slice counts its own `inference.slice.<i>.batches`.
    inference_batches = sum(
        v for k, v in counters.items()
        if k == "inference.batches"
        or (k.startswith("inference.slice.") and k.endswith(".batches"))
    )
    _check(inference_batches > 0, "no inference batches")
    _check(
        counters["state_table.dispatches"] > 0,
        "the device state table was not on the acting path",
    )
    recovered = {
        k: v for k, v in counters.items()
        if k.startswith("recovery.") and v
    }
    _check(not recovered, f"recovery counters moved: {recovered}")
    _check(
        np.isfinite(stats["total_loss"]), f"loss {stats['total_loss']}"
    )
    return {
        "health": stats["health"],
        "learner_updates": counters["learner.updates"],
        "inference_batches": inference_batches,
        "state_table_dispatches": counters["state_table.dispatches"],
        "env_steps": counters["actor.env_steps"],
        "total_loss": float(stats["total_loss"]),
    }


def phase_poly(out, t=T, b=B, updates=3, seed=0, actors=32, servers=4):
    """polybeast through its parser and main(): the native runtime
    demanded (absence raises), shm transport, spawned env servers, the
    LSTM's device state table on the acting path."""
    from torchbeast_tpu import polybeast

    savedir = _fresh_dir(out, "poly")
    before = _counters_now()
    stats = polybeast.main(polybeast.make_parser().parse_args(
        _poly_argv(savedir, t, b, updates, seed, actors, servers)
    ))
    return _check_poly_run(
        stats, _final_counters(savedir, "run", before), updates
    )


def phase_anakin(out, updates=40, seed=0):
    """anakin --env Catch through its parser and main(): env, policy and
    update in one program on the device."""
    from torchbeast_tpu import anakin

    savedir = _fresh_dir(out, "anakin")
    parser = anakin.make_parser()
    defaults = parser.parse_args([])
    frames = updates * defaults.batch_size * defaults.unroll_length
    stats = anakin.main(parser.parse_args([
        "--env", "Catch", "--total_steps", str(frames),
        "--savedir", savedir, "--xpid", "run", "--seed", str(seed),
    ]))
    _check(stats["step"] >= frames, f"stopped at step {stats['step']}")
    _check(
        np.isfinite(stats["total_loss"]), f"loss {stats['total_loss']}"
    )
    _check(
        os.path.exists(os.path.join(savedir, "run", "model.ckpt")),
        "no checkpoint",
    )
    return {
        "step": stats["step"],
        "total_loss": float(stats["total_loss"]),
        "mean_episode_return": stats.get("mean_episode_return"),
    }


def _device_ids(tree):
    import jax

    return [
        sorted(d.id for d in leaf.devices())
        for leaf in jax.tree_util.tree_leaves(tree)
    ]


def phase_dp(n=4, t=T, b=B, seed=0):
    """The flagship update under parallel/dp.make_parallel_update_step
    on an n-device `data` mesh, against the single-device step on the
    same params and batch."""
    import jax

    import __graft_entry__
    from torchbeast_tpu import learner as learner_lib
    from torchbeast_tpu.parallel import (
        create_mesh,
        make_parallel_update_step,
        replicate,
        shard_batch,
    )

    model, params, batch, state = __graft_entry__._flagship(
        batch_size=b, t=t
    )
    params = jax.device_get(params)
    hp = learner_lib.HParams(batch_size=b, unroll_length=t)
    optimizer = learner_lib.make_optimizer(hp)

    def timed_second_call(step, *args):
        """Wall seconds of a call that compiles nothing — a bring-up
        fact (is the sharded step in the same league?), not a metric."""
        t0 = time.monotonic()
        jax.block_until_ready(step(*args))
        return round(time.monotonic() - t0, 4)

    single = learner_lib.make_update_step(
        model, optimizer, hp, donate=False
    )
    opt_state = jax.device_get(optimizer.init(params))
    args1 = jax.device_put((params, opt_state, batch, state))
    p1, _, stats1 = single(*args1)
    p1 = jax.device_get(p1)
    single_seconds = timed_second_call(single, *args1)
    del args1

    mesh = create_mesh(n)
    mesh_ids = sorted(d.id for d in mesh.devices.flat)
    parallel = make_parallel_update_step(
        model, optimizer, hp, mesh, donate=False
    )
    params_n = replicate(mesh, params)
    batch_n, state_n = shard_batch(mesh, batch, state)
    for ids in _device_ids((batch_n, state_n)):
        _check(ids == mesh_ids, f"batch leaf on devices {ids}")
    for leaf in jax.tree_util.tree_leaves(batch_n):
        rows = sorted(
            (s.index[1].start or 0, s.device.id)
            for s in leaf.addressable_shards
        )
        _check(
            len({r for r, _ in rows}) == n
            and len({d for _, d in rows}) == n,
            f"batch shards not on {n} distinct devices: {rows}",
        )
    argsn = (params_n, replicate(mesh, opt_state), batch_n, state_n)
    pn, _, statsn = parallel(*argsn)
    dp_seconds = timed_second_call(parallel, *argsn)
    for leaf in jax.tree_util.tree_leaves(pn):
        _check(
            leaf.sharding.is_fully_replicated
            and sorted(d.id for d in leaf.devices()) == mesh_ids,
            f"updated params not replicated on {mesh_ids}",
        )
    report = {"mesh_device_ids": mesh_ids}
    for key, rtol in (
        ("total_loss", DP_LOSS_RTOL), ("grad_norm", DP_GRAD_NORM_RTOL)
    ):
        one, many = float(stats1[key]), float(statsn[key])
        rel = abs(many - one) / abs(one)
        _check(rel <= rtol, f"DP-{n} {key} {many} vs single {one}")
        report[key] = {
            "single": one, "dp": many, "rel_diff": rel, "rtol": rtol,
        }
    leaves = [
        jax.tree_util.tree_leaves(tree)
        for tree in (params, p1, jax.device_get(pn))
    ]
    step1 = np.concatenate(
        [np.ravel(b - a) for a, b, _ in zip(*leaves)]
    )
    stepn = np.concatenate(
        [np.ravel(c - a) for a, _, c in zip(*leaves)]
    )
    rel_l2 = float(np.linalg.norm(stepn - step1) / np.linalg.norm(step1))
    _check(
        rel_l2 <= DP_UPDATE_REL_L2,
        f"DP-{n} parameter update differs from single-device: "
        f"relative L2 {rel_l2}",
    )
    report["update"] = {
        "rel_l2_diff": rel_l2, "rel_l2_bound": DP_UPDATE_REL_L2,
        "max_abs_diff": float(np.max(np.abs(stepn - step1))),
        "max_abs_step": float(np.max(np.abs(step1))),
    }
    report["second_call_seconds"] = {
        "single": single_seconds, "dp": dp_seconds,
    }
    return report


def phase_split(out, t=T, b=36, updates=3, seed=0, actors=32, servers=4):
    """polybeast --device_split inf=1,learn=rest: the state table and
    the act step on the inference device, the learner state on the
    others. Placement is read off the arrays the run used — the serving
    stacks and the update step's first arguments — not off the flag."""
    import jax

    from torchbeast_tpu import parallel, polybeast
    from torchbeast_tpu.parallel import sebulba

    seen = {}
    build_serving = sebulba.build_sebulba_serving
    make_update = parallel.make_parallel_update_step

    def capture_serving(*args, **kwargs):
        seen["serving"] = build_serving(*args, **kwargs)
        return seen["serving"]

    def capture_update(*args, **kwargs):
        update = make_update(*args, **kwargs)

        def recording(params, opt_state, batch, state):
            seen.setdefault("learner", {
                "params": _device_ids(params),
                "opt_state": _device_ids(opt_state),
                "batch": _device_ids(batch),
            })
            return update(params, opt_state, batch, state)

        return recording

    savedir = _fresh_dir(out, "split")
    argv = _poly_argv(savedir, t, b, updates, seed, actors, servers) + [
        "--device_split", "inf=1,learn=rest",
    ]
    before = _counters_now()
    sebulba.build_sebulba_serving = capture_serving
    parallel.make_parallel_update_step = capture_update
    try:
        stats = polybeast.main(polybeast.make_parser().parse_args(argv))
    finally:
        sebulba.build_sebulba_serving = build_serving
        parallel.make_parallel_update_step = make_update
    checked = _check_poly_run(
        stats, _final_counters(savedir, "run", before), updates
    )

    ids = [d.id for d in jax.devices()]
    inference, learners = ids[:1], sorted(ids[1:])
    stacks = seen["serving"].stacks
    _check(len(stacks) == 1, f"{len(stacks)} inference slices")
    # The table is the act step's donated output: where it lives after
    # the run is where every act dispatch executed.
    table = _device_ids(stacks[0].state_table._table)
    _check(
        all(leaf == inference for leaf in table),
        f"state table on devices {table}, not {inference}",
    )
    for name, leaves in seen["learner"].items():
        _check(
            all(leaf == learners for leaf in leaves),
            f"learner {name} on {leaves[0]}, not {learners}",
        )
    checked.update(
        inference_device_ids=inference, learner_device_ids=learners,
        state_table_device_ids=table[0],
        learner_param_device_ids=seen["learner"]["params"][0],
    )
    return checked


# ------------------------------------------------------------------ main


def phases_for(chips, out, seed):
    """[(name, thunk)] — with --chips 4, the multi-chip path and what it
    is compared with, and no other phase."""
    device = ("device", lambda: phase_device(chips))
    native = ("native_build", lambda: phase_native_build(out))
    if chips == 4:
        return [
            device, native,
            ("dp4", lambda: phase_dp(4, seed=seed)),
            ("split", lambda: phase_split(out, seed=seed)),
        ]
    return [
        device, native,
        ("learner", lambda: phase_learner(seed=seed)),
        ("mono", lambda: phase_mono(out, seed=seed)),
        ("poly", lambda: phase_poly(out, seed=seed)),
        ("anakin", lambda: phase_anakin(out, seed=seed)),
    ]


def run(phases, meter=None):
    """Run the phases in order; returns (exit code, last line)."""
    start = time.monotonic()
    device = None
    for name, phase in phases:
        if meter is not None:
            meter.reset()
        t0 = time.monotonic()
        line = {"phase": name}
        try:
            checked = phase()
        except (Exception, SystemExit) as e:
            traceback.print_exc()
            line.update(
                ok=False, seconds=round(time.monotonic() - t0, 2),
                error=f"{type(e).__name__}: {e}"[:4000],
            )
            _emit(line)
            return 1, {"ok": False, "failed_phase": name, "device": device}
        line.update(ok=True, seconds=round(time.monotonic() - t0, 2))
        if meter is not None:
            line.update(meter.report())
        line["checked"] = checked
        _emit(line)
        if name == "device":
            device = {
                "platform": checked["platform"],
                "kind": checked["device_kind"],
                "count": checked["count"],
            }
    _emit({
        "phases": [name for name, _ in phases],
        "total_seconds": round(time.monotonic() - start, 2),
    })
    return 0, {"ok": True, "device": device}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the multi-chip phases (DP-4 vs single device, "
             "polybeast --device_split), on a four-chip host.",
    )
    parser.add_argument(
        "--out", default=os.path.join(REPO, "chiprun_out", "chip_smoke"),
        help="Directory for savedirs, sockets and the native build.",
    )
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    code, last = run(
        phases_for(args.chips, out, args.seed), meter=CompileMeter()
    )
    sys.stderr.flush()
    _emit(last)
    return code


if __name__ == "__main__":
    sys.exit(main())

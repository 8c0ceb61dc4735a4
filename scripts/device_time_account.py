"""Where an update step's device time goes, by the scopes the program
enters: a learner cell's update compiled ahead, a few traced steps,
then `torchbeast_tpu/telemetry/device_scopes.py`'s account of the trace
(every op's SELF time joined to the compiled program's `op_name`s; a
row a scope with its forward / backward / rematerialised parts and its
op kinds; what has no scope listed under the loop or the value it
belongs to), beside the counters the update's layers sowed.

    chiprun -- python3 scripts/device_time_account.py \
        --workload qwen3next_policy.learner --seed 7

It builds the cell through the benchmark's own `perfbench.drivers.
learner.build`, runs three warm and `--steps` traced steps, and prints
one JSON line `{"account": ...}` and the same as a table; `--out` also
writes the JSON there. It serves every learner cell (`deep_lstm.
poly`'s update is `deep_lstm.learner`'s program); a whole run's trace,
act step and all, is what a driver's `--profile_dir` accounts for at
its end. Without a TPU it exits 1 as the benchmark does; tier-1 runs
`traced_account` on a toy update on the CPU, where a profile has
another layout and the numbers mean nothing.
"""

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WARM_STEPS = 3


def traced_account(update_step, params, opt_state, batch, state, steps):
    """The account of `steps` traced steps of `update_step`, compiled
    ahead so that no trace holds a compile."""
    import jax

    from torchbeast_tpu import learner_setup

    compiled = update_step.lower(params, opt_state, batch, state).compile()
    stats = None
    for _ in range(WARM_STEPS):
        params, opt_state, stats = compiled(params, opt_state, batch, state)
    jax.block_until_ready(params)
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(steps):
            params, opt_state, stats = compiled(
                params, opt_state, batch, state
            )
        jax.block_until_ready(params)
        t_traced = time.monotonic()
        jax.profiler.stop_trace()
        t_stopped = time.monotonic()
        account = learner_setup.device_time_account(
            trace_dir, [compiled.as_text()], stats
        )
    # What the instrument costs the host, beside what it reads.
    account["host_seconds"] = {
        "stop_trace": t_stopped - t_traced,
        "read_and_account": time.monotonic() - t_stopped,
    }
    return account


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--steps", type=int, default=3)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    from perfbench import manifest
    from perfbench import run as bench
    from perfbench.drivers import learner as driver

    from torchbeast_tpu.telemetry import device_scopes

    cell = manifest.load_cell(args.workload)
    if manifest.DRIVERS[cell.traffic["driver"]] != driver.__name__:
        parser.error(
            f"{args.workload} is no learner cell: its update is "
            "accounted for by the learner cell of its configuration, a "
            "whole run by the driver's --profile_dir"
        )
    devices = bench.claim_devices(cell.chips)
    # A program read from the compile cache carries the names of the
    # source that first compiled it: for this table the metadata joins
    # the cache's key, so a scope entered since is in the program.
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    bench.use_compile_cache()
    update_step, params, opt_state, batch, state, _ = driver.build(
        cell, args.seed, devices
    )
    account = traced_account(
        update_step, params, opt_state, batch, state, args.steps
    )
    account.update(workload=args.workload, seed=args.seed)
    print(json.dumps({"account": account}), flush=True)
    print(device_scopes.render(account), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(account, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

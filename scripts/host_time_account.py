"""Where a serving thread's wall time goes: one traced run of the poly
cell, then every span's wall time split into the thread's own CPU
time, the wait for the interpreter lock that `_tbt_core` stamps inside
it, and the rest (asleep for the chip or a transfer, waiting for the
lock inside JAX's own calls, runnable with no core), beside the
kernel's account of every thread role.

    chiprun -- python3 scripts/host_time_account.py --seed 7

It runs the cell through the benchmark's own driver (`perfbench.run`'s
steps, `--trace 1`) and prints, after the driver's lines, one JSON line
`{"account": ...}` and the same as a table, under it one actor cycle
term by term (ISSUE 66: the measured cycle, the four terms that make it
up, what each is made of, and what is left over); `--out` also writes
the JSON there. `--cpu_rehearsal` runs a tiny version on the CPU, to see
that every instrument reports; its numbers mean nothing.
"""

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# span -> the sites of host.gil_wait_s.<site> stamped inside it. Spans
# that enter no GIL-free call of _tbt_core have none: the lock's wait
# inside them (JAX's calls) is part of "rest".
SPANS = (
    ("inference.wait_batch", ("batcher_next",)),
    ("inference.prep", ("get_inputs",)),
    ("inference.dispatch", ()),
    ("state_table.context", ()),
    ("state_table.call", ()),
    ("inference.reply", ("set_outputs",)),
    ("state_table.fetch", ()),
    ("state_table.read_slot", ()),
    ("prefetch.stage", ()),
    ("learner.update_dispatch", ()),
    ("learner.publish", ()),
    ("learner.stats_fetch", ()),
)


def span_account(facts):
    """{span: {count, wall_ms, cpu_ms, gil_wait_ms, rest_ms}}, means a
    call, for the spans the run observed."""
    hists = facts["histograms"]
    out = {}
    for name, sites in SPANS:
        wall, cpu = hists.get(name + "_s"), hists.get(name + "_cpu_s")
        if not wall or not cpu or not wall["count"]:
            continue
        n = wall["count"]
        stamped = sum(
            hists.get(f"host.gil_wait_s.{site}", {}).get("total", 0.0)
            for site in sites
        )
        row = {
            "count": n,
            "wall_ms": 1e3 * wall["total"] / n,
            "cpu_ms": 1e3 * cpu["total"] / n,
            "gil_wait_ms": 1e3 * stamped / n,
        }
        row["rest_ms"] = row["wall_ms"] - row["cpu_ms"] - row["gil_wait_ms"]
        out[name] = row
    return out


def role_account(facts):
    """{role: {cpu_s, run_delay_s, cpu_pct, run_delay_pct}} over the
    window, from the thread ledger's counters."""
    window = facts["values"]["window_s"]
    out = {}
    for name, value in sorted(facts["counters"].items()):
        for kind in ("cpu_s", "run_delay_s"):
            prefix = f"host.{kind}."
            if name.startswith(prefix):
                row = out.setdefault(name[len(prefix):], {})
                row[kind] = value
                row[kind[:-2] + "_pct"] = 100.0 * value / window
    return out


def gil_account(facts):
    """{site: {count, mean_us, total_s}} of the stamped lock waits."""
    out = {}
    for name, hist in sorted(facts["histograms"].items()):
        if name.startswith("host.gil_wait_s.") and hist["count"]:
            out[name[len("host.gil_wait_s."):]] = {
                "count": hist["count"],
                "mean_us": 1e6 * hist["total"] / hist["count"],
                "total_s": hist["total"],
            }
    return out


# One actor cycle, term by term: (term, histogram, its parts). The
# serving pair's spans are means a BATCH, which every row of the batch
# waits out; the actors' terms are means a frame.
CYCLE_TERMS = (
    ("request_rtt", "actor.request_rtt_s", (
        ("queue wait", "inference.request_wait_s"),
        ("prep", "inference.prep_s"),
        ("dispatch", "inference.dispatch_s"),
        ("hand-over wait", "inference.handover_wait_s"),
        ("reply", "inference.reply_s"),
    )),
    ("reply_wake", "actor.reply_wake_s", ()),
    ("own", "actor.own_s", ()),
    ("env_rtt", "actor.env_rtt_s", (
        ("wire down", "actor.env_wire_down_s"),
        ("env step", "actor.env_step_s"),
        ("wire up", "actor.env_wire_up_s"),
    )),
)


def cycle_account(facts, actors):
    """An actor's cycle from the window's histograms, in ms a frame:
    the cycle as measured (loop top to loop top) and as the rate has it
    (actors over env frames a second), its four terms with the
    remainder (the enqueue's own microseconds), and what each term is
    made of with its own `rest` (request_rtt's: the gaps between the
    serving pair's spans, less the end of `reply` that lies after
    set_outputs is entered, where request_rtt ends; env_rtt's: 0 when
    every stream shares the machine's clock). None where the run has no
    `actor.cycle_s` (an extension from before ISSUE 66)."""
    hists = facts["histograms"]

    def mean_ms(name):
        hist = hists.get(name)
        if not hist or not hist["count"]:
            return None
        return 1e3 * hist["total"] / hist["count"]

    cycle = mean_ms("actor.cycle_s")
    if cycle is None:
        return None
    rows, accounted = [], 0.0
    for term, name, parts in CYCLE_TERMS:
        whole = mean_ms(name)
        rows.append({"term": term, "ms": whole, "indent": 0})
        accounted += whole or 0.0
        known = [(part, mean_ms(hist)) for part, hist in parts]
        rows.extend(
            {"term": part, "ms": ms, "indent": 1} for part, ms in known
        )
        if whole is not None and any(ms is not None for _, ms in known):
            rest = whole - sum(ms or 0.0 for _, ms in known)
            rows.append({"term": "rest", "ms": rest, "indent": 1})
    frames_per_s = (
        facts["counters"]["pool.env_steps"] / facts["values"]["window_s"]
    )
    return {
        "cycle_ms": cycle,
        "cycle_by_rate_ms": 1e3 * actors / frames_per_s,
        "frames_per_s": frames_per_s,
        "terms": rows,
        "remainder_ms": cycle - accounted,
        "env_clock_unshared": facts["counters"].get(
            "actor.env_clock_unshared"
        ),
    }


def render_cycle(cycle):
    lines = [
        "actor cycle, ms a frame        "
        f"{cycle['cycle_ms']:>9.4f}   ({cycle['cycle_by_rate_ms']:.4f} by "
        f"the rate, {cycle['frames_per_s']:.1f} frames/s)"
    ]
    for row in cycle["terms"]:
        value = "        -" if row["ms"] is None else f"{row['ms']:>9.4f}"
        name = "  " * (1 + row["indent"]) + row["term"]
        lines.append(f"{name:<30} {value}")
    lines.append(f"{'  remainder':<30} {cycle['remainder_ms']:>9.4f}")
    lines.append(
        f"{'  env_clock_unshared':<30} {cycle['env_clock_unshared']!s:>9}"
    )
    return "\n".join(lines)


def render(account):
    lines = ["span                       count   wall ms    cpu ms  "
             "gil-wait ms   rest ms"]
    for name, r in account["spans"].items():
        lines.append(
            f"{name:<24} {r['count']:>7} {r['wall_ms']:>9.3f} "
            f"{r['cpu_ms']:>9.3f} {r['gil_wait_ms']:>12.3f} "
            f"{r['rest_ms']:>9.3f}"
        )
    lines.append("role             cpu s   cpu % of window   "
                 "run-delay s   run-delay %")
    for name, r in account["roles"].items():
        lines.append(
            f"{name:<14} {r.get('cpu_s', 0.0):>7.2f} "
            f"{r.get('cpu_pct', 0.0):>17.1f} "
            f"{r.get('run_delay_s', float('nan')):>13.2f} "
            f"{r.get('run_delay_pct', float('nan')):>13.1f}"
        )
    lines.append("gil-wait site      count    mean us    total s")
    for name, r in account["gil_waits"].items():
        lines.append(
            f"{name:<16} {r['count']:>7} {r['mean_us']:>10.1f} "
            f"{r['total_s']:>10.3f}"
        )
    if account.get("cycle"):
        lines.append(render_cycle(account["cycle"]))
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", default="deep_lstm.poly")
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--out", default=None)
    parser.add_argument("--cpu_rehearsal", action="store_true")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    from perfbench import common, manifest
    from perfbench import run as bench

    cell = manifest.load_cell(args.workload)
    trace = True
    if args.cpu_rehearsal:
        import jax

        devices, trace = jax.devices()[:1], False
        common.device_report = lambda devices: {
            "platform": "cpu", "kind": "none", "count": 1,
            "memory_peak_bytes": 1,
        }
        traffic = dict(cell.traffic, num_actors=4, num_servers=2)
        traffic["window"] = dict(
            traffic["window"], min_updates=2, steady_seconds=2,
            steady_share=0.9, max_wait_s=30,
        )
        cell = cell._replace(
            config=dict(cell.config, unroll_length=4, batch_size=4),
            traffic=traffic,
        )
    else:
        devices = bench.claim_devices(cell.chips)
    bench.use_compile_cache()
    driver = importlib.import_module(
        manifest.DRIVERS[cell.traffic["driver"]]
    )
    result = driver.run(
        cell, args.seed, args.seconds, trace, devices,
        common.CompileMeter(),
    )
    facts = result["facts"]
    print(json.dumps({
        "correct": result["correct"], "end_to_end": result["end_to_end"],
        "metrics": bench.layer_metrics(cell, facts),
        "notes": result["notes"],
    }), flush=True)
    account = {
        "seed": args.seed,
        "window_s": facts["values"]["window_s"],
        "batches": facts["counters"].get("inference.batches"),
        "spans": span_account(facts),
        "roles": role_account(facts),
        "gil_waits": gil_account(facts),
        "cycle": cycle_account(facts, int(cell.traffic["num_actors"])),
    }
    print(json.dumps({"account": account}), flush=True)
    print(render(account), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(account, f, indent=1)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())

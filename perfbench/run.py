"""python3 -m perfbench.run --workload W --seed N --seconds S --trace 0|1

One process, which holds the cell's chips. Prints the per-second series
of a cell that has them on earlier lines and, last, the one JSON line
of the benchmark's contract. Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits 1.
"""

import argparse
import importlib
import json
import sys

def parse_args(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return parser.parse_args(argv)


def claim_devices(chips: int):
    """The cell's chips, or an error: a measurement never falls back
    to another platform."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices() reports platform "
            f"{devices[0].platform!r}"
        )
    if len(devices) < chips:
        raise RuntimeError(
            f"the cell asks for {chips} chips, jax.devices() has "
            f"{len(devices)}"
        )
    return devices[:chips]


def use_compile_cache() -> str:
    """The program's own rule for where the cache lives
    (JAX_COMPILATION_CACHE_DIR, else .jax_cache/ in the checkout), and
    every program cached, however quickly it compiled, so that a later
    run of the cell compiles nothing."""
    import jax

    from torchbeast_tpu.utils.xla_cache import use_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return use_compile_cache()


def layer_metrics(cell, facts):
    from perfbench import readers

    out = {}
    for spec in cell.per_layer:
        value = readers.read_metric(spec, facts)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    from perfbench import common, manifest

    cell = manifest.load_cell(args.workload)
    devices = claim_devices(cell.chips)
    cache_dir = use_compile_cache()
    meter = common.CompileMeter()
    driver = importlib.import_module(
        manifest.DRIVERS[cell.traffic["driver"]]
    )
    result = driver.run(
        cell, args.seed, args.seconds, bool(args.trace), devices, meter
    )

    if "series" in result:
        print(json.dumps({"series": result["series"]}), flush=True)
    reduced = result["facts"].get("trace")
    notes = dict(
        result.get("notes", {}), compile_cache_dir=cache_dir,
        compiles=meter.report(),
    )
    if reduced is not None:
        notes["trace"] = {
            k: reduced[k] for k in ("modules", "lines_seen", "chips")
        }
    print(json.dumps({"notes": notes}), flush=True)

    device = result["device"]
    if args.trace:
        metrics = layer_metrics(cell, result["facts"])
    else:
        metrics = {
            m["name"]: {
                "value": result["end_to_end"][m["name"]], "unit": m["unit"],
            }
            for m in cell.end_to_end
        }
    line = {
        "correct": result["correct"],
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        if reduced is None:
            raise RuntimeError("--trace 1 but no trace was taken")
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception as e:  # noqa: BLE001 - the boundary: report, exit 1
        import traceback

        traceback.print_exc()
        print(f"perfbench: run failed: {e}", file=sys.stderr)
        code = 1
    sys.exit(code)

"""Readings the limits of a training cell are set from, in one process on
the chip at the cell's own size:

    python benchmarks/tools/calibrate_train.py --workload <cell> --seeds 12 --controls 3

For each seed the program's numbers (the checked steps of a window of no
length, through the timed entry) against the reference; for the first
``--controls`` seeds also the control (the reference in the next precision
below the configuration's, put in the program's place) and the planted
fault the reference can carry (half of the batch left out, the mean taken
over the rest), each against the same reference. Writes
``chiprun_out/calibrate_<cell>.json``.
"""
import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=2_200_000_001)
    parser.add_argument("--control-precision", default="fp8")
    parser.add_argument("--attention", default="auto", help="the program's attention path: "
                        "'xla' reads the einsum path as a second witness to a wide reading")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import harness
    from benchmarks.drivers import train
    from benchmarks.traffic.train_batches import TrainBatches

    harness.require_chips(1)
    harness.configure_compile_cache(ROOT)
    spec = harness.load_cell(ROOT, args.workload)
    config, mix = spec["config"], spec["mix"]
    if args.attention != "auto":
        _pin_attention(config["program"], args.attention)
    ref = importlib.import_module(f"benchmarks.reference.{config['reference']}")
    rows = int(mix.get("reference_rows", 4))
    out = {"workload": args.workload, "program": [], "control": [], "half_batch": []}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        result = harness.run_cell(ROOT, args.workload, seed, 0.0, False)
        entry = {"seed": seed, **result["readings"],
                 **result["where"], "leaf_table": result["leaf_table"][:4],
                 "setup_s": result["metrics"]["setup_s"]["value"],
                 "seconds": time.perf_counter() - t0}
        out["program"].append(entry)
        print("program", json.dumps(entry), flush=True)
        if i < args.controls:
            batches = TrainBatches(mix["feed"], seed)
            check = [batches.next_batch() for _ in range(train.CHECK_STEPS)]
            optimizer = result["optimizer"]
            exact = train.reference_readings(ref, config, optimizer, 0, seed, check, rows)
            low = train.reference_readings(
                ref, config, optimizer, 0, seed, check, rows, precision=args.control_precision)
            entry = {"seed": seed, **train.compare(low, exact)}
            out["control"].append(entry)
            print("control", json.dumps(entry), flush=True)
            half = [_half(b) for b in check]
            fault = train.reference_readings(ref, config, optimizer, 0, seed, half, rows)
            entry = {"seed": seed, **train.compare(fault, exact)}
            out["half_batch"].append(entry)
            print("half_batch", json.dumps(entry), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    suffix = "" if args.attention == "auto" else "_" + args.attention
    with open(os.path.join(ROOT, "chiprun_out", f"calibrate_{args.workload}{suffix}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


def _pin_attention(program: str, impl: str) -> None:
    """Build the family's model on attention path ``impl`` (diagnosis only)."""
    import dataclasses

    import jax.numpy as jnp

    module = importlib.import_module(f"perceiver_io_tpu.scripts.text.{program}")
    plain = module.FAMILY.build_model
    module.FAMILY = dataclasses.replace(
        module.FAMILY,
        build_model=lambda cfg, dm: type(plain(cfg, dm))(cfg, dtype=jnp.bfloat16, attention_impl=impl),
    )


def _half(batch: dict) -> dict:
    """The batch with the labels of its second half of rows ignored: those
    rows are left out and the mean is taken over the rest."""
    n = batch["labels"].shape[0] // 2
    labels = batch["labels"].copy()
    labels[n:] = -100
    return {**batch, "labels": labels}


if __name__ == "__main__":
    sys.exit(main())

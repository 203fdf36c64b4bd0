"""Readings the limits of ``lfm2moe-train-8k`` are set from, in one process
on the chip at the cell's own size:

    python benchmarks/tools/calibrate_lfm2_moe.py --seeds 12 --controls 3

As ``calibrate_train.py``, with the planted fault this architecture can
carry: for each seed the program's checked steps against the reference; for
the first ``--controls`` seeds also the control (the reference computed in
float8, put in the program's place) and the fault (the reference with one
held expert's output left out of every expert layer), each against the same
reference. Every entry carries the verdict of the mix's committed limits on
its readings (``correct`` and, where not, ``over``: the limits passed), as
``harness.run_cell`` decides it, so the control and the fault are shown to
come out not correct at the cell's own size, rate and bias. Writes
``chiprun_out/calibrate_lfm2moe-train-8k.json``.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WORKLOAD = "lfm2moe-train-8k"


def verdict(readings: dict, limits: dict) -> dict:
    over = [n for n, limit in limits.items() if not readings[n] <= limit]
    return {"correct": not over, "over": over}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=2_800_000_001)
    parser.add_argument("--control-precision", default="fp8")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import harness
    from benchmarks.drivers import train
    from benchmarks.reference import lfm2_moe as ref
    from benchmarks.traffic.train_batches import TrainBatches

    harness.require_chips(1)
    harness.configure_compile_cache(ROOT)
    spec = harness.load_cell(ROOT, WORKLOAD)
    config, mix = spec["config"], spec["mix"]
    rows = int(mix.get("reference_rows", 4))
    limits = mix["limits"]
    out = {"workload": WORKLOAD, "fit": mix["fit"], "bias_scale": ref.BIAS_SCALE, "limits": limits,
           "program": [], "control": [], "expert_left_out": []}
    path = os.path.join(ROOT, "chiprun_out", f"calibrate_{WORKLOAD}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        result = harness.run_cell(ROOT, WORKLOAD, seed, 0.0, False)
        entry = {"seed": seed, "correct": result["correct"], **result["readings"], **result["where"],
                 "leaf_table": result["leaf_table"][:4], "losses": result["window"].get("losses"),
                 "memory_peak_bytes": result["device"]["memory_peak_bytes"],
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
            entry = train.compare(low, exact)
            entry = {"seed": seed, **verdict(entry, limits), **entry}
            out["control"].append(entry)
            print("control", json.dumps(entry), flush=True)
            del low
            fault = train.reference_readings(
                ref, {**config, "_skip_experts": (0,)}, optimizer, 0, seed, check, rows)
            entry = train.compare(fault, exact)
            entry = {"seed": seed, **verdict(entry, limits), **entry}
            out["expert_left_out"].append(entry)
            print("expert_left_out", json.dumps(entry), flush=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

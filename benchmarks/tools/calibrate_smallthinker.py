"""Readings the limits of ``smallthinker-train-16k`` are set from, in one
process on the chip at the cell's own size:

    python benchmarks/tools/calibrate_smallthinker.py --seeds 12 --controls 2

As ``calibrate_glm4_moe_lite.py``, with the faults this architecture can
carry: for each seed the program's checked steps against the reference; for
the first ``--controls`` seeds also the control (the reference computed in
float8, put in the program's place) and five planted faults (the reference
with the window ignored on the window layers, with rotary applied on the
global layer, with the router fed the expert layer's own input, with ``silu``
for ``relu``, with one held expert left out of every layer), each against the
same exact reference. Every entry carries the verdict of the mix's committed
limits on its readings (``correct`` and, where not, ``over``: the limits
passed), as ``harness.run_cell`` decides it, so the control and the faults
are shown to come out not correct at the cell's own size and rate. Writes
``chiprun_out/calibrate_smallthinker-train-16k.json``.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WORKLOAD = "smallthinker-train-16k"
FAULTS = {
    "window_ignored": {"_window_ignored": True},
    "global_layer_rotated": {"_global_rotated": True},
    "router_reads_ffn_input": {"_router_reads_ffn_input": True},
    "silu_for_relu": {"_silu": True},
    "expert_left_out": {"_skip_experts": (0,)},
}


def verdict(readings: dict, limits: dict) -> dict:
    over = [n for n, limit in limits.items() if not readings[n] <= limit]
    return {"correct": not over, "over": over}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--controls", type=int, default=2)
    parser.add_argument("--first-seed", type=int, default=3_500_000_011)
    parser.add_argument("--control-precision", default="fp8")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import harness
    from benchmarks.drivers import train
    from benchmarks.reference import smallthinker as ref
    from benchmarks.traffic.train_batches import TrainBatches

    harness.require_chips(1)
    harness.configure_compile_cache(ROOT)
    spec = harness.load_cell(ROOT, WORKLOAD)
    config, mix = spec["config"], spec["mix"]
    rows = int(mix.get("reference_rows", 4))
    limits = mix["limits"]
    out = {"workload": WORKLOAD, "fit": mix["fit"], "limits": limits,
           "program": [], "control": [], **{name: [] for name in FAULTS}}
    path = os.path.join(ROOT, "chiprun_out", f"calibrate_{WORKLOAD}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        result = harness.run_cell(ROOT, WORKLOAD, seed, 0.0, False)
        optimizer = result["optimizer"]
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
            exact = train.reference_readings(ref, config, optimizer, 0, seed, check, rows)
            plan = [("control", config, args.control_precision)]
            plan += [(name, {**config, **fault}, "float32") for name, fault in FAULTS.items()]
            for name, faulty, precision in plan:
                t0 = time.perf_counter()
                found = train.reference_readings(
                    ref, faulty, optimizer, 0, seed, check, rows, precision=precision)
                entry = train.compare(found, exact)
                entry = {"seed": seed, **verdict(entry, limits), **entry, "losses": found["losses"],
                         "seconds": time.perf_counter() - t0}
                out[name].append(entry)
                print(name, json.dumps(entry), flush=True)
                del found
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

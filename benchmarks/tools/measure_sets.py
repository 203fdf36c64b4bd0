"""The builder's measurement of a cell as the contract sets it out: two sets
of runs of ``run.py`` with the same seeds in both, each run a process of its
own (this launcher stays off JAX, so each child gets the chip), then the
spread of every end-to-end metric: the distance between the first and third
quartile (``statistics.quantiles(values, n=4)``) over the median, per set.

    python benchmarks/tools/measure_sets.py --workload <cell> --runs 6 --seconds 30 [--traced 3]

Appends every result line to ``chiprun_out/sets_<cell>.jsonl`` as it comes.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_once(workload: str, seed: int, seconds: int, trace: int) -> dict:
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, cwd=ROOT)
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        return {"error": proc.returncode, "stderr": proc.stderr[-2000:], "seed": seed}
    result = json.loads(lines[-1])
    result.update(seed=seed, trace=trace, wall_s=time.perf_counter() - t0)
    return result


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--traced", type=int, default=0)
    parser.add_argument("--seconds", type=int, required=True)
    parser.add_argument("--first-seed", type=int, default=2_300_000_011)
    args = parser.parse_args(argv)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = os.path.join(out_dir, f"sets_{args.workload}.jsonl")
    seeds = [args.first_seed + 104_729 * i for i in range(args.runs)]
    summary = {"workload": args.workload, "seconds": args.seconds, "sets": []}
    plan = [(s, 0, k) for k in range(args.sets) for s in seeds]
    plan += [(args.first_seed + 15_485_863 * (i + 1), 1, "traced") for i in range(args.traced)]
    by_set: dict = {}
    for seed, trace, label in plan:
        result = run_once(args.workload, seed, args.seconds, trace)
        result["set"] = label
        with open(log, "a") as f:
            f.write(json.dumps(result) + "\n")
        brief = {k: result.get(k) for k in ("seed", "set", "correct", "wall_s", "error")}
        brief["metrics"] = {k: v["value"] for k, v in result.get("metrics", {}).items()}
        print(json.dumps(brief), flush=True)
        if "error" not in result and not trace:
            by_set.setdefault(label, []).append(result)
    for label, results in by_set.items():
        names = results[0]["metrics"].keys()
        row = {"set": label, "correct": all(r["correct"] for r in results)}
        for name in names:
            values = [r["metrics"][name]["value"] for r in results]
            if name == "setup_s":
                values = values[1:] if label == 0 else values  # the first run compiles
            row[name] = {"median": statistics.median(values),
                         "spread": spread(values) if len(values) >= 2 else None}
        summary["sets"].append(row)
    print("summary", json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

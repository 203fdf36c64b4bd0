"""A kept trace's device time by scope class, by model block and by the
instructions no scope places, a step:

    BENCH_KEEP_TRACE=chiprun_out/traces python benchmarks/run.py --workload <cell> ... --trace 1
    python benchmarks/tools/scope_breakdown.py chiprun_out/traces <cell>

Reads ``<cell>.xplane.pb`` (kept by the harness) and ``<cell>.scopes.json``
(kept by ``benchmarks/scopes.py``); needs no chip. This is where PERF.md's
"step by scope class" tables come from. ``by_held_ms`` names each operation
by everything it holds (``mlp+optimizer``: an MLP matmul with an AdamW update
fused in).
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BLOCK_DEPTH = 4  # Model/encoder/self_attn_1/layers_3


def main(argv) -> int:
    sys.path.insert(0, ROOT)
    from benchmarks import scopes, trace_reduce

    keep, cell = argv[1], argv[2]
    step_module = argv[3] if len(argv) > 3 else "jit_step"
    trace = trace_reduce.read(os.path.join(keep, f"{cell}.xplane.pb"))
    with open(os.path.join(keep, f"{cell}.scopes.json")) as f:
        tables = json.load(f)
    table = tables[0]
    found = scopes.device_seconds(trace, step_module, tables)
    if found is None:
        print("no device plane, no step or an empty table", file=sys.stderr)
        return 1
    steps = found["steps"]
    by_block, by_kind, by_held, unplaced = {}, {}, {}, {}
    for device in trace.devices:
        for name, _, duration in device.ops:
            instruction = scopes.instruction_of(name)
            op_name = table.get(instruction, "")
            mosaic = bool(trace_reduce.CUSTOM_CALL.search(name))
            cls, held = scopes.classes_of(instruction, mosaic, tables)
            label = "+".join(sorted(held)) or "unscoped"
            by_held[label] = by_held.get(label, 0.0) + duration
            block = "/".join(scopes.scopes_of(op_name)[:BLOCK_DEPTH]) or "(none)"
            by_block[block] = by_block.get(block, 0.0) + duration
            kind = (cls, trace_reduce.short_name(name))
            by_kind[kind] = by_kind.get(kind, 0.0) + duration
            if cls == "unscoped":
                key = trace_reduce.short_name(name) + (" (not in table)" if instruction not in table else "")
                unplaced[key] = unplaced.get(key, 0.0) + duration
    per_step = 1e3 / steps / len(trace.devices)
    out = {
        "cell": cell, "steps": steps, "step_ms": 1e3 * found["total"] / steps,
        **{f"{which}_ms": {k: 1e3 * v / steps for k, v in sorted(found[which].items(), key=lambda kv: -kv[1])}
           for which in ("by_class", "alone", "holding")},
        "by_held_ms": {k: v * per_step for k, v in sorted(by_held.items(), key=lambda kv: -kv[1])},
        "by_class_and_instruction_ms": [[*k, v * per_step] for k, v in sorted(by_kind.items(), key=lambda kv: -kv[1])[:40]],
        "by_block_ms": [[k, v * per_step] for k, v in sorted(by_block.items(), key=lambda kv: -kv[1])[:40]],
        "unscoped_ms": [[k, v * per_step] for k, v in sorted(unplaced.items(), key=lambda kv: -kv[1])[:20]],
        "custom_call_ms": 1e3 * trace.custom_call_s() / steps,
    }
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""Readings the limits of ``keyevl2-train-16k`` are set from, in one process
on the chip at the cell's own size:

    python benchmarks/tools/calibrate_keye_vl2.py --seeds 6 --controls 2

For each seed the cell as the benchmark runs it (a window of ``--seconds``,
``run_seconds`` by default, untraced): ``correct`` against the mix's limits,
the readings, ``train_tokens_per_s`` and ``setup_s``, so that the same runs
give the spread of the end-to-end metrics. For the first ``--controls`` seeds
also the control (the reference computed in float8, put in the program's
place) and five planted faults (the reference attending to every causal key,
keeping 1,024 keys where the model keeps 2,048, without the indexer's loss,
taking each chosen key's predecessor, and with one held expert left out of
every layer), each against the same exact reference, with the verdict of the
mix's committed limits (``correct`` and, where not, ``over``). For the first
seed, the share of each row's selected keys on which the program's selection
(bfloat16 products, float32 scores) and the reference's (float32 ``highest``)
differ, in the first layer, where the two read the same input. Writes
``chiprun_out/calibrate_keyevl2-train-16k.json``.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WORKLOAD = "keyevl2-train-16k"
FAULTS = {
    "dense_attention": {"_dense_attention": True},
    "top_1024": {"_topk": 1024},
    "no_indexer_loss": {"_no_indexer_loss": True},
    "selection_shift": {"_selection_shift": True},
    "expert_left_out": {"_skip_experts": (0,)},
}


def verdict(readings: dict, limits: dict) -> dict:
    over = [n for n, limit in limits.items() if not readings[n] <= limit]
    return {"correct": not over, "over": over}


def selection_disagreement(config: dict, seed: int, ids) -> dict:
    """The first layer's selection by the program (its indexer module in
    bfloat16, ``ops/sparse_attention.select``) and by the reference
    (``reference/keye_vl2.py``, float32 ``highest``, ``lax.top_k``) on the
    same embedded row: per row past the top-k, the share of the reference's
    keys the program did not select; mean and worst over rows."""
    import jax
    import jax.numpy as jnp

    from benchmarks.adapters import lm_sparse as adapter
    from benchmarks.reference import keye_vl2 as ref
    from benchmarks.reference.lfm2_moe import rms_norm
    from perceiver_io_tpu.models.core.modules import Indexer
    from perceiver_io_tpu.ops import sparse_attention
    from perceiver_io_tpu.ops.position import RotaryEmbedding, frequency_position_encoding, positions

    sa, eps = config["sa_config"], config["rms_norm_eps"]
    k, (b, n) = sa["topk"], ids.shape
    tree = adapter.common.seeded_tree(ref, config, adapter.path_of, seed)
    p = jax.jit(lambda key: ref.init_params(key, config))(jax.random.PRNGKey(seed % (2**31)))
    lp = {name[len("layer.0."):]: v for name, v in p.items() if name.startswith("layer.0.")}

    @jax.jit
    def program(tree, ids):
        h = tree["embed"]["embedding"][ids].astype(jnp.bfloat16)
        u = (rms_norm(h.astype(jnp.float32), tree["layers_0"]["operator_norm"]["scale"], eps)).astype(jnp.bfloat16)
        rot = RotaryEmbedding(frequency_position_encoding(positions(b, n), sa["indexer_head_dim"],
                                                          config["rope_theta"]))
        q_i, k_i, w = Indexer(sa["indexer_num_heads"], sa["indexer_head_dim"], eps, dtype=jnp.bfloat16).apply(
            {"params": tree["layers_0"]["indexer"]}, u, rot)
        return sparse_attention.select(q_i, k_i, w, k)

    bits = program(tree, ids)

    @jax.jit
    def missed(p, ids, bits, first):
        # the reference's indexer on one block of 512 rows, as reference/keye_vl2.py has it
        with jax.default_matmul_precision("highest"):
            u = rms_norm(p["emb.tok"][ids], lp["op_norm.g"], eps)
            mm = lambda a, w_: jnp.einsum("bnc,cd->bnd", a, w_, precision="highest")
            heads = lambda x, c: x.reshape(b, n, c, -1).transpose(0, 2, 1, 3)
            qi = ref.rotary(heads(mm(u, lp["idx.q.w"]), sa["indexer_num_heads"]), config["rope_theta"])
            ki = ref.layer_norm(mm(u, lp["idx.k.w"]), lp["idx.k_norm.g"], lp["idx.k_norm.b"], eps)
            ki = ref.rotary(ki[:, None], config["rope_theta"])[:, 0]
            w = mm(u, lp["idx.w.w"]) * (sa["indexer_num_heads"] * sa["indexer_head_dim"]) ** -0.5
            q_blk = jax.lax.dynamic_slice_in_dim(qi, first, 512, axis=2).transpose(1, 0, 2, 3)
            w_blk = jax.lax.dynamic_slice_in_dim(w, first, 512, axis=1).transpose(2, 0, 1)
            rows = first + jnp.arange(512)
            want = ref.selected(ref.index_scores(q_blk, ki, w_blk), rows, k)
        got = sparse_attention.unpack(bits, first, 512)
        return jnp.sum(want & ~got, axis=-1) / jnp.sum(want, axis=-1)

    shares = jnp.concatenate([missed(p, ids, bits, first)[0] for first in range(k, n, 512)])
    return {"rows": int(shares.shape[0]), "mean": float(shares.mean()), "max": float(shares.max()),
            "rows_differing": float((shares > 0).mean())}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", type=int, default=6)
    parser.add_argument("--controls", type=int, default=2)
    parser.add_argument("--first-seed", type=int, default=3_600_400_061)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--control-precision", default="fp8")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import harness
    from benchmarks.drivers import train
    from benchmarks.reference import keye_vl2 as ref
    from benchmarks.traffic.train_batches import TrainBatches

    harness.require_chips(1)
    harness.configure_compile_cache(ROOT)
    spec = harness.load_cell(ROOT, WORKLOAD)
    config, mix = spec["config"], spec["mix"]
    seconds = spec["bench"]["run_seconds"] if args.seconds is None else args.seconds
    rows = int(mix.get("reference_rows", 4))
    limits = mix["limits"]
    out = {"workload": WORKLOAD, "fit": mix["fit"], "limits": limits, "seconds": seconds,
           "program": [], "control": [], **{name: [] for name in FAULTS}}
    path = os.path.join(ROOT, "chiprun_out", f"calibrate_{WORKLOAD}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)

    def save():
        with open(path, "w") as f:
            json.dump(out, f, indent=1)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        result = harness.run_cell(ROOT, WORKLOAD, seed, seconds, False)
        optimizer = result["optimizer"]
        entry = {"seed": seed, "correct": result["correct"], **result["readings"], **result["where"],
                 "leaf_table": result["leaf_table"][:4], "losses": result["window"].get("losses"),
                 "memory_peak_bytes": result["device"]["memory_peak_bytes"],
                 **{k: v["value"] for k, v in result["metrics"].items()},
                 "steps": result["window"].get("steps"), "seconds": time.perf_counter() - t0}
        out["program"].append(entry)
        print("program", json.dumps(entry), flush=True)
        save()
        batches = TrainBatches(mix["feed"], seed)
        check = [batches.next_batch() for _ in range(train.CHECK_STEPS)]
        if i == 0:
            import jax.numpy as jnp

            t0 = time.perf_counter()
            try:
                found = selection_disagreement(config, seed, jnp.asarray(check[0]["input_ids"]))
            except Exception as e:  # a reading, not the calibration: the rest goes on
                found = {"error": repr(e)}
            out["selection_disagreement"] = {**found, "seconds": time.perf_counter() - t0}
            print("selection_disagreement", json.dumps(out["selection_disagreement"]), flush=True)
            save()
        if i < args.controls:
            t0 = time.perf_counter()
            exact = train.reference_readings(ref, config, optimizer, 0, seed, check, rows)
            print("exact reference", "%.1f s" % (time.perf_counter() - t0), flush=True)
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
                save()
    return 0


if __name__ == "__main__":
    sys.exit(main())

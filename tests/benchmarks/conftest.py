"""The benchmark's tests: the repository's root on the path (``benchmarks``
is a package there), and a toy benchmark (tiny configurations, cells and
mixes as files in a temporary root) that the harness runs on the CPU."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TOY_AR = {
    "name": "toy-ar", "reference": "perceiver_ar", "program": "clm", "vocab_size": 262,
    "max_seq_len": 128, "max_latents": 64, "num_channels": 32, "num_heads": 2,
    "num_self_attention_layers": 2, "self_attention_widening_factor": 2,
    "cross_attention_widening_factor": 2, "cross_attention_dropout": 0.5,
    "post_attention_dropout": 0.0, "residual_dropout": 0.0, "abs_pos_emb": True,
    "output_norm": False, "output_bias": True, "init_scale": 0.02,
}
TOY_MLM = {
    "name": "toy-mlm", "reference": "perceiver_io_mlm", "program": "mlm", "vocab_size": 262,
    "max_position_embeddings": 64, "d_model": 24, "d_latents": 32, "num_latents": 8,
    "num_blocks": 1, "num_self_attends_per_block": 2, "num_self_attention_heads": 2,
    "num_cross_attention_heads": 2, "qk_channels": 16, "v_channels": 32,
    "cross_attention_widening_factor": 1, "self_attention_widening_factor": 1,
    "attention_probs_dropout_prob": 0.0, "initializer_range": 0.02,
}
#: batch 8: the suite's eight virtual CPU devices share the rows
TOY_FEEDS = {
    "toy-fit-ar": {"task": "clm", "batch": 8, "seq_len": 128, "corpus_tokens": 20000},
    "toy-fit-mlm": {"task": "mlm", "batch": 8, "seq_len": 64, "corpus_tokens": 20000},
}
#: limits of the toy cells, set as the real ones are: above what sound runs
#: of the toy program read on the CPU over four seeds (loss 9e-7, first
#: gradient 0.0050, change 0.0085) and, for the gradient, below the least
#: the float8 control reads over three seeds (0.018)
TOY_LIMITS = {"loss1": 3e-6, "loss2": 3e-6, "loss3": 3e-6, "grad_leaf": 0.01, "delta_leaf": 0.03}
TOY_LFM2 = {
    "name": "toy-lfm2", "reference": "lfm2_moe", "program": "lm",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 48,
    "num_attention_heads": 2, "num_key_value_heads": 1, "conv_L_cache": 3, "norm_eps": 1e-5,
    "norm_topk_prob": True, "num_experts": 4, "router_width": 8, "expert_offset": 2,
    "num_experts_per_tok": 2, "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 262, "rope_parameters": {"rope_theta": 1000000}, "max_position_embeddings": 512,
    "layer_types": ["conv", "conv", "full_attention", "conv"], "first_layer": 1, "num_layers": 3,
    "num_dense_layers": 1,
}
#: the toy cell's limits, set as the real ones are: three times what sound
#: runs of the toy program (bfloat16) read on the CPU over three seeds (loss
#: 9.4e-6, first gradient 0.0017, change 0.0015) and below the float8 control
#: and the left-out expert (``test_bench_lfm2_moe.py``); the experts held are 2..5 of 8,
#: so a layout that mistook the offset would read of the order of 1
TOY_LFM2_LIMITS = {"loss1": 3e-5, "loss2": 3e-5, "loss3": 3e-5, "grad_leaf": 0.005, "delta_leaf": 0.005}


def build_toy_root(tmp_path) -> tuple:
    """``(root, files_dir)`` of a toy benchmark: the real metrics' readers and
    peaks copied, toy configurations, mixes and a ``BENCHMARK.json``."""
    root = str(tmp_path)
    files = os.path.join(root, "files")
    os.makedirs(os.path.join(files, "traffic", "mixes"))
    os.makedirs(os.path.join(root, "cfg"))
    shutil.copytree(os.path.join(ROOT, "benchmarks", "metrics"), os.path.join(files, "metrics"))
    shutil.copy(os.path.join(ROOT, "benchmarks", "peaks.json"), files)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"], bench["workloads"] = [], []
    for cfg, traffic in ((TOY_AR, "toy-fit-ar"), (TOY_MLM, "toy-fit-mlm")):
        with open(os.path.join(root, "cfg", cfg["name"] + ".json"), "w") as f:
            json.dump(cfg, f)
        bench["configs"].append({"name": cfg["name"], "source": "toy", "reduced": [],
                                 "file": f"cfg/{cfg['name']}.json", "why": "toy"})
        bench["workloads"].append({"name": cfg["name"] + "-train", "config": cfg["name"],
                                   "traffic": traffic, "chips": 1, "why": "toy"})
        mix = {"driver": "train", "feed": TOY_FEEDS[traffic], "warmup_steps": 1,
               "fit": {"trainer": {"max_steps": 100000, "enable_tensorboard": False}},
               "trace_steps": 2, "reference_rows": 2, "trace": {"step_module": "jit_step"},
               "limits": TOY_LIMITS}
        with open(os.path.join(files, "traffic", "mixes", traffic + ".json"), "w") as f:
            json.dump(mix, f)
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [w["name"] for w in bench["workloads"]]
    bench["per_layer"] = [m for m in bench["per_layer"] if "workloads" not in m]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, files


def build_toy_lfm2_root(tmp_path) -> tuple:
    """The toy benchmark with a third cell, ``toy-lfm2-train``, and the expert
    layers' metrics listed for it."""
    root, files = build_toy_root(tmp_path)
    with open(os.path.join(root, "cfg", "toy-lfm2.json"), "w") as f:
        json.dump(TOY_LFM2, f)
    mix = {"driver": "train", "feed": {"task": "clm", "batch": 8, "seq_len": 128, "corpus_tokens": 20000},
           "fit": {"trainer": {"max_steps": 100000, "enable_tensorboard": False},
                   "model": {"activation_checkpointing": True}},
           "warmup_steps": 1, "trace_steps": 2, "reference_rows": 2,
           "trace": {"step_module": "jit_step"}, "limits": TOY_LFM2_LIMITS}
    with open(os.path.join(files, "traffic", "mixes", "toy-fit-lfm2.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-lfm2", "source": "toy", "reduced": [],
                             "file": "cfg/toy-lfm2.json", "why": "toy"})
    bench["workloads"].append({"name": "toy-lfm2-train", "config": "toy-lfm2",
                               "traffic": "toy-fit-lfm2", "chips": 1, "why": "toy"})
    bench["end_to_end"][0]["workloads"].append("toy-lfm2-train")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in ("expert_load_max_over_mean", "expert_matmul_device_ms", "expert_matmul_roofline",
                 "moe_routing_device_ms", "short_conv_device_ms"):
        bench["per_layer"].append({**real[name], "workloads": ["toy-lfm2-train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, files


@pytest.fixture
def toy_root(tmp_path):
    return build_toy_root(tmp_path)

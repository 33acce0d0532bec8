"""A throw-away benchmark root: tiny configurations and mixes in a
temporary directory, registered by files and entries alone. The harness
finds its drivers, readers, families and references in the repository's
``benchmark/`` package; nothing that is there is touched."""

import json
import os

from benchmark.manifest import REPO_ROOT

TINY_DENSE = {
    "family": "mistral", "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "sliding_window": None,
    "tie_word_embeddings": False, "vocab_size": 256,
    "compute_dtype": "float32",
}
TINY_MOE = {**TINY_DENSE, "family": "mixtral", "num_local_experts": 4,
            "num_experts_per_tok": 2, "router_aux_loss_coef": 0.02}
TRAIN = {"weights": {"dtype": "float32", "float32_leaves": []},
         "train": {"sequence_length": 64, "rows_per_chip": 2,
                   "attn": "flash", "optimizer": "adam_compact",
                   "learning_rate": 1e-3, "step_kwargs": {}},
         "check": {"tokens": 32, "grad_leaves": ["lnf_s", "wk", "w2"]}}
SERVE = {"weights": {"dtype": "float32",
                     "float32_leaves": ["ln1_s", "ln2_s", "lnf_s", "wg"]},
         "engine": {"n_slots": 4, "max_len": 96, "max_queue": 64},
         "check": {"prompt_lengths": [5, 16, 21], "decode_steps": 2}}
LENGTHS = {"prompt_tokens": {"median": 12, "sigma": 0.8, "min": 4, "max": 32},
           "output_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}}
MIXES = {
    "tiny-steps": {"driver": "train_steps", "pool_batches": 2,
                   "profile_steps": 2},
    "tiny-open": {"driver": "open_loop", "shape_seed": 1,
                  "arrivals": {"rate_per_s": 20.0}, **LENGTHS,
                  "warm_in_s": 0.2, "grace_s": 20.0, "profile_s": 0.3},
    "tiny-closed": {"driver": "closed_loop", "shape_seed": 1, "callers": 6,
                    "pool": 64, **LENGTHS, "warm_in_s": 0.2,
                    "profile_s": 0.3},
}
CELLS = [("tiny-train", "tiny-dense-train", "tiny-steps", 1),
         ("tiny-train-dp2", "tiny-dense-train-dp", "tiny-steps", 4),
         ("tiny-chat", "tiny-dense-serve", "tiny-open", 1),
         ("tiny-batch", "tiny-moe-serve", "tiny-closed", 1)]
CONFIGS = {"tiny-dense-train": {**TINY_DENSE, **TRAIN},
           "tiny-dense-train-dp": {**TINY_DENSE, **TRAIN},
           "tiny-dense-serve": {**TINY_DENSE, **SERVE},
           "tiny-moe-serve": {**TINY_MOE, **SERVE}}


def make_root(tmp_path) -> str:
    """Write the throw-away root and return its path. Its metrics
    (``data/tiny_metrics.json``) are the benchmark's own entries pointed
    at the tiny cells, the open-loop ones included: their readers and
    driver are in the repository whether or not a cell uses them yet."""
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "benchmark", "configs"))
    os.makedirs(os.path.join(root, "benchmark", "traffic"))
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tiny_metrics.json")) as f:
        metrics = json.load(f)
    for name, cfg in CONFIGS.items():
        with open(os.path.join(root, "benchmark", "configs",
                               name + ".json"), "w") as f:
            json.dump(cfg, f)
    for name, mix in MIXES.items():
        with open(os.path.join(root, "benchmark", "traffic",
                               name + ".json"), "w") as f:
            json.dump(mix, f)
    bench = {
        "command": command, "paths": ["benchmark"], "run_seconds": 1,
        "configs": [{"name": n, "source": "tests",
                     "file": f"benchmark/configs/{n}.json", "reduced": [],
                     "why": "tiny"} for n in CONFIGS],
        "workloads": [{"name": c, "config": cfg, "traffic": mix,
                       "chips": chips, "why": "tiny"}
                      for c, cfg, mix, chips in CELLS],
        **metrics,
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root

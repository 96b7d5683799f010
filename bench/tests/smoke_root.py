"""A throwaway checkout for the CPU tests: the benchmark's own files plus
smoke-size configurations and traffic mixes, written as new files only,
the way a later change would add a cell."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

SMOKE_CONFIG = {
    "arch_id": "qwen3-4b", "family": "qwen3", "program_preset": "smoke",
    "source": "https://huggingface.co/Qwen/Qwen3-4B/blob/main/config.json",
    "config": {"hidden_size": 64, "intermediate_size": 128,
               "num_hidden_layers": 2, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
               "rms_norm_eps": 1e-6, "rope_theta": 1000000,
               "attention_bias": False},
}
SMOKE_MAMBA2 = {
    "arch_id": "mamba2-370m", "family": "mamba2", "program_preset": "smoke",
    "source": "https://huggingface.co/state-spaces/mamba2-370m",
    "config": {"d_model": 64, "n_layer": 2, "vocab_size": 256},
    "mamba2_layer": {"d_state": 16, "d_conv": 4, "expand": 2, "headdim": 16,
                     "ngroups": 1, "chunk_size": 16, "norm_eps": 1e-5},
    "dtype": "float32", "token_vocab": 250,
}
ENGINE = {"slots": 4, "page_size": 16, "max_len": 64, "max_new_tokens": 12,
          "prefill_chunk": 8, "prefill_token_budget": 32, "num_pages": 40,
          "prefix_cache": True}
SMOKE_TRAFFIC = {
    "smoke-chat": {
        "kind": "serve", "loop": "open", "rate_per_s": 12.0,
        "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.6,
                   "min": 8, "max": 40},
        "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                   "min": 3, "max": 12},
        "drain_max_s": 20, "trace_seconds": 0.5, "engine": ENGINE,
        "check": {"sample_requests": 3, "max_logit_gap": 1e-3}},
    "smoke-docqa": {
        "kind": "serve", "loop": "closed", "clients": 3, "sessions": 40,
        "document": {"dist": "lognormal", "median": 24, "sigma": 0.4,
                     "min": 17, "max": 40},
        "asks": {"min": 2, "max": 3},
        "question": {"dist": "uniform", "min": 3, "max": 8},
        "output": {"dist": "fixed", "value": 4}, "trace_seconds": 0.5,
        "engine": {**ENGINE, "max_new_tokens": 4},
        "check": {"sample_requests": 4, "max_logit_gap": 1e-3}},
    "smoke-train": {
        "kind": "train", "batch": 4, "seq_len": 64,
        "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                      "weight_decay": 0.1, "clip_norm": 1.0,
                      "warmup_steps": 5, "total_steps": 10000,
                      "min_lr_frac": 0.1},
        "loss": {"z_weight": 1e-4}, "trace_seconds": 0.5,
        "check": {"steps": 3, "limits": {"grad_norm_gap": 1e-3,
                                         "update_norm_gap": 1e-3}}},
}
CELLS = (("qwen3-smoke", "smoke-chat", "qwen3-4b.chat"),
         ("qwen3-smoke", "smoke-docqa", "qwen3-4b.docqa"),
         ("mamba2-smoke", "smoke-train", "mamba2-370m.train"))


def make(tmp: str) -> str:
    """A checkout under ``tmp`` with the smoke cells; returns its root."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, cfg in (("qwen3-smoke", SMOKE_CONFIG),
                      ("mamba2-smoke", SMOKE_MAMBA2)):
        with open(os.path.join(root, "bench", "configs", f"{name}.json"),
                  "w") as f:
            json.dump(cfg, f)
    for name, t in SMOKE_TRAFFIC.items():
        with open(os.path.join(root, "bench", "traffic", f"{name}.json"),
                  "w") as f:
            json.dump(t, f)
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name in ("qwen3-smoke", "mamba2-smoke"):
        bench["configs"].append({"name": name, "source": "smoke",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "CPU tests"})
    for config, mix, like in CELLS:
        bench["workloads"].append({"name": f"{config}.{mix}",
                                   "config": config, "traffic": mix,
                                   "chips": 1, "why": "CPU tests"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(f"{config}.{mix}")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root

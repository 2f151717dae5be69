"""Tiny cells for running the benchmark's harness on the CPU."""
from __future__ import annotations

import copy

import pytest

TINY_MODEL = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
              "rope_theta": 100000.0, "rms_norm_eps": 1e-5,
              "tie_word_embeddings": False}

TINY_SERVING = {
    "kind": "serving", "module": "tpu_bench.danube", "model": TINY_MODEL,
    "tenants": 2,
    "program": {"arch": "h2o-danube-3-4b",
                "settings": {"num_layers": 2, "d_model": 64, "d_ff": 128,
                             "num_heads": 4, "num_kv_heads": 2,
                             "head_dim": 16, "vocab_size": 256,
                             "vocab_pad_to": 256, "param_dtype": "bfloat16",
                             "rope_theta": 100000.0, "attention": "full",
                             "window": 0, "attn_chunk": 32,
                             "remat": "none"}},
    "engine": {"slot_cap": 4, "max_seq_len": 96, "page_size": 16,
               "capacity_slots": 8, "capacity_pages": 96,
               "default_units": 4, "round_interval_steps": 8,
               "policy": "sdps"},
    "tenant_spec": {"min_units": 4},
}

TINY_CHAT = {
    "rate_rps": 60.0, "arrivals": {"dist": "gamma", "cv": 2.0},
    "tenants": {"split": [3, 1], "swap_every_s": 0.5},
    "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
               "buckets": [16, 32]},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2,
               "max": 12},
    "slo_s": 10.0, "reference_len": 48, "warm_in_s": 0.5,
    "check": {"tokens": 40, "max_requests": 8, "max_logit_gap": 0.05},
}

TINY_FLEET = {
    "kind": "fleet", "nodes": 1, "tenants_per_node": 32,
    "capacity_units": 520,
    "federation": {"duration_s": 10**7, "round_interval": 300,
                   "default_units": 16, "policy": "sdps",
                   "scaling_policy": "reactive", "placement": "least_loaded",
                   "engine": "jax"},
}


#: a benchmark file for the tiny runs: one serving and two fleet cells,
#: whose configuration and traffic the tests hand in themselves
TINY_BENCH = {
    "configs": [
        {"name": "danube3-4b-x2",
         "file": "tpu_bench/configs/danube3-4b-x2.json"},
        {"name": "dyverse-fd-32",
         "file": "tpu_bench/configs/dyverse-fd-32.json"}],
    "workloads": [
        {"name": "serve-chat", "config": "danube3-4b-x2", "traffic": "chat",
         "chips": 1},
        {"name": "fleet-stream", "config": "dyverse-fd-32",
         "traffic": "stream", "chips": 1},
        {"name": "fleet-game", "config": "dyverse-fd-32",
         "traffic": "game", "chips": 1}],
    "end_to_end": [
        {"name": "tokens_per_s", "unit": "tokens/s",
         "workloads": ["serve-chat"]},
        {"name": "tenant_s_per_s", "unit": "tenant-s/s",
         "workloads": ["fleet-stream", "fleet-game"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


@pytest.fixture
def tiny_serving():
    return copy.deepcopy(TINY_SERVING), copy.deepcopy(TINY_CHAT)


@pytest.fixture
def tiny_fleet():
    return copy.deepcopy(TINY_FLEET)

"""A tiny cell written as files into a directory, the way a later change
adds a cell: a manifest, a configuration, a traffic mix and its limits.
CPU-sized (2 layers of width 64, 32 tokens), bf16 parameters as on the
chip.  The traffic is a committed mix's job (FedBiOAcc over 2 clients, or
FedBiOAcc-Local over 4 with 2 taking part in each round) at 32 tokens."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

SSM = {"name": "tiny-ssm", "source": "CPU-sized test model",
       "arch": "mamba2-130m", "family": "ssm", "param_dtype": "bfloat16",
       "sizes": {"num_layers": 2, "d_model": 64, "vocab_size": 256,
                 "ssm_state": 16, "ssm_heads": 4, "ssm_head_dim": 16,
                 "ssm_chunk": 16, "conv_width": 4, "norm_eps": 1e-06},
       "reduced": ["num_layers", "d_model", "vocab_size", "ssm_state",
                   "ssm_heads", "ssm_head_dim", "ssm_chunk"]}
MOE = {"name": "tiny-moe", "source": "CPU-sized test model",
       "arch": "granite-moe-1b-a400m", "family": "moe",
       "param_dtype": "bfloat16",
       "sizes": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                 "num_kv_heads": 2, "head_dim": 0, "d_ff": 32,
                 "vocab_size": 256, "num_experts": 4,
                 "experts_per_token": 2, "rope_theta": 10000.0,
                 "norm_eps": 1e-06},
       "reduced": ["num_layers", "d_model", "vocab_size", "num_heads",
                   "num_kv_heads", "d_ff", "num_experts",
                   "experts_per_token"]}
#: limits for tiny-ssm.t, set from its readings on the CPU: sound runs read
#: at most 0.0031 (grad1), 0.024 (change), 0.018 (mom) over seeds 1-4; the
#: float8 control at least 0.010, 0.026, 0.083 over seeds 1-3
LIMITS = {"grad1_gap": 0.006, "change_gap": 0.1, "mom_gap": 0.04}
#: the committed mix of each tiny job
GLOBAL, LOCAL = "fedbioacc.m2.seq1024", "fedbioacc_local.m4.u2.seq1024"
#: limits for tiny-ssm.local, set from its readings on the CPU: sound runs
#: read at most 0.0051 (grad1), 0.0133 (change), 0.0109 (mom) over seeds
#: 1-5; the float8 control at least 0.0077, 0.028, 0.068 and the float8
#: operand control 0.0060, 0.019, 0.036 over the same seeds
LOCAL_LIMITS = {"grad1_gap": 0.01, "change_gap": 0.025, "mom_gap": 0.03}


def traffic(seq_len=32, mix=GLOBAL):
    with open(os.path.join(REPO, "bench", "traffic", mix + ".json")) as fh:
        t = json.load(fh)
    t["seq_len"] = seq_len
    # a compiled kernel streams whole 1024-element tiles; off the chip the
    # substrate takes the jnp lowering, so the tile only sets the padding
    t["experiment"]["execution"]["storm_block"] = 1024
    return t


def write(root: str, config=SSM, limits=LIMITS, mix=GLOBAL,
          cell="t") -> str:
    """Write the tiny cell ``<config name>.<cell>`` of the traffic ``mix``
    under ``root``; returns the manifest's path."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        man = json.load(fh)
    name = config["name"]
    man["configs"] = [{"name": name, "source": config["source"],
                       "file": f"bench/configs/{name}.json",
                       "reduced": config["reduced"], "why": "test"}]
    man["workloads"] = [{"name": f"{name}.{cell}", "config": name,
                         "traffic": "t", "chips": 1, "why": "test"}]
    for m in man["per_layer"]:
        m.pop("workloads", None)
    files = {"BENCHMARK.json": man,
             f"bench/configs/{name}.json": config,
             "bench/traffic/t.json": traffic(mix=mix),
             f"bench/limits/{name}.{cell}.json": {"numbers": limits}}
    for rel, obj in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fh:
            json.dump(obj, fh)
    return os.path.join(root, "BENCHMARK.json")


PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

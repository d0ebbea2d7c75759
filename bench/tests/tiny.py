"""A configuration and traffic small enough for the CPU, in the layout of the
real ones: olmo-1b's program at its ``reduced()`` widths."""
import copy

CONFIG = {
    "model": {"n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
              "head_dim": 16, "d_ff": 128, "vocab_size": 256,
              "norm": "layernorm_nonparametric", "norm_eps": 1e-05,
              "rope_theta": 10000.0, "tie_embeddings": True,
              "dtype": "bfloat16"},
    "program": {"arch": "olmo-1b", "embed_rows": 256, "reduced": True},
    "bench_arch": "dense",
    "check": {"logit_gap_limit": 0.02},
}

TRAFFIC = {
    "order_seed": 0,
    "arrivals": {"kind": "gamma", "cv": 2.0, "rate_per_s": 12.0, "draw_seed": 0},
    "catalogue": [[16, 4, 0.5], [32, 8, 0.5]],
    "variants": 3,
    "popularity": {"kind": "zipf", "s": 1.0},
    "serving": {"workers": 2, "device_capacity_gib": 0.0007, "host_fill": True},
    "sample": 6,
}

CLOSED = {
    "order_seed": 0,
    "arrivals": {"kind": "closed", "clients": 2},
    "catalogue": [[16, 4, 0.5], [32, 8, 0.5]],
    "variants": 1,
    "block": 4,
    "serving": {"workers": 2, "device_capacity_gib": 0.001, "host_fill": False},
    "sample": 6,
}

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def bench(cell_name="tiny.open"):
    import json
    from pathlib import Path
    b = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
    b = copy.deepcopy(b)
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    return b

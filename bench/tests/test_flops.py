"""The counts of bench/flops.py against the program's own parameter count."""
import json
from pathlib import Path

import pytest

from bench import flops as F

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["olmo-1b", "deepseek-7b"])
def test_param_count_matches_program(name):
    from repro.configs import get_config

    conf = model(name)
    m = conf["model"]
    cfg = get_config(conf["program"]["arch"])
    # ModelConfig.param_count() counts norm scales the model does not have:
    # for RMSNorm 4 * d_model per layer where ln1 and ln2 hold 2 * d_model,
    # and a final-norm scale also for OLMo's LayerNorm, which has none
    d = m["d_model"]
    extra = 2 * d * m["n_layers"] if m["norm"] == "rmsnorm" else d
    assert F.param_count(m) == cfg.param_count() - extra


def test_published_sizes():
    olmo, ds = model("olmo-1b")["model"], model("deepseek-7b")["model"]
    assert F.param_count(olmo) == 1_176_764_416
    assert F.param_count(ds) == 6_490_935_296
    assert F.weight_bytes(ds) == 2 * F.param_count(ds)   # tied head, no pad
    assert F.kv_bytes_per_token(ds) == 491_520


@pytest.mark.parametrize("name", ["olmo-1b", "deepseek-7b"])
def test_prefill_and_decode_counts(name):
    m = model(name)["model"]
    L, H, hd = m["n_layers"], m["n_heads"], m["head_dim"]
    S = 512
    attn = 4 * L * H * hd * S * (S + 1) // 2     # causal: token p sees p + 1 keys
    assert F.prefill_flops(m, S) == S * F.trunk_flops(m) + 2 * F.head_params(m) + attn
    assert F.decode_flops(m, S) - F.decode_flops(m, S - 1) == 4 * L * H * hd
    assert F.decode_bytes(m, S) - F.weight_bytes(m) == F.kv_bytes_per_token(m) * (S + 1)
    # 2 N per decode token, N the matrix-product parameters (embedding
    # gather excluded), plus attention
    n = m["n_layers"] * F.layer_matmul_params(m) + F.head_params(m)
    assert F.decode_flops(m, 0) == 2 * n + 4 * L * H * hd


def test_least_seconds_picks_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert F.least_seconds(1000, 10, peak) == 10.0
    assert F.least_seconds(100, 100, peak) == 10.0
    m = model("olmo-1b")["model"]
    v5e = json.loads((CONFIGS.parent / "peaks.json").read_text())["TPU v5 lite"]
    # olmo-1b decode at B=1 is bound by memory: about 2.9 ms
    t = F.least_seconds(F.decode_flops(m, 100), F.decode_bytes(m, 100), v5e)
    assert 2.8e-3 < t < 3.0e-3


GOLDEN = json.loads((Path(__file__).resolve().parent / "data"
                     / "dense_golden.json").read_text())["counts"]
ARCH_COUNTS = ("prefill_flops", "decode_flops", "decode_bytes", "param_count",
               "weight_bytes")


@pytest.mark.parametrize("count", sorted(GOLDEN["olmo-1b"]))
@pytest.mark.parametrize("name", ["olmo-1b", "deepseek-7b"])
def test_counts_as_recorded(name, count):
    """Every count, as bench/flops.py and the dense module give it, equals
    the one recorded before the architecture seam."""
    from bench.arch import dense

    m = model(name)["model"]
    want = GOLDEN[name][count]
    fns = [getattr(F, count)] + ([getattr(dense, count)]
                                 if count in ARCH_COUNTS else [])
    for fn in fns:
        if isinstance(want, dict):
            assert {k: fn(m, int(k)) for k in want} == want
        else:
            assert fn(m) == want

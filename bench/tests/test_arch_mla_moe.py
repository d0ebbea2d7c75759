"""The MLA/MoE architecture module (``bench/arch/mla_moe.py``): its reference
against the program on the same seeded weights, a whole run of the harness
at a CPU size, its counts at the published size, and the reader of the
program's routing counts."""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, run
from bench import trace as T
from bench import weights as W
from bench.arch import mla_moe
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]

# moonlight-16b-a3b-ep4 at its reduced() widths: one dense layer, three MoE
# layers of 8 routed experts (2 held), top-2, MLA, untied head
MODEL = {"n_layers": 4, "first_k_dense": 1, "d_model": 64, "n_heads": 4,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "d_ff_dense": 256, "d_ff_expert": 128,
         "n_experts_router": 8, "experts_held": 2, "first_held_expert": 0,
         "top_k": 2, "n_shared_experts": 2,
         "routed_scaling": 2.446, "vocab_size": 256, "norm_eps": 1e-05,
         "rope_theta": 50000.0, "tie_embeddings": False, "dtype": "bfloat16",
         "router_std": 0.125, "router_bias_std": 0.05,
         "routed_down_scale": 0.25}
CONFIG = {"model": MODEL, "bench_arch": "mla_moe",
          "program": {"arch": "moonlight-16b-a3b-ep4", "embed_rows": 256,
                      "reduced": True},
          "check": {"logit_gap_limit": 0.05}}
PUBLISHED = json.loads((ROOT / "bench/configs/moonlight-16b-a3b.json")
                       .read_text())["model"]


def test_reference_is_the_program_in_float32():
    """The program run in float32 on the seeded (bfloat16-rounded) weights
    gives the reference's logits, prefill and decode alike; in bfloat16 it
    does not, by more than the tolerance."""
    from repro.models import model as M

    seed = 2 ** 33 + 9
    pc = harness.program_config(CONFIG, mla_moe)
    flat = W.make_flat(seed, mla_moe.weight_groups(MODEL, 256))
    params = mla_moe.nest(jax.device_get(flat), MODEL)
    seq = (np.arange(30) * 71 % 256).astype(np.int32)
    ref = mla_moe.logits_at(seed, MODEL, 256, [seq], [np.arange(26, 30)])[0]

    def program(cfg, p):
        lg, cache = M.prefill(cfg, p, {"tokens": jnp.asarray(seq[None, :27])}, 32)
        out = [lg[0]]
        for i in range(27, 30):
            lg, cache = M.decode_step(cfg, p, cache, jnp.asarray(seq[i:i + 1]),
                                      jnp.int32(i))
            out.append(lg[0])
        return np.stack([np.asarray(o[:256], np.float32) for o in out])

    f32 = pc.replace(param_dtype="float32", compute_dtype="float32")
    got = program(f32, jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params))
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=0)
    assert np.abs(program(pc, params) - ref).max() > 2e-3


def test_sound_run_is_correct():
    """The harness end to end at a CPU size: weights from the seed, the MRM
    and the engine serving the MLA/MoE program, the reference's check."""
    cell = {"name": "tiny", "config": "tiny", "traffic": "tiny", "chips": 1}
    res = run.run_cell(tiny.bench(), cell, CONFIG, tiny.CLOSED, 2 ** 31 + 4242,
                       3.0, False, tiny.PEAK, lambda m: None, time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 10 and res["failed"] == 0


def test_param_count_and_bytes_at_the_published_size():
    """Experts 0-15 of 64 in each of 26 MoE layers, the rest whole:
    5,163,971,712 parameters, as the seeded layout draws them and as the
    program's config counts them (which, as for the dense models in
    test_flops.py, counts 2 * d_model more norm scales a layer)."""
    from repro.configs import get_config

    assert mla_moe.param_count(PUBLISHED) == 5_163_971_712
    assert mla_moe.weight_bytes(PUBLISHED) == 10_327_943_424
    assert (get_config("moonlight-16b-a3b-ep4").param_count()
            == 5_163_971_712 + 2 * 2048 * 27)
    stem, groups = mla_moe.weight_groups(PUBLISHED, 163840)
    drawn = sum(int(np.prod(s)) for s, _ in stem.values()) + sum(
        n * sum(int(np.prod(s)) for s, _ in leaves.values())
        for _, n, leaves in groups)
    assert drawn == 5_163_971_712


def test_counts_at_a_hand_checked_position():
    m, pos = PUBLISHED, 1023
    # matrix parameters one token multiplies through: 27 x 13,762,560 of
    # attention (expanded or absorbed, the same here), 69,206,016 of the
    # dense MLP, and per MoE layer the router 131,072, the shared experts
    # 17,301,504 and 1.5 expected held experts of 8,650,752: 1,231,421,440
    token = 27 * 13_762_560 + 69_206_016 + 26 * (131_072 + 17_301_504
                                                  + 1.5 * 8_650_752)
    assert token == 1_231_421_440
    head = 2 * 163_840 * 2048
    # absorbed scores over 1024 entries of c_kv (512) and k_pe (64), and
    # the weighted sum of c_kv: 2 x 16 x (2 x 512 + 64) a layer and entry
    assert mla_moe.decode_flops(m, pos) == (
        2 * token + head + 2 * 27 * 16 * 1024 * 1088) == 4_096_524_288
    # 1020 tokens through the expanded attention (16 x (192 + 128) a key)
    assert mla_moe.prefill_flops(m, 1020) == (
        2 * 1020 * token + 2 * 27 * 16 * 320 * 1020 * 1021 // 2 + head)
    # every weight outside the routed experts once (the embedding's one
    # row, not its table), 1.5 expected experts a layer, and 1024 latent
    # entries of 27 x 576 x 2 bytes
    weights = (5_163_971_712 - 26 * 16 * 8_650_752 - 163_840 * 2048 + 2048
               + 26 * 1.5 * 8_650_752)
    assert mla_moe.decode_bytes(m, pos) == 2 * weights + 31_104 * 1024
    assert 3.1e9 < mla_moe.decode_bytes(m, 0) < 3.2e9


def _decode_spans(stats):
    tr = T.Trace(devices=1)
    tr.host.append(T.HostEvent(0, 10e9, "window", 0, {}))
    for i, st in enumerate(stats):
        tr.host.append(T.HostEvent((1 + i) * 1e9, (1.5 + i) * 1e9,
                                   "engine.decode", 1, dict(st, req=i)))
    tr.host.append(T.HostEvent(11e9, 12e9, "engine.decode", 1,
                               {"req": 9, "experts_touched": 9.0}))
    return tr


def _view(tr):
    return run.RunView({}, {}, {}, {}, None, {}, tr,
                       None if tr is None else T.window(tr))


def test_experts_touched_reader():
    tr = _decode_spans([{"experts_touched": 1.25, "held_share": 0.2},
                        {"experts_touched": 1.75, "held_share": 0.3},
                        {"steps": 4}])
    assert run.read_metric("moe_experts_touched", _view(tr)) == pytest.approx(1.5)
    # a dense model's spans, and an untraced run, give nothing
    assert run.read_metric("moe_experts_touched",
                           _view(_decode_spans([{"steps": 4}]))) is None
    assert run.read_metric("moe_experts_touched", _view(None)) is None


@pytest.mark.parametrize("metric", ["decode_roofline", "mfu"])
def test_cell_readers_are_the_accepted_ones_over_its_counts(metric):
    """``<metric>.mla_moe`` reads what ``<metric>`` reads over the view's
    architecture module, on a recorded trace."""
    from bench import mix

    tr = T.load(str(Path(__file__).resolve().parent / "data" / "olmo-1s.xplane.pb"))
    prompts = {ex.req: mix.Spec(ex.req, 0, np.zeros(1500, np.int32), 13)
               for ex in tr.executions if ex.req is not None}
    view = run.RunView({}, PUBLISHED, {}, tiny.PEAK, None, prompts, tr,
                       T.window(tr), mla_moe)
    got = run.read_metric(f"{metric}.mla_moe", view)
    assert got is not None and got == run.read_metric(metric, view)


def test_limit_readings_at_a_cpu_size():
    """``bench/limits_mla_moe.py`` at the tiny size: the program fed the
    reference's input agrees with it layer by layer, and a planted fault
    in the routed path lies further below the reference than the sound
    program."""
    from bench import limits_mla_moe as LM

    r = LM.readings(CONFIG, tiny.CLOSED, 2 ** 31 + 17, routes=True, serve=True,
                    faults=("drop", "roll"), log=lambda m: None)
    n_moe = MODEL["n_layers"] - MODEL["first_k_dense"]
    assert len(r["free_differ"]) == len(r["forced_differ"]) == n_moe
    assert len(r["forced_err"]) == MODEL["n_layers"]
    assert max(r["forced_differ"]) < 0.2
    assert max(r["forced_err"]) < 0.05      # bfloat16 rounding of one layer
    assert r["fault_drop_gap"] > r["logit_gap"]
    assert r["fault_roll_gap"] > r["logit_gap"]

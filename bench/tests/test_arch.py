"""The architecture seam: each configuration names the module that gives the
harness its weights, reference, program check and counts, and a module
other than the dense one is used as it stands, found by name."""
import json
from pathlib import Path

import numpy as np
import pytest

import bench.arch as A
from bench import harness, mix, run
from bench import trace as T
from bench import weights as W
from bench.arch import dense
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]

COUNTING = '''"""The dense decoder, counting every call of the interface."""
from bench.arch import INTERFACE, dense

CALLS = {}


def _counted(name):
    inner = getattr(dense, name)

    def call(*args, **kwargs):
        CALLS[name] = CALLS.get(name, 0) + 1
        return inner(*args, **kwargs)
    return call


for _name in INTERFACE:
    globals()[_name] = _counted(_name)
'''


@pytest.fixture
def counting(tmp_path, monkeypatch):
    """``bench_arch: "counting"``, found in a temporary directory by the
    same loader."""
    (tmp_path / "counting.py").write_text(COUNTING)
    monkeypatch.setattr(A, "DIR", tmp_path)
    return dict(tiny.CONFIG, bench_arch="counting")


def test_every_configuration_names_a_module():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        config = json.loads((ROOT / c["file"]).read_text())
        assert (A.DIR / f"{config['bench_arch']}.py").is_file()
        mod = A.load(config)
        assert all(callable(getattr(mod, f)) for f in A.INTERFACE)
        assert config["reference"].startswith(
            f"bench/arch/{config['bench_arch']}.py")


@pytest.mark.parametrize("arch", ["no_such_arch", None, "../weights"])
def test_unknown_module_fails_at_setup(arch):
    config = {k: v for k, v in tiny.CONFIG.items() if k != "bench_arch"}
    if arch is not None:
        config["bench_arch"] = arch
    with pytest.raises(ValueError, match="bench_arch"):
        harness.Cell(config, tiny.TRAFFIC, 1, log=lambda m: None).setup()


def test_module_lacking_the_interface_is_refused(tmp_path, monkeypatch):
    (tmp_path / "partial.py").write_text(
        "from bench.arch.dense import nest, logits_at\n")
    monkeypatch.setattr(A, "DIR", tmp_path)
    with pytest.raises(ValueError, match="lacks"):
        A.load({"bench_arch": "partial"})


def test_cell_uses_the_named_module(counting):
    c = harness.Cell(counting, tiny.TRAFFIC, 2 ** 31 + 77, log=lambda m: None)
    try:
        assert c.arch is not dense and c.arch.CALLS == {}
        c.setup()
        assert c.arch.CALLS == {"program_fields": 1, "weight_groups": 1,
                                "nest": tiny.TRAFFIC["variants"]}
        win = c.run_window(1.0)
        picked = c.sample([s for s in win.requests if s.error is None], 4)
        c.release()
        g = c.logit_gaps(picked)
    finally:
        c.close()
    assert picked and g["gap"] <= tiny.CONFIG["check"]["logit_gap_limit"]
    assert c.arch.CALLS["logits_at"] == len({s.variant for s in picked})


def test_count_readers_take_the_views_module(counting):
    """``RunView.arch`` is the cell's module: the count readers take their
    counts from it, and read what the dense module gives."""
    tr = T.load(str(Path(__file__).resolve().parent / "data"
                    / "olmo-1s.xplane.pb"))
    lo, hi = T.window(tr)
    config = json.loads((ROOT / "bench/configs/olmo-1b.json").read_text())
    model = config["model"]
    prompts = {ex.req: mix.Spec(ex.req, 0, np.zeros(1500, np.int32), 13)
               for ex in tr.executions if ex.req is not None}
    arch = A.load(counting)
    for metric, calls in [
            ("decode_roofline", {"decode_flops", "decode_bytes"}),
            ("mfu", {"prefill_flops", "decode_flops"})]:
        views = [run.RunView({}, model, {}, tiny.PEAK, None, prompts, tr,
                             (lo, hi), a) for a in (arch, dense)]
        arch.CALLS.clear()
        got, want = (run.read_metric(metric, v) for v in views)
        assert got is not None and got == want
        assert set(arch.CALLS) == calls


def test_two_groups_draw_from_distinct_keys():
    """A leading layer and a stack: each group's leaves have keys of their
    own, ``layer_f32`` gives ``make_flat``'s numbers, and a group draws the
    same bits whatever other groups the layout holds."""
    leaf = {"mlp/w": ((8, 16), 0.1), "norm/scale": ((8,), 0.0)}
    stem = {"embed": ((32, 8), 0.02)}
    groups = [("dense_layers", 1, leaf), ("layers", 3, leaf)]
    seed = 2 ** 35 + 3
    flat = W.make_flat(seed, (stem, groups))
    assert flat["dense_layers/mlp/w"].shape == (1, 8, 16)
    assert flat["layers/mlp/w"].shape == (3, 8, 16)
    first = np.asarray(flat["dense_layers/mlp/w"][0], np.float32)
    stack = np.asarray(flat["layers/mlp/w"], np.float32)
    assert all(not np.array_equal(first, stack[i]) for i in range(3))
    assert len({stack[i].tobytes() for i in range(3)}) == 3
    for group in groups:
        prefix, count, _ = group
        for i in range(count):
            for name, v in W.layer_f32(seed, group, i).items():
                np.testing.assert_array_equal(
                    np.asarray(flat[f"{prefix}/{name}"][i], np.float32),
                    np.asarray(v))
    alone = W.make_flat(seed, (stem, groups[1:]))
    for k, v in alone.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(flat[k]))

"""Architecture modules: what the harness needs of one kind of model.

A configuration file names its module by the top-level key ``"bench_arch"``:
``bench/arch/<bench_arch>.py``. The module gives

- ``program_fields(config) -> dict``: the ``ModelConfig`` attributes, and
  their values, that the program's config must show for this file;
- ``weight_groups(model, embed_rows) -> (stem, groups)``: the stem leaves
  ``{leaf: (shape, std)}``, then the stacked layer groups as
  ``[(prefix, count, {leaf: (shape, std)}), ...]`` (``bench/weights.py``);
- ``nest(flat, model) -> dict``: the flat weights as the program's tree;
- ``logits_at(seed, model, embed_rows, seqs, rows, fp8=False)``: the float32
  reference's logits at the given positions of each sequence, with float8
  matrix inputs for the control;
- ``prefill_flops(model, prompt_len)``, ``decode_flops(model, pos)``,
  ``decode_bytes(model, pos)``, ``param_count(model)`` and
  ``weight_bytes(model)``: the counts the per-layer metrics divide by.
"""
from __future__ import annotations

import importlib.util
import re
import sys
from pathlib import Path

DIR = Path(__file__).resolve().parent

INTERFACE = ("program_fields", "weight_groups", "nest", "logits_at",
             "prefill_flops", "decode_flops", "decode_bytes", "param_count",
             "weight_bytes")


def load(config: dict):
    """The architecture module the configuration file names; refused if the
    file names none, or one that does not exist or lacks the interface."""
    name = config.get("bench_arch")
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z_]\w*", name):
        raise ValueError(f"the configuration file names no valid "
                         f"'bench_arch' (got {name!r})")
    path = DIR / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"bench_arch {name!r}: no architecture module {path}")
    spec = importlib.util.spec_from_file_location(f"bench_arch_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod    # as an import would: dataclasses need it
    spec.loader.exec_module(mod)
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"bench_arch {name!r} ({path}) lacks {missing}")
    return mod

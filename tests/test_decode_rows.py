"""``decode_step_rows``: one decode step over rows of one dense model, each
with its own cache at its own length and position, equals a ``decode_step``
per row; other families are refused."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import decode_step, decode_step_rows, init_params, prefill


@pytest.fixture(scope="module")
def dense():
    cfg = get_config("olmo-1b").reduced().replace(n_layers=2)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _primed(cfg, params, S, max_len, seed):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (1, S), 0,
                              cfg.vocab_size, jnp.int32)
    logits, cache = jax.jit(lambda p, t: prefill(cfg, p, {"tokens": t},
                                                 max_len))(params, toks)
    return jnp.argmax(logits, -1).astype(jnp.int32), cache


# (prompt, max_len) per row: different lengths and positions; equal lengths
@pytest.mark.parametrize("rows", [((12, 20), (5, 9)), ((7, 16), (7, 16)),
                                  ((3, 8), (10, 13), (6, 30))])
def test_rows_equal_one_decode_step_per_row(dense, rows):
    cfg, params = dense
    primed = [_primed(cfg, params, S, T, i) for i, (S, T) in enumerate(rows)]
    caches = [c for _, c in primed]
    pos = [S for S, _ in rows]
    tokens = jnp.concatenate([t for t, _ in primed])
    step = jax.jit(lambda p, c, t, i: decode_step_rows(cfg, p, c, t, i))
    one = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i))
    for _ in range(3):     # three steps, each row advancing its own cache
        want = [one(params, c, tokens[r:r + 1], jnp.int32(pos[r]))
                for r, c in enumerate(caches)]
        logits, got = step(params, tuple(caches),
                           tokens, jnp.asarray(pos, jnp.int32))
        assert len(got) == len(rows)
        for r, (wl, wc) in enumerate(want):
            v = cfg.vocab_size
            np.testing.assert_allclose(
                np.asarray(logits[r, :v], np.float32),
                np.asarray(wl[0, :v], np.float32), rtol=2e-2, atol=2e-2)
            assert int(jnp.argmax(logits[r])) == int(jnp.argmax(wl[0]))
            for a, b in zip(jax.tree.leaves(got[r]), jax.tree.leaves(wc)):
                assert a.shape == b.shape
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32),
                                           rtol=2e-2, atol=2e-2)
        caches = list(got)
        tokens = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = [p + 1 for p in pos]


def test_a_row_does_not_see_its_neighbour(dense):
    cfg, params = dense
    (ta, ca), (tb, cb), (tc, cc) = (_primed(cfg, params, 6, 12, s)
                                    for s in (1, 2, 3))
    pos = jnp.asarray([6, 6], jnp.int32)
    step = jax.jit(lambda p, c, t, i: decode_step_rows(cfg, p, c, t, i))
    la, _ = step(params, (ca, cb), jnp.concatenate([ta, tb]), pos)
    lb, _ = step(params, (ca, cc), jnp.concatenate([ta, tc]), pos)
    np.testing.assert_array_equal(np.asarray(la[0], np.float32),
                                  np.asarray(lb[0], np.float32))


@pytest.mark.parametrize("arch", ["qwen3-moe-30b-a3b", "mamba2-370m"])
def test_other_families_are_refused(arch):
    cfg = get_config(arch).reduced()
    with pytest.raises(ValueError, match="dense"):
        decode_step_rows(cfg, {}, (), jnp.zeros((0,), jnp.int32),
                         jnp.zeros((0,), jnp.int32))

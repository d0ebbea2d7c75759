"""Moonlight-16B-A3B's mechanisms against a plain float32 reference, at the
reduced size of the served share (``moonlight-16b-a3b-ep4``: experts 0-1 of
8 held, top-2, one dense layer then three MoE layers, MLA, untied head):
prefill and decode through the latent cache, absorbed against expanded
attention, ``noaux_tc`` routing, the expert shares against the uncut layer,
the untied head, the engine's routing counts, and the dense models' outputs
left as they were.

The reference below reads the program's parameter tree and nothing of its
code: MLA with the latent expanded, sigmoid scores with the selection bias,
every held expert applied to every token and masked by its routing weight.
"""
import glob
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import DiskStore, MRM
from repro.models import model as M
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.serving import InferenceEngine, publish_model

HI = jax.lax.Precision.HIGHEST
SHARE = "moonlight-16b-a3b-ep4"

# float32 program against float32 reference on the CPU: they differ only in
# the order of sums, ~1e-6 of logits of size ~1; computing the program's
# matmuls in bfloat16 moves them by ~1e-2 (test_bf16_program_fails_it)
LOGIT_TOL = 2e-4


def _cfg(dtype="float32", **kw):
    return get_config(SHARE).reduced().replace(
        param_dtype=dtype, compute_dtype=dtype, **kw)


def _params(cfg, seed=0):
    """init_params, with norm scales drawn near 1, router logits about
    N(0, 1) and a selection bias large enough to change choices (init
    leaves ones, 0.02 and zeros)."""
    p = M.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def draw(path, a):
        name = path[-1].key
        z = jax.random.normal(next(keys), a.shape, jnp.float32)
        if name in ("scale", "kv_norm"):
            return (1.0 + 0.1 * z).astype(a.dtype)
        if name == "router":
            return (z / np.sqrt(cfg.d_model)).astype(a.dtype)
        if name == "router_bias":
            return 0.3 * z
        return a

    return jax.tree_util.tree_map_with_path(draw, p)


# ------------------------------------------------------------- reference
def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def _rope(x, theta):
    """x (T, ..., e): dims i and i + e/2 rotated at positions 0..T-1."""
    T, e = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, e, 2) / e)
    ang = (np.arange(T)[:, None] * inv).reshape((T,) + (1,) * (x.ndim - 2) + (-1,))
    c, s = np.cos(ang), np.sin(ang)
    a, b = x[..., : e // 2], x[..., e // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def ref_attention(cfg, p, h):
    T, H = h.shape[0], cfg.n_heads
    nope, rope, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    q = _mm("td,de->te", h, p["wq"]).reshape(T, H, nope + rope)
    kv = _mm("td,de->te", h, p["wkv_a"])
    c_kv = _rms(kv[:, :r], p["kv_norm"], cfg.norm_eps)
    k_pe = _rope(kv[:, r:], cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg.rope_theta)], -1)
    kvb = _mm("tr,re->te", c_kv, p["wkv_b"]).reshape(T, H, -1)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_pe[:, None], (T, H, rope))], -1)
    s = _mm("qhe,khe->hqk", q, k) / np.sqrt(nope + rope)
    s = jnp.where(np.tril(np.ones((T, T), bool))[None], s, -jnp.inf)
    o = _mm("hqk,khe->qhe", jax.nn.softmax(s, -1), kvb[..., nope:])
    return _mm("te,ed->td", o.reshape(T, -1), p["wo"])


def _swiglu(h, g, u, d):
    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", h, g))
               * _mm("td,df->tf", h, u), d)


def ref_route(cfg, p, h):
    scores = jax.nn.sigmoid(_mm("td,de->te", h, p["router"]))
    _, ids = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
    w = jnp.take_along_axis(scores, ids, -1)
    return ids, w / w.sum(-1, keepdims=True) * cfg.routed_scaling


def ref_moe(cfg, p, h, first, n, shared=True):
    """Experts [first, first + n) of the routed output (p holds exactly
    those), and the shared experts'."""
    ids, w = ref_route(cfg, p, h)
    out = 0.0
    for j in range(n):
        gate = jnp.sum(jnp.where(ids == first + j, w, 0.0), -1)
        out = out + gate[:, None] * _swiglu(h, p["w_gate"][j], p["w_up"][j],
                                            p["w_down"][j])
    if shared:
        out = out + _swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return out


def ref_logits(cfg, params, tokens):
    """Logits (T, vocab) of the whole sequence."""
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for stack in ("dense_layers", "layers"):
        for i in range(params[stack]["ln1"]["scale"].shape[0]):
            p = jax.tree.map(lambda a: a[i].astype(jnp.float32), params[stack])
            x = x + ref_attention(cfg, p["attn"],
                                  _rms(x, p["ln1"]["scale"], cfg.norm_eps))
            h = _rms(x, p["ln2"]["scale"], cfg.norm_eps)
            if stack == "layers":
                x = x + ref_moe(cfg, p["ffn"], h, cfg.first_held_expert,
                                cfg.n_held_experts)
            else:
                x = x + _swiglu(h, p["ffn"]["w_gate"], p["ffn"]["w_up"],
                                p["ffn"]["w_down"])
    h = _rms(x, params["final_norm"]["scale"], cfg.norm_eps)
    return _mm("td,vd->tv", h, params["lm_head"][: cfg.vocab_size])


# ------------------------------------------------------------------ tests
TOKENS = (np.arange(40) * 53 % 251).astype(np.int32)


def _program_logits(cfg, params, n_decode=3):
    """Prefill of TOKENS[:-n_decode] (last logits), then n_decode steps
    through the latent cache fed TOKENS' next tokens."""
    S = len(TOKENS) - n_decode
    lg, cache = M.prefill(cfg, params, {"tokens": jnp.asarray(TOKENS[None, :S])},
                          len(TOKENS))
    out = [lg[0]]
    for i in range(n_decode):
        lg, cache = M.decode_step(cfg, params, cache,
                                  jnp.asarray(TOKENS[S + i:S + i + 1]),
                                  jnp.int32(S + i))
        out.append(lg[0])
    return np.stack([np.asarray(o[: cfg.vocab_size], np.float32) for o in out])


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = _params(cfg)
    ref = np.asarray(ref_logits(cfg, params, TOKENS))[-4:]
    return cfg, params, ref


def test_prefill_then_decode_match_reference(model):
    cfg, params, ref = model
    got = _program_logits(cfg, params)
    np.testing.assert_allclose(got, ref, atol=LOGIT_TOL, rtol=0)
    assert np.abs(ref).max() > 0.3          # logits of a size that tests


def test_bf16_program_fails_it(model):
    """The same program with bfloat16 activations and matmuls is outside
    the tolerance: it is tight enough to see one precision step down."""
    cfg, params, ref = model
    low = _cfg("bfloat16")
    got = _program_logits(low, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params))
    assert np.abs(got - ref).max() > 10 * LOGIT_TOL


def test_absorbed_decode_matches_expanded_attention(model):
    cfg, params, _ = model
    p = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 12, cfg.d_model))
    pos = jnp.arange(12)[None]
    full = MLA.attend(cfg, p, h, pos)
    _, latent = MLA.prefill(cfg, p, h[:, :11], pos[:, :11])
    cl = MLA.write(MLA.cache_zeros(cfg, 1, 16), *latent, 0)
    one, cl = MLA.decode(cfg, p, h[:, 11:], cl, jnp.int32(11))
    np.testing.assert_allclose(np.asarray(one[0, 0]), np.asarray(full[0, 11]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(cl["c_kv"][0, :12]),
                               np.asarray(MLA.prefill(cfg, p, h, pos)[1][0][0]),
                               atol=1e-6)
    assert cl["c_kv"].shape == (1, 16, cfg.kv_lora_rank)


def test_noaux_tc_bias_chooses_scores_weigh():
    """The bias moves expert 5 into the top 2; its weight comes from its
    unbiased score, the k weights are renormalized, then scaled."""
    cfg = _cfg(n_experts=8, top_k=2, held_experts=0)
    logits = np.array([[2.0, 1.5, 1.0, 0.5, 0.0, -1.0, -2.0, -3.0]], np.float32)
    d = cfg.d_model
    p = {"router": np.zeros((d, 8), np.float32),
         "router_bias": np.array([0, 0, 0, 0, 0, 0.9, 0, 0], np.float32)}
    p["router"][0] = logits[0]
    x = np.zeros((1, d), np.float32)
    x[0, 0] = 1.0
    w, ids, _ = MOE.router_topk(cfg, p, jnp.asarray(x))
    assert sorted(np.asarray(ids[0]).tolist()) == [0, 5]
    s = 1 / (1 + np.exp(-logits[0, [0, 5]]))
    order = np.argsort(np.asarray(ids[0]))
    np.testing.assert_allclose(np.asarray(w[0])[order],
                               s / s.sum() * cfg.routed_scaling, rtol=1e-6)


def test_four_shares_sum_to_the_uncut_layer():
    """Experts 0-1, 2-3, 4-5 and 6-7 on four chips: their routed parts,
    with the shared experts counted once, are the uncut reference layer."""
    uncut = _cfg(held_experts=0)
    p = jax.tree.map(lambda a: a[0], _params(uncut, seed=4)["layers"]["ffn"])
    h = jax.random.normal(jax.random.PRNGKey(5), (24, uncut.d_model))
    shared = _swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    parts, held = [], []
    for c in range(4):
        cfg = _cfg(held_experts=2, first_held_expert=2 * c)
        share = {k: (v[2 * c:2 * c + 2] if k in ("w_gate", "w_up", "w_down")
                     else v) for k, v in p.items()}
        out, _, counts = MOE.moe_ragged(cfg, share, h)
        parts.append(np.asarray(out - shared))
        held.append(int(counts[1]))
    whole = ref_moe(uncut, p, h, 0, 8)
    np.testing.assert_allclose(sum(parts) + np.asarray(shared),
                               np.asarray(whole), atol=1e-5, rtol=1e-5)
    assert sum(held) == 24 * uncut.top_k     # each assignment lands once


def test_untied_head_reads_lm_head(model):
    cfg, params, _ = model
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 2, cfg.d_model))
    base = np.asarray(M._logits(cfg, params, x))
    other = dict(params, embed=params["embed"] * 0)
    np.testing.assert_array_equal(np.asarray(M._logits(cfg, other, x)), base)
    flipped = dict(params, lm_head=-params["lm_head"])
    np.testing.assert_allclose(np.asarray(M._logits(cfg, flipped, x))[..., :cfg.vocab_size],
                               -base[..., :cfg.vocab_size], rtol=1e-6)
    with pytest.raises(ValueError, match="MLA"):
        M.decode_step_rows(cfg.replace(n_experts=0, family="dense"), params,
                           (), jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))


def _digest(*trees):
    h = hashlib.sha1()
    for a in jax.tree.leaves(trees):
        h.update(np.asarray(a, np.float32).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("arch,want", [
    ("olmo-1b", ("603b1c81d99a612c", "fa4576d1b0d99215")),
    ("deepseek-7b", ("5e7252a066a42653", "0f0cb862145b9562"))])
def test_dense_prefill_and_decode_unchanged(arch, want):
    """The dense models' reduced prefill and decode step give, bit for bit,
    what they gave before MLA, the mixed layout and the untied head (the
    digests were taken from the code before them)."""
    cfg = get_config(arch).reduced()
    p = M.init_params(cfg, jax.random.PRNGKey(0))
    toks = (jnp.arange(24, dtype=jnp.int32)[None] * 37) % cfg.vocab_size
    lg, cache = jax.jit(lambda p, b: M.prefill(cfg, p, b, 32))(p, {"tokens": toks})
    dl, dc = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))(
        p, cache, jnp.argmax(lg, -1).astype(jnp.int32), jnp.int32(24))
    assert (_digest(lg, cache), _digest(dl, dc)) == want


def test_engine_counts_on_the_decode_span(tmp_path):
    """Traced, ``engine.decode`` carries the held experts touched per MoE
    layer and step and the held share of assignments, as the decode
    steps count them; untraced it carries neither."""
    cfg = get_config(SHARE).reduced().replace(moe_impl="ragged")
    params = _params(cfg, seed=7)
    disk = DiskStore(str(tmp_path / "models"))
    publish_model(disk, cfg, params, name="ml")
    engine = InferenceEngine(disk, MRM(disk, device_capacity=1 << 30))
    prompt = TOKENS[None, :16]
    engine.generate("ml", prompt, 6)                        # untraced
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        out, _ = engine.generate("ml", prompt, 6)
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(glob.glob(str(tmp_path / "trace/**/*.xplane.pb"),
                                         recursive=True)[0])
    stats = [dict(e.stats) for p in pd.planes for line in p.lines
             for e in line.events if e.name == "engine.decode"]
    assert len(stats) == 1
    _, cache = jax.jit(lambda p, b: M.prefill(cfg, p, b, 22))(
        params, {"tokens": jnp.asarray(prompt)})
    step = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos,
                                                      moe_counts=True))
    touched = held = 0
    for i in range(5):
        _, cache, c = step(params, cache, jnp.asarray(out[:, i]),
                           jnp.int32(16 + i))
        touched, held = touched + int(c[0]), held + int(c[1])
    steps = 5 * cfg.n_moe_layers
    assert stats[0]["experts_touched"] == pytest.approx(touched / steps)
    assert stats[0]["held_share"] == pytest.approx(held / (steps * cfg.top_k))
    assert 0 < held < steps * cfg.top_k

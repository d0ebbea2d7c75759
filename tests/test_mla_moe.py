"""Moonlight-16B-A3B's mechanisms against a plain float32 reference, at the
reduced size of the served share (``moonlight-16b-a3b-ep4``: experts 0-1 of
8 held, top-2, one dense layer then three MoE layers, MLA, untied head):
prefill and decode through the latent cache, absorbed against expanded
attention, ``noaux_tc`` routing, the expert shares against the uncut layer,
the untied head, the engine's routing counts, the decode step's read of only
the held experts its token reaches, and the dense models' outputs and
Moonlight's prefill left as they were.

The reference below reads the program's parameter tree and nothing of its
code: MLA with the latent expanded, sigmoid scores with the selection bias,
every held expert applied to every token and masked by its routing weight.
"""
import functools
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
    ("deepseek-7b", ("5e7252a066a42653", "0f0cb862145b9562")),
    (SHARE, ("f09548b3566aa7d0", "806500ecb457ff21"))])
def test_dense_prefill_and_decode_unchanged(arch, want):
    """The dense models' reduced prefill and decode step give, bit for bit,
    what they gave before MLA, the mixed layout and the untied head; the
    reduced Moonlight share's, what they gave before decode read only the
    touched experts (its prefill is ragged_dot's, and so is its decode: one
    token's top-2 on 2 held experts is not fewer). The digests were taken
    from the code before each."""
    cfg = get_config(arch).reduced()
    p = M.init_params(cfg, jax.random.PRNGKey(0))
    toks = (jnp.arange(24, dtype=jnp.int32)[None] * 37) % cfg.vocab_size
    lg, cache = jax.jit(lambda p, b: M.prefill(cfg, p, b, 32))(p, {"tokens": toks})
    dl, dc = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))(
        p, cache, jnp.argmax(lg, -1).astype(jnp.int32), jnp.int32(24))
    assert (_digest(lg, cache), _digest(dl, dc)) == want


def test_engine_counts_on_the_decode_span(tmp_path):
    """Traced, ``engine.decode`` carries the held experts touched and read
    per MoE layer and step and the held share of assignments, as the
    decode steps count them; untraced it carries none. With 4 of 8 experts
    held, a step's 2 assignments are fewer: it reads only those touched."""
    cfg = get_config(SHARE).reduced().replace(held_experts=4)
    assert MOE.reads_touched(cfg, 1)
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
    touched = held = read = 0
    for i in range(5):
        _, cache, c = step(params, cache, jnp.asarray(out[:, i]),
                           jnp.int32(16 + i))
        touched, held = touched + int(c[0]), held + int(c[1])
        read += int(c[2])
    steps = 5 * cfg.n_moe_layers
    assert stats[0]["experts_touched"] == pytest.approx(touched / steps)
    assert stats[0]["held_share"] == pytest.approx(held / (steps * cfg.top_k))
    assert stats[0]["experts_read"] == pytest.approx(read / steps)
    assert read == touched
    assert stats[0]["experts_read"] == stats[0]["experts_touched"]
    assert 0 < held < steps * cfg.top_k


# ------------------------------------------- decode reads touched experts
# 8 of 32 experts held, from expert 8, top-2: a call of T tokens has 2T
# assignments, fewer than the 8 held up to T = 3
TOUCHED = dict(n_experts=32, held_experts=8, first_held_expert=8)


def _touched_cfg(router="sigmoid"):
    return get_config(SHARE).reduced().replace(router_score=router, **TOUCHED)


def _ffn(cfg, seed=0):
    return jax.tree.map(lambda a: a[0], _params(cfg, seed)["layers"]["ffn"])


def _grouped(monkeypatch, fn, *args):
    """fn(*args), jitted afresh, with every expert layer on ragged_dot's
    grouped path (a jit of fn itself would reuse fn's traced program)."""
    with monkeypatch.context() as m:
        m.setattr(MOE, "reads_touched", lambda cfg, tokens: False)
        return jax.jit(lambda *a: fn(*a))(*args)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if hasattr(sub, "jaxpr") and hasattr(sub, "consts"):
                    sub = sub.jaxpr                       # ClosedJaxpr
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _prims(jaxpr):
    return {e.primitive.name for e in _eqns(jaxpr)}


def _shapes(jaxpr):
    """The shapes of every value the jaxpr and its inner jaxprs bind."""
    out = set()
    for e in _eqns(jaxpr):
        for v in list(e.invars) + list(e.outvars):
            if hasattr(v.aval, "shape"):
                out.add(tuple(v.aval.shape))
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                for var in getattr(sub, "invars", ()):
                    out.add(tuple(var.aval.shape))
    return out


def _force(cfg, p, x, experts):
    """p with routing that sends token 0 of x to ``experts``: the selection
    bias (sigmoid), or router columns along x[0] (softmax)."""
    p = dict(p)
    if cfg.router_score == "sigmoid":
        p["router_bias"] = jnp.zeros_like(p["router_bias"]).at[
            jnp.asarray(experts)].set(10.0)
    else:
        along = x[0].astype(jnp.float32) / jnp.sum(x[0].astype(jnp.float32) ** 2)
        p["router"] = p["router"].at[:, jnp.asarray(experts)].add(
            8.0 * along[:, None])
    return p


@pytest.mark.parametrize("router", ["sigmoid", "softmax"])
@pytest.mark.parametrize("case", ["T1", "T2", "none_held", "all_held"])
def test_touched_experts_match_the_grouped_path(monkeypatch, router, case):
    """Where a call has fewer assignments than held experts, reading only
    the touched experts gives ragged_dot's grouped output within one
    bfloat16 rounding step, and the same counts: touched, assignments
    held, and now read (the touched, not all 8)."""
    cfg = _touched_cfg(router)
    T = 2 if case == "T2" else 1
    p = _ffn(cfg, seed=11)
    x = jax.random.normal(jax.random.PRNGKey(12), (T, cfg.d_model)
                          ).astype(cfg.cdtype)
    # held: 8..15; T1 and T2 send token 0 (sigmoid: each token) to expert 9
    p = _force(cfg, p, x, {"none_held": [0, 20], "all_held": [9, 14]}
               .get(case, [9]))
    assert MOE.reads_touched(cfg, T)
    layer = functools.partial(MOE.moe_ragged, cfg)
    got, _, c = jax.jit(layer)(p, x)
    want, _, cw = _grouped(monkeypatch, layer, p, x)
    c, cw = np.asarray(c).tolist(), np.asarray(cw).tolist()
    assert c == [cw[0], cw[1], cw[0]] and cw[2] == cfg.n_held_experts
    held = {"none_held": 0, "all_held": 2}.get(case)
    if held is not None:
        assert c[:2] == [held, held]
    else:
        assert c[0] >= 1 and c[1] >= 1
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if case == "none_held":
        np.testing.assert_array_equal(got, want)          # shared part alone
    # each path rounds its own bfloat16 intermediates: one step of each
    step = np.abs(want).max() * 2.0 ** -7
    np.testing.assert_allclose(got, want, rtol=0, atol=2 * step)
    if router == "sigmoid":         # and the touched path is no less exact
        f32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        ref = np.asarray(ref_moe(cfg, f32, x.astype(jnp.float32),
                                 cfg.first_held_expert, cfg.n_held_experts))
        assert np.abs(got - ref).max() <= np.abs(want - ref).max() + step / 2


def test_many_assignments_take_ragged_dot():
    """The rule is the call's shape: 3 tokens x top-2 < 8 held loops over
    the touched experts; 4 x 2 = 8 held goes to ragged_dot and reads all."""
    cfg = _touched_cfg()
    p = _ffn(cfg)
    for T, grouped in ((3, False), (4, True)):
        x = jax.random.normal(jax.random.PRNGKey(T), (T, cfg.d_model)
                              ).astype(cfg.cdtype)
        assert MOE.reads_touched(cfg, T) is not grouped
        jx = jax.make_jaxpr(lambda p, x: MOE.moe_ragged(cfg, p, x))(p, x)
        assert ("ragged_dot_general" in _prims(jx.jaxpr)) is grouped
        touched, _, read = np.asarray(MOE.moe_ragged(cfg, p, x)[2]).tolist()
        assert read == (cfg.n_held_experts if grouped else touched)


def test_moe_decode_reads_no_whole_bank(monkeypatch):
    """The MoE decode step holds no ragged_dot and no value of one layer's
    whole expert bank (n, D, F): each touched expert's matrices are sliced
    from the stacked banks. Prefill keeps ragged_dot. The step's logits
    are the grouped path's within bfloat16 rounding, its counts equal."""
    cfg = _touched_cfg()
    params = _params(cfg, seed=3)
    toks = jnp.asarray(TOKENS[None, :16])
    pre = jax.jit(lambda p, b: M.prefill(cfg, p, b, 24))
    lg, cache = pre(params, {"tokens": toks})
    tok = jnp.argmax(lg, -1).astype(jnp.int32)

    def step(p, c, t):
        return M.decode_step(cfg, p, c, t, jnp.int32(16), moe_counts=True)

    jx = jax.make_jaxpr(step)(params, cache, tok).jaxpr
    n, d, f = cfg.n_held_experts, cfg.d_model, cfg.d_ff
    assert "ragged_dot_general" not in _prims(jx)
    assert not {(n, d, f), (n, f, d)} & _shapes(jx)
    assert {(1, 1, d, f), (1, 1, f, d)} <= _shapes(jx)
    assert "ragged_dot_general" in _prims(
        jax.make_jaxpr(lambda p, b: M.prefill(cfg, p, b, 24))(
            params, {"tokens": toks}).jaxpr)

    got, _, c = jax.jit(step)(params, cache, tok)
    want, _, cw = _grouped(monkeypatch, step, params, cache, tok)
    assert np.asarray(c).tolist() == [int(cw[0]), int(cw[1]), int(cw[0])]
    assert int(cw[2]) == n * cfg.n_moe_layers
    got = np.asarray(got[0, :cfg.vocab_size], np.float32)
    want = np.asarray(want[0, :cfg.vocab_size], np.float32)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2 * np.abs(want).max() * 2.0 ** -7)

"""Quantized optimizer-state subsystem (src/repro/quant/): codecs, policy
resolution, 8-bit GaLore parity, int4 projectors, checkpointing, kernels."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import GaLoreConfig, TrainConfig, get_config
from repro.core.galore import galore, galore_state_bytes, plan_for_params
from repro.core.projector import read_projector, store_projector
from repro.kernels import ops, ref
from repro.optim.adam import scale_by_adam
from repro.quant import QuantPolicy, codec

HP = dict(b1=0.9, b2=0.999, eps=1e-8)


def _params(key=None):
    key = jax.random.PRNGKey(0) if key is None else key
    return {
        "wide": jax.random.normal(key, (48, 130)),
        "tall": jax.random.normal(jax.random.fold_in(key, 1), (130, 48)),
        "stack": jax.random.normal(jax.random.fold_in(key, 2), (3, 40, 96)),
        "bias": jax.random.normal(jax.random.fold_in(key, 3), (130,)),
        "embed": jax.random.normal(jax.random.fold_in(key, 4), (200, 64)),
    }


def _grads(params, key, i=0):
    return jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.fold_in(key, 100 + i), p.shape) * 0.1,
        params,
    )


# ---------------------------------------------------------------------------
# Codecs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("axis,shape", [(-1, (7, 130)), (-1, (16, 128)),
                                        (-2, (130, 7)), (-2, (3, 200, 9))])
@pytest.mark.parametrize("signed", [True, False])
def test_axis_codec_roundtrip(axis, shape, signed):
    """Axis-blocked int8: shape-preserving codes, blocked scales, bounded
    error — including non-divisible tails."""
    x = jax.random.normal(jax.random.PRNGKey(0), shape) * 3.0
    if not signed:
        x = jnp.abs(x)
    codes, scales = codec.quantize_axis(x, axis=axis, signed=signed)
    assert codes.shape == shape and codes.dtype == jnp.uint8
    nb = -(-shape[axis] // codec.QBLOCK)
    expect_scale = list(shape)
    expect_scale[axis] = nb
    assert scales.shape == tuple(expect_scale)
    x2 = codec.dequantize_axis(codes, scales, axis=axis, signed=signed)
    rel = float(jnp.max(jnp.abs(x - x2)) / (jnp.max(jnp.abs(x)) + 1e-12))
    assert rel < 0.05, rel


def test_int4_roundtrip_and_packing():
    p = jax.random.normal(jax.random.PRNGKey(1), (96, 24)) / 9.0
    st = codec.quant4_state(p)
    nb = -(-p.size // codec.BLOCK)
    assert st["q"].shape == (nb, codec.BLOCK // 2)  # two codes per byte
    assert st["q"].dtype == jnp.uint8 and st["scale"].shape == (nb,)
    p2 = codec.dequant4_state(st, p.shape)
    rel = float(jnp.max(jnp.abs(p - p2)) / jnp.max(jnp.abs(p)))
    assert rel < 0.12, rel  # 15-level linear map: half-step = 1/14 of absmax
    # zeros round-trip exactly (projector init invariant)
    z = codec.quant4_state(jnp.zeros((24, 8)))
    assert float(jnp.max(jnp.abs(codec.dequant4_state(z, (24, 8))))) == 0.0


def test_projector_store_read_modes():
    P = jax.random.normal(jax.random.PRNGKey(2), (48, 16)) / 7.0
    for mode, tol in [("fp32", 0.0), ("bf16", 1e-2), ("int4", 0.12)]:
        stored = store_projector(P, mode)
        back = read_projector(stored, P.shape)
        assert back.dtype == jnp.float32
        err = float(jnp.max(jnp.abs(back - P)) / jnp.max(jnp.abs(P)))
        assert err <= tol, (mode, err)
    # fp32 storage is bit-identical (the default path)
    np.testing.assert_array_equal(np.asarray(store_projector(P, "fp32")),
                                  np.asarray(P))


# ---------------------------------------------------------------------------
# Policy resolution / plans
# ---------------------------------------------------------------------------


def test_min_quant_size_gates_on_weight_not_compact_moment():
    """The historical adam8bit inconsistency: a large weight whose compact
    (r, n) moments dip under min_quant_size must STILL quantize (the floor
    applies to the weight's element count), while small leaves stay fp32."""
    params = _params()
    # wide is 48*130 = 6240 elems; its compact moments at rank 16 are
    # 16*130 = 2080 < 4096 — the old compact-size gate would drop to fp32
    qp = QuantPolicy(moments="int8", min_quant_size=4096)
    cfg = GaLoreConfig(rank=16, quant=qp)
    plans = plan_for_params(params, cfg)
    assert plans["wide"].moments == "int8"
    assert plans["tall"].moments == "int8"
    assert plans["bias"].moments == "fp32"      # 130 elems < 4096
    assert plans["embed"].moments == "int8"     # excluded from galore, large
    assert not plans["embed"].galore
    # and the state realizes the decision
    opt = galore(scale_by_adam(), cfg, **HP)
    st = opt.init(params)
    assert codec.is_qstate(st["inner"]["m"]["wide"])
    assert codec.is_qstate(st["inner"]["m"]["embed"])
    assert not codec.is_qstate(st["inner"]["m"]["bias"])


def test_policy_overrides_per_path():
    params = _params()
    qp = QuantPolicy(moments="int8", projectors="int4", min_quant_size=1,
                     overrides=(("tall", "fp32", "bf16"),))
    plans = plan_for_params(params, GaLoreConfig(rank=16, quant=qp))
    assert plans["wide"].moments == "int8" and plans["wide"].proj_store == "int4"
    assert plans["tall"].moments == "fp32" and plans["tall"].proj_store == "bf16"


def test_default_policy_keeps_layout_bit_identical():
    """All-fp32 default: no qstate dicts anywhere, projector dtype f32 —
    the state layout is exactly the pre-quantization original."""
    params = _params()
    cfg = GaLoreConfig(rank=16, update_freq=2)
    assert cfg.quant == QuantPolicy() and not cfg.quant.active
    opt = galore(scale_by_adam(), cfg)
    st = opt.init(params)
    for path, leaf in jax.tree_util.tree_leaves_with_path(st):
        assert hasattr(leaf, "dtype"), path  # arrays only, no codec dicts
    assert st["proj"]["wide"].dtype == jnp.float32
    assert st["inner"]["m"]["wide"].dtype == jnp.float32
    # structurally identical to the fused variant (checkpoint interchange)
    fused = galore(scale_by_adam(), cfg, fused_adam=True, **HP)
    assert (jax.tree_util.tree_structure(st)
            == jax.tree_util.tree_structure(fused.init(params)))


# ---------------------------------------------------------------------------
# 8-bit GaLore parity (acceptance: ≤ 5e-2 relative drift over 50 steps)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stochastic", [False, True])
def test_quantized_paths_track_fp32_oracle_50_steps(stochastic):
    key = jax.random.PRNGKey(7)
    params = _params(key)
    qp = QuantPolicy(moments="int8", projectors="int4", min_quant_size=1000,
                     stochastic_round=stochastic)
    cfg_q = GaLoreConfig(rank=16, update_freq=5, scale=0.25, quant=qp)
    cfg_f = GaLoreConfig(rank=16, update_freq=5, scale=0.25)
    oracle = galore(scale_by_adam(), cfg_f)          # fp32 composable oracle
    comp_q = galore(scale_by_adam(), cfg_q, **HP)    # quantized composable
    fused_q = galore(scale_by_adam(), cfg_q, fused_adam=True, **HP)
    st_o, st_c, st_f = oracle.init(params), comp_q.init(params), fused_q.init(params)
    assert (jax.tree_util.tree_structure(st_c)
            == jax.tree_util.tree_structure(st_f))
    p_o = p_c = p_f = params
    step = lambda p, u: jax.tree_util.tree_map(lambda a, b: a - 0.01 * b, p, u)
    for i in range(50):
        g = _grads(params, key, i)
        u_o, st_o = oracle.update(g, st_o, p_o)
        u_c, st_c = comp_q.update(g, st_c, p_c)
        u_f, st_f = fused_q.update(g, st_f, p_f)
        p_o, p_c, p_f = step(p_o, u_o), step(p_c, u_c), step(p_f, u_f)
    for k in params:
        for p_q, tag in [(p_c, "composable"), (p_f, "fused")]:
            drift = float(jnp.linalg.norm(p_q[k] - p_o[k])
                          / (jnp.linalg.norm(p_o[k]) + 1e-12))
            assert drift < 5e-2, (k, tag, drift)


def test_int4_projector_refresh_and_lazy_skip():
    """int4 storage survives refreshes; lazy_refresh keeps the state
    unchanged when the quantized codes would be identical."""
    key = jax.random.PRNGKey(9)
    U = jnp.linalg.qr(jax.random.normal(key, (48, 4)))[0]
    params = {"w": jnp.zeros((48, 96))}
    qp = QuantPolicy(projectors="int4", lazy_refresh=True, min_quant_size=1)
    cfg = GaLoreConfig(rank=4, update_freq=1, scale=1.0, projector="svd", quant=qp)
    from repro.optim.transform import GradientTransformation

    identity_inner = GradientTransformation(lambda p: (), lambda g, s, p=None: (g, s))
    opt = galore(identity_inner, cfg)
    st = opt.init(params)
    assert codec.is_qstate(st["proj"]["w"])
    C = jax.random.normal(jax.random.fold_in(key, 0), (4, 96))
    _, st = opt.update({"w": U @ C}, st, params)
    q_first = np.asarray(st["proj"]["w"]["q"]).copy()
    s_first = np.asarray(st["proj"]["w"]["scale"]).copy()
    assert q_first.any()  # a real projector landed in int4 storage
    # a tiny in-subspace perturbation rotates P imperceptibly: the int4
    # codes come out identical, so the lazy refresh must keep the stored
    # state byte-identical — scales included, even though a fresh
    # quantization would recompute them slightly differently
    Cp = C + 1e-4 * jax.random.normal(jax.random.fold_in(key, 1), (4, 96))
    _, st = opt.update({"w": U @ Cp}, st, params)
    np.testing.assert_array_equal(np.asarray(st["proj"]["w"]["q"]), q_first)
    np.testing.assert_array_equal(np.asarray(st["proj"]["w"]["scale"]), s_first)
    # contrast: without lazy_refresh the same sequence rewrites the scales
    cfg_nl = dataclasses.replace(
        cfg, quant=dataclasses.replace(qp, lazy_refresh=False))
    opt_nl = galore(identity_inner, cfg_nl)
    st_nl = opt_nl.init(params)
    _, st_nl = opt_nl.update({"w": U @ C}, st_nl, params)
    _, st_nl = opt_nl.update({"w": U @ Cp}, st_nl, params)
    assert not np.array_equal(np.asarray(st_nl["proj"]["w"]["scale"]), s_first)
    # update still projects with the dequantized P (finite outputs)
    C2 = jax.random.normal(jax.random.fold_in(key, 2), (4, 96))
    u, _ = opt.update({"w": U @ C2}, st, params)
    assert bool(jnp.all(jnp.isfinite(u["w"])))


# ---------------------------------------------------------------------------
# Kernels (interpret mode) vs oracles
# ---------------------------------------------------------------------------


def _q8_inputs(key, shape, right=False):
    lead, (m, r, n) = shape[:-3], shape[-3:]
    ks = jax.random.split(key, 5)
    P = jax.random.normal(ks[0], lead + ((n, r) if right else (m, r)))
    G = jax.random.normal(ks[1], lead + (m, n))
    mom = lead + ((m, r) if right else (r, n))
    M = jax.random.normal(ks[2], mom) * 0.01
    V = jnp.abs(jax.random.normal(ks[3], mom)) * 1e-4
    W = jax.random.normal(ks[4], lead + (m, n))
    ax = -2 if right else -1
    mq, ms = codec.quantize_axis(M, axis=ax, signed=True)
    vq, vs = codec.quantize_axis(V, axis=ax, signed=False)
    return P, G, W, M, V, mq, ms, vq, vs


def _check(got, want, tag):
    for name, a, b in zip(["out", "mq", "ms", "vq", "vs"], got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape, (tag, name, a.shape, b.shape)
        if a.dtype == np.uint8:
            # codes agree to 1 ulp of the codebook (searchsorted vs the
            # kernel's midpoint-count rule differ only on exact mid hits)
            assert int(np.max(np.abs(a.astype(np.int32) - b.astype(np.int32)))) <= 1, (tag, name)
        else:
            np.testing.assert_allclose(
                a, b, rtol=2e-2, atol=2e-2 * max(np.abs(b).max(), 1e-6),
                err_msg=f"{tag} {name}")


@pytest.mark.parametrize("shape", [(64, 16, 48), (72, 16, 130),
                                   (3, 72, 16, 130), (1000, 96, 520)])
def test_fused_q8_kernel_left(shape):
    """INT8-epilogue kernel vs codec oracle — ragged tails masked in-kernel."""
    P, G, W, M, V, mq, ms, vq, vs = _q8_inputs(jax.random.PRNGKey(30), shape)
    count = jnp.int32(7)
    got = ops.galore_fused_adam8_step(P, G, mq, ms, vq, vs, count, alpha=0.25,
                                      use_pallas=True, interpret=True)
    want = ref.galore_fused_adam8_step(P, G, mq, ms, vq, vs, count, alpha=0.25)
    _check(got, want, shape)


@pytest.mark.parametrize("shape", [(130, 16, 72), (3, 130, 16, 72),
                                   (2, 3, 96, 8, 40)])
def test_fused_q8_kernel_right(shape):
    P, G, W, M, V, mq, ms, vq, vs = _q8_inputs(jax.random.PRNGKey(31), shape,
                                               right=True)
    count = jnp.int32(5)
    got = ops.galore_fused_adam8_step_right(P, G, mq, ms, vq, vs, count,
                                            alpha=0.25, use_pallas=True,
                                            interpret=True)
    want = ref.galore_fused_adam8_step_right(P, G, mq, ms, vq, vs, count,
                                             alpha=0.25)
    _check(got, want, shape)


@pytest.mark.parametrize("shape,right", [((72, 16, 130), False),
                                         ((3, 72, 16, 130), False),
                                         ((256, 16, 96), False),
                                         ((130, 16, 72), True)])
def test_fused_int4_packed_projector_matches_dequant_oracle(shape, right):
    """The in-kernel INT4 dequant claim: feeding the packed nibble codes +
    per-block absmax scales straight into the fused kernel (unpack→dequant
    in VMEM) lands on the exact update of dequantizing P on the host and
    launching with the f32 projector — no transient f32 P tree needed."""
    P, G, W, M, V, mq, ms, vq, vs = _q8_inputs(jax.random.PRNGKey(33), shape,
                                               right=right)
    Pq = codec.quant4_axis_state(P)
    Pdq = codec.dequant4_axis_state(Pq, P.shape)
    fn = (ops.galore_fused_adam8_step_right if right
          else ops.galore_fused_adam8_step)
    kw = dict(alpha=0.25, use_pallas=True, interpret=True)
    got = fn(Pq, G, mq, ms, vq, vs, jnp.int32(6), **kw)
    want = fn(Pdq, G, mq, ms, vq, vs, jnp.int32(6), **kw)
    for name, a, b in zip(["out", "mq", "ms", "vq", "vs"], got, want):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.uint8:
            np.testing.assert_array_equal(a, b, err_msg=str((shape, name)))
        else:
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-5,
                                       err_msg=str((shape, name)))


@pytest.mark.parametrize("right", [False, True])
def test_stochastic_requant_kernel_matches_oracle(right):
    """Q-GaLore stochastic rounding: the kernel's counter-hash uniforms are
    the oracle's exact uniforms, so the int8 codes must agree bitwise."""
    shape = (130, 16, 72) if right else (72, 16, 130)
    P, G, W, M, V, mq, ms, vq, vs = _q8_inputs(jax.random.PRNGKey(34), shape,
                                               right=right)
    count = jnp.int32(9)
    if right:
        got = ops.galore_fused_adam8_step_right(
            P, G, mq, ms, vq, vs, count, alpha=0.25, stochastic=True,
            use_pallas=True, interpret=True)
        want = ref.galore_fused_adam8_step_right(
            P, G, mq, ms, vq, vs, count, 0.9, 0.999, 1e-8, 0.25,
            stochastic=True)
    else:
        got = ops.galore_fused_adam8_step(
            P, G, mq, ms, vq, vs, count, alpha=0.25, stochastic=True,
            use_pallas=True, interpret=True)
        want = ref.galore_fused_adam8_step(
            P, G, mq, ms, vq, vs, count, 0.9, 0.999, 1e-8, 0.25,
            stochastic=True)
    for name, a, b in zip(["out", "mq", "ms", "vq", "vs"], got, want):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.uint8:
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            np.testing.assert_allclose(
                a, b, rtol=2e-2, atol=2e-2 * max(np.abs(b).max(), 1e-6),
                err_msg=name)
    # the deterministic path draws no uniforms: same inputs, different codes
    det = ops.galore_fused_adam8_step_right(
        P, G, mq, ms, vq, vs, count, alpha=0.25, use_pallas=True,
        interpret=True) if right else ops.galore_fused_adam8_step(
        P, G, mq, ms, vq, vs, count, alpha=0.25, use_pallas=True,
        interpret=True)
    assert not np.array_equal(np.asarray(det[1]), np.asarray(got[1]))


@pytest.mark.parametrize("shape,small_tiles,stochastic", [
    ((64, 256), False, False),        # a 2-D leaf, one tile
    ((3, 32, 128), False, False),     # stacked: leading dims fold into rows
    ((100, 128), True, False),        # 100 rows, 32 per grid step: padded
    ((7, 640), True, False),          # 3 lane tiles of 256, the last ragged;
                                      # 7 rows, as a stack of norm scales
    ((3, 40, 384), True, True),       # all of the above, stochastic codes
], ids=["2d", "stacked", "ragged_rows", "ragged_tiles", "stochastic"])
def test_full_shape_int8_fold_matches_codec(monkeypatch, shape, small_tiles,
                                            stochastic):
    """The epilogue's fold over a full-shape int8 leaf (no projection) vs
    dequantize → Adam → quantize_axis. `small_tiles` shrinks the fold's
    lane tile and grid step, so that small leaves span several tiles and
    row counts the step does not divide; the stochastic coin's index is
    the leaf's own ravel index, so its codes agree bit for bit."""
    from repro.kernels import galore_fused as F

    if small_tiles:
        monkeypatch.setattr(F, "FOLD_MAX_TILE", 256)
        monkeypatch.setattr(F, "FOLD_STEP_ELEMS", 4096)
    ks = jax.random.split(jax.random.PRNGKey(35), 3)
    G = jax.random.normal(ks[0], shape).astype(jnp.bfloat16)
    mq, ms = codec.quantize_axis(jax.random.normal(ks[1], shape) * 0.01,
                                 axis=-1, signed=True)
    vq, vs = codec.quantize_axis(
        jnp.abs(jax.random.normal(ks[2], shape)) * 1e-4, axis=-1, signed=False)
    count = jnp.int32(6)
    got = ops.adam8_full_step(G, mq, ms, vq, vs, count, stochastic=stochastic,
                              use_pallas=True, interpret=True)
    m = codec.dequantize_axis(mq, ms, axis=-1, signed=True)
    v = codec.dequantize_axis(vq, vs, axis=-1, signed=False)
    upd, m_t, v_t = ref.lowrank_adam_update(G, m, v, count)
    kw = dict(axis=-1, stochastic=stochastic, count=count)
    want = (upd.astype(G.dtype),
            *codec.quantize_axis(m_t, signed=True, salt=codec.SR_SALT_M, **kw),
            *codec.quantize_axis(v_t, signed=False, salt=codec.SR_SALT_V, **kw))
    assert got[0].dtype == G.dtype
    _check([x.astype(jnp.float32) for x in got[:1]] + list(got[1:]),
           [want[0].astype(jnp.float32)] + list(want[1:]), shape)
    if stochastic:
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("right", [False, True])
def test_fused_apply_kernels(quant, right):
    """Weight-apply epilogue (W aliased in place) vs its oracle, all variants,
    bf16 weights included."""
    shape = (130, 16, 72) if right else (72, 16, 130)
    P, G, W, M, V, mq, ms, vq, vs = _q8_inputs(jax.random.PRNGKey(32), shape,
                                               right=right)
    W = W.astype(jnp.bfloat16)
    count = jnp.int32(4)
    kw = dict(alpha=0.25, eta=-0.01, wd=0.1)
    if quant:
        fn = (ops.galore_fused_adam8_apply_step_right if right
              else ops.galore_fused_adam8_apply_step)
        rf = (ref.galore_fused_adam8_apply_step_right if right
              else ref.galore_fused_adam8_apply_step)
        got = fn(P, G, W, mq, ms, vq, vs, count, use_pallas=True,
                 interpret=True, **kw)
        want = rf(P, G, W, mq, ms, vq, vs, count, **kw)
    else:
        fn = (ops.galore_fused_adam_apply_step_right if right
              else ops.galore_fused_adam_apply_step)
        rf = (ref.galore_fused_adam_apply_step_right if right
              else ref.galore_fused_adam_apply_step)
        got = fn(P, G, W, M, V, count, use_pallas=True, interpret=True, **kw)
        want = rf(P, G, W, M, V, count, **kw)
    assert got[0].dtype == jnp.bfloat16
    _check([g.astype(jnp.float32) if g.dtype == jnp.bfloat16 else g for g in got],
           [w.astype(jnp.float32) if w.dtype == jnp.bfloat16 else w for w in want],
           ("apply", quant, right))


# ---------------------------------------------------------------------------
# Train-step integration
# ---------------------------------------------------------------------------


def test_fused_apply_train_step_matches_chain():
    """tc.galore_fused_apply (W updated inside the kernel epilogue) follows
    the exact trajectory of the two-step chain path — the numerics oracle."""
    from repro.distributed.step import make_train_step
    from repro.models import model as M

    cfg = get_config("llama_60m", smoke=True)
    gal = GaLoreConfig(rank=8, update_freq=2)
    key = jax.random.PRNGKey(0)
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab_size)}
    tc_a = TrainConfig(optimizer="adamw", lr=1e-2, weight_decay=0.01,
                       galore=gal, galore_fused_adam=True)
    tc_b = dataclasses.replace(tc_a, galore_fused_apply=True)
    step_a, opt_a = make_train_step(cfg, tc_a)
    step_b, opt_b = make_train_step(cfg, tc_b)
    params = M.init_params(cfg, key)
    sa, sb = opt_a.init(params), opt_b.init(params)
    assert jax.tree_util.tree_structure(sa) == jax.tree_util.tree_structure(sb)
    pa = pb = params
    for _ in range(5):
        pa, sa, _ = step_a(pa, sa, batch)
        pb, sb, _ = step_b(pb, sb, batch)
    for (ka, xa), (_, xb) in zip(jax.tree_util.tree_leaves_with_path(pa),
                                 jax.tree_util.tree_leaves_with_path(pb)):
        np.testing.assert_allclose(np.asarray(xa), np.asarray(xb),
                                   rtol=2e-5, atol=2e-6, err_msg=str(ka))


def test_full_shape_int8_leaves_route_by_shape(monkeypatch):
    """With `fused_adam` a full-shape int8 leaf runs the epilogue's fold
    when its last dim is whole 128-lane rows, a Pallas backend is selected
    and no multi-device mesh is active; every other case, and every leaf
    with `fused_adam` off, keeps the XLA codec bit for bit."""
    from repro.kernels import galore_fused as F
    from repro.utils import ShardingRules, sharding_context

    key = jax.random.PRNGKey(36)
    shapes = {"embed": (200, 256), "embed_odd": (64, 72), "kv": (3, 16, 256),
              "wide": (48, 130), "bias": (130,)}
    params = {k: jax.random.normal(jax.random.fold_in(key, i), s)
              for i, (k, s) in enumerate(shapes.items())}
    grads = _grads(params, key)
    cfg = GaLoreConfig(rank=16, quant=QuantPolicy(moments="int8",
                                                  min_quant_size=1000))
    plans = plan_for_params(params, cfg)
    assert {k for k, plan in plans.items() if plan.galore} == {"wide"}

    def routes_and_update(fused, tree=params):
        opt = galore(scale_by_adam(), cfg, fused_adam=fused, **HP)
        g = {k: grads[k] for k in tree}
        with ops.record_routes() as routes:
            out = opt.update(g, opt.init(tree), tree)
        return sorted(routes), out

    # no Pallas backend (the CPU): the XLA codec, as with fused_adam off
    routes, on = routes_and_update(True)
    assert routes == [("full", (3, 16, 256), "reference"),
                      ("full", (64, 72), "reference"),
                      ("full", (200, 256), "reference"),
                      ("left", (48, 130), "reference")]
    routes, off = routes_and_update(False)
    assert routes == []
    for a, b in zip(jax.tree_util.tree_leaves(on), jax.tree_util.tree_leaves(off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # a Pallas backend (kernel bodies interpreted): lane-aligned leaves fold
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    call = F._fused_epilogue_call
    monkeypatch.setattr(F, "_fused_epilogue_call",
                        lambda *a, **kw: call(*a, **{**kw, "interpret": True}))
    routes, fold = routes_and_update(True)
    assert routes == [("full", (3, 16, 256), "fused"),
                      ("full", (64, 72), "reference"),
                      ("full", (200, 256), "fused"),
                      ("left", (48, 130), "fused")]
    for k in ("embed", "kv"):
        st_f, st_x = fold[1]["inner"], off[1]["inner"]
        _check([fold[0][k], st_f["m"][k]["q"], st_f["m"][k]["scale"],
                st_f["v"][k]["q"], st_f["v"][k]["scale"]],
               [off[0][k], st_x["m"][k]["q"], st_x["m"][k]["scale"],
                st_x["v"][k]["q"], st_x["v"][k]["scale"]], k)
    routes, off_pallas = routes_and_update(False)
    assert routes == []
    full_leaves = lambda out: [(out[0][k], out[1]["inner"][mv][k])
                               for k in ("embed", "embed_odd", "kv", "bias")
                               for mv in ("m", "v")]
    for a, b in zip(jax.tree_util.tree_leaves(full_leaves(off_pallas)),
                    jax.tree_util.tree_leaves(full_leaves(off))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # inside a multi-device mesh the full-shape leaves keep the XLA codec
    full = {k: params[k] for k in ("embed", "kv")}
    mesh = jax.sharding.AbstractMesh((4,), ("data",))
    with sharding_context(ShardingRules(mesh=mesh, rules={})):
        routes, _ = routes_and_update(True, full)
    assert routes == [("full", (3, 16, 256), "reference"),
                      ("full", (200, 256), "reference")]


def test_adam8bit_galore_routes_through_quant_subsystem():
    """optimizer='adam8bit' + galore = plan-aware int8 moments (weight-size
    min_quant_size), managed by galore — and training still improves."""
    from repro.distributed.step import make_train_step
    from repro.models import model as M
    from repro.optim.factory import effective_galore_config, galore_state_index

    cfg = get_config("llama_60m", smoke=True)
    tc = TrainConfig(optimizer="adam8bit", lr=1e-2,
                     galore=GaLoreConfig(rank=8, update_freq=2))
    assert effective_galore_config(tc).quant.moments == "int8"
    step, opt = make_train_step(cfg, tc)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    st = opt.init(params)
    qleaves = [l for l in jax.tree_util.tree_leaves_with_path(
        st[galore_state_index(tc)]["inner"]["m"],
        is_leaf=lambda x: codec.is_qstate(x)) if codec.is_qstate(l[1])]
    assert len(qleaves) > 0
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab_size)}
    losses = []
    p = params
    for _ in range(4):
        p, st, m = step(p, st, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_quant_state_axes_zip_with_real_state():
    """optimizer_state_axes mirrors the quantized state tree exactly."""
    from repro.distributed.state_sharding import optimizer_state_axes
    from repro.models import model as M
    from repro.optim.factory import build_optimizer

    cfg = get_config("llama_60m", smoke=True)
    qp = QuantPolicy(moments="int8", projectors="int4")
    tc = TrainConfig(optimizer="adamw",
                     galore=GaLoreConfig(rank=8, rank_frac=0.25, quant=qp),
                     galore_fused_adam=True)
    opt = build_optimizer(tc, param_axes=M.param_axes(cfg))
    p_struct = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    s_struct = jax.eval_shape(opt.init, p_struct)
    axes = optimizer_state_axes(tc, M.param_axes(cfg), p_struct)
    jax.tree_util.tree_map(
        lambda leaf, ax: None, s_struct, axes,
        is_leaf=lambda x: hasattr(x, "shape"),
    )


def test_quantized_checkpoint_roundtrip_step_parity(tmp_path):
    """Save the quantized GaLore state mid-run, restore into zeros, continue:
    every subsequent step matches the uninterrupted run exactly."""
    from repro.distributed.step import make_train_step
    from repro.models import model as M

    cfg = get_config("llama_60m", smoke=True)
    qp = QuantPolicy(moments="int8", projectors="int4")
    tc = TrainConfig(optimizer="adamw", lr=1e-2,
                     galore=GaLoreConfig(rank=8, update_freq=2, quant=qp),
                     galore_fused_adam=True)
    step, opt = make_train_step(cfg, tc)
    key = jax.random.PRNGKey(0)
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab_size)}
    params = M.init_params(cfg, key)
    state = opt.init(params)
    p_a, s_a = params, state
    for _ in range(3):
        p_a, s_a, _ = step(p_a, s_a, batch)
    p_mid, s_mid = p_a, s_a
    for _ in range(3):
        p_a, s_a, _ = step(p_a, s_a, batch)

    ckpt = CheckpointManager(str(tmp_path), async_save=False)
    ckpt.save(3, {"params": p_mid, "opt_state": s_mid}, block=True)
    zeros = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), {"params": p_mid, "opt_state": s_mid})
    restored = ckpt.restore(3, zeros)
    p_b, s_b = restored["params"], restored["opt_state"]
    for _ in range(3):
        p_b, s_b, _ = step(p_b, s_b, batch)
    for (pa, xa), (_, xb) in zip(jax.tree_util.tree_leaves_with_path(p_a),
                                 jax.tree_util.tree_leaves_with_path(p_b)):
        np.testing.assert_allclose(np.asarray(xa, np.float32),
                                   np.asarray(xb, np.float32),
                                   rtol=1e-6, atol=1e-7, err_msg=str(pa))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-6, atol=1e-7),
        s_a, s_b)


def test_checkpoint_rejects_layout_mismatch(tmp_path):
    """A quantized checkpoint cannot be silently cast into an fp32 layout."""
    ckpt = CheckpointManager(str(tmp_path), async_save=False)
    ckpt.save(1, {"m": {"q": jnp.zeros((4, 128), jnp.uint8),
                        "scale": jnp.ones((4,), jnp.float32)}}, block=True)
    with pytest.raises(ValueError, match="not.*interchangeable|was saved as"):
        ckpt.restore(1, {"m": {"q": jnp.zeros((4, 128), jnp.float32),
                               "scale": jnp.ones((4,), jnp.float32)}})


# ---------------------------------------------------------------------------
# Memory accounting (acceptance: ≥ 75 % optimizer-state reduction at 7B)
# ---------------------------------------------------------------------------


def test_state_bytes_reduction_paper_scale():
    from repro.models import model as M

    cfg = get_config("llama_7b")
    struct = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    fp32 = galore_state_bytes(struct, GaLoreConfig(rank=1024))
    q8 = galore_state_bytes(
        struct, GaLoreConfig(rank=1024, quant=QuantPolicy(moments="int8")))
    q84 = galore_state_bytes(
        struct, GaLoreConfig(rank=1024, quant=QuantPolicy(moments="int8",
                                                          projectors="int4")))
    # default fp32 byte totals are exactly elems × 4 (bit-compatible model)
    assert fp32["optimizer_state_bytes"] == 4 * fp32["adam_state_elems"]
    assert q8["reduction_vs_fp32_adam"] >= 0.75
    assert q84["optimizer_state_bytes"] < q8["optimizer_state_bytes"]
    # int4 projector storage is ~8x smaller than fp32
    ratio = fp32["projector_bytes"] / q84["projector_bytes"]
    assert 7.0 < ratio < 8.1, ratio


def test_state_bytes_default_keys_unchanged():
    params = {"w": jnp.zeros((256, 1024))}
    acct = galore_state_bytes(params, GaLoreConfig(rank=64))
    assert acct["adam_state_elems"] == 256 * 64 + 2 * (64 * 1024)

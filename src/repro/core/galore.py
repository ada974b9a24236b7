"""GaLore: gradient low-rank projection as a composable gradient transform.

Wraps ANY inner GradientTransformation (Adam, AdamW, Adafactor, 8-bit Adam):

    R_t   = P_t^T G_t            (project the short side; m <= n projects left)
    N_t   = inner(R_t)           (optimizer statistics live in r × n)
    G̃_t  = alpha * P_t N_t      (project back to full shape)

P_t is refreshed every `update_freq` (T) steps from the instantaneous
gradient (Algorithm 2 of the paper). Non-matrix leaves (norm scales, biases,
1-D params) and excluded paths (embeddings) pass through the inner optimizer
at full shape, exactly as the paper treats them.

Leaves may carry leading batch dims (stacked layers (L, m, n) or stacked
experts (L, E, m, n)) — projection and refresh vmap over them.

All per-leaf decisions — which leaves project, each leaf's rank, refresh
period and stagger offset, the adaptive-T schedule — come from the
SubspaceManager in core/subspace.py (the single source of truth; see its
docstring for the policy knobs). Ranks may differ per leaf; every shape here
is derived from the plan, so ragged ranks flow through projector init,
compact moments, and the fused kernel dispatch without special cases.

When the inner optimizer is plain Adam, `fused_adam=True` collapses steps
2-4 into one Pallas kernel per leaf (kernels/galore_fused.py) with identical
numerics and state layout; the composable path here is the oracle.

Quantized state (GaLoreConfig.quant, src/repro/quant/): when the policy
quantizes moments, galore manages the Adam math itself (the inner transform
is bypassed, so b1/b2/eps are required exactly as for fused_adam) and int8
leaves store {"q": codes, "scale": absmax} dicts in place of the fp32 m/v
arrays — in the axis-blocked layout the fused kernels consume, so the
dequant→Adam→requant epilogue runs in one VMEM pass on TPU (with
`fused_adam`, full-shape int8 leaves such as embeddings too). Quantized
projectors (bf16 / packed int4) are dequantized on read in every path. The
all-fp32 default leaves both layout and numerics bit-identical.

State layout:
    {"step", "key", "proj": {path-matching subtree of P arrays}, "inner": ...}
plus, only when the adaptive-T policy is on, "schedule": per-leaf
{period, next, overlap} scalars (checkpointed with everything else).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import GaLoreConfig
from repro.core.projector import init_projector_state, read_projector
from repro.core.subspace import (
    DEFAULT_EXCLUDE,
    LeafPlan,
    SubspaceManager,
    SubspacePlan,
    _lead,
    constrain_zero_moment,
    moment_quant_axis,
    plan_rank_axis,
    proj_shape,
    r_shape,
    rank_axis,
    tree_all_finite,
)
from repro.optim.transform import GradientTransformation
from repro.quant import codec
from repro.utils import logical_constraint


def plan_for_params(params, cfg: GaLoreConfig, exclude=DEFAULT_EXCLUDE, param_axes=None):
    """Pytree of SubspacePlan mirroring params (thin wrapper over the
    SubspaceManager so legacy callers share the single source of truth)."""
    return SubspaceManager(cfg, exclude, param_axes).plans(params)


def _project(g, P, plan: SubspacePlan):
    if plan.side == "left":  # P (..., m, r): R = P^T G -> (..., r, n)
        R = jnp.einsum("...mr,...mn->...rn", P, g.astype(jnp.float32))
        return logical_constraint(
            R, *_lead(R, plan_rank_axis(plan, plan.ax_n), plan.ax_n))
    R = jnp.einsum("...mn,...nr->...mr", g.astype(jnp.float32), P)
    return logical_constraint(
        R, *_lead(R, plan.ax_m, plan_rank_axis(plan, plan.ax_m)))


def _project_back(R, P, plan: SubspacePlan):
    if plan.side == "left":
        G = jnp.einsum("...mr,...rn->...mn", P, R)
    else:
        G = jnp.einsum("...mr,...nr->...mn", R, P)
    return logical_constraint(G, *_lead(G, plan.ax_m, plan.ax_n))


def galore(
    inner: GradientTransformation,
    cfg: GaLoreConfig,
    exclude=DEFAULT_EXCLUDE,
    param_axes=None,
    external_refresh: bool = False,
    pre_projected: bool = False,
    fused_adam: bool = False,
    b1: float | None = None,
    b2: float | None = None,
    eps: float | None = None,
    seed: int = 0,
) -> GradientTransformation:
    """external_refresh=True removes the in-step `lax.cond` SVD refresh —
    the launcher then calls `refresh_projectors` every T steps as a separate
    jitted step. GSPMD replicates tensors inside conditional branches, so at
    pod scale the inline cond would replicate full-gradient copies per device
    (measured +140 GB/dev on grok-314b); the two-step split also matches how
    production systems stagger amortized work.

    pre_projected=True: galore-leaf gradients arrive ALREADY in the compact
    space (the GaLore-DP compressed all-reduce path, distributed/step.py) —
    projection is skipped, back-projection still applies. Implies
    external_refresh.

    fused_adam=True: the hot path. Requires `inner` to be plain Adam
    (scale_by_adam-shaped state {m, v, count}; b1/b2/eps must match). GaLore
    leaves bypass the composable project → inner.update → back-project
    sequence and run the fused Pallas kernel — one launch per leaf that keeps
    R/N̂ in VMEM and updates the compact moments in place; non-galore leaves
    get the identical Adam math at full shape. State layout is unchanged
    (checkpoints swap freely between the two paths), and the composable path
    remains the numerics oracle. Left- and right-side leaves each have a
    dedicated kernel (kernels/galore_fused.py) — no transposes on either
    side. Incompatible with pre_projected (fused path wants the full-shape
    gradient). b1/b2/eps are required with fused_adam and MUST equal the
    inner Adam's hyperparameters — the fused kernel computes the moment math
    itself, and a mismatch would silently diverge from the composable oracle.

    seed: PRNG seed for the projector sketch randomness (threaded from
    TrainConfig.seed by optim/factory.py)."""
    if fused_adam and pre_projected:
        raise ValueError("fused_adam is incompatible with pre_projected gradients")
    if fused_adam and None in (b1, b2, eps):
        raise ValueError(
            "fused_adam=True requires explicit b1/b2/eps matching the inner Adam"
        )
    quantized = cfg.quant.quantizes_moments
    if quantized and None in (b1, b2, eps):
        raise ValueError(
            "quantized moments (QuantPolicy.moments='int8') bypass the inner "
            "transform — explicit b1/b2/eps matching an Adam inner are required"
        )
    if quantized and pre_projected:
        raise ValueError(
            "quantized moments are incompatible with pre_projected gradients"
        )
    mgr = SubspaceManager(cfg, exclude, param_axes)

    def init(params):
        plans = mgr.plans(params)

        def proj_init(p, plan):
            if not plan.galore:
                # scalar placeholder keeps the tree structure aligned with params
                return jnp.zeros((), jnp.float32)
            return init_projector_state(proj_shape(p, plan), plan.proj_store)

        def inner_struct(p, plan):
            if not plan.galore:
                return p
            return jnp.zeros(r_shape(p, plan), jnp.float32)

        proj = jax.tree_util.tree_map(proj_init, params, plans)
        if quantized:
            inner_state = _managed_adam_init(params, plans)
        else:
            projected_params = jax.tree_util.tree_map(inner_struct, params, plans)
            inner_state = inner.init(projected_params)
        state = {
            "step": jnp.zeros((), jnp.int32),
            "key": jax.random.PRNGKey(seed),
            "proj": proj,
            "inner": inner_state,
        }
        sched = mgr.init_schedule(params, plans)
        if sched is not None:
            state["schedule"] = sched
        return state

    def update(grads, state, params=None):
        plan_src = params if pre_projected else grads
        plans = mgr.plans(plan_src)
        step = state["step"]
        sched = state.get("schedule")

        # --- 1) maybe refresh projectors from the current gradient ---
        if external_refresh or pre_projected:
            proj = state["proj"]
        else:
            key = jax.random.fold_in(state["key"], step)
            valid = tree_all_finite(grads) if cfg.guard_refresh else None
            proj, sched = mgr.refresh_tree(
                grads, state["proj"], sched, plans, key, step=step,
                valid=valid,
            )

        # persistent P may be stored bf16 / packed int4 — dequantize once per
        # step; the f32 copy is transient (consumed by the projection matmuls).
        # Fused dispatch keeps axis-blocked int4 states PACKED: the kernel
        # unpacks nibbles in VMEM, so no f32 projector tree ever hits HBM.
        proj_eff = _read_proj_tree(plan_src, proj, plans, keep_packed=fused_adam)

        if quantized or fused_adam:
            # --- 2-4 managed) galore owns the Adam math, bypassing the inner
            # transform: fused leaves run one kernel (project → Adam →
            # back-project, R/N̂ never leave VMEM; ops dispatches Pallas on
            # TPU, the ref oracle elsewhere) and int8 leaves additionally get
            # the dequant→Adam→requant epilogue in either mode ---
            updates, inner_state = _managed_adam_update(
                grads, proj_eff, state["inner"], plans, cfg, b1, b2, eps,
                fused=fused_adam,
            )
        else:
            # --- 2) project gradients into the compact space ---
            def proj_leaf(g, P, plan):
                if not plan.galore or pre_projected:
                    return g
                return _project(g, P, plan)

            lor_grads = jax.tree_util.tree_map(proj_leaf, grads, proj_eff, plans)

            # --- 3) inner optimizer in the compact space ---
            lor_updates, inner_state = inner.update(lor_grads, state["inner"], params)
            if cfg.zero and isinstance(inner_state, dict) and \
                    "m" in inner_state and "v" in inner_state:
                # GaLore-ZeRO: pin the Adam-shaped inner moments to their
                # ownership shards (the rank-block each DP replica owns)
                inner_state = dict(inner_state)
                for _k in ("m", "v"):
                    inner_state[_k] = jax.tree_util.tree_map(
                        constrain_zero_moment, inner_state[_k], plans)

            # --- 4) project back + alpha scale ---
            def back_leaf(u, P, plan):
                if not plan.galore:
                    return u
                full = _project_back(u.astype(jnp.float32), P, plan)
                return cfg.scale * full  # apply_updates casts to the param dtype

            updates = jax.tree_util.tree_map(back_leaf, lor_updates, proj_eff, plans)
        new_state = {
            "step": step + 1,
            "key": state["key"],
            "proj": proj,
            "inner": inner_state,
        }
        if sched is not None:
            new_state["schedule"] = sched
        return updates, new_state

    return GradientTransformation(init, update)


def _read_proj_tree(ref_tree, proj, plans, keep_packed: bool = False):
    """Dequant-on-read over the whole projector tree (no-op for fp32 storage).

    `ref_tree` supplies the full WEIGHT shapes (params or full-shape grads)
    from which each leaf's projector shape is derived.

    keep_packed=True (the fused dispatch): axis-blocked int4 qstates pass
    through UNTOUCHED — kernels/ops.py routes the packed codes + scales into
    the epilogue, which dequantizes nibble blocks in VMEM. The transient f32
    projector tree (4 B/elem of HBM read per step) disappears entirely;
    legacy flat-int4 and bf16 storage still dequantize here."""

    def read(p, P, plan):
        if not plan.galore:
            return P
        if keep_packed and codec.is_axis4_qstate(P):
            return P
        return read_projector(P, proj_shape(p, plan))

    return jax.tree_util.tree_map(read, ref_tree, proj, plans)


# blocked axis of an int8 moment leaf — shared with the async buffer swap's
# moment re-projection (core/subspace.py, the single source of truth)
_moment_quant_axis = moment_quant_axis


def _managed_adam_init(params, plans):
    """scale_by_adam-layout state with per-plan quantized leaves: int8 leaves
    hold {"q": codes, "scale": absmax} in the axis-blocked codec layout."""

    def per_leaf(p, plan, signed):
        shape = r_shape(p, plan) if plan.galore else p.shape
        zeros = jnp.zeros(shape, jnp.float32)
        if plan.moments == "int8":
            return codec.quant_axis_state(
                zeros, axis=_moment_quant_axis(plan), signed=signed)
        return zeros

    t = jax.tree_util.tree_map
    return {
        "m": t(lambda p, pl: per_leaf(p, pl, True), params, plans),
        "v": t(lambda p, pl: per_leaf(p, pl, False), params, plans),
        "count": jnp.zeros((), jnp.int32),
    }


def _managed_adam_update(grads, proj_eff, inner_state, plans, cfg: GaLoreConfig,
                         b1: float, b2: float, eps: float, *, fused: bool,
                         params=None, eta: float | jnp.ndarray = 0.0,
                         wd: float = 0.0):
    """Adam step bypassing the generic inner transform (fused fast path,
    quantized moments, and the in-place weight apply — one implementation).

    Galore leaves run the side-matched fused kernel (single HBM pass, moments
    updated in place) when `fused`, else the composable project → Adam →
    back-project composition; int8-moment leaves (plan.moments) run the
    dequant→Adam→requant epilogue in either mode. Other leaves get the same
    Adam math at full shape: with `fused`, the int8 ones through
    `ops.adam8_full_step` (the epilogue's fold where their shape admits
    it, else the XLA codec). Reads and writes the scale_by_adam state layout
    {m, v, count} (int8 leaves store {"q", "scale"} dicts). Per-leaf ranks
    are carried by the array shapes — each distinct (side, m, r, n) gets its
    own kernel specialization, which is exactly what Pallas wants.

    With `params` given, the weight update is folded in: returns
    (new_params, state) where W' = W + eta·(update + wd·W) — the fused-apply
    epilogue (galore leaves never materialize a full-size f32 update).
    Without it, returns (updates, state)."""
    from repro.kernels import ops, ref

    apply_w = params is not None
    count = inner_state["count"] + 1
    stochastic = cfg.quant.stochastic_round

    def dequant_mv(m_st, v_st, plan):
        ax = _moment_quant_axis(plan)
        return (codec.dequant_axis_state(m_st, axis=ax, signed=True),
                codec.dequant_axis_state(v_st, axis=ax, signed=False))

    def requant_mv(m_t, v_t, plan):
        ax = _moment_quant_axis(plan)
        return (codec.quant_axis_state(m_t, axis=ax, signed=True,
                                       stochastic=stochastic, count=count,
                                       salt=codec.SR_SALT_M),
                codec.quant_axis_state(v_t, axis=ax, signed=False,
                                       stochastic=stochastic, count=count,
                                       salt=codec.SR_SALT_V))

    def finish(out, p):
        """Fold eta/wd into the weight when applying, else emit the update."""
        if not apply_w:
            return out
        w32 = p.astype(jnp.float32)
        return (w32 + eta * (out.astype(jnp.float32) + wd * w32)).astype(p.dtype)

    def leaf(g, P, m_st, v_st, plan, p):
        qm = plan.moments == "int8"
        if not plan.galore:
            if fused and qm:
                # the epilogue's fold where the leaf's shape admits it
                out, mq, ms, vq, vs = ops.adam8_full_step(
                    g, m_st["q"], m_st["scale"], v_st["q"], v_st["scale"],
                    count, b1=b1, b2=b2, eps=eps, stochastic=stochastic)
                m_t, v_t = {"q": mq, "scale": ms}, {"q": vq, "scale": vs}
            else:
                # same bias-corrected Adam math as the kernel, from the
                # single source of truth (also what scale_by_adam computes)
                if qm:
                    m, v = dequant_mv(m_st, v_st, plan)
                else:
                    m, v = m_st, v_st
                out, m_t, v_t = ref.lowrank_adam_update(g, m, v, count, b1,
                                                        b2, eps)
                if qm:
                    m_t, v_t = requant_mv(m_t, v_t, plan)
            m_t = constrain_zero_moment(m_t, plan)
            v_t = constrain_zero_moment(v_t, plan)
            return finish(out.astype(g.dtype), p), m_t, v_t

        if fused and qm:
            left = plan.side == "left"
            if apply_w:
                fn = (ops.galore_fused_adam8_apply_step if left
                      else ops.galore_fused_adam8_apply_step_right)
                out = fn(P, g, p, m_st["q"], m_st["scale"], v_st["q"],
                         v_st["scale"], count, b1=b1, b2=b2, eps=eps,
                         alpha=cfg.scale, eta=eta, wd=wd,
                         stochastic=stochastic)
            else:
                fn = (ops.galore_fused_adam8_step if left
                      else ops.galore_fused_adam8_step_right)
                out = fn(P, g, m_st["q"], m_st["scale"], v_st["q"],
                         v_st["scale"], count, b1=b1, b2=b2, eps=eps,
                         alpha=cfg.scale, stochastic=stochastic,
                         zero=plan.zero)
            upd, mq, ms, vq, vs = out
            m_t, v_t = {"q": mq, "scale": ms}, {"q": vq, "scale": vs}
        elif fused:
            left = plan.side == "left"
            if apply_w:
                fn = (ops.galore_fused_adam_apply_step if left
                      else ops.galore_fused_adam_apply_step_right)
                upd, m_t, v_t = fn(P, g, p, m_st, v_st, count, b1=b1, b2=b2,
                                   eps=eps, alpha=cfg.scale, eta=eta, wd=wd)
            else:
                # dedicated transposed-blockspec kernel on the right: R = G P,
                # G̃ = α N̂ Pᵀ — no swapaxes round-trips on g/m/v
                fn = (ops.galore_fused_adam_step if left
                      else ops.galore_fused_adam_step_right)
                upd, m_t, v_t = fn(P, g, m_st, v_st, count, b1=b1, b2=b2,
                                   eps=eps, alpha=cfg.scale, zero=plan.zero)
        else:
            # composable managed path (the oracle for the quantized kernels)
            R = _project(g, P, plan)
            if qm:
                m, v = dequant_mv(m_st, v_st, plan)
            else:
                m, v = m_st, v_st
            N, m_t, v_t = ref.lowrank_adam_update(R, m, v, count, b1, b2, eps)
            upd = cfg.scale * _project_back(N, P, plan)
            if qm:
                m_t, v_t = requant_mv(m_t, v_t, plan)
            upd = finish(upd, p)
        upd = logical_constraint(upd, *_lead(upd, plan.ax_m, plan.ax_n))
        # GaLore-ZeRO: the updated moments land on their ownership shard —
        # the persistent compact state never re-replicates across steps
        m_t = constrain_zero_moment(m_t, plan)
        v_t = constrain_zero_moment(v_t, plan)
        return upd, m_t, v_t

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_p = (treedef.flatten_up_to(params) if apply_w
              else [None] * len(flat_g))
    flat = [
        leaf(g, P, m, v, plan, p)
        for g, P, m, v, plan, p in zip(
            flat_g,
            treedef.flatten_up_to(proj_eff),
            treedef.flatten_up_to(inner_state["m"]),
            treedef.flatten_up_to(inner_state["v"]),
            treedef.flatten_up_to(plans),
            flat_p,
        )
    ]
    updates = treedef.unflatten([t[0] for t in flat])
    new_m = treedef.unflatten([t[1] for t in flat])
    new_v = treedef.unflatten([t[2] for t in flat])
    return updates, {"m": new_m, "v": new_v, "count": count}


def make_fused_apply(cfg: GaLoreConfig, *, b1: float, b2: float, eps: float,
                     weight_decay: float = 0.0, exclude=DEFAULT_EXCLUDE,
                     param_axes=None, external_refresh: bool = False):
    """The W-in-place fast path: returns
        apply_step(params, grads, galore_state, eta) -> (params', galore_state')
    where every galore leaf runs ONE kernel that folds the weight update into
    the fused epilogue — W' = W + eta·(α P N̂ + wd·W) with W aliased in place,
    so the full-size f32 update write of the emit path disappears (eta is the
    launcher's -lr for this step; weight decay matches the AdamW chain
    ordering clip → galore → +wd·W → ·(-lr)). Passthrough leaves get the
    identical math at full shape. State layout and refresh behavior are
    exactly `galore(...)`'s — checkpoints swap freely between the two paths,
    and the emit path + chain remains the numerics oracle (enforced by
    tests/test_quant.py)."""
    mgr = SubspaceManager(cfg, exclude, param_axes)

    def apply_step(params, grads, galore_state, eta):
        plans = mgr.plans(grads)
        step = galore_state["step"]
        sched = galore_state.get("schedule")
        if external_refresh:
            proj = galore_state["proj"]
        else:
            key = jax.random.fold_in(galore_state["key"], step)
            valid = tree_all_finite(grads) if cfg.guard_refresh else None
            proj, sched = mgr.refresh_tree(
                grads, galore_state["proj"], sched, plans, key, step=step,
                valid=valid)
        proj_eff = _read_proj_tree(grads, proj, plans, keep_packed=True)
        new_params, inner_state = _managed_adam_update(
            grads, proj_eff, galore_state["inner"], plans, cfg, b1, b2, eps,
            fused=True, params=params, eta=eta, wd=weight_decay,
        )
        new_state = {
            "step": step + 1,
            "key": galore_state["key"],
            "proj": proj,
            "inner": inner_state,
        }
        if sched is not None:
            new_state["schedule"] = sched
        return new_params, new_state

    return apply_step


def refresh_projectors(grads, galore_state, cfg: GaLoreConfig,
                       exclude=DEFAULT_EXCLUDE, param_axes=None, step=None,
                       assignment=None, shard_id=None, axis_name=None,
                       precomputed=None, valid=None):
    """External projector refresh (the launcher-driven path).

    step=None recomputes EVERY projector from `grads` — the legacy every-T
    spike refresh. step=<int or traced int32> is the partial-refresh mode:
    only the leaves due at `step` (per their plan offsets / adaptive periods)
    recompute, so a staggered launcher can call this every step and amortize
    the SVD work across the window. With a concrete Python-int step and the
    static schedule the not-due leaves cost nothing at trace time.

    Distributed refresh (pod-scale): `assignment` (a partition_refresh tree)
    + shard_id + axis_name run the per-unit SVDs masked to this replica and
    psum-gather the results — the caller must be inside `shard_map` over
    `axis_name`. Alternatively pass `precomputed` (a sharded_projector_tree
    output gathered in a separate shard_map region, the make_refresh_step
    pattern) so this epilogue lowers as the plain GSPMD program and stays
    bit-identical to the unsharded refresh. Defaults touch nothing.

    Under cfg.guard_refresh the gradient snapshot is validated before any
    SVD: `valid` (a scalar bool) gates every leaf's dueness; when None it is
    computed here as tree_all_finite(grads) — pass it explicitly when
    `grads` is a stand-in tree (the async sharded epilogue)."""
    mgr = SubspaceManager(cfg, exclude, param_axes)
    plans = mgr.plans(grads)
    key = jax.random.fold_in(galore_state["key"], galore_state["step"])
    sched = galore_state.get("schedule")
    sched_step = galore_state["step"] if step is None else step
    if cfg.guard_refresh and valid is None:
        valid = tree_all_finite(grads)
    if assignment is not None:
        precomputed = mgr.sharded_projector_tree(
            grads, plans, sched, key, step=sched_step, force_all=step is None,
            assignment=assignment, shard_id=shard_id, axis_name=axis_name,
            valid=valid,
        )
    proj, sched = mgr.refresh_tree(
        grads, galore_state["proj"], sched, plans, key,
        step=sched_step, force_all=step is None, precomputed=precomputed,
        valid=valid,
    )
    out = {**galore_state, "proj": proj}
    if sched is not None:
        out["schedule"] = sched
    return out


# ---------------------------------------------------------------------------
# Async double-buffered refresh (P_active / P_next, GaLore-2-style)
#
# The pending buffer {"proj", "flag"[, "schedule"]} deliberately lives BESIDE
# the optimizer state, never inside it: any pending leaf in the train step's
# input tree is an input-readiness dependency, and XLA would park the due
# step's train launch behind the SVD program — exactly the stall the async
# mode exists to remove. The launcher (launch/train.py AsyncRefreshDriver)
# holds the pending tree between dispatch and the next step boundary, swaps
# it in with a dedicated tiny program (distributed/step.py make_swap_step),
# and checkpoints it as its own top-level group when a refresh is in flight
# (checkpoint/manager.py records the group set in META).
# ---------------------------------------------------------------------------


def init_pending_state(params, cfg: GaLoreConfig, exclude=DEFAULT_EXCLUDE,
                       param_axes=None) -> dict:
    """Zero pending buffer matching refresh_projectors_pending's output —
    the checkpoint restore target for a mid-pending-refresh resume comes
    from jax.eval_shape of this."""
    mgr = SubspaceManager(cfg, exclude, param_axes)
    return mgr.init_pending(params, mgr.plans(params))


def refresh_projectors_pending(grads, galore_state, cfg: GaLoreConfig,
                               exclude=DEFAULT_EXCLUDE, param_axes=None,
                               step=None, precomputed=None, valid=None) -> dict:
    """External refresh written into a pending buffer (async dispatch form).

    Same dueness / key-folding semantics as refresh_projectors, but the
    active galore_state is untouched: the due leaves' new projectors land in
    pending["proj"] with pending["flag"] marking them, and the post-refresh
    adaptive schedule rides along. Swap with swap_pending_state at the next
    step boundary. `grads` is typically STALE by one step (the launcher
    snapshots the previous batch), which GaLore 2 shows costs no loss — and
    is exactly the snapshot cfg.guard_refresh validates (`valid` auto-
    computed as tree_all_finite(grads) when not supplied): a non-finite
    snapshot yields an all-zero-flag pending buffer instead of a poisoned
    P_next."""
    mgr = SubspaceManager(cfg, exclude, param_axes)
    plans = mgr.plans(grads)
    key = jax.random.fold_in(galore_state["key"], galore_state["step"])
    sched = galore_state.get("schedule")
    sched_step = galore_state["step"] if step is None else step
    if cfg.guard_refresh and valid is None:
        valid = tree_all_finite(grads)
    return mgr.refresh_pending_tree(
        grads, galore_state["proj"], sched, plans, key,
        step=sched_step, force_all=step is None, precomputed=precomputed,
        valid=valid)


def swap_pending_state(params, galore_state, pending, cfg: GaLoreConfig,
                       exclude=DEFAULT_EXCLUDE, param_axes=None) -> dict:
    """P_active ← P_next on the flagged leaves (plus schedule scalars and,
    under cfg.reproject_moments, the ReLoRA-style moment rotation). `params`
    only supplies leaf shapes — a ShapeDtypeStruct tree works."""
    mgr = SubspaceManager(cfg, exclude, param_axes)
    return mgr.swap_pending(galore_state, pending, mgr.plans(params), params)


# bytes per element of persistent storage, scale overhead included
_PROJ_BYTES = {"fp32": 4.0, "bf16": 2.0,
               "int4": 0.5 + 4.0 / codec.QBLOCK}  # packed nibbles + absmax/128
_MOMENT_BYTES = {"fp32": 4.0,
                 "int8": 1.0 + 4.0 / codec.QBLOCK}  # codes + absmax/128


def galore_state_bytes(params, cfg: GaLoreConfig, exclude=DEFAULT_EXCLUDE) -> dict:
    """Analytic memory accounting (paper Table 1): projector + compact moments.

    Uses each leaf's OWN rank from its SubspacePlan, so heterogeneous-rank
    configs (rank_frac / rank_overrides) report their true reduced footprint,
    and each leaf's resolved QuantPolicy modes, so the byte totals reflect
    the REAL quantized storage (int8 codes + per-block absmax, packed int4
    projectors) — the numbers behind the paper's 8-bit GaLore table
    (benchmarks/memory_breakdown.py cross-checks the 82.5 % claim)."""
    plans = plan_for_params(params, cfg, exclude)
    proj_elems = 0
    moment_elems = 0
    full_moment_elems = 0
    proj_bytes = 0.0
    moment_bytes = 0.0
    total_params = 0
    import numpy as np

    for (path, p), (_, plan) in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree_util.tree_leaves_with_path(plans, is_leaf=lambda x: isinstance(x, SubspacePlan)),
    ):
        size = int(np.prod(p.shape))
        total_params += size
        mom_b = _MOMENT_BYTES[plan.moments]
        if plan.galore:
            pe = int(np.prod(proj_shape(p, plan)))
            me = int(np.prod(r_shape(p, plan)))
            proj_elems += pe
            moment_elems += me
            proj_bytes += pe * _PROJ_BYTES[plan.proj_store]
            moment_bytes += 2 * me * mom_b
        else:
            full_moment_elems += size
            moment_bytes += 2 * size * mom_b
    fp32_adam = 8 * total_params  # m + v, fp32, no projector
    opt_bytes = proj_bytes + moment_bytes
    return {
        "projector_elems": proj_elems,
        "lowrank_moment_elems_each": moment_elems,
        "fullrank_moment_elems_each": full_moment_elems,
        "adam_state_elems": proj_elems + 2 * (moment_elems + full_moment_elems),
        # policy-aware byte totals (fp32 default: elems × 4, bit-compatible)
        "projector_bytes": proj_bytes,
        "moment_bytes": moment_bytes,
        "optimizer_state_bytes": opt_bytes,
        "fp32_adam_state_bytes": fp32_adam,
        "reduction_vs_fp32_adam": 1.0 - opt_bytes / max(fp32_adam, 1),
    }


def galore_zero_state_bytes(params, cfg: GaLoreConfig, n_dp: int,
                            exclude=DEFAULT_EXCLUDE) -> dict:
    """Analytic PER-REPLICA optimizer bytes under GaLore-ZeRO ownership.

    Mirrors the ``core/subspace.zero_state_axes`` contract (GaLoreConfig.zero):
    galore compact moments, projector stores and their quantized scales divide
    by ``n_dp`` on the rank dim; full-shape passthrough moments divide on dim
    -2. A dim that does not divide ``n_dp`` replicates, exactly as
    ``ShardingRules.spec_for`` falls back at trace time — so these totals
    match the measured ``addressable_shards[0].data.nbytes`` accounting in
    benchmarks/memory_breakdown.py up to the per-block scale remainders.

    Parameters
    ----------
    params : pytree
        Parameter arrays or ShapeDtypeStructs.
    cfg : GaLoreConfig
        Resolved config (``cfg.zero`` does not need to be set; this reports
        what ownership WOULD cost at ``n_dp`` replicas).
    n_dp : int
        Data-parallel replica count owning the partition.
    exclude : tuple of str
        Leaf-name substrings kept out of the galore projection.

    Returns
    -------
    dict
        Per-replica byte totals plus the replicated baseline and the
        reduction factor ``replicated / per_replica``.
    """
    import numpy as np

    full = galore_state_bytes(params, cfg, exclude)
    plans = plan_for_params(params, cfg, exclude)
    proj_b = 0.0
    mom_b = 0.0
    for (path, p), (_, plan) in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree_util.tree_leaves_with_path(
            plans, is_leaf=lambda x: isinstance(x, SubspacePlan)),
    ):
        size = int(np.prod(p.shape))
        mb = _MOMENT_BYTES[plan.moments]
        if plan.galore:
            div = n_dp if plan.rank % n_dp == 0 else 1
            mom_b += 2 * int(np.prod(r_shape(p, plan))) * mb / div
            proj_b += (int(np.prod(proj_shape(p, plan)))
                       * _PROJ_BYTES[plan.proj_store] / div)
        else:
            div = (n_dp if len(p.shape) >= 2 and p.shape[-2] % n_dp == 0
                   else 1)
            mom_b += 2 * size * mb / div
    opt = proj_b + mom_b
    return {
        "n_dp": n_dp,
        "projector_bytes_per_replica": proj_b,
        "moment_bytes_per_replica": mom_b,
        "opt_state_bytes_per_replica": opt,
        "replicated_opt_state_bytes": full["optimizer_state_bytes"],
        "zero_reduction_vs_replicated": (full["optimizer_state_bytes"]
                                         / max(opt, 1.0)),
    }

"""Training driver: sharded train loop with fault tolerance.

Features exercised end-to-end (examples/pretrain_c4_style.py):
  * pjit train step with logical-axis shardings (mesh from launch/mesh.py)
  * gradient accumulation (TrainConfig.microbatch)
  * checkpoint every N steps (async, atomic) + auto-resume from latest
  * preemption hook: touch <ckpt_root>/PREEMPT to force save-and-exit
  * straggler watchdog: the mean step time between two log steps (host
    time between their blocking loss reads, over the steps between them);
    logs an interval slower than 2x the EMA of the earlier ones — at real
    multi-host scale this feeds the coordinator's replace-node decision
  * host spans (repro/obs.py) around the set-up phases and each step's parts,
    for the profiler's trace and an installed obs.Recorder
  * elastic restore: checkpoints reload onto a different mesh shape

CLI:  PYTHONPATH=src python -m repro.launch.train --arch llama_60m --steps 200
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import GaLoreConfig, TrainConfig, get_config
from repro.data.pipeline import DataConfig, SyntheticC4
from repro.distributed.step import make_train_step, params_specs, opt_state_specs
from repro.launch import mesh as mesh_lib
from repro.models import model as M
from repro.obs import span, step_span


@dataclasses.dataclass
class RunConfig:
    arch: str = "llama_60m"
    smoke: bool = True
    steps: int = 200
    batch_per_host: int = 8
    seq_len: int = 256
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    log_every: int = 10
    # file codec for large float params leaves in checkpoints (None | "int8"
    # | "int4"): int4 shrinks params bytes ~7× (checkpoint/manager.py);
    # optimizer state always round-trips verbatim
    ckpt_quantize: str | None = None


def opt_state_shardings(cfg, tc, rules, opt_state=None):
    """NamedSharding tree for the optimizer state on the rules' mesh.

    Derived from distributed/state_sharding.optimizer_state_axes — with
    GaLore-ZeRO, the same ownership map (core/subspace.py zero_state_axes)
    the in-step constraints pin — so initial placement, per-step outputs and
    checkpoint restores all agree on where each leaf lives (which rank
    block each DP replica holds). Leaves without a shape (empty chain
    states) come back as None."""
    from repro.distributed.state_sharding import optimizer_state_axes
    from repro.utils import is_axes

    p_struct = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    axes = optimizer_state_axes(tc, M.param_axes(cfg), p_struct)
    if opt_state is None:
        _, opt = make_train_step(cfg, tc, rules)
        opt_state = jax.eval_shape(opt.init, p_struct)

    def per_leaf(ax, s):
        if not hasattr(s, "shape"):
            return None
        return rules.sharding_for(ax, s.shape)

    return jax.tree_util.tree_map(per_leaf, axes, opt_state, is_leaf=is_axes)


def state_shardings(cfg, tc, rules):
    """(params, optimizer state) NamedSharding trees on the rules' mesh."""
    p_shard = jax.tree_util.tree_map(lambda s: s.sharding,
                                     params_specs(cfg, rules))
    return p_shard, opt_state_shardings(cfg, tc, rules)


def build_state(cfg, tc, rules, key):
    # params are created directly on their mesh shardings, never whole on
    # the first device; the optimizer state starts where the train step
    # keeps it (with GaLore-ZeRO each DP replica holds its rank block from
    # step 0), so the step compiles once for the shardings of every step
    p_shard, o_shard = (state_shardings(cfg, tc, rules) if rules is not None
                        else (None, None))
    params = jax.jit(lambda k: M.init_params(cfg, k), out_shardings=p_shard)(key)
    _, opt = make_train_step(cfg, tc, rules)
    opt_state = opt.init(params)
    if o_shard is not None:
        opt_state = _place(opt_state, o_shard)
    return params, opt_state


def _place(tree, shardings, put=jax.device_put):
    """put(leaf, sharding) over a tree whose sharding tree holds None for
    shapeless state nodes (shardings first: a None pairs with a whole
    subtree, never traversed as an empty pytree)."""
    return jax.tree_util.tree_map(
        lambda sh, x: x if sh is None else put(x, sh), shardings, tree,
        is_leaf=lambda x: x is None)


def _pinned_state(fn, cfg, tc, rules):
    """`fn(...) -> opt_state` with its output pinned to the state's
    shardings on the rules' mesh: a refresh program that hands the state
    back with other shardings than the train step keeps would make that
    step compile anew on the next step."""
    if rules is None:
        return fn
    o_shard = opt_state_shardings(cfg, tc, rules)

    def pinned(*args):
        return _place(fn(*args), o_shard, jax.lax.with_sharding_constraint)

    return pinned


def _with_measured_importance(cfg, tc: TrainConfig, params, batch) -> TrainConfig:
    """Stamp GaLoreConfig.importance_order from one measured gradient: the
    per-leaf Frobenius norms of the first batch's gradient, descending. The
    order is static config, so every plan derivation (optimizer init, update,
    external refresh, partitioning) agrees on the importance-ranked stagger."""
    from repro.core.subspace import importance_order_from_grads

    grads = jax.grad(
        lambda p: M.loss_fn(cfg, p, batch, z_loss=tc.z_loss)[0]
    )(params)
    order = importance_order_from_grads(grads)
    return dataclasses.replace(
        tc, galore=dataclasses.replace(tc.galore, importance_order=order))


def _with_calibrated_costs(cfg, tc: TrainConfig) -> TrainConfig:
    """Stamp GaLoreConfig.unit_costs from measured per-shape SVD wall times
    (one timed projector compute per distinct galore-leaf shape), so
    partition_refresh bins the distributed refresh on real costs instead of
    the asymptotic model — static config, measured once at startup."""
    from repro.core.subspace import calibrate_unit_costs

    p_struct = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    costs = calibrate_unit_costs(p_struct, tc.galore, param_axes=M.param_axes(cfg))
    print(f"[train] calibrated {len(costs)} SVD unit costs: "
          + ", ".join(f"{k}={v * 1e3:.1f}ms" for k, v in costs))
    return dataclasses.replace(
        tc, galore=dataclasses.replace(tc.galore, unit_costs=costs))


def _galore_due_offsets(cfg, tc: TrainConfig) -> set:
    """Host-side set of due phases (refresh_offset % T over the galore
    leaves) — ONE derivation shared by the sync refresh caller and the async
    driver, so their host-side dueness can never desynchronize. With K galore
    leaves only K distinct offsets exist, so every other phase is a
    statically-known no-op the caller skips without tracing."""
    from repro.core.subspace import SubspaceManager, SubspacePlan
    from repro.optim.factory import effective_galore_config

    T = tc.galore.update_freq
    p_struct = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    plans = SubspaceManager(effective_galore_config(tc),
                            param_axes=M.param_axes(cfg)).plans(p_struct)
    return {pl.refresh_offset % T for pl in jax.tree_util.tree_leaves(
        plans, is_leaf=lambda x: isinstance(x, SubspacePlan)) if pl.galore}


def _fold_phase(T: int, step: int) -> int:
    """Fold a concrete step to a due-equivalent window phase: p and T + p are
    due-equivalent for p != 0, and phase 0 only at the real step 0 — so jit
    retraces on the static-step refresh programs are bounded by
    n_galore + 1 distinct values ever."""
    return 0 if step == 0 else T + step % T


class AsyncRefreshDriver:
    """Launcher-side double-buffered refresh (tc.galore_refresh_async).

    At a due step t the refresh program is DISPATCHED on the previous step's
    batch (the stale-gradient snapshot) and its result — the pending buffer
    {"proj", "flag"[, "schedule"]} — is held here as in-flight futures; the
    train step at t runs on P_active with no data dependency on the SVDs.
    At the next step boundary a tiny swap program installs P_next. Step 0
    refreshes synchronously (cold start: the projectors are zeros and there
    is no previous batch). The pending tree is exposed for checkpointing:
    a save while a refresh is in flight stores it as its own group, and
    restore_pending() re-arms the swap so a resumed run lands the identical
    active buffer.

    tc.galore_recalibrate_every = N > 0: every N dispatches the driver
    re-measures the per-shape SVD unit costs (core/subspace.py
    calibrate_unit_costs) and rebuilds its refresh programs with the new
    GaLoreConfig.unit_costs, so the sharded refresh's bin-packing tracks
    cost drift (host contention, thermal throttling) over a long run."""

    def __init__(self, cfg, tc: TrainConfig, rules):
        self._cfg = cfg
        self._rules = rules
        self.recal_every = int(tc.galore_recalibrate_every or 0)
        self.dispatch_count = 0
        self.recalibrations = 0
        self.pending = None
        self._prev_batch = None
        self._build(tc)

    def _build(self, tc: TrainConfig):
        """(Re)compile every program for an effective config — called at
        init and again after each cost recalibration. In-flight state
        (pending buffer, stale-batch snapshot) is deliberately untouched:
        a pending tree dispatched by the old programs swaps in fine."""
        from repro.distributed.step import (
            make_async_refresh_step,
            make_refresh_step,
            make_swap_step,
        )
        from repro.optim.factory import galore_state_index

        cfg, rules = self._cfg, self._rules
        self._tc = tc
        self.gcfg = tc.galore
        self.T = self.gcfg.update_freq
        self.idx = galore_state_index(tc)
        self.adaptive = bool(self.gcfg.adaptive_t)
        self.stagger = bool(self.gcfg.refresh_stagger)
        pend = make_async_refresh_step(cfg, tc, rules)
        self._dispatch_static = jax.jit(pend, static_argnums=(3,))
        self._dispatch_traced = jax.jit(pend)
        # donate the pre-swap opt_state (dead after the call); the pending
        # tree is NOT donated — its flag scalars and pass-through projector
        # leaves often cannot alias an output, and the resulting
        # unusable-donation warnings would fire every swap
        self._swap = jax.jit(
            _pinned_state(make_swap_step(cfg, tc, rules), cfg, tc, rules),
            donate_argnums=(0,))
        cold = _pinned_state(make_refresh_step(cfg, tc, rules), cfg, tc, rules)
        self._cold_static = jax.jit(cold, static_argnums=(3,), donate_argnums=(1,))
        self._cold_traced = jax.jit(cold, donate_argnums=(1,))
        self._due_offsets = _galore_due_offsets(cfg, tc)

    def _recalibrate(self):
        """Re-measure per-shape SVD costs and rebuild with the new
        unit_costs (the partition_refresh bin-packing reads them)."""
        from repro.core.subspace import calibrate_unit_costs

        p_struct = jax.eval_shape(
            lambda: M.init_params(self._cfg, jax.random.PRNGKey(0)))
        costs = calibrate_unit_costs(p_struct, self._tc.galore,
                                     param_axes=M.param_axes(self._cfg))
        self.recalibrations += 1
        print(f"[train] recalibrated {len(costs)} SVD unit costs "
              f"(#{self.recalibrations}): "
              + ", ".join(f"{k}={v * 1e3:.1f}ms" for k, v in costs))
        self._build(dataclasses.replace(
            self._tc, galore=dataclasses.replace(self._tc.galore,
                                                 unit_costs=costs)))

    def _note_dispatch(self):
        self.dispatch_count += 1
        if self.recal_every and self.dispatch_count % self.recal_every == 0:
            self._recalibrate()

    def _sub(self, opt_state):
        g = opt_state[self.idx]
        sub = {"step": g["step"], "key": g["key"], "proj": g["proj"]}
        if "schedule" in g:
            sub["schedule"] = g["schedule"]
        return sub

    def _swap_if_pending(self, opt_state):
        if self.pending is not None:
            opt_state = self._swap(opt_state, self.pending)
            self.pending = None
        return opt_state

    def restore_pending(self, pending):
        """Re-arm a checkpointed in-flight refresh: it swaps in at the next
        maybe_refresh call, exactly where the interrupted run would have."""
        self.pending = pending

    def prime_stale(self, batch):
        """Seed the stale-gradient snapshot after a resume: a refresh due on
        the very first post-resume step must dispatch on the PREVIOUS step's
        batch, as the uninterrupted run would have (without this it would
        fall back to the current batch and the trajectories diverge)."""
        self._prev_batch = batch

    def flush(self, opt_state):
        """Install any in-flight refresh (end of training / orderly exit)."""
        return self._swap_if_pending(opt_state)

    def maybe_refresh(self, params, opt_state, batch, step):
        opt_state = self._swap_if_pending(opt_state)
        stale = self._prev_batch if self._prev_batch is not None else batch
        self._prev_batch = batch
        if step == 0:
            # synchronous cold start, identical to the sync caller's step 0
            if self.adaptive:
                return self._cold_traced(params, opt_state, batch, jnp.int32(0))
            return self._cold_static(params, opt_state, batch,
                                     0 if self.stagger else None)
        if self.adaptive:
            # dueness is runtime state — dispatch every step, leaves cond
            self.pending = self._dispatch_traced(
                params, self._sub(opt_state), stale, jnp.int32(step))
            self._note_dispatch()
            return opt_state
        if self.stagger:
            if step % self.T in self._due_offsets:
                # same phase folding as the sync caller: bounded retraces
                self.pending = self._dispatch_static(
                    params, self._sub(opt_state), stale,
                    _fold_phase(self.T, step))
                self._note_dispatch()
            return opt_state
        if step % self.T == 0:
            self.pending = self._dispatch_static(
                params, self._sub(opt_state), stale, None)
            self._note_dispatch()
        return opt_state


def _make_refresh_caller(cfg, tc: TrainConfig, rules):
    """Launcher-side external refresh driver: returns
    maybe_refresh(params, opt_state, batch, step) -> opt_state.

    Staggered schedules call the partial refresh on due steps only (the due
    phases are known host-side from the plan offsets); the concrete step is
    folded to a window phase (phase % T == step % T, phase 0 only at real
    step 0) so jit retraces are bounded by n_galore + 1. Adaptive-T needs the
    true step value in the schedule state, so it passes a traced int32 — one
    trace, per-leaf runtime conds. The legacy un-staggered schedule keeps the
    every-T force-all spike."""
    from repro.distributed.step import make_refresh_step

    gcfg = tc.galore
    T = gcfg.update_freq
    refresh = _pinned_state(make_refresh_step(cfg, tc, rules), cfg, tc, rules)
    # the pre-refresh opt_state is dead after the call — donate it so the
    # refresh never holds two copies of the optimizer state
    jit_static = jax.jit(refresh, static_argnums=(3,), donate_argnums=(1,))
    jit_traced = jax.jit(refresh, donate_argnums=(1,))
    # host-side due-phase set (shared derivation with the async driver):
    # skipping statically-not-due phases without tracing matters because T
    # can be 200 with K ≈ 7 — tracing 194 identity programs would dominate
    # startup
    due_offsets = _galore_due_offsets(cfg, tc)

    def maybe_refresh(params, opt_state, batch, step):
        if gcfg.adaptive_t:
            return jit_traced(params, opt_state, batch, jnp.int32(step))
        if gcfg.refresh_stagger:
            if step != 0 and step % T not in due_offsets:
                return opt_state  # statically not due for any leaf
            return jit_static(params, opt_state, batch, _fold_phase(T, step))
        if step % T == 0:
            return jit_static(params, opt_state, batch, None)
        return opt_state

    return maybe_refresh


def train_loop(run: RunConfig, tc: TrainConfig, cfg=None, on_step=None,
               faults=None, mesh=None, on_build=None):
    """Run the training loop; returns (params, opt_state, metrics, last_step).

    `mesh`: the (data, model) mesh to train on; default every device as
    data-parallel replicas (launch/mesh.py make_host_mesh).
    `on_build`: called with the jitted train step each time the programs
    are (re)built, e.g. to read its compiled text.

    `faults`: optional fault-injection specs (strings "kind@step[*count]" or
    FaultSpec objects, robust/faults.py) — deterministic corruption for the
    chaos tests and the CI chaos job. Traced kinds require tc.anomaly_guard
    (they poison the loss/grads INSIDE the step; without the guard nothing
    would stop the poison from entering the weights)."""
    cfg = cfg or get_config(run.arch, smoke=run.smoke)
    mesh = mesh or mesh_lib.make_host_mesh()
    rules = mesh_lib.default_rules(mesh)
    data = SyntheticC4(
        DataConfig(
            vocab_size=cfg.vocab_size,
            seq_len=run.seq_len,
            batch_per_host=run.batch_per_host,
            seed=tc.seed,
        )
    )
    guarded = bool(tc.anomaly_guard)
    injector = None
    if faults:
        from repro.robust import FaultInjector, FaultSpec, parse_fault

        specs = [f if isinstance(f, FaultSpec) else parse_fault(f) for f in faults]
        injector = FaultInjector(specs)
        if injector.needs_traced_hooks:
            if not guarded:
                raise ValueError("traced fault kinds require tc.anomaly_guard")
            if not tc.fault_hooks:
                tc = dataclasses.replace(tc, fault_hooks=True)
    # checksum only when guarded: the recovery path needs exact corruption
    # detection; unguarded runs keep the original META bytes (quantized
    # leaves carry their own mandatory per-entry crc32s either way)
    ckpt = CheckpointManager(run.ckpt_dir, checksum=guarded,
                             quantize=run.ckpt_quantize)

    key = jax.random.PRNGKey(tc.seed)
    gcfg = tc.galore
    if gcfg is not None and gcfg.stagger_by_importance and not gcfg.importance_order:
        with mesh:
            probe = M.init_params(cfg, key)
            tc = _with_measured_importance(cfg, tc, probe, data.batch(0))
            del probe
    if gcfg is not None and tc.galore_calibrate_costs and not gcfg.unit_costs:
        with mesh:
            tc = _with_calibrated_costs(cfg, tc)
        gcfg = tc.galore
    external = gcfg is not None and (tc.galore_external_refresh
                                     or tc.galore_refresh_shard
                                     or tc.galore_refresh_async)

    def build_programs(tc_eff):
        """(Re)build every jitted program for an effective config — called
        once at startup and again on a rollback that decays the LR."""
        train_step, opt = make_train_step(cfg, tc_eff, rules)

        def pinned_step(params, opt_state, *rest):
            # outputs keep the input shardings build_state gave the state,
            # so step 1 reuses step 0's program instead of compiling anew
            params, opt_state, *out = train_step(params, opt_state, *rest)
            constrain = jax.lax.with_sharding_constraint
            return (_place(params, p_shard, constrain),
                    _place(opt_state, o_shard, constrain), *out)

        jitted = jax.jit(pinned_step, donate_argnums=(0, 1))
        if on_build is not None:
            on_build(jitted)
        driver = None
        maybe_refresh = None
        if external and tc_eff.galore_refresh_async:
            driver = AsyncRefreshDriver(cfg, tc_eff, rules)
            maybe_refresh = driver.maybe_refresh
        elif external:
            maybe_refresh = _make_refresh_caller(cfg, tc_eff, rules)
        resync = None
        if (guarded and tc_eff.recover_resync and maybe_refresh is not None
                and not tc_eff.galore.adaptive_t):
            # post-rollback re-sync: one synchronous force-all refresh so the
            # restored run starts from projectors of ITS OWN gradients instead
            # of whatever the checkpoint carried (phase 0 == cold start ==
            # every leaf due; adaptive-T owns its schedule, skip there)
            from repro.distributed.step import make_refresh_step

            resync = jax.jit(
                _pinned_state(make_refresh_step(cfg, tc_eff, rules), cfg,
                              tc_eff, rules),
                static_argnums=(3,))
        return opt, jitted, driver, maybe_refresh, resync

    with span("setup.shardings"):
        p_shard, o_shard = state_shardings(cfg, tc, rules)
    tc_eff = tc
    with span("setup.programs"):
        opt, jitted, driver, maybe_refresh, resync = build_programs(tc_eff)
    with span("setup.state") as rec:
        params, opt_state = build_state(cfg, tc, rules, key)
        if rec is not None:  # a recorder's span holds opt.init's device work
            jax.block_until_ready((params, opt_state))
    guard = None
    recov = None
    if guarded:
        from repro.robust import RecoveryController, init_guard_state

        guard = init_guard_state()
        recov = RecoveryController(max_skips=tc.recover_max_skips,
                                   max_rollbacks=tc.recover_max_rollbacks,
                                   backoff=tc.recover_backoff)

    def try_restore(params, opt_state, guard, driver, which):
        """Restore params/opt_state (+ optional pending/guard groups) from
        checkpoint `which`; returns the new (params, opt_state, guard,
        start_step). Shared by startup resume and rollback."""
        meta = ckpt.meta(which)
        groups = ckpt.groups(which)
        target = {"params": params, "opt_state": opt_state}
        if driver is not None and "pending" in groups:
            # a refresh was in flight at save time — restore the pending
            # buffer and re-arm the swap so the resumed trajectory is the
            # interrupted one (structure from the zero pending eval_shape)
            from repro.core.galore import init_pending_state
            from repro.optim.factory import effective_galore_config

            target["pending"] = jax.eval_shape(
                lambda: init_pending_state(
                    params, effective_galore_config(tc),
                    param_axes=M.param_axes(cfg)))
        if guarded and "guard" in groups:
            target["guard"] = guard
        # elastic restore: saves gather full leaves onto the host
        # (manager._flatten), so a checkpoint written at any n_dp re-places
        # onto THIS mesh's shardings (with ZeRO, its ownership shards) —
        # restore at a different replica count is just a different
        # device_put
        rep = jax.sharding.NamedSharding(rules.mesh, jax.sharding.PartitionSpec())
        shardings = jax.tree_util.tree_map(lambda _: rep, target)
        shardings["params"] = p_shard
        shardings["opt_state"] = o_shard
        restored = ckpt.restore(which, target, shardings=shardings)
        params, opt_state = restored["params"], restored["opt_state"]
        if "pending" in restored:
            driver.restore_pending(restored["pending"])
        if "guard" in restored:
            guard = restored["guard"]
        start = meta["step"] + 1
        if driver is not None and start > 0:
            driver.prime_stale(data.batch(start - 1))
        return params, opt_state, guard, start

    start_step = 0
    with span("setup.restore"):
        # guarded runs only trust checkpoints that pass integrity validation
        # — a torn/corrupted latest degrades to the one before it
        latest = ckpt.latest_valid_step() if guarded else ckpt.latest_step()
        if latest is not None:
            params, opt_state, guard, start_step = try_restore(
                params, opt_state, guard, driver, latest)
            print(f"[train] resumed from step {latest}")

    ema_dt = None
    t_logged = step_logged = None  # host time and step of the last loss read
    metrics = {}
    preempt_flag = os.path.join(run.ckpt_dir, "PREEMPT")
    step = start_step
    while step < run.steps:
        with step_span(step):
            with span("train.data"):
                batch = data.batch(step)
            if maybe_refresh is not None:
                with span("train.refresh"):
                    opt_state = maybe_refresh(params, opt_state, batch, step)
                if (injector is not None and driver is not None
                        and driver.pending is not None
                        and injector.take("corrupt_pending", step)):
                    print(f"[faults] poisoning in-flight pending buffer at step {step}")
                    driver.pending = injector.poison_pending(driver.pending)
            if guarded:
                with span("train.dispatch"):
                    if tc.fault_hooks:
                        from repro.robust import identity_fault

                        fault = (injector.traced_fault(step) if injector is not None
                                 else identity_fault())
                        params, opt_state, guard, metrics = jitted(
                            params, opt_state, guard, batch, fault)
                    else:
                        params, opt_state, guard, metrics = jitted(
                            params, opt_state, guard, batch)
                ok = bool(metrics["guard_ok"])
                if not ok:
                    print(f"[guard] anomalous step {step}: update skipped "
                          f"(total skips {int(metrics['guard_skips'])})")
            else:
                ok = True
                with span("train.dispatch"):
                    params, opt_state, metrics = jitted(params, opt_state, batch)
            if recov is not None and recov.observe_step(ok):
                n = recov.start_rollback()
                ckpt.wait()  # let an in-flight save commit before choosing a target
                if tc.recover_lr_decay < 1.0:
                    tc_eff = dataclasses.replace(
                        tc_eff, lr=tc_eff.lr * tc.recover_lr_decay)
                    opt, jitted, driver, maybe_refresh, resync = build_programs(tc_eff)
                elif driver is not None:
                    driver.pending = None  # an in-flight refresh may be the poison
                    driver._prev_batch = None
                params, opt_state = build_state(cfg, tc_eff, rules, key)
                from repro.robust import init_guard_state

                # the guard's running stats only absorb ACCEPTED steps, so the
                # checkpointed monitor is clean by construction — restoring it
                # keeps the z-score armed across the rollback (a fresh one
                # would be blind to spikes for another full warmup)
                guard = init_guard_state()
                which = ckpt.latest_valid_step()
                if which is not None:
                    params, opt_state, guard, step = try_restore(
                        params, opt_state, guard, driver, which)
                else:
                    step = 0  # nothing valid on disk — restart from init
                print(f"[recover] rollback {n}/{tc.recover_max_rollbacks}: "
                      f"restored step {which}, resuming at step {step}"
                      + (f", lr -> {tc_eff.lr:.2e}" if tc.recover_lr_decay < 1.0 else ""))
                if resync is not None:
                    opt_state = resync(
                        params, opt_state, data.batch(step),
                        0 if tc_eff.galore.refresh_stagger else None)
                    if driver is not None:
                        driver.prime_stale(data.batch(step))
                continue  # re-enter the loop at the restored step
            if step % run.log_every == 0:
                with span("train.log"):
                    loss = float(metrics["loss"])  # the loop's one wait on the device
                now = time.perf_counter()
                timing = ""
                if step_logged is not None and step > step_logged:
                    # dispatch returns before the device is done: a step's
                    # time shows only between two waits
                    dt = (now - t_logged) / (step - step_logged)
                    if ema_dt is not None and dt > 2.0 * ema_dt:
                        print(f"[watchdog] straggler steps {step_logged + 1}-{step}: "
                              f"{dt:.3f}s/step vs EMA {ema_dt:.3f}s")
                    ema_dt = dt if ema_dt is None else 0.9 * ema_dt + 0.1 * dt
                    timing = f" ({dt * 1e3:.0f} ms/step)"
                t_logged, step_logged = now, step
                print(f"[train] step {step} loss {loss:.4f}{timing}")
            if on_step is not None:
                with span("train.hook"):
                    on_step(step, metrics)
            with span("train.ckpt"):
                if run.ckpt_every and step > 0 and step % run.ckpt_every == 0:
                    tree = {"params": params, "opt_state": opt_state}
                    if driver is not None and driver.pending is not None:
                        tree["pending"] = driver.pending  # in-flight refresh rides along
                    if guarded:
                        tree["guard"] = guard  # monitor stats resume with the run
                    ckpt.save(step, tree, extra_meta={"data": data.state(step)})
                    if injector is not None:
                        if injector.take("corrupt_ckpt", step):
                            ckpt.wait()  # corrupt the COMMITTED files, not the tmp
                            print(f"[faults] corrupting latest checkpoint after step {step}")
                            injector.corrupt_latest(run.ckpt_dir)
                        if injector.take("kill_save", step):
                            ckpt.wait()
                            print(f"[faults] simulating kill mid-save at step {step}")
                            injector.leave_stale_tmp(run.ckpt_dir, step)
                if os.path.exists(preempt_flag):
                    print(f"[train] preemption signal at step {step}: checkpoint + exit")
                    tree = {"params": params, "opt_state": opt_state}
                    if driver is not None and driver.pending is not None:
                        tree["pending"] = driver.pending
                    if guarded:
                        tree["guard"] = guard
                    ckpt.save(step, tree, block=True)
                    os.remove(preempt_flag)
                    return params, opt_state, metrics, step
        step += 1
    if driver is not None:
        opt_state = driver.flush(opt_state)
    ckpt.wait()
    return params, opt_state, metrics, run.steps - 1


def build_parser():
    """Argparse parser for the training launcher.

    Kept separate from main() so docs/gen_cli.py can introspect the full
    flag surface (the generated docs/cli.md is drift-checked in CI).
    """
    from repro.launch import cli

    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.train",
        description="GaLore training launcher (smoke-scale by default)")
    cli.add_arch_flags(ap, default_arch="llama_60m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--galore-rank", type=int, default=0)
    ap.add_argument("--galore-t", type=int, default=200)
    ap.add_argument("--galore-fused", action="store_true",
                    help="fused project→Adam→back kernel per leaf (adam/adamw)")
    cli.add_galore_subspace_flags(ap)
    ap.add_argument("--galore-stagger-importance", action="store_true",
                    help="order stagger offsets by measured gradient norm "
                         "(AdaRankGrad-style; implies --galore-stagger)")
    ap.add_argument("--galore-external-refresh", action="store_true",
                    help="refresh projectors in a dedicated jitted step "
                         "driven by the launcher (no in-step SVD cond)")
    ap.add_argument("--galore-refresh-shard", action="store_true",
                    help="partition the refresh SVD work across data-parallel "
                         "replicas and all-gather the projectors (implies "
                         "external refresh; per-refresh ceiling Σc_i → max "
                         "bin ≈ Σc_i/n_dp)")
    ap.add_argument("--galore-refresh-async", action="store_true",
                    help="double-buffered async refresh: dispatch the SVD "
                         "program on the previous step's gradient snapshot "
                         "and swap P_active <- P_next at the next step "
                         "boundary, keeping refresh off the train critical "
                         "path (implies external refresh; composes with "
                         "--galore-refresh-shard)")
    ap.add_argument("--galore-reproject-moments", action="store_true",
                    help="on each async buffer swap, rotate the compact Adam "
                         "moments into the new subspace (ReLoRA-style reset "
                         "hygiene) instead of carrying old-basis statistics")
    ap.add_argument("--galore-calibrate-costs", action="store_true",
                    help="measure per-shape SVD wall time once at startup "
                         "and bin-pack the distributed refresh on measured "
                         "costs instead of the asymptotic model")
    ap.add_argument("--galore-recalibrate-costs", type=int, default=0,
                    metavar="N",
                    help="async refresh: re-measure SVD unit costs every N "
                         "refresh dispatches and rebuild the refresh "
                         "programs, so bin-packing tracks cost drift "
                         "(requires --galore-refresh-async; 0 disables)")
    ap.add_argument("--galore-fused-apply", action="store_true",
                    help="fold the weight update into the fused-kernel "
                         "epilogue (requires --galore-fused)")
    ap.add_argument("--galore-dp-compress", action="store_true",
                    help="all-reduce gradients in the compact r-dim domain "
                         "(project per-replica, mean R, update once) instead "
                         "of the full m×n domain")
    ap.add_argument("--galore-zero", type=int, default=0, choices=(0, 1, 2),
                    help="GaLore-ZeRO optimizer-state partitioning: 1 shards "
                         "the persistent compact state (moments, projectors, "
                         "quantization payloads) rank-blockwise across "
                         "data-parallel replicas (~1/n_dp optimizer bytes "
                         "per replica; the back-projection's psum doubles as "
                         "the weight-delta all-gather); 2 additionally "
                         "reduce-scatters compact gradients to owners "
                         "(implies --galore-dp-compress, fp32 moments only); "
                         "0 keeps state replicated")
    ap.add_argument("--galore-tp-aware-side", action="store_true",
                    help="choose the projection side from the parameter's "
                         "sharding instead of min(m, n): a tensor-parallel "
                         "weight projects along its REPLICATED dim so the "
                         "kept dim stays sharded (changes numerics vs the "
                         "paper's shape rule; off by default)")
    cli.add_quant_flags(ap)
    ap.add_argument("--anomaly-guard", action="store_true",
                    help="per-step anomaly guard: non-finite loss/grad-norm "
                         "or an EMA z-score loss spike turns the step into a "
                         "no-op; with GaLore also validates refresh inputs "
                         "and pending-projector swaps (guard_refresh)")
    ap.add_argument("--inject-fault", action="append", default=[],
                    metavar="KIND@STEP[*N]",
                    help="deterministic fault injection (repeatable): traced "
                         "kinds nan_loss/inf_loss/spike_loss/nan_grad "
                         "(require --anomaly-guard), host kinds "
                         "corrupt_pending/corrupt_ckpt/kill_save")
    ap.add_argument("--recover-max-skips", type=int, default=3,
                    help="consecutive guard skips before rolling back to the "
                         "newest valid checkpoint")
    ap.add_argument("--recover-max-rollbacks", type=int, default=2,
                    help="rollback budget before hard TrainingFailure")
    ap.add_argument("--recover-lr-decay", type=float, default=1.0,
                    help="multiply LR by this on each rollback (<1 enables)")
    ap.add_argument("--recover-resync", action="store_true",
                    help="after a rollback, force one synchronous force-all "
                         "projector refresh before resuming")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    cli.add_ckpt_flags(ap, default_dir="/tmp/repro_ckpt")
    ap.add_argument("--log-every", type=int, default=10)
    return ap


def configs_from_args(ap, args):
    """(RunConfig, TrainConfig, fault specs or None) for parsed launcher
    flags; invalid flag combinations exit through `ap.error`."""
    from repro.launch import cli

    galore = (
        GaLoreConfig(rank=args.galore_rank, update_freq=args.galore_t,
                     rank_frac=args.galore_rank_frac,
                     adaptive_t=args.galore_adaptive_t,
                     refresh_stagger=(args.galore_stagger
                                      or args.galore_stagger_importance),
                     stagger_by_importance=args.galore_stagger_importance,
                     reproject_moments=args.galore_reproject_moments,
                     tp_aware_side=args.galore_tp_aware_side,
                     quant=cli.quant_policy_from(args))
        if args.galore_rank > 0 or args.galore_rank_frac > 0
        else None
    )
    if args.galore_fused and galore is None:
        ap.error("--galore-fused requires --galore-rank or --galore-rank-frac > 0")
    if args.galore_fused_apply and not args.galore_fused:
        ap.error("--galore-fused-apply requires --galore-fused")
    if args.galore_refresh_shard and galore is None:
        ap.error("--galore-refresh-shard requires --galore-rank or "
                 "--galore-rank-frac > 0")
    if args.galore_refresh_async and galore is None:
        ap.error("--galore-refresh-async requires --galore-rank or "
                 "--galore-rank-frac > 0")
    if args.galore_reproject_moments and not args.galore_refresh_async:
        ap.error("--galore-reproject-moments acts on async buffer swaps; "
                 "add --galore-refresh-async")
    if args.galore_recalibrate_costs and not args.galore_refresh_async:
        ap.error("--galore-recalibrate-costs is driven by the async refresh "
                 "driver; add --galore-refresh-async")
    if args.galore_zero and galore is None:
        ap.error("--galore-zero requires --galore-rank or "
                 "--galore-rank-frac > 0")
    if args.galore_tp_aware_side and galore is None:
        ap.error("--galore-tp-aware-side requires --galore-rank or "
                 "--galore-rank-frac > 0")
    if args.galore_dp_compress and galore is None:
        ap.error("--galore-dp-compress requires --galore-rank or "
                 "--galore-rank-frac > 0")
    if (args.galore_zero == 2 and galore is not None
            and galore.quant.quantizes_moments):
        ap.error("--galore-zero 2 reduce-scatters compact gradients onto "
                 "fp32 owner moments; it cannot compose with quantized "
                 "moment state (drop --quant-moments / use --galore-zero 1)")
    from repro.robust import TRACED_KINDS, parse_fault

    try:
        faults = [parse_fault(s) for s in args.inject_fault]
    except ValueError as e:
        ap.error(str(e))
    traced = any(f.kind in TRACED_KINDS for f in faults)
    if traced and not args.anomaly_guard:
        ap.error("traced fault kinds (nan_loss/inf_loss/spike_loss/nan_grad) "
                 "poison the step from inside — they require --anomaly-guard")
    if galore is not None and args.anomaly_guard:
        # the guard implies poison-proof refresh: validate stale-gradient
        # snapshots, SVD outputs, and pending swaps
        galore = dataclasses.replace(galore, guard_refresh=True)
    tc = TrainConfig(
        optimizer=args.optimizer, galore=galore, lr=args.lr, total_steps=args.steps,
        warmup_steps=max(1, args.steps // 10),
        galore_fused_adam=args.galore_fused,
        galore_fused_apply=args.galore_fused_apply,
        galore_external_refresh=args.galore_external_refresh,
        galore_refresh_shard=args.galore_refresh_shard,
        galore_refresh_async=args.galore_refresh_async,
        # ZeRO-2 reduce-scatters in the compact domain, so it rides on the
        # dp-compress step path (base.py: galore_zero == 2 implies it)
        galore_dp_compress=(args.galore_dp_compress or args.galore_zero == 2),
        galore_zero=args.galore_zero,
        galore_calibrate_costs=args.galore_calibrate_costs,
        galore_recalibrate_every=args.galore_recalibrate_costs,
        anomaly_guard=args.anomaly_guard,
        fault_hooks=traced,
        recover_max_skips=args.recover_max_skips,
        recover_max_rollbacks=args.recover_max_rollbacks,
        recover_lr_decay=args.recover_lr_decay,
        recover_resync=args.recover_resync,
    )
    run = RunConfig(
        arch=args.arch, smoke=not args.full, steps=args.steps,
        batch_per_host=args.batch, seq_len=args.seq, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, log_every=args.log_every,
        ckpt_quantize=args.ckpt_quantize,
    )
    return run, tc, faults or None


def main(argv=None):
    from repro.launch import cli

    ap = build_parser()
    args = ap.parse_args(argv)
    print(f"[train] compile cache: {cli.enable_compile_cache()}")
    run, tc, faults = configs_from_args(ap, args)
    train_loop(run, tc, faults=faults)


if __name__ == "__main__":
    main()

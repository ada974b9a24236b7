"""Bring-up smoke test of the main path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one host of four chips

One chip: trains the GaLore paper's LLaMA-1B (24 layers, d_model 2048,
32 heads, d_ff 5461, vocab 32000; random weights from a seed) with 8-bit
GaLore at rank 512 — int8 Adam moments, int4 projectors, the fused Pallas
optimizer kernels and a subspace refresh every 2 steps — through the
training launcher's own flags and `train_loop`, saves a checkpoint, then
loads it with the serving launcher's loader and answers four requests of
different prompt lengths through the continuous-batching `Engine`.

--four-chips: only the data-parallel path — `train_loop` on a 4-way `data`
mesh with the sharded refresh and ZeRO-1 optimizer state, against the same
seed and global batch on a one-device mesh in the same process (LLaMA-1B
widths, one layer, rank 128).

Everything runs in this one process, which holds the chips. The numbers
printed before the last line are bring-up observations, not benchmark
metrics. The last line of stdout is {"ok": true, "device": {...}}; a failed
check exits non-zero without it, and so does a machine where JAX finds no
TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# per-device batch of the one-chip train step: the whole LLaMA-1B step
# (bf16 weights and gradients, int8/int4 GaLore state, seq 256, no remat)
# compiled for a v5e needs 13.1 GiB at 4 and 17.2 GiB at 8 of the 15.75 GiB
# XLA may use (compiled.memory_analysis() against a described v5e:2x2)
BATCH = 4
SEQ = 256
STEPS = 10
GALORE_T = 2          # a refresh every even step: SVDs on the chip
# --four-chips bounds. Per-step losses of the 4-way and one-device runs part
# as refreshes on gradients summed in another order turn near-degenerate
# singular directions: 3.1e-3 at most on 4 CPU devices in float32 (1.6e-2 in
# bf16), so the loss bound is a coarse one. The sharp check is one update
# from one state: |dW_4 - dW_1| / |dW_1| was 8.2e-6 on 4 CPU devices, and
# 0.50 with a planted fault (one owner's rank block left out of G̃), which
# moved the losses by 1.6e-2 only (LLaMA-60M widths, one layer, float32).
FOUR_CHIP_LOSS_TOL = 2e-2
FOUR_CHIP_UPDATE_TOL = 1e-2
WORK = os.path.join(ROOT, ".chip_smoke")


def fail(msg: str):
    print(f"[smoke] FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def check(cond: bool, msg: str):
    if not cond:
        fail(msg)


class CompileClock:
    """Sums the backend compile time JAX reports while it is installed."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1


def train_args(ckpt_dir: str, *extra: str) -> list:
    return ["--arch", "llama_1b", "--full",
            "--galore-rank", "512", "--galore-t", str(GALORE_T),
            "--galore-fused", "--quant-moments", "int8", "--quant-proj", "int4",
            "--seq", str(SEQ), "--batch", str(BATCH), "--steps", str(STEPS),
            "--ckpt-every", str(STEPS - 1), "--ckpt-dir", ckpt_dir,
            # the paper's 1e-2 (reached after a 10k-step warmup there)
            # diverged on the chip here with this run's one warmup step
            "--lr", "3e-3",
            "--log-every", "1", *extra]


def smoke_configs(args):
    """(RunConfig, TrainConfig) for launcher flags, with the randomized-SVD
    refresh: an exact SVD of the rank+8 sketch, because the exact SVD of a
    2048-wide gradient alone takes over six minutes to compile for a v5e
    (the 512-wide one 33 s), too long for this run's time limit."""
    from repro.launch import train as T

    ap = T.build_parser()
    run, tc, _ = T.configs_from_args(ap, ap.parse_args(args))
    return run, dataclasses.replace(
        tc, galore=dataclasses.replace(tc.galore, projector="randomized"))


def galore_leaves(cfg, tc):
    """(path, shape) of every GaLore leaf, in the order the optimizer
    update visits them (the parameters' flattening order)."""
    import jax

    from repro.core.subspace import SubspaceManager
    from repro.models import model as M
    from repro.optim.factory import effective_galore_config
    from repro.utils import tree_paths

    p_struct = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    plans = SubspaceManager(effective_galore_config(tc),
                            param_axes=M.param_axes(cfg)).plans(p_struct)
    plan_of = dict(tree_paths(plans))
    return [(path, tuple(leaf.shape)) for path, leaf in tree_paths(p_struct)
            if plan_of[path].galore]


def kernel_phase():
    """The fused int8/int4 kernels of the LLaMA-1B step (one stacked layer
    of each leaf shape) against kernels/ref.py in float32 at "highest"
    matmul precision, on seeded random inputs. Tolerance 2e-2 of each
    output's largest magnitude: G is bf16, and the kernel's f32 matmuls may
    run in fewer bf16 passes than the reference; a wrong codebook lookup,
    scale or tile is off by O(1) of that magnitude."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.quant import codec

    r = 512
    for side, (m, n) in (("left", (2048, 2048)), ("left", (2048, 5461)),
                         ("right", (5461, 2048))):
        ks = jax.random.split(jax.random.PRNGKey(m + n), 4)
        short = m if side == "left" else n
        ax = -1 if side == "left" else -2
        mom = (1, r, n) if side == "left" else (1, m, r)
        P = jnp.linalg.qr(jax.random.normal(ks[0], (1, short, r)))[0]
        Pq = codec.quant4_axis_state(P)
        G = (jax.random.normal(ks[1], (1, m, n)) * 1e-2).astype(jnp.bfloat16)
        M = jax.random.normal(ks[2], mom) * 1e-3
        V = jnp.abs(jax.random.normal(ks[3], mom)) * 1e-6
        mq, ms = codec.quantize_axis(M, axis=ax, signed=True)
        vq, vs = codec.quantize_axis(V, axis=ax, signed=False)
        fn, rf = ((ops.galore_fused_adam8_step, ref.galore_fused_adam8_step)
                  if side == "left" else
                  (ops.galore_fused_adam8_step_right,
                   ref.galore_fused_adam8_step_right))
        count = jnp.int32(7)
        got = jax.jit(lambda *a: fn(*a, alpha=0.25))(
            Pq, G, mq, ms, vq, vs, count)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: rf(*a, alpha=0.25))(
                codec.dequant4_axis_state(Pq, P.shape), G.astype(jnp.float32),
                mq, ms, vq, vs, count)
        deq = lambda o, signed: codec.dequantize_axis(
            o[0], o[1], axis=ax, signed=signed)
        pairs = {"update": (got[0], want[0]),
                 "m": (deq(got[1:3], True), deq(want[1:3], True)),
                 "v": (deq(got[3:5], False), deq(want[3:5], False))}
        errs = {}
        for k, (a, b) in pairs.items():
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            errs[k] = float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))
        print(f"[smoke] kernel check {side} {m}x{n} r{r} int8+int4P: max err "
              "/ max |ref| " + ", ".join(f"{k} {v:.2e}" for k, v in errs.items()))
        check(all(v <= 2e-2 for v in errs.values()),
              f"fused kernel {side} {m}x{n} disagrees with the reference")


def train_phase(dev):
    import jax
    import numpy as np

    from repro.configs.base import get_config
    from repro.launch import train as T

    from repro.kernels import ops

    ckpt = os.path.join(WORK, "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    run, tc = smoke_configs(train_args(ckpt))
    cfg = get_config(run.arch, smoke=run.smoke)
    print(f"[smoke] model {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}")
    print(f"[smoke] GaLore rank {tc.galore.rank}, T={tc.galore.update_freq}, "
          f"fused, moments {tc.galore.quant.moments}, projectors "
          f"{tc.galore.quant.projectors}; batch {run.batch_per_host} x seq "
          f"{run.seq_len} (batch {BATCH}: the train step compiled for a v5e "
          f"needs 13.1 GiB of 15.75 at 4, 17.2 at 8)")
    losses, stamps, built = {}, {}, []

    def on_step(step, metrics):
        losses[step] = float(jax.block_until_ready(metrics["loss"]))
        stamps[step] = time.perf_counter()

    clock = CompileClock()
    t0 = time.perf_counter()
    with ops.record_routes() as routes:
        params, opt_state, _, _ = T.train_loop(run, tc, on_step=on_step,
                                               on_build=built.append)
    wall = time.perf_counter() - t0
    # the kernel path of every GaLore leaf and of every full-shape int8
    # leaf, as the traced step dispatched it
    leaves = galore_leaves(cfg, tc)
    galore_routes = [r for r in routes if r[0] != "full"]
    step_routes = galore_routes[:len(leaves)]
    for (path, shape), (side, g_shape, route) in zip(leaves, step_routes):
        check(shape == g_shape, f"update order: {path} {shape} vs {g_shape}")
        print(f"[smoke] kernel path {path} {shape} {side}: {route}")
    check(len(step_routes) == len(leaves) and galore_routes
          and all(r == "fused" for *_, r in galore_routes),
          "a GaLore leaf does not run the fused Pallas kernel")
    full_routes = sorted({r for r in routes if r[0] == "full"})
    for _, g_shape, route in full_routes:
        print(f"[smoke] full-shape int8 leaf {g_shape}: {route}")
    check(all(r == "fused" for *_, r in full_routes),
          "a full-shape int8 leaf does not run the epilogue's fold")
    steps = sorted(losses)
    check(steps == list(range(STEPS)), f"steps run: {steps}")
    for s in steps:
        print(f"[smoke] step {s} loss {losses[s]:.4f}")
    check(all(np.isfinite(losses[s]) for s in steps), "non-finite loss")
    check(losses[steps[-1]] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[steps[-1]]:.4f}")
    dts = [stamps[s] - stamps[s - 1] for s in steps[2:]]
    print(f"[smoke] train wall {wall:.1f} s; backend compile {clock.seconds:.1f} s "
          f"over {clock.count} programs; first step "
          f"{stamps[0] - t0:.1f} s (compile included)")
    print("[smoke] step wall after warm-up (s, steps 2..): "
          + " ".join(f"{d:.3f}" for d in dts)
          + " (even steps refresh the subspace)")

    # the compiled train step, taken from the jitted function the loop ran
    # (same arguments as its last call: an in-memory cache hit)
    data_batch = T.SyntheticC4(T.DataConfig(
        vocab_size=cfg.vocab_size, seq_len=run.seq_len,
        batch_per_host=run.batch_per_host, seed=tc.seed)).batch(STEPS - 1)
    t0 = time.perf_counter()
    text = built[-1].lower(params, opt_state, data_batch).compile().as_text()
    n_custom = text.count("tpu_custom_call")
    print(f"[smoke] compiled train step: {n_custom} tpu_custom_call ops "
          f"(text fetched in {time.perf_counter() - t0:.1f} s)")
    check(n_custom > 0, "no Pallas kernel in the compiled train step")
    stats = dev.memory_stats() or {}
    print(f"[smoke] peak_bytes_in_use after training: "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    del params, opt_state
    return cfg, ckpt


def serve_phase(cfg, ckpt):
    from repro.launch.serve import load_checkpoint_params
    from repro.serve import Engine, Request, ServeConfig

    params, step = load_checkpoint_params(cfg, ckpt)
    print(f"[smoke] serve: restored params of step {step} from {ckpt}")
    check(step == STEPS - 1, f"checkpoint step {step}, expected {STEPS - 1}")
    engine = Engine(cfg, params, ServeConfig(block_size=16, num_blocks=64,
                                             slots=4, max_len_cap=128,
                                             prefill_chunk=32))
    max_new = 8
    reqs = [Request(tokens=tuple((7 * i + 3 * n) % cfg.vocab_size
                                 for i in range(n)), max_new=max_new)
            for n in (5, 17, 40, 77)]
    t0 = time.perf_counter()
    ids = [engine.submit(r) for r in reqs]
    engine.run_until_drained()
    dt = time.perf_counter() - t0
    for rid, req in zip(ids, reqs):
        c = engine.result(rid)
        check(c is not None, f"request {rid} never finished")
        print(f"[smoke] request {rid}: prompt {len(req.tokens)} tokens -> "
              f"{len(c.tokens)} new [{c.finish_reason}] ttft "
              f"{c.ttft_s * 1e3:.0f} ms")
        check(c.finish_reason in ("max_new", "eos"),
              f"request {rid} finished with {c.finish_reason}")
        want = len(c.tokens) == max_new if c.finish_reason == "max_new" \
            else 1 <= len(c.tokens) <= max_new
        check(want, f"request {rid}: {len(c.tokens)} tokens for "
              f"finish_reason {c.finish_reason}")
    print(f"[smoke] served {len(reqs)} requests in {dt:.1f} s (compile included)")


def four_chip_phase():
    import jax
    import numpy as np

    from repro.configs.base import get_config
    from repro.kernels import ops
    from repro.launch import mesh as mesh_lib
    from repro.launch import train as T
    from repro.utils import tree_paths

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    # the same flags, seed and global batch (--batch is per host) on both
    # meshes; on one device the sharded refresh and ZeRO-1 degenerate to
    # the plain external refresh and unsharded state
    # LLaMA-1B widths, depth cut to one layer and rank to 128: the sharded
    # refresh program holds one projector computation per (leaf, layer)
    # unit, and compiling the 7 units of one layer for a v5e took 227 s at
    # rank 512, 32 s at rank 128
    flags = ("--galore-refresh-shard", "--galore-zero", "1",
             "--galore-rank", "128")
    # float32 weights: bf16 ones move by about one rounding step per
    # update, so any reordered float sum flips roundings and the two runs
    # part by rounding alone (one update from one state differed by 4-5% of
    # its size on 4 CPU devices in bf16); in float32 they agree closely
    cfg = dataclasses.replace(get_config("llama_1b"), n_layers=1,
                              dtype="float32")

    def run(mesh, tag):
        ckpt = os.path.join(WORK, f"ckpt_{tag}")
        shutil.rmtree(ckpt, ignore_errors=True)
        run_cfg, tc = smoke_configs(train_args(ckpt, "--ckpt-every", "0",
                                               *flags))
        losses, stamps, built = {}, {}, []

        def on_step(step, metrics):
            losses[step] = float(metrics["loss"])
            stamps[step] = time.perf_counter()

        t0 = time.perf_counter()
        with ops.record_routes() as routes:
            params, opt_state, _, _ = T.train_loop(
                run_cfg, tc, cfg=cfg, mesh=mesh, on_build=built.append,
                on_step=on_step)
        dts = [stamps[s] - stamps[s - 1] for s in sorted(stamps)[2:]]
        print(f"[smoke] {tag}: {len(losses)} steps in "
              f"{time.perf_counter() - t0:.1f} s (compile included), step "
              "wall after warm-up (s, steps 2..) "
              + " ".join(f"{d:.3f}" for d in dts) + "; "
              "kernel paths " + ", ".join(f"{side} {shape}: {route}"
                                          for side, shape, route
                                          in sorted(set(routes))))
        # full-shape int8 leaves keep the XLA codec on a multi-device mesh
        galore_routes = [r for r in routes if r[0] != "full"]
        check(galore_routes and all(r == "fused" for *_, r in galore_routes),
              "a GaLore leaf does not run the fused Pallas kernel")
        return ([losses[s] for s in sorted(losses)], params, opt_state,
                (mesh, tc, built[-1]))

    def stat(key):
        vals = [(d.memory_stats() or {}).get(key, 0) for d in devs]
        check(min(vals) > 0, f"a device reports no {key}: {vals}")
        return vals

    # the 4-way run first, so that each device's peak is its own
    l_dp, params, opt_state, prog_dp = run(mesh_lib.make_host_mesh(devs),
                                           "data4")
    peak_dp, in_use = stat("peak_bytes_in_use"), stat("bytes_in_use")
    print("[smoke] 4-way bytes_in_use after training per device: "
          + " ".join(map(str, in_use)))
    check(max(in_use) < 1.5 * min(in_use),
          "device memory is not spread over the four devices")
    # the ZeRO-1 state is owned rank-block-wise: every replica holds a shard
    zero_leaves = [x for x in jax.tree_util.tree_leaves(opt_state)
                   if hasattr(x, "sharding") and not x.sharding.is_fully_replicated]
    check(zero_leaves, "no optimizer-state leaf is sharded across the mesh")
    held = {d: 0 for d in devs}
    for x in zero_leaves:
        for sh in x.addressable_shards:
            held[sh.device] += sh.data.nbytes
    print("[smoke] sharded optimizer-state bytes per device: "
          + " ".join(str(held[d]) for d in devs))
    check(min(held.values()) > 0 and max(held.values()) < 2 * min(held.values()),
          "ZeRO state is not spread over the four devices")
    del params, opt_state

    l_one, params, opt_state, prog_one = run(
        mesh_lib.make_host_mesh(devs[:1]), "one_device")
    # device 0's peak now covers both runs; a 4-way peak at or above it
    # means the 4-way step held more on a device than one device needs
    peak_one = stat("peak_bytes_in_use")[0]
    print("[smoke] peak_bytes_in_use per device, 4-way: "
          + " ".join(map(str, peak_dp)) + f"; one device: {peak_one}")
    check(max(peak_dp) < peak_one,
          "a device of the 4-way run peaked above the one-device run")

    for s, (a, b) in enumerate(zip(l_one, l_dp)):
        print(f"[smoke] step {s} loss one-device {a:.5f} 4-way {b:.5f} "
              f"diff {abs(a - b):.2e}")
    # one more step from the SAME state — the one-device run's last — on
    # both meshes: the updates agree up to the float reduction order, and
    # an owner's rank block that went missing is a quarter of the update
    state = jax.device_get((params, opt_state))
    del params, opt_state
    batch = T.SyntheticC4(T.DataConfig(
        vocab_size=cfg.vocab_size, seq_len=SEQ, batch_per_host=BATCH,
        seed=prog_one[1].seed)).batch(STEPS)

    def update(mesh, tc, step):
        p_shard, o_shard = T.state_shardings(
            cfg, tc, mesh_lib.default_rules(mesh))
        new, _, _ = step(jax.device_put(state[0], p_shard),
                         T._place(state[1], o_shard), batch)
        return {k: np.asarray(v, np.float32) - np.asarray(w, np.float32)
                for (k, v), (_, w) in zip(tree_paths(new),
                                          tree_paths(state[0]))}

    d_one, d_dp = update(*prog_one), update(*prog_dp)
    rel = {k: float(np.linalg.norm(d_dp[k] - d_one[k]) / np.linalg.norm(d)
                    ) for k, d in d_one.items() if np.any(d)}
    worst = max(rel, key=rel.get)
    print(f"[smoke] one update from one state, 4-way vs one device, "
          f"|dW_4 - dW_1| / |dW_1|: max {rel[worst]:.3e} ({worst}), median "
          f"{float(np.median(list(rel.values()))):.3e} over {len(rel)} leaves")
    check(all(np.isfinite(l_one + l_dp)), "non-finite loss")
    check(max(abs(a - b) for a, b in zip(l_one, l_dp)) <= FOUR_CHIP_LOSS_TOL,
          f"4-way losses differ from one device by more than "
          f"{FOUR_CHIP_LOSS_TOL}")
    check(rel[worst] <= FOUR_CHIP_UPDATE_TOL,
          f"4-way update differs from one device's by more than "
          f"{FOUR_CHIP_UPDATE_TOL} of its size")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way data-parallel GaLore path and "
                         "its one-device reference")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is {dev.platform} ({dev.device_kind})")
    print(f"[smoke] device {dev.device_kind} x{len(jax.devices())}")
    from repro.launch import cli

    print(f"[smoke] compile cache: {cli.enable_compile_cache()}")
    os.makedirs(WORK, exist_ok=True)
    if args.four_chips:
        four_chip_phase()
    else:
        kernel_phase()
        cfg, ckpt = train_phase(dev)
        serve_phase(cfg, ckpt)
    shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's training job through the program's own
`repro.launch.train.train_loop` on a mesh of the cell's chips, with weights
and token rows that the benchmark makes from the seed. The loop's first
steps are set-up: they compile, refresh the GaLore subspace, and are the
steps the reference checks. Then the window runs whole steps through the
same loop until `--seconds` have passed and blocks on the last one. Nothing
compiles inside it.

With --trace 0 the last line of stdout is the cell's end-to-end metrics,
with --trace 1 its per-layer metrics from a profiler trace of the window.
Both check the first steps against the float32 reference (bench/check.py)
and print every compared number beside its limit, last on stderr.

A machine where JAX finds no TPU, or fewer chips than the cell asks for,
or a device the peak table does not hold, exits non-zero with no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CHECK_STEPS = 2  # set-up steps the reference follows
COMPILE_CACHE = os.path.join(ROOT, ".jax_cache")


class Refused(SystemExit):
    """The run cannot give a result on this machine."""

    def __init__(self, msg: str):
        print(f"[bench] refused: {msg}", file=sys.stderr)
        super().__init__(2)


def load_peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json "
                      f"({sorted(table)})")
    return table[kind]


def require_chips(n: int):
    """The first n TPU devices, or a refusal."""
    import jax

    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        raise Refused(f"JAX finds no TPU (platform "
                      f"{devices[0].platform if devices else None!r})")
    if len(devices) < n:
        raise Refused(f"the cell asks for {n} chips, JAX finds {len(devices)}")
    return devices[:n]


def peak_bytes(devices) -> int:
    """The most bytes any of the chips has held at once in this process."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileClock:
    """Counts the backend compiles JAX reports while it is installed."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1


def _loop_locals() -> dict:
    """The local variables of the running `train_loop` (its params and
    optimizer state after the step it just dispatched). A stopgap, as the
    swap in `benchmark_inputs` is, until the loop hands its state to
    `on_step`."""
    from repro.launch.train import train_loop

    f = sys._getframe(1)
    while f is not None and f.f_code is not train_loop.__code__:
        f = f.f_back
    if f is None:
        raise RuntimeError("on_step was not called from train_loop")
    return f.f_locals


class Window:
    """train_loop's on_step hook: set-up readings, then the timed window.

    The first CHECK_STEPS steps block on their loss and take the readings
    the reference is compared with. After them the window opens at a step
    boundary. Inside it the hook waits only for the step before the one
    just dispatched, so one step is always queued on the device, and ends
    the loop (RunConfig.steps) once the next step would finish past
    `seconds`."""

    def __init__(self, run, cell, seed, seconds, clock, on_open=None):
        self.run, self.cell, self.seed = run, cell, seed
        self.seconds = seconds
        self.clock = clock
        self.on_open = on_open
        self.losses: list = []
        self.grad_norms = None
        self.first_moments = None
        self.change_norms = None
        self.t_open = None
        self.compiles_open = None
        self.step_est = None
        self.first = None
        self.last = None
        self.prev = None
        self.prev_t = None
        self.window_losses: list = []
        self.jitted = None
        self.phases = [("process start", T_START)]
        self.peaks: list = []  # (phase, the fullest chip's peak bytes so far)

    def on_build(self, jitted):
        self.jitted = jitted

    def watch_batches(self, batch):
        """`batch`, which also reads the fullest chip's peak the first time
        the loop asks for rows with its state built: before step 0's
        subspace refresh and step."""
        import jax

        def watched(step):
            if not self.peaks:
                loop = _loop_locals()
                if "opt_state" in loop:
                    jax.block_until_ready((loop["params"], loop["opt_state"]))
                    self.peaks.append(("state built", peak_bytes(jax.devices())))
            return batch(step)

        return watched

    def on_step(self, step, metrics):
        import jax

        from bench import state

        if step < CHECK_STEPS:
            self.losses.append(float(metrics["loss"]))
            now = time.perf_counter()
            self.phases.append((f"step {step}", now))
            self.peaks.append((f"step {step}", peak_bytes(jax.devices())))
            if step > 0:
                self.step_est = now - self.prev_t
            if step == 0:
                b1 = self.cell.config["assumed"]["train"]["b1"]
                opt_state = _loop_locals()["opt_state"]
                self.grad_norms = state.first_grad_norms(
                    self.cell.config, opt_state, b1)
                self.first_moments = state.first_moments(self.cell.config,
                                                         opt_state)
                del opt_state
                self.phases.append(("first gradient read", time.perf_counter()))
            if step == CHECK_STEPS - 1:
                self.change_norms = state.change_norms(
                    self.cell.config, self.seed, _loop_locals()["params"])
                self.phases.append(("change read", time.perf_counter()))
                self.compiles_open = self.clock.count
                if self.on_open is not None:
                    self.on_open()
                self.t_open = time.perf_counter()
            # the next step is dispatched only now: time it from here
            self.prev_t = time.perf_counter()
            return
        if self.first is None:
            self.first = step
        if self.prev is not None:
            self.window_losses.append(float(jax.block_until_ready(self.prev)))
            now = time.perf_counter()
            self.step_est = now - self.prev_t
            self.prev_t = now
        self.prev = metrics["loss"]
        self.last = step
        if (self.prev_t - self.t_open) + self.step_est >= self.seconds:
            self.run.steps = step + 1  # this dispatched step is the last

    def close(self):
        import jax

        self.window_losses.append(float(jax.block_until_ready(self.prev)))
        self.t_close = time.perf_counter()
        return self.t_close - self.t_open


@contextlib.contextmanager
def benchmark_inputs(cell, seed: int):
    """The program's initializer and token pipeline, swapped for the
    benchmark's own (weights and rows from the seed) while the loop runs.
    A stopgap until `train_loop` takes its data and initial state from its
    caller: the swap holds only for the cell's own model configuration."""
    import jax

    from bench import data as bdata
    from bench import weights as bw
    from bench.cell import model_config
    import repro.launch.train as T
    from repro.models import model as M

    cfg = model_config(cell)
    want = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: bw.init_params(cell.config, seed))
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got))):
        raise RuntimeError("the benchmark's weights do not match the program's "
                           f"parameter tree: {want} vs {got}")
    stream = bdata.TokenStream(cell.traffic, cfg.vocab_size, cell.rows, seed)
    saved = (M.init_params, T.SyntheticC4)
    M.init_params = lambda c, key: (bw.init_params(cell.config, seed) if c == cfg
                                    else saved[0](c, key))
    T.SyntheticC4 = lambda _dc: stream
    try:
        yield cfg, stream
    finally:
        M.init_params, T.SyntheticC4 = saved


def drive(cell, seed: int, seconds: float, trace: bool, devices,
          trace_summary: str | None = None) -> dict:
    """Set-up, window and readings of one run; the reference comes after."""
    import jax

    from bench import state
    from bench.cell import train_configs
    import repro.launch.train as T
    from repro.launch import mesh as mesh_lib

    run, tc = train_configs(cell, seed)
    run.ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    clock = CompileClock()
    tracer = None
    if trace:
        from bench import trace as btrace

        tracer = btrace.Tracer()
    win = Window(run, cell, seed, seconds, clock,
                 on_open=tracer.start if tracer else None)
    mesh = mesh_lib.make_host_mesh(devices=devices)
    win.phases.append(("loop start", time.perf_counter()))
    try:
        with benchmark_inputs(cell, seed) as (cfg, stream):
            stream.batch = win.watch_batches(stream.batch)
            params, opt_state, _, _ = T.train_loop(
                run, tc, cfg=cfg, on_step=win.on_step, mesh=mesh,
                on_build=win.on_build)
            window_s = win.close()
            compiles = clock.count - win.compiles_open
            if tracer is not None:
                tracer.stop()
            peak = peak_bytes(devices)
            hlo = None
            if tracer is not None:
                hlo = win.jitted.lower(params, opt_state,
                                       stream.batch(win.last)).compile().as_text()
            batches = [stream.batch(s) for s in range(CHECK_STEPS)]
    finally:
        shutil.rmtree(run.ckpt_dir, ignore_errors=True)
    del params, opt_state
    win.jitted = None
    gc.collect()
    steps = win.last - win.first + 1
    out = {
        "setup_phases": [(name, round(t - T_START, 3)) for name, t in win.phases],
        "setup_peaks": win.peaks,
        "setup_compiles": (win.compiles_open, round(clock.seconds, 3)),
        "setup_s": win.t_open - T_START,
        "window_s": window_s,
        "steps": steps,
        "tokens": steps * cell.tokens_per_step,
        "compiles_in_window": compiles,
        "peak_bytes": peak,
        "losses": win.losses,
        "window_losses": win.window_losses,
        "grad_norms": win.grad_norms,
        "first_grads": state.FirstGrads(
            cell.config, win.first_moments,
            cell.config["assumed"]["train"]["b1"]),
        "change_norms": win.change_norms,
        "batches": batches,
    }
    if tracer is not None:
        if trace_summary:
            tracer.summary(trace_summary, hlo)
        out["trace"] = tracer.reduce(hlo, window_s)
    return out


def reference_readings(cell, seed: int, batches, precision="f32", rows=None,
                       fault=None):
    """The reference's readings of the first steps (bench/reference/)."""
    import jax

    from bench.reference import galore as ref

    with jax.default_matmul_precision("highest"):
        return ref.run(cell.config, seed, batches, precision, rows, fault)


def setup_jax():
    """JAX with the persistent compilation cache at the checkout's fixed
    path, holding every program, however small or large."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the train step's executable is hundreds of MB: a size cap on the cache
    # (JAX_COMPILATION_CACHE_MAX_SIZE) would evict it and recompile it in
    # every run's set-up
    jax.config.update("jax_compilation_cache_max_size", -1)


def per_layer_metrics(bench: dict, cell, ctx) -> dict:
    """Every per-layer metric of BENCHMARK.json that lists this cell (or
    lists none), read by its own reader in bench/metrics/<name>.py. A
    reader that finds nothing returns None and the metric is left out."""
    from bench.metrics import load_reader

    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell.name not in m["workloads"]:
            continue
        value = load_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end_metrics(bench: dict, cell, r: dict) -> dict:
    values = {
        "train_tokens_per_s": r["tokens"] / r["window_s"],
        "peak_hbm_bytes": r["peak_bytes"],
        "setup_s": r["setup_s"],
    }
    out = {}
    for m in bench["end_to_end"]:
        if "workloads" in m and cell.name not in m["workloads"]:
            continue
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-summary", default=None, metavar="PATH",
                    help="with --trace 1, also write the trace's planes, lines "
                         "and sample events to PATH (JSON), to read by hand")
    args = ap.parse_args(argv)

    from bench.cell import benchmark, load_cell

    bench = benchmark()
    cell = load_cell(args.workload)
    setup_jax()
    devices = require_chips(cell.chips)
    kind = devices[0].device_kind
    peaks = load_peaks(kind)

    r = drive(cell, args.seed, args.seconds, bool(args.trace), devices,
              args.trace_summary)
    if r["compiles_in_window"]:
        print(f"[bench] {r['compiles_in_window']} compiles inside the window",
              file=sys.stderr)
        raise SystemExit(3)
    print(f"[bench] {cell.name} seed {args.seed}: setup {r['setup_s']:.3f} s, "
          f"window {r['window_s']:.3f} s, {r['steps']} steps, peak "
          f"{r['peak_bytes']} bytes, set-up losses {r['losses']}, window "
          f"losses {r['window_losses']}", file=sys.stderr)
    print(f"[bench] set-up phases (s from process start): {r['setup_phases']}; "
          f"compiles before the window (count, backend seconds): "
          f"{r['setup_compiles']}; peak bytes after each set-up step: "
          f"{r['setup_peaks']}", file=sys.stderr)

    from bench import check

    ref = reference_readings(cell, args.seed, r.pop("batches"))
    nums = check.numbers(r, ref)
    correct, compared = check.verdict(nums, check.limits(cell.name))
    failed = sum(1 for x in r["window_losses"] if not math.isfinite(x))

    if args.trace:
        from bench.metrics import Context

        ctx = Context(cell=cell, peaks=peaks, run=r, n_chips=len(devices))
        metrics = per_layer_metrics(bench, cell, ctx)
    else:
        metrics = end_to_end_metrics(bench, cell, r)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": r["peak_bytes"]}
    result = {"correct": bool(correct and not failed), "attempted": r["steps"],
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace:
        t = r["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = t["breakdown"]
    result["checked"] = {k: {"value": v["value"], "limit": v["limit"]}
                         for k, v in compared.items()}
    for k, (value, where) in nums.items():
        lim = (f"limit {compared[k]['limit']:.6g}" if k in compared
               else "not compared")
        print(f"[bench] check {k}: {value:.6g} ({lim}, at {where})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

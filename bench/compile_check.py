"""Compile a cell's train step for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python bench/compile_check.py <workload> [--rows N ...]

Prints, for each row count, what the TPU compiler reports of the step that
`train_loop` jits (`compiled.memory_analysis()`), or the compiler's
refusal. Nothing runs: this says whether a cell fits the chip's memory, not
how fast it is.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")]


def compile_step(cell, rows: int, device):
    import jax
    import jax.numpy as jnp

    from bench.cell import model_config, train_configs
    from repro.distributed.step import make_train_step
    from repro.kernels import ops
    from repro.launch import mesh as mesh_lib
    from repro.launch.train import _place, state_shardings
    from repro.models import model as M

    ops._on_tpu = lambda: True  # dispatch the Pallas kernels, as on the chip
    cell = dataclasses.replace(cell, traffic={**cell.traffic, "micro_batch": rows})
    cfg = model_config(cell)
    run, tc = train_configs(cell, seed=0)
    mesh = mesh_lib.make_host_mesh(devices=[device])
    rules = mesh_lib.default_rules(mesh)
    p_shard, o_shard = state_shardings(cfg, tc, rules)
    train_step, opt = make_train_step(cfg, tc, rules)

    def pinned_step(params, opt_state, *rest):
        params, opt_state, *out = train_step(params, opt_state, *rest)
        constrain = jax.lax.with_sharding_constraint
        return (_place(params, p_shard, constrain),
                _place(opt_state, o_shard, constrain), *out)

    p_struct = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    o_struct = jax.eval_shape(opt.init, p_struct)
    sds = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
    params = jax.tree_util.tree_map(sds, p_struct, p_shard)
    opt_state = jax.tree_util.tree_map(
        lambda sh, s: s if sh is None else sds(s, sh), o_shard, o_struct,
        is_leaf=lambda x: x is None)
    one = jax.sharding.SingleDeviceSharding(device)
    B, S = run.batch_per_host, run.seq_len
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one),
             "targets": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one),
             "loss_mask": jax.ShapeDtypeStruct((B, S), jnp.float32, sharding=one)}
    t0 = time.perf_counter()
    compiled = jax.jit(pinned_step, donate_argnums=(0, 1)).lower(
        params, opt_state, batch).compile()
    return compiled, time.perf_counter() - t0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload")
    ap.add_argument("--rows", type=int, nargs="*", default=None,
                    help="micro-batch rows to compile (default: the traffic's)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from bench.cell import load_cell

    jax.config.update("jax_enable_compilation_cache", False)
    cell = load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for rows in args.rows or [int(cell.traffic["micro_batch"])]:
        try:
            compiled, secs = compile_step(cell, rows, topo.devices[0])
        except Exception as e:  # the compiler's refusal is the answer
            print(json.dumps({"workload": cell.name, "rows": rows,
                              "error": str(e).splitlines()[0][:400]}), flush=True)
            continue
        ma = compiled.memory_analysis()
        keys = ("argument_size_in_bytes", "output_size_in_bytes",
                "alias_size_in_bytes", "temp_size_in_bytes",
                "generated_code_size_in_bytes")
        out = {k: int(getattr(ma, k)) for k in keys}
        out["total_bytes"] = (out["argument_size_in_bytes"]
                              + out["output_size_in_bytes"]
                              - out["alias_size_in_bytes"]
                              + out["temp_size_in_bytes"])
        text = compiled.as_text()
        print(json.dumps({"workload": cell.name, "rows": rows,
                          "compile_s": round(secs, 1),
                          "tpu_custom_call": text.count("tpu_custom_call"),
                          **out}), flush=True)


if __name__ == "__main__":
    main()

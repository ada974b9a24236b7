"""The architectures the benchmark runs: one module per published `model_type`.

The module of a configuration is `bench/arch/<model_type>.py`, named for the
`model_type` of its published config.json, and holds everything the
harness knows of that architecture:

- `model_fields(config) -> dict`: the program's ModelConfig fields given by
  the configuration's published keys;
- `leaves(config) -> {path: bench.weights.Leaf}`: every leaf of the
  program's parameter tree, with the shape of one instance, its fan-in, how
  it is initialised and how many instances are stacked;
- `loss_and_grads(config, weight, batch, on_grad, precision) -> loss`: the
  plain float32 reference's loss, and its gradients one layer at a time.
  `weight(path, instance)` gives a float32 weight; `on_grad(path, instance,
  g)` takes each gradient once, as it comes;
- `flops_per_token(config, seq_len) -> int`: the forward and backward FLOPs
  per trained token that `step_mfu` divides by.

Model types that share an architecture share its code: `qwen2.py`,
`internlm2.py` and `llama.py` each re-export `dense.py`. `of(config)` loads
the one file its `model_type` names and no other, so an architecture is
added as a new file here with the configuration's files, no file of the
harness is edited, and a module of one model type never runs in a cell of
another.
"""
from __future__ import annotations

import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
DIRS = [HERE]  # where architecture modules are looked for, in order
_loaded: dict = {}


def _load(path: str):
    if path not in _loaded:
        name = "bench_arch_" + re.sub(r"\W", "_", os.path.splitext(
            os.path.basename(path))[0])
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def of(config: dict):
    """The module `<model_type>.py` of the configuration's `model_type`, from
    the first of DIRS that holds it."""
    model_type = config.get("model_type")
    if isinstance(model_type, str) and re.fullmatch(r"[A-Za-z0-9_-]+", model_type):
        for d in DIRS:
            path = os.path.join(d, f"{model_type}.py")
            if os.path.isfile(path):
                return _load(path)
    raise ValueError(f"no module implements model_type {model_type!r}: "
                     f"bench/arch/{model_type}.py does not exist")

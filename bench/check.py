"""How `correct` is decided: the program's first steps against the reference.

Numbers, each with a limit of its own (bench/limits/<workload>.json):

- loss1, loss2: the relative gap between the program's loss and the
  reference's at each of the first steps;
- grad: the first gradient as the optimizer got it, by the worst leaf and
  layer: |‖g_prog‖ − ‖g_ref‖| over the reference's norm of that leaf or of
  the median leaf, whichever is larger;
- grad_elem: the same gradient element by element, for the leaves that take
  full-shape 8-bit Adam (the embedding, K/V, biases, norm scales), by the
  worst leaf and layer: ‖g_prog − g_ref‖ over the same norm. A gap of norms
  averages rounding away; this one sees a gradient computed in a lower
  precision. The GaLore leaves are left out of it: the optimizer holds
  their gradient projected on its own subspace, which is not the
  reference's;
- change_galore, change_adam: the same gap of the norms of each leaf's
  change over the first steps, by the worst leaf and layer of each group:
  the leaves that move in a GaLore subspace, and those that take full-shape
  8-bit Adam (the embedding, K/V, biases).

Which leaves count in a change, by two rules on the reference alone:
a leaf whose first gradient is under a thousandth of the median leaf's (a
key bias under softmax: nought but rounding) moves under Adam by round-off
alone; and a leaf whose first update, in root mean square, is under half a
spacing of its initial weights' own type (a norm scale of 1.0 in bfloat16
against a step of the learning rate) cannot move in that type at all. Both
are left out; `change_unresolved`, the worst gap among the second kind, is
printed and not compared.
"""
from __future__ import annotations

import json
import os
import statistics

import jax
import jax.numpy as jnp

from bench.cell import ROOT

ROUNDING_FLOOR = 1e-3  # of the median leaf's first gradient


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))


def grad_elem_gaps(prog: dict, ref: dict) -> dict:
    """{(path, instance): ‖g_prog − g_ref‖ / max(‖g_ref‖, median leaf's)} of
    every leaf whose whole first gradient the reference keeps."""
    med = statistics.median(ref["grad_norms"].values())
    return {k: float(_diff_norm(prog["first_grads"][k], g))
            / max(ref["grad_norms"][k], med)
            for k, g in ref["first_grads"].items()}


def _gaps(prog: dict, ref: dict, keys, med: float) -> dict:
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}


def _worst(gaps: dict, keys) -> tuple:
    keys = [k for k in keys if k in gaps]
    if not keys:
        return None
    k = max(keys, key=gaps.get)
    return gaps[k], _name(k)


def groups(ref: dict) -> dict:
    """{"galore", "adam", "unresolved"}: the leaves and layers each change
    number reads, by the reference's gradients and first updates."""
    g = ref["grad_norms"]
    med = statistics.median(g.values())
    moving = [k for k, n in g.items() if n >= ROUNDING_FLOOR * med]
    resolved = {k for k in moving
                if ref["first_step_rms"][k] > ref["w0_half_ulp_rms"][k]}
    galore = {tuple(k) for k in ref["galore"]}
    return {"galore": sorted(resolved & galore),
            "adam": sorted(resolved - galore),
            "unresolved": sorted(set(moving) - resolved)}


def numbers(prog: dict, ref: dict) -> dict:
    """{name: (value, where)} of the compared numbers; `prog` holds
    "losses", "grad_norms", "first_grads" and "change_norms", `ref` the
    reference's readings (bench/reference/galore.py::run)."""
    out = {}
    for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss{i + 1}"] = (abs(p - r) / abs(r), f"step {i + 1}")
    keys = list(ref["grad_norms"])
    med = statistics.median(ref["grad_norms"].values())
    grad = _gaps(prog["grad_norms"], ref["grad_norms"], keys, med)
    out["grad"] = _worst(grad, keys)
    elem = grad_elem_gaps(prog, ref)
    out["grad_elem"] = _worst(elem, list(elem))
    grp = groups(ref)
    resolved = grp["galore"] + grp["adam"]
    med = statistics.median(ref["change_norms"][k] for k in resolved)
    change = _gaps(prog["change_norms"], ref["change_norms"],
                   resolved + grp["unresolved"], med)
    for name in ("galore", "adam", "unresolved"):
        worst = _worst(change, grp[name])
        if worst is not None:
            out[f"change_{name}"] = worst
    return out


def _name(key) -> str:
    path, layer = key
    return f"{path}[{layer}]"


def limits(workload: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "limits", f"{workload}.json")) as f:
        return json.load(f)["limits"]


def verdict(nums: dict, lim: dict) -> tuple:
    """(correct, {name: {"value", "limit", "at"}}). A limited number that a
    run does not give fails."""
    out = {k: {"value": v, "limit": lim[k], "at": where}
           for k, (v, where) in nums.items() if k in lim}
    ok = set(lim) <= set(out) and all(o["value"] <= o["limit"]
                                      for o in out.values())
    return ok, out

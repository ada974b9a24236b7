"""From a profiler trace of the window to device time by layer, by source
file and by named scope.

The window's device operations come from the JAX profiler's trace (the
"XLA Ops" lines of the TPU planes). Each operation is joined to the HLO
instruction of the same name in the compiled train step, and through that
instruction's metadata to the source file that emitted it and to the
innermost `jax.named_scope` of its `op_name`. The file's directory names
the layer (LAYERS). Device time that no rule claims, and the operations of
other programs, go to `unattributed`; a per-layer metric of one module or
one scope reads `files` or `scopes`.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile

# source directory of the program -> layer, first match wins
LAYERS = (
    ("/repro/kernels/", "kernels"),
    ("/repro/models/", "model"),
    ("/repro/core/", "optimizer"),
    ("/repro/optim/", "optimizer"),
    ("/repro/quant/", "optimizer"),
    ("/repro/distributed/", "step"),
    ("/repro/launch/", "step"),
)
OPS_LINE = "XLA Ops"
NS = 1e-9


# ---------------------------------------------------------------------------
# HLO text: instruction -> source file, and the operands of custom calls
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\))?.*\{\s*$")
_SOURCE = re.compile(r'source_file="([^"]*)"')
_OP_NAME_META = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_ARRAY = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)"
                    r"\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


_TABLE_ROW = re.compile(r"^(\d+) (.*)$")
_FIELD = re.compile(r"(\w+)=(\d+)")
_FRAME_ID = re.compile(r"stack_frame_id=(\d+)")


def _tables(lines) -> tuple:
    """The module's FileNames, FileLocations and StackFrames tables, which
    the TPU compiler prints instead of an inline source_file per op."""
    files, locs, frames, section = {}, {}, {}, None
    for line in lines:
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            section = line
            continue
        m = _TABLE_ROW.match(line)
        if m is None or section is None:
            if line and not line[0].isdigit():
                section = None
            continue
        key, body = int(m.group(1)), m.group(2)
        if section == "FileNames":
            files[key] = body.strip('"')
        elif section == "FileLocations":
            locs[key] = dict((k, int(v)) for k, v in _FIELD.findall(body))
        elif section == "StackFrames":
            frames[key] = dict((k, int(v)) for k, v in _FIELD.findall(body))
    return files, locs, frames


def _frame_files(frame: int, files, locs, frames) -> list:
    """Source files of a stack frame and its callers, innermost first."""
    out, seen = [], set()
    while frame and frame in frames and frame not in seen:
        seen.add(frame)
        loc = locs.get(frames[frame].get("file_location_id"), {})
        if loc.get("file_name_id") in files:
            out.append(files[loc["file_name_id"]])
        frame = frames[frame].get("parent_frame_id", 0)
    return out


def _balanced(text: str, start: int) -> int:
    """Index just past the bracket group that opens at text[start]."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] in "([{":
            depth += 1
        elif text[i] in ")]}":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _split(rest: str):
    """(result type, opcode, operand entries) of an instruction's text
    after "name = ". Layouts hold brackets of their own ("{2,1,0:T(8,128)}"),
    so every group is matched, not split at the first ")"."""
    if rest.startswith("("):
        end = _balanced(rest, 0)
    else:
        end = 0
        while end < len(rest) and rest[end] != " ":
            end = _balanced(rest, end) if rest[end] in "([{" else end + 1
    rtype = rest[:end]
    m = re.match(r"\s*([\w\-]+)\(", rest[end:])
    if m is None:
        return rtype, None, []
    open_at = end + m.end() - 1
    body = rest[open_at + 1:_balanced(rest, open_at) - 1]
    entries, depth, cur = [], 0, ""
    for ch in body:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            entries.append(cur.strip())
            cur = ""
        else:
            cur += ch
    if cur.strip():
        entries.append(cur.strip())
    return rtype, m.group(1), entries


# components of an op_name that JAX itself writes: transformations hold a
# parenthesis ("jit(f)", "transpose(jvp())"), control flow and remat these
_JAX_NAMES = frozenset({"while", "body", "cond", "closed_call", "core_call",
                        "checkpoint", "remat", "rematted_computation", "pjit",
                        "scan", "shard_map", "custom_jvp_call", "custom_vjp_call"})
_BRANCH = re.compile(r"^branch_\d+_fun$")


def innermost_scope(op_name):
    """The innermost `jax.named_scope` of an op_name
    ("jit(step)/jvp()/while/body/int8_quantize/mul" -> "int8_quantize"), or
    None: the last component, the primitive, and JAX's own are passed over."""
    for part in reversed(op_name.split("/")[:-1]):
        if part and "(" not in part and part not in _JAX_NAMES \
                and not _BRANCH.match(part):
            return part
    return None


def program_file(source):
    """A source path relative to the program's package ("models/moe.py"),
    or None outside it."""
    if source and "/repro/" in source:
        return source.rsplit("/repro/", 1)[1]
    return None


def parse_hlo(text: str) -> dict:
    """{instruction name: {"source", "scope", "custom", "operands", "results"}}.

    `source` is the innermost file of the op's stack that lies in the
    program (src/repro/), else its innermost file: inline `source_file`
    metadata, or the module's stack-frame tables. `scope` is the innermost
    named scope of its `op_name`. A fusion without a source or a scope of
    its own takes the first found in the computation it calls.
    `operands` and `results` list the (dtype, shape) of a custom call's
    array operands and results; an operand printed by name alone takes
    the type of the instruction that defines it."""
    lines = text.splitlines()
    files, locs, frames = _tables(lines)
    instrs, comp_source, comp_scope, comp = {}, {}, {}, None
    for line in lines:
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name, rest = m.groups()
        src = _SOURCE.search(rest)
        stack = [src.group(1)] if src else []
        fid = _FRAME_ID.search(rest)
        if fid:
            stack += _frame_files(int(fid.group(1)), files, locs, frames)
        src = next((f for f in stack if "/repro/" in f), stack[0] if stack else None)
        op = _OP_NAME_META.search(rest)
        scope = innermost_scope(op.group(1)) if op else None
        if comp is not None:
            if src:
                comp_source.setdefault(comp, src)
            if scope:
                comp_scope.setdefault(comp, scope)
        rtype, opcode, entries = _split(rest)
        info = {"source": src, "scope": scope, "type": rtype,
                "custom": opcode == "custom-call"}
        calls = _CALLS.search(rest)
        if calls:
            info["calls"] = calls.group(1)
        if info["custom"]:
            info["entries"] = entries
            info["target"] = (re.search(r'custom_call_target="([^"]*)"', rest)
                              or [None, None])[1]
        instrs[name] = info
    for info in instrs.values():
        if "calls" in info:
            if info["source"] is None:
                info["source"] = comp_source.get(info["calls"])
            if info["scope"] is None:
                info["scope"] = comp_scope.get(info["calls"])
        if info["custom"]:
            info["results"] = _arrays(info["type"])
            info["operands"] = []
            for e in info.pop("entries"):
                ref = e.split()[-1].lstrip("%")
                typed = _arrays(e)
                info["operands"] += typed or _arrays(instrs.get(ref, {}).get("type", ""))
    return instrs


def _arrays(text: str) -> list:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _ARRAY.findall(text)]


def nbytes(arrays) -> int:
    n = 0
    for dt, shape in arrays:
        size = _BYTES[dt]
        for d in shape:
            size *= d
        n += size
    return n


def layer_of(source) -> str:
    if source:
        for prefix, layer in LAYERS:
            if prefix in source:
                return layer
    return "unattributed"


def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def galore_call(info: dict):
    """(side, layers, m, n, rank) of a fused GaLore kernel call, read off
    its operands: G is the bf16/f32 (…, m, n) weight gradient, the int8
    moments are (…, r, n) on the left and (…, m, r) on the right, over the
    same leading axes (layers, experts), whose product is `layers`. The
    full-shape int8 fold, whose codes have G's shape, is not one."""
    if not info.get("custom"):
        return None
    floats = [s for dt, s in info["operands"] if dt in ("bf16", "f32") and len(s) >= 2]
    if not floats:
        return None
    G = max(floats, key=_size)  # the largest
    lead, (m, n) = G[:-2], G[-2:]
    for c in (s for dt, s in info["operands"] if dt == "u8" and len(s) == len(G)):
        if c[:-2] != lead:
            continue
        if c[-1] == n and c[-2] != m:
            return ("left", _size(lead), m, n, c[-2])
        if c[-2] == m and c[-1] != n:
            return ("right", _size(lead), m, n, c[-1])
    return None


# ---------------------------------------------------------------------------
# The trace
# ---------------------------------------------------------------------------


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """(start, end) of the idle stretches between lo and hi."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [g for g in out if g[1] > g[0]]


_OP_NAME = re.compile(r"^%?([\w.\-]+)\s*=")
_CONTAINER = re.compile(r"[\]})] (while|conditional|call)\(")
MODULES_LINE = "XLA Modules"


def op_name(event_name: str) -> str:
    """The HLO instruction's name in an op event's name, which holds the
    whole instruction text ("%fusion.12 = bf16[...] fusion(...), ...")."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def device_ops(pdata) -> dict:
    """{device plane: [(op, module, start_ns, end_ns, container)]} of the
    ops lines. `module` is the program ("jit_pinned_step(...)") whose run
    holds the op; `container` marks a while / conditional / call, whose
    time its inner ops also report."""
    out = {}
    for plane in pdata.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        mods, evs = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                mods += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in line.events]
        mods.sort()
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                start, end = ev.start_ns, ev.start_ns + ev.duration_ns
                mod = next((n for s, e, n in mods if s <= start < e), "")
                evs.append((op_name(ev.name), mod, start, end,
                            bool(_CONTAINER.search(ev.name))))
        if evs:
            out[plane.name] = evs
    return out


def host_spans(pdata) -> list:
    """(name, start_ns, end_ns) of the host's events, on the same clock."""
    out = []
    for plane in pdata.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def reduce(ops_by_device: dict, hosts: list, hlo: dict, window_s: float,
           module: str = "jit_pinned_step", top: int = 10) -> dict:
    """Busy time, device time by layer (`layers`), by program source file
    (`files`, "models/moe.py") and by innermost named scope (`scopes`), the
    GaLore kernel calls and the breakdown, averaged over the devices traced.
    Ops of the train step's program (`module`) are joined to its HLO; every
    other program's ops are `unattributed`, in no file and no scope."""
    n_dev = max(1, len(ops_by_device))
    busy = 0.0
    layers: dict = {}
    files: dict = {}
    scopes: dict = {}
    by_op: dict = {}
    kernels: dict = {}
    all_gaps = []
    for evs in ops_by_device.values():
        iv = [(s, e) for _, _, s, e, container in evs if not container]
        busy += union_length(iv) * NS
        for name, mod, s, e, container in evs:
            if container:
                continue
            info = hlo.get(name) if mod.startswith(module) else None
            layer = layer_of(info["source"]) if info else "unattributed"
            if info and info.get("custom") and layer == "unattributed":
                layer = "kernels"
            dt = (e - s) * NS / n_dev
            layers[layer] = layers.get(layer, 0.0) + dt
            if info:
                f = program_file(info["source"])
                if f:
                    files[f] = files.get(f, 0.0) + dt
                if info["scope"]:
                    scopes[info["scope"]] = scopes.get(info["scope"], 0.0) + dt
            label = f"{name} ({layer})" if info else f"{name} ({mod or 'no program'})"
            by_op[label] = by_op.get(label, 0.0) + dt
            call = galore_call(info) if info else None
            if call is not None:
                k = kernels.setdefault(name, {"call": call, "seconds": 0.0,
                                              "count": 0,
                                              "operand_bytes": nbytes(info["operands"]),
                                              "result_bytes": nbytes(info["results"])})
                k["seconds"] += dt
                k["count"] += 1 / n_dev
        if iv:
            lo, hi = min(s for s, _ in iv), max(e for _, e in iv)
            all_gaps += gaps(iv, lo, hi)
    named = []
    for s, e in sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        cover = [h for h in hosts if h[1] <= mid <= h[2]]
        what = min(cover, key=lambda h: h[2] - h[1])[0] if cover else "no host span"
        named.append([what, (e - s) * NS])
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy / n_dev,
        "window_s": window_s,
        "layers": layers,
        "files": files,
        "scopes": scopes,
        "kernels": list(kernels.values()),
        "breakdown": {"device_ops": [[k, v] for k, v in ops],
                      "idle_gaps": named},
    }


class Tracer:
    """Profiler trace of the window, in a temporary directory."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self):
        import jax

        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def load(self):
        from jax.profiler import ProfileData

        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return ProfileData.from_file(max(files, key=os.path.getmtime))

    def reduce(self, hlo_text: str, window_s: float) -> dict:
        try:
            pdata = self.load()
            return reduce(device_ops(pdata), host_spans(pdata),
                          parse_hlo(hlo_text), window_s)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

    def summary(self, path: str, hlo_text: str):
        """A look at the trace by hand: planes, lines, counts and a few
        events with their stats, written to `path` as JSON."""
        import json

        pdata = self.load()
        out = []
        for plane in pdata.planes:
            lines = []
            for line in plane.lines:
                evs = list(line.events)
                sample = []
                for ev in evs[:5] + evs[len(evs) // 2:len(evs) // 2 + 5]:
                    stats = {}
                    try:
                        stats = {str(k): str(v)[:200] for k, v in ev.stats}
                    except Exception as e:  # the stats API differs by version
                        stats = {"error": str(e)}
                    sample.append({"name": ev.name, "start_ns": ev.start_ns,
                                   "duration_ns": ev.duration_ns, "stats": stats})
                lines.append({"name": line.name, "events": len(evs),
                              "sample": sample})
            out.append({"plane": plane.name, "lines": lines})
        with open(path, "w") as f:
            json.dump({"planes": out, "hlo_head": hlo_text[:20000]}, f, indent=1)

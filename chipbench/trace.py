"""From a profiler trace to what the per-layer readers read.

Two steps, kept apart so that the second can be checked on a small
recorded trace (``chipbench/testdata``):

1. ``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
   keeps a compact record: every op on device 0's "XLA Ops" line (by
   its short name), every program on its "XLA Modules" line, and the
   benchmark's own host spans (``TraceAnnotation`` names starting with
   ``chipbench.``, ``client.`` or ``engine.``), each as [name, start
   ns, duration ns], and the operands of the ops that feed the kernels.
   The profiler puts host and device events on one clock.
2. ``reduce`` turns that record into seconds: the traced window (the
   ``chipbench.window`` span), the union of device op intervals inside
   it (busy), per-program and per-op-family totals (loops and branches
   left out, since their bodies are on the same line), the kernel
   families' time, and the idle gaps labelled by the host span that
   covers them.
"""
from __future__ import annotations

import bisect
import glob
import re

SPAN_PREFIXES = ("chipbench.", "client.", "engine.")
WINDOW_SPAN = "chipbench.window"
# Kernel families, matched against a device op's short name: the Mosaic
# custom call is named after the jitted kernel entry point
# (``%apsq_matmul_kernel.81``, ``%int8_kv_attention_kernel.11``).
KERNELS = {
    "apsq_gemm": re.compile(r"^apsq_(expert_)?matmul(_m1)?_kernel$"),
    "kv_attn": re.compile(r"^int8_kv_attention_kernel$"),
}
# Ops that contain other ops on the same line (loops, branches): left out
# of the per-op totals, which would count their bodies twice.
CONTAINERS = re.compile(r"^(while|conditional|call|cond)$")
# Ops that only move or re-lay data.  A kernel's time counts the ops that
# stage its operands: its direct operands, and through these, theirs.
# (XLA copies a layer's weight slab out of the stacked weights, pads it to
# the kernel's block, and may place it in VMEM before the kernel runs, so
# the kernel's own events leave out the HBM reads of its operands.)
STAGING = re.compile(r"(pad|slice|copy|transpose|bitcast|gather|broadcast)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def short_name(name: str) -> str:
    """``%apsq_matmul_kernel.81 = s32[16,4096] custom-call(...)`` ->
    ``%apsq_matmul_kernel.81``: the op's own name, without its HLO text."""
    return name.split(" = ", 1)[0]


def operands(name: str) -> list:
    """The operand op names in an op's HLO text (before its attributes)."""
    body = name.split(" = ", 1)[-1]
    body = re.split(r"(custom_call_target|backend_config|kind|calls)=",
                    body, 1)[0]
    return ["%" + m for m in _OPERAND.findall(body)]


def extract(profile_dir: str) -> dict:
    """Compact record of the newest trace under ``profile_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{profile_dir}/**/*.xplane.pb",
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(paths[-1])
    rec = {"device_ops": [], "modules": [], "host_spans": [], "operands": {}}
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            if int(m.group(1)) != 0:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Modules" in lines:
                rec["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                  for e in lines["XLA Modules"].events]
            starts = [mo[1] for mo in rec["modules"]]
            seen = set()
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines
                      else ()):
                short = short_name(e.name)
                rec["device_ops"].append([short, e.start_ns, e.duration_ns])
                fam = op_family(short)
                if e.name in seen or not (kernel_family(fam)
                                          or STAGING.search(fam)
                                          or fam == "fusion"):
                    continue
                seen.add(e.name)
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = module_name(rec["modules"][i][0]) if i >= 0 else ""
                rec["operands"][f"{mod}|{short}"] = operands(e.name)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        rec["host_spans"].append([e.name, e.start_ns,
                                                  e.duration_ns])
    return rec


def _union(intervals: list) -> list:
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def module_name(name: str) -> str:
    """``jit__decode_impl(12)`` -> ``_decode_impl``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def op_family(name: str) -> str:
    """``%apsq_matmul_kernel.81`` -> ``apsq_matmul_kernel``, ``%cond.2.clone``
    -> ``cond``: an op's name without its instance number and clone
    suffixes, for the totals and the breakdown."""
    return re.sub(r"(\.(\d+|clone))+$", "", name.lstrip("%")) or name


def kernel_family(family: str):
    for fam, pat in KERNELS.items():
        if pat.match(family):
            return fam
    return None


def _staging_sets(opnds: dict) -> dict:
    """{kernel family: {"module|op"}} of the ops that stage each kernel
    family's operands: direct operands, then operands of staging ops."""
    out = {}
    for key, ops in opnds.items():
        mod, short = key.split("|", 1)
        fam = kernel_family(op_family(short))
        if not fam:
            continue
        todo = [f"{mod}|{o}" for o in ops]
        got = out.setdefault(fam, set())
        while todo:
            k = todo.pop()
            if k in got:
                continue
            o = k.split("|", 1)[1]
            of = op_family(o)
            if kernel_family(of) or CONTAINERS.match(of):
                continue
            got.add(k)
            if STAGING.search(of):
                todo += [f"{mod}|{x}" for x in opnds.get(k, [])]
    return out


def reduce(rec: dict, gap_top: int = 10) -> dict:
    """Seconds of the traced window from a compact record."""
    win = [s for s in rec["host_spans"] if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = win[0][1], win[0][1] + win[0][2]

    def clip(s, d):
        return max(s, w0), min(s + d, w1)

    mods_sorted = sorted(rec["modules"], key=lambda m: m[1])
    starts = [m[1] for m in mods_sorted]

    def module_of(t):
        i = bisect.bisect_right(starts, t) - 1
        return module_name(mods_sorted[i][0]) if i >= 0 else ""

    staged = _staging_sets(rec.get("operands", {}))
    ops = []
    op_tot, kern_tot, stage_tot = {}, {}, {}
    for name, s, d in rec["device_ops"]:
        a, b = clip(s, d)
        if b <= a:
            continue
        ops.append([a, b])
        fam = op_family(name)
        if CONTAINERS.match(fam):
            continue
        sec = (b - a) * 1e-9
        op_tot[fam] = op_tot.get(fam, 0.0) + sec
        k = kernel_family(fam)
        if k:
            kern_tot[k] = kern_tot.get(k, 0.0) + sec
            continue
        key = f"{module_of(s)}|{name}"
        for kf, keys in staged.items():
            if key in keys:
                stage_tot[kf] = stage_tot.get(kf, 0.0) + sec
    busy = _union(ops)
    mods = {}
    for name, s, d in rec["modules"]:
        a, b = clip(s, d)
        if b <= a:
            continue
        n, t = mods.get(module_name(name), (0, 0.0))
        mods[module_name(name)] = (n + 1, t + (b - a) * 1e-9)

    spans = [s for s in rec["host_spans"] if s[0] != WINDOW_SPAN]
    gaps, prev = [], w0
    for a, b in busy + [[w1, w1]]:
        if a > prev:
            mid = (a + prev) / 2
            cover = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
            label = (min(cover, key=lambda s: s[2])[0] if cover
                     else "no host span")
            gaps.append([label, (a - prev) * 1e-9])
        prev = max(prev, b)
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(b - a for a, b in busy) * 1e-9,
        "modules": mods,
        "ops": op_tot,
        "kernels": kern_tot,
        "staging": stage_tot,
        "gaps": gaps[:gap_top],
        "n_ops": len(ops),
    }


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: top device ops, longest gaps."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [list(g) for g in red["gaps"][:top]]}

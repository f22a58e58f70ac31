"""Device time by the program's named scopes, from the traced run.

The program names its device work with ``jax.named_scope``: ``decode``
(the fused scan), ``prefill`` (one chunk), ``attn`` and ``mlp`` (the two
halves of a layer), ``lm_head``, ``sample``, ``kv_update`` (the paged
cache's gather, shift and scatter), ``kv_attention`` and ``apsq_gemm``
with ``quantize`` and ``rescale`` inside.  XLA keeps each op's scope path
in its ``op_name`` metadata, and the TPU profiler writes that path as the
``tf_op`` stat of the op's event metadata on device 0's "XLA Ops" line.  ``jax.profiler.ProfileData`` does not expose the
stats of event metadata, so ``extract`` reads them from the
``.xplane.pb`` itself (``_event_metadata``: the few protobuf fields it
needs, by field number).

Two steps, as in ``chipbench.trace``, so that the second can be checked
on a small recorded record (``chipbench/testdata``):

1. ``extract`` keeps the ``chipbench.window`` span, the "XLA Modules"
   events and, for every "XLA Ops" event overlapping the window, its
   short name, its scope path (an index into a table of paths) and its
   interval.
2. ``reduce`` turns that into ``{"programs": {program: {scope:
   seconds}}, "kernels": {program: {kernel family: {scope: seconds}}}}``.
   A scope is the path's known names in order (``decode/attn/kv_update``)
   or ``unscoped``; an op belongs to the program whose module event
   started last before it; loops and branches are left out, as their
   bodies are on the same line.  Where XLA fused ops of two scopes, the
   fusion's one path decides.

``table`` does both for the newest trace a run left at
``<checkout>/.chipbench/trace`` (the path ``harness.run`` writes), once
per trace file, and logs the table on standard error.  The per-layer
readers take their numbers from it with ``under``.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import sys
from pathlib import Path

from chipbench import trace

SCOPES = ("decode", "prefill", "attn", "mlp", "lm_head", "sample",
          "kv_update", "kv_attention", "apsq_gemm", "quantize", "rescale")
UNSCOPED = "unscoped"
SCOPE_STAT = "tf_op"
TRACE_DIR = Path(".chipbench") / "trace"
_DEVICE0 = "/device:TPU:0"


# ---------------------------------------------------------------------------
# The few protobuf fields of an XSpace that ProfileData leaves out
# ---------------------------------------------------------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field, raw bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            v, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} in an XSpace")
        yield key >> 3, v


def _map_value(entry):
    """The value message of one protobuf map entry (key 1, value 2)."""
    for f, v in _fields(entry):
        if f == 2:
            return v
    return memoryview(b"")


def _event_metadata(data: bytes, plane_name: str) -> dict:
    """{event metadata name: {stat name: value}} of one plane of an
    XSpace (XSpace.planes = 1; XPlane.name = 2, event_metadata = 4,
    stat_metadata = 5; XEventMetadata.name = 2, stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, str_value = 5,
    ref_value = 7, which names a stat metadata entry holding the
    string)."""
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, events, stat_names = None, [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(_map_value(v))
            elif g == 5:
                sid, sname = 0, ""
                for h, w in _fields(_map_value(v)):
                    if h == 1:
                        sid = w
                    elif h == 2:
                        sname = bytes(w).decode()
                stat_names[sid] = sname
        if name != plane_name:
            continue
        out = {}
        for ev in events:
            ename, st = "", {}
            for g, v in _fields(ev):
                if g == 2:
                    ename = bytes(v).decode()
                elif g == 5:                     # strings kept, others None
                    sid, val = 0, None
                    for h, w in _fields(v):
                        if h == 1:
                            sid = w
                        elif h == 5:
                            val = bytes(w).decode()
                        elif h == 7:
                            val = stat_names.get(w)
                    st[stat_names.get(sid, str(sid))] = val
            out[ename] = st
        return out
    return {}


def extract(profile_dir: str) -> dict:
    """Compact scoped record of the newest trace under ``profile_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{profile_dir}/**/*.xplane.pb",
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    with open(paths[-1], "rb") as f:
        data = f.read()
    meta = _event_metadata(data, _DEVICE0)
    pd = ProfileData.from_serialized_xspace(data)
    rec = {"window": None, "modules": [], "paths": [], "ops": [],
           "stats_seen": sorted({k for st in meta.values() for k in st})}
    w0 = w1 = None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace.WINDOW_SPAN and w0 is None:
                        w0, w1 = e.start_ns, e.start_ns + e.duration_ns
    if w0 is None:
        raise ValueError(f"no {trace.WINDOW_SPAN} span in the trace")
    rec["window"] = [w0, w1]
    index = {}
    for plane in pd.planes:
        if plane.name != _DEVICE0:
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Modules" in lines:
            rec["modules"] = [[e.name, e.start_ns, e.duration_ns]
                              for e in lines["XLA Modules"].events]
        for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
            if e.start_ns + e.duration_ns <= w0 or e.start_ns >= w1:
                continue
            path = meta.get(e.name, {}).get(SCOPE_STAT) or ""
            if path not in index:
                index[path] = len(rec["paths"])
                rec["paths"].append(path)
            rec["ops"].append([trace.short_name(e.name), index[path],
                               e.start_ns, e.duration_ns])
    return rec


def scope_of(path: str) -> str:
    """``jit(_decode_impl)/decode/while/body/attn/kv_update/gather:`` ->
    ``decode/attn/kv_update``: the known scope names of an op's path (the
    profiler ends a path with ``:`` and the op's type, if any)."""
    names = [p for p in (c.split(":", 1)[0] for c in path.split("/"))
             if p in SCOPES]
    return "/".join(names) if names else UNSCOPED


def reduce(rec: dict) -> dict:
    """Seconds of the traced window by program and scope."""
    w0, w1 = rec["window"]
    mods = sorted(rec["modules"], key=lambda m: m[1])
    starts = [m[1] for m in mods]
    keys = [scope_of(p) for p in rec["paths"]]
    progs, kerns = {}, {}
    for short, pi, s, d in rec["ops"]:
        a, b = max(s, w0), min(s + d, w1)
        fam = trace.op_family(short)
        if b <= a or trace.CONTAINERS.match(fam):
            continue
        i = bisect.bisect_right(starts, s) - 1
        prog = trace.module_name(mods[i][0]) if i >= 0 else ""
        sec = (b - a) * 1e-9
        tab = progs.setdefault(prog, {})
        tab[keys[pi]] = tab.get(keys[pi], 0.0) + sec
        k = trace.kernel_family(fam)
        if k:
            tab = kerns.setdefault(prog, {}).setdefault(k, {})
            tab[keys[pi]] = tab.get(keys[pi], 0.0) + sec
    return {"programs": progs, "kernels": kerns}


def under(tab: dict, name: str) -> float:
    """Seconds of the scopes of ``tab`` ({scope: seconds}) that lie
    inside the scope ``name``, at any depth."""
    return sum(v for k, v in tab.items() if name in k.split("/"))


@functools.lru_cache(maxsize=1)
def _table(profile_dir: str, newest: str, mtime_ns: int) -> dict:
    """Memoised on the newest trace file and its time of writing."""
    rec = extract(profile_dir)
    red = reduce(rec)
    print(f"[chipbench] scope stats seen: {rec['stats_seen']}",
          file=sys.stderr)
    for prog, tab in sorted(red["programs"].items()):
        total = sum(tab.values())
        rows = ", ".join(f"{k} {v:.6f}" for k, v in
                         sorted(tab.items(), key=lambda kv: -kv[1]))
        print(f"[chipbench] scopes of {prog or 'no program'}: {total:.6f} "
              f"s, unscoped {100 * tab.get(UNSCOPED, 0) / total:.2f}%: "
              f"{rows}", file=sys.stderr, flush=True)
    return red


def table(root) -> dict | None:
    """The scope table of the trace the run left under ``root``, or None
    where there is none."""
    d = str(Path(root) / TRACE_DIR)
    paths = sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))
    if not paths:
        return None
    try:
        return _table(d, paths[-1], os.stat(paths[-1]).st_mtime_ns)
    except ValueError as e:
        print(f"[chipbench] no scope table: {e}", file=sys.stderr)
        return None


def per_decode_step(ctx: dict, seconds: float):
    """Milliseconds per decode scan step of the traced window, or None."""
    steps = ctx["trace_counters"]["decode_device_steps"]
    if not steps or seconds <= 0:
        return None
    return 1e3 * seconds / steps

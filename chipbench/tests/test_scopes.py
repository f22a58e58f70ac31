"""CPU tests of the scope reduction and the readers that take from it.

    PYTHONPATH=src python -m pytest chipbench/tests/test_scopes.py -q

The scope table of a small recorded chip trace (``chipbench/testdata``)
must account for each program's device time; the protobuf reading of
the op metadata is checked on a serialized trace written here; the two
readers that take from it are checked by hand, and must report nothing
where they have nothing to read (as on a program without scopes).
"""
from __future__ import annotations

import bisect
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import harness, scopes, trace  # noqa: E402

MS = 1_000_000


def test_scope_of_keeps_the_known_names_in_order():
    assert scopes.scope_of(
        "jit(_decode_impl)/decode/while/body/vmap(attn)/attn/kv_update/"
        "scatter") == "decode/attn/kv_update"
    assert scopes.scope_of("jit(_prefill_chunk_impl)/prefill/mlp/"
                           "apsq_gemm/jit(apsq_matmul_kernel)/pallas_call"
                           ) == "prefill/mlp/apsq_gemm"
    assert scopes.scope_of("jit(_decode_impl)/while/body/add") == "unscoped"
    assert scopes.scope_of("") == "unscoped"


def _record():
    """Two programs in a 10 ms window: a decode program of 6 ms, then a
    prefill chunk cut by the window's end."""
    paths = ["jit(_decode_impl)/decode/while/body/attn/kv_update/gather",
             "jit(_decode_impl)/decode/while/body/mlp/apsq_gemm/quantize/x",
             "jit(_decode_impl)/decode/while/body/mlp/apsq_gemm/"
             "jit(apsq_matmul_kernel)/pallas_call",
             "jit(_decode_impl)/decode/lm_head/apsq_gemm/pad",
             "jit(_decode_impl)/decode/while/body/sample/argmax",
             "",
             "jit(_prefill_chunk_impl)/prefill/attn/kv_update/scatter"]
    ops = [["%fusion.1", 0, 1 * MS, 1 * MS],            # kv_update
           ["%while.3", 5, 1 * MS, 4 * MS],             # container: out
           ["%convert_fusion.2", 1, 2 * MS, 1 * MS],    # quantize
           ["%apsq_matmul_kernel.7", 2, 3 * MS, 2 * MS],
           ["%pad.4", 3, 5 * MS, 1 * MS],               # lm_head's GEMM
           ["%reduce.5", 4, 6 * MS, 500_000],           # sample
           ["%copy.6", 5, 6_500_000, 500_000],          # unscoped
           ["%fusion.8", 6, 8 * MS, 4 * MS]]            # 3 ms in window
    return {"window": [1 * MS, 11 * MS],
            "modules": [["jit__decode_impl(3)", 1 * MS, 6 * MS],
                        ["jit__prefill_chunk_impl(1)", 8 * MS, 4 * MS]],
            "paths": paths, "ops": ops, "stats_seen": ["tf_op"]}


def test_reduce_by_hand():
    red = scopes.reduce(_record())
    dec = red["programs"]["_decode_impl"]
    assert dec == pytest.approx({
        "decode/attn/kv_update": 0.001, "decode/mlp/apsq_gemm/quantize":
        0.001, "decode/mlp/apsq_gemm": 0.002,
        "decode/lm_head/apsq_gemm": 0.001, "decode/sample": 0.0005,
        "unscoped": 0.0005})
    assert red["programs"]["_prefill_chunk_impl"] == pytest.approx(
        {"prefill/attn/kv_update": 0.003})
    assert red["kernels"] == {"_decode_impl": {"apsq_gemm": pytest.approx(
        {"decode/mlp/apsq_gemm": 0.002})}}
    assert scopes.under(dec, "apsq_gemm") == pytest.approx(0.004)
    assert scopes.under(dec, "decode") == pytest.approx(0.0055)
    assert scopes.under(dec, "kv") == 0.0       # whole names only


def test_recorded_scope_table_sums_to_program_time():
    """On a recorded chip trace (the end of a chatglm3-6b decode program
    and the start of the prefill chunk after it) every program's scopes
    add up to its device time, the union of its ops' intervals, within
    1%, and lie inside its module events; nearly all of it is scoped."""
    rec = json.loads((ROOT / "chipbench" / "testdata"
                      / "scopes_record.json").read_text())
    red = scopes.reduce(rec)
    w0, w1 = rec["window"]
    mods = sorted(rec["modules"], key=lambda m: m[1])
    starts = [m[1] for m in mods]
    busy, span = {}, {}
    for name, s, d in mods:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            prog = trace.module_name(name)
            span[prog] = span.get(prog, 0.0) + (b - a) * 1e-9
    for short, _, s, d in rec["ops"]:
        a, b = max(s, w0), min(s + d, w1)
        if b > a and not trace.CONTAINERS.match(trace.op_family(short)):
            prog = trace.module_name(
                mods[bisect.bisect_right(starts, s) - 1][0])
            busy.setdefault(prog, []).append([a, b])
    assert {"_decode_impl", "_prefill_chunk_impl"} <= set(red["programs"])
    for prog, tab in red["programs"].items():
        union = sum(b - a for a, b in trace._union(busy[prog])) * 1e-9
        assert sum(tab.values()) == pytest.approx(union, rel=0.01)
        assert union <= span[prog] * (1 + 1e-9)
    for prog in ("_decode_impl", "_prefill_chunk_impl"):
        tab = red["programs"][prog]
        assert tab.get(scopes.UNSCOPED, 0.0) < 0.05 * sum(tab.values())
    for name in ("kv_update", "kv_attention", "apsq_gemm"):
        assert scopes.under(red["programs"]["_prefill_chunk_impl"], name)
    assert scopes.under(red["kernels"]["_prefill_chunk_impl"]["apsq_gemm"],
                        "apsq_gemm") > 0


_XSPACE = '''
planes { id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } } }
planes { id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 50000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 11000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 200000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = s8[4] fusion(%p), kind=kLoop"
    stats { metadata_id: 2 str_value:
      "jit(_decode_impl)/decode/while/body/attn/kv_update/scatter" } } }
  event_metadata { key: 2 value { id: 2
    name: "%apsq_matmul_kernel.3 = s32[4] custom-call(%a)"
    stats { metadata_id: 4 int64_value: 12 }
    stats { metadata_id: 2 ref_value: 5 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.2 = s8[4] copy(%b)" } }
  event_metadata { key: 9 value { id: 9 name: "jit__decode_impl(1)" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 4 value { id: 4 name: "flops" } }
  stat_metadata { key: 5 value { id: 5
    name: "jit(_decode_impl)/decode/while/body/mlp/apsq_gemm/pallas_call" } }
}
'''


def test_extract_reads_the_scope_from_op_metadata(tmp_path):
    """The scope path sits in the stats of each op's event metadata, as
    a string or a reference to an interned one; ops outside the window
    are dropped."""
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_XSPACE))
    rec = scopes.extract(str(tmp_path))
    assert rec["window"] == [1000, 101000]
    assert rec["stats_seen"] == ["flops", "tf_op"]
    assert [(op[0], scopes.scope_of(rec["paths"][op[1]]))
            for op in rec["ops"]] == [
        ("%fusion.1", "decode/attn/kv_update"),
        ("%apsq_matmul_kernel.3", "decode/mlp/apsq_gemm"),
        ("%copy.2", "unscoped")]
    red = scopes.reduce(rec)
    assert red["kernels"]["_decode_impl"]["apsq_gemm"] == pytest.approx(
        {"decode/mlp/apsq_gemm": 3e-6})


def _bench_reader(name):
    return harness.Bench(ROOT).reader(name)


def test_scope_readers_by_hand(monkeypatch):
    tab = scopes.reduce(_record())
    monkeypatch.setattr(scopes, "table", lambda root: tab)
    ctx = {"trace_counters": {"decode_device_steps": 4}}
    # kv_update: 1 ms over 4 steps; staging: apsq_gemm 4 ms less the
    # kernel's 2 ms, over 4 steps
    assert _bench_reader("kv_update_ms")(ctx) == pytest.approx(0.25)
    assert _bench_reader("gemm_staging_ms")(ctx) == pytest.approx(0.5)
    ctx0 = {"trace_counters": {"decode_device_steps": 0}}
    for name in ("kv_update_ms", "gemm_staging_ms"):
        assert _bench_reader(name)(ctx0) is None
    # a program without scopes: everything unscoped
    monkeypatch.setattr(scopes, "table", lambda root: {
        "programs": {"_decode_impl": {"unscoped": 0.005}}, "kernels": {}})
    for name in ("kv_update_ms", "gemm_staging_ms"):
        assert _bench_reader(name)(ctx) is None
    monkeypatch.setattr(scopes, "table", lambda root: None)
    for name in ("kv_update_ms", "gemm_staging_ms"):
        assert _bench_reader(name)(ctx) is None


def test_table_is_none_without_a_usable_trace(tmp_path):
    """No trace under the checkout, or one without the window span (a
    run that stopped before its window), gives no table, not an error."""
    assert scopes.table(tmp_path) is None
    from jax.profiler import ProfileData
    d = tmp_path / scopes.TRACE_DIR / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    xspace = _XSPACE.replace("chipbench.window", "chipbench.setup")
    (d / "h.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(xspace))
    assert scopes.table(tmp_path) is None

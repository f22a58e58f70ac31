"""CPU tests of the chip benchmark's harness.

    PYTHONPATH=src python -m pytest chipbench/tests -q

They cover the yardstick (traffic, op and byte counts, the trace
reduction on a recorded trace, the peak table), discovery by name, the
entry point's refusal without a TPU, and whole runs at a test size with
the oracle backend: a sound run passes its check, the control (the
reference at INT4 in the program's place) fails it, and one slot's
tokens altered where the engine produces them, or a decode that drops
its state, makes ``correct`` false.  The dispatch records that the
roofline readers take are checked against the engine's own dispatches.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import (counts, faults, harness, stats, trace,  # noqa: E402
                       traffic)

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXES = sorted(p.stem for p in (ROOT / "chipbench" / "traffic").glob("*.json"))


# ---------------------------------------------------------------------------
# Traffic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mix", MIXES)
def test_traffic_sizes_fixed_ids_follow_the_seed(mix):
    """Every seed serves the same sizes in the same order; the token ids
    are the same for the same seed and differ for another."""
    m = harness.load_json(ROOT / "chipbench" / "traffic" / f"{mix}.json")
    a = traffic.generate(m, 2**31 + 5, 1000, 200)
    b = traffic.generate(m, 2**31 + 5, 1000, 200)
    c = traffic.generate(m, 2**31 + 6, 1000, 200)
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in c]
    assert [x.max_new for x in a] == [x.max_new for x in c]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert all(x.prompt.max() < 1000 for x in a)


@pytest.mark.parametrize("mix", MIXES)
def test_traffic_blocks_hold_the_quantiles(mix):
    """Each block holds the same multiset of sizes, spread over the whole
    clipped distribution, and every request fits a slot's pages."""
    m = harness.load_json(ROOT / "chipbench" / "traffic" / f"{mix}.json")
    blk = m["block"]
    a = traffic.lengths(m, 4 * blk)
    for i in range(1, 4):
        part = a[i * blk:(i + 1) * blk]
        assert sorted(p for p, _ in part) == sorted(p for p, _ in a[:blk])
        assert sorted(o for _, o in part) == sorted(o for _, o in a[:blk])
    assert max(p for p, _ in a) <= m["prompt"]["max"]
    assert min(p for p, _ in a) >= m["prompt"]["min"]
    for w in BENCH["workloads"]:
        if w["traffic"] != mix:
            continue
        conf = json.loads((ROOT / "chipbench" / "configs"
                           / f"{w['config']}.json").read_text())
        e = conf["engine"]
        assert max(p + o for p, o in a) <= (e["max_pages_per_slot"]
                                            * e["page_size"])


# ---------------------------------------------------------------------------
# Discovery by name
# ---------------------------------------------------------------------------

def test_every_named_file_exists():
    b = harness.Bench(ROOT)
    for w in BENCH["workloads"]:
        b.cell(w["name"])
        assert b.config(w["config"])["chips"] == w["chips"]
        b.traffic(w["traffic"])
        assert all(v > 0 for v in b.limits(w["name"])["compare"].values())
        for tr_ in (False, True):
            for m in b.metrics(w["name"], tr_):
                assert callable(b.reader(m["name"]))
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_new_files_are_found_without_code_change(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(BENCH))
    spec["per_layer"].append({
        "name": "new_metric", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "output_tok_s", "workloads": ["cfg_new.mix_new"]})
    spec["workloads"].append({"name": "cfg_new.mix_new", "config": "cfg_new",
                              "traffic": "mix_new", "chips": 1, "why": "t"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    d = tmp_path / "chipbench"
    (d / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (d / "configs" / "cfg_new.json").write_text('{"chips": 1}')
    (d / "traffic" / "mix_new.json").write_text(
        json.dumps({"block": 4, "concurrency": 1,
                    "prompt": {"dist": "fixed", "value": 9},
                    "output": {"dist": "fixed", "value": 3}}))
    b = harness.Bench(tmp_path)
    assert b.cell("cfg_new.mix_new")["config"] == "cfg_new"
    assert b.config("cfg_new")["chips"] == 1
    assert traffic.lengths(b.traffic("mix_new"), 2) == [(9, 3), (9, 3)]
    names = [m["name"] for m in b.metrics("cfg_new.mix_new", True)]
    assert names == ["new_metric"]
    assert b.reader("new_metric")({}) == 42.0


# ---------------------------------------------------------------------------
# Ops and bytes, against hand counts
# ---------------------------------------------------------------------------

def _dims(name):
    return harness.model_dims(json.loads(
        (ROOT / "chipbench" / "configs" / f"{name}.json").read_text()))


def test_counts_deepseek_by_hand():
    d = _dims("deepseek-7b")
    assert counts.gemm_ops(16, 4096, 4096) == 536_870_912
    # 4096*11008 weights + 16*4096 codes + 4*16*11008 out + 4*4*11008 exps
    assert counts.gemm_bytes(16, 4096, 11008, 4) == 46_034_944
    # per layer 4*4096^2 + 3*4096*11008 = 202,375,168; 30 layers + head
    assert counts.gemm_params(d) == 30 * 202_375_168 + 4096 * 102400
    # one decode row at ctx 300: 2*GEMM params + 30 * 4*300*32*128
    assert counts.token_ops(d, 300) == (2 * (30 * 202_375_168
                                             + 4096 * 102400)
                                        + 30 * 4 * 300 * 32 * 128)
    # 2 slots at 100 and 200 live tokens: K and V codes of 300 positions
    assert counts.attn_bytes(d, 300, 2) == 2 * 300 * 32 * 128 + 8 * 2 * 4096


def test_counts_chatglm_by_hand():
    d = _dims("chatglm3-6b")
    assert counts.attn_bytes(d, 100, 1) == 83_968
    assert counts.token_ops(d, 100) == 11_999_641_600
    peak = harness.peaks_for("TPU v5 lite")
    c = counts.call_costs(d, {"kind": "prefill", "start": 32, "chunk": 2},
                          peak)
    assert c["tokens"] == 2
    assert c["model_ops"] == counts.token_ops(d, 33) + counts.token_ops(d, 34)
    # prefill at M=2 is bandwidth-bound in every GEMM
    want = 28 * sum(counts.gemm_bytes(2, k, n, 4)
                    for k, n in counts.linear_shapes(d)) / 819e9
    assert c["gemm_s"] == pytest.approx(want, rel=1e-12)
    dec = counts.call_costs(d, {"kind": "decode", "rows": 16,
                                "steps": [[10, 20], [11]]}, peak)
    assert dec["tokens"] == 3
    assert dec["attn_s"] == pytest.approx(
        28 * (counts.attn_bytes(d, 30, 2) + counts.attn_bytes(d, 11, 1))
        / 819e9, rel=1e-12)


# ---------------------------------------------------------------------------
# Trace reduction on a recorded trace
# ---------------------------------------------------------------------------

def test_trace_reduction_on_recorded_trace():
    rec = json.loads((ROOT / "chipbench" / "testdata"
                      / "trace_record.json").read_text())
    want = json.loads((ROOT / "chipbench" / "testdata"
                       / "trace_reduced.json").read_text())
    red = trace.reduce(rec)
    assert red["n_ops"] == want["n_ops"]
    for k in ("window_s", "busy_s"):
        assert red[k] == pytest.approx(want[k], rel=1e-12)
    assert red["kernels"] == pytest.approx(want["kernels"], rel=1e-12)
    assert {k: list(v) for k, v in red["modules"].items()} == pytest.approx(
        want["modules"])
    assert [g[0] for g in red["gaps"]] == [g[0] for g in want["gaps"]]


def test_trace_reduction_by_hand():
    ms = 1_000_000
    rec = {"host_spans": [["chipbench.window", 0, 10 * ms],
                          ["engine.step", 0, 10 * ms],
                          ["engine.decode_dispatch", 6 * ms, 1 * ms]],
           "device_ops": [["%fusion.1", 1 * ms, 2 * ms],
                          ["%apsq_matmul_kernel.3", 2 * ms, 2 * ms],
                          ["%int8_kv_attention_kernel.9", 8 * ms, 4 * ms],
                          ["%while.2", 1 * ms, 3 * ms]],
           "modules": [["jit__decode_impl(3)", 1 * ms, 11 * ms]],
           "operands": {"_decode_impl|%apsq_matmul_kernel.3": [
               "%pad.4", "%get-tuple-element.1"],
               "_decode_impl|%pad.4": ["%fusion.1"]}}
    rec["device_ops"].append(["%pad.4", 1 * ms, 1 * ms])
    red = trace.reduce(rec)
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.005)          # [1,4] + [8,10]
    assert red["kernels"] == pytest.approx({"apsq_gemm": 0.002,
                                            "kv_attn": 0.002})
    assert red["modules"]["_decode_impl"][1] == pytest.approx(0.009)
    assert set(red["ops"]) == {"fusion", "apsq_matmul_kernel",
                               "int8_kv_attention_kernel", "pad"}
    # the pad stages the GEMM's weights, and the fusion the pad's input
    assert red["staging"] == pytest.approx({"apsq_gemm": 0.003})
    assert red["gaps"] == [["engine.decode_dispatch", pytest.approx(0.004)],
                           ["engine.step", pytest.approx(0.001)]]


def test_percentile_nearest_rank():
    assert stats.percentile(list(range(1, 11)), 90) == 9
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([], 90) is None


# ---------------------------------------------------------------------------
# Peaks and the entry point
# ---------------------------------------------------------------------------

def test_peak_table_has_v5e_and_its_source():
    p = harness.peaks_for("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_s"]) == (
        197e12, 393e12, 819e9)
    assert "TPU v5e" in harness.load_json(
        ROOT / "chipbench" / "peaks.json")["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.peaks_for("TPU v9 imaginary")


def test_run_without_tpu_exits_nonzero_with_one_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert len(p.stderr.strip().splitlines()) == 1
    assert "no TPU" in p.stderr


def test_run_without_program_exits_nonzero(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert p.returncode != 0 and p.stdout == ""


# ---------------------------------------------------------------------------
# Whole runs at a test size (oracle backend, CPU)
# ---------------------------------------------------------------------------

TINY_CONF = {
    "arch": "deepseek-7b", "smoke": True,
    "model_cfg": {"n_kv_heads": 2, "rope_fraction": 0.5},
    "model": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab": 256,
              "rope_fraction": 0.5, "rope_theta": 10000.0,
              "norm_eps": 1e-06},
    "dtype": "bfloat16", "chips": 1,
    "quant": {"mode": "apsq", "gs": 2, "n_p": 4, "bits": 8},
    "engine": {"max_batch": 4, "page_size": 16, "max_pages_per_slot": 8,
               "n_pages": 33, "prefill_chunk": 16, "decode_horizon": 8}}
TINY_MIX = {"loop": "closed", "concurrency": 4, "block": 8,
            "check_requests": 12,
            "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                       "min": 8, "max": 80},
            "output": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 16, "max": 40}}
# At this size (CPU, oracle backend, 12 requests checked, so that slot 0
# serves some of them) over 8 seeds, worst_request_gap / gap_share: sound
# runs 0.034-0.063 / 0, the control (INT4 codes) 2.90-3.63 / 0.75-0.87, a
# decode that drops its state 1.11-1.65 / 0.17-0.31, slot 0's tokens
# altered 2.15-3.05 / 0.04-0.28 (0.056 / 0 on the one seed whose checked
# requests hold none that slot 0 served).  Outputs span several fused
# decode blocks, so a dropped state shows.
TINY_LIMITS = {"compare": {"worst_request_gap": 0.2, "gap_share": 0.15},
               "control_bits": 4}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    d = root / "chipbench"
    shutil.copytree(ROOT / "chipbench" / "metrics", d / "metrics")
    for sub, name, obj in (("configs", "tiny", TINY_CONF),
                           ("traffic", "small", TINY_MIX),
                           ("limits", "tiny.small", TINY_LIMITS)):
        (d / sub).mkdir(parents=True, exist_ok=True)
        (d / sub / f"{name}.json").write_text(json.dumps(obj))
    spec = json.loads(json.dumps(BENCH))
    spec["workloads"] = [{"name": "tiny.small", "config": "tiny",
                          "traffic": "small", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _run(root, seed, **kw):
    return harness.run(root, "tiny.small", seed, 2.0, False,
                       t_start=time.perf_counter(), backend="oracle",
                       require_tpu=False, **kw)


@pytest.mark.parametrize("control,seed", [(False, 2**31 + 77),
                                          (True, 2**31 + 77)],
                         ids=["sound", "control"])
def test_sound_run_is_correct_and_control_is_not(tiny_root, control, seed):
    """The served tokens pass the check; the reference at INT4, put in
    the program's place, fails the same comparison."""
    out = _run(tiny_root, seed, control=control)
    assert out["correct"] is (not control)
    assert list(out)[-1] == "check"
    assert set(out["check"]) == set(TINY_LIMITS["compare"])
    for v in out["check"].values():
        assert v["tokens"] > 0
        assert (v["value"] > v["limit"]) is control
    assert set(out["metrics"]) == {"output_tok_s", "ttft_p90_ms",
                                   "tpot_p90_ms", "setup_s"}


@pytest.mark.parametrize("fault,seed", [("token_altered", 2**31 + 78),
                                        ("state_unchanged", 2**31 + 79)])
def test_broken_timed_path_is_not_correct(tiny_root, fault, seed):
    """Break the timed path underneath; the check must fail."""
    out = _run(tiny_root, seed, fault=faults.FAULTS[fault])
    assert out["correct"] is False
    assert any(v["value"] > v["limit"] for v in out["check"].values())


def test_dispatch_records_match_the_engine():
    """The dispatches told from counters and deliveries are the ones the
    engine made (read here by wrapping its private programs)."""
    from chipbench import weights

    conf, mix = TINY_CONF, TINY_MIX
    cfg = weights.model_config(conf)
    params = weights.make_params(2**31 + 80, cfg)
    eng = harness._engine(params, cfg, conf["engine"], "oracle")
    harness.warm(eng, conf["engine"])
    true = []
    dec, pre = eng._decode, eng._prefill_chunk

    def decode(h, *args):
        pos = eng.pos.copy()
        out = dec(h, *args)
        em = np.asarray(out[1])
        true.append({"kind": "decode", "rows": em.shape[0],
                     "steps": [[int(pos[s]) + t + 1
                                for s in range(em.shape[0]) if em[s, t]]
                               for t in range(h)]})
        return out

    def prefill(*args):
        true.append({"kind": "prefill", "chunk": int(args[2].shape[1]),
                     "start": int(np.asarray(args[4]))})
        return pre(*args)

    eng._decode, eng._prefill_chunk = decode, prefill
    clients = harness.Clients(
        eng, traffic.stream(mix, 2**31 + 80, cfg.vocab), mix["concurrency"])
    clients.start(0.0)
    disp = harness.Dispatches(eng, clients, conf["engine"]["max_batch"],
                              conf["engine"]["prefill_chunk"])
    disp.on = True
    for i in range(60):
        disp.heartbeat(clients.pump(float(i), eng.step()))
    got = disp.records()
    assert got is not None and len(clients.done) > 4

    def key(c):                       # slots' order within a step aside
        if c["kind"] == "decode":
            c = dict(c, steps=[sorted(x) for x in c["steps"]])
        return json.dumps(c, sort_keys=True)

    assert sum(c["kind"] == "prefill" for c in true) > 20
    assert sorted(map(key, got)) == sorted(map(key, true))

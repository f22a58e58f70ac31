"""One run of one cell: set-up, the measured window, metrics, the check.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration, traffic mix and metrics; the configuration lives
in ``configs/<config>.json``, its model family (the file's ``family``,
else ``dense``: sizes, deployed tree and weight rules, plain reference
and op counts) in ``families/<family>.py``, the mix in
``traffic/<mix>.json``, the check's limits in ``limits/<cell>.json``,
each metric's reader in ``metrics/<metric>.py`` and the chip's peaks in
``peaks.json``.

The served path is the program's own: ``PagedServingEngine`` over the
deployed INT8 tree, driven through ``add_request`` and ``step`` by a
closed loop of clients.  The window opens at the first engine drain
(the return of ``step``) at or after the end of set-up and closes at the
first drain at or after ``seconds`` later, so a fused decode block is
never split.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
# The traced run profiles this much of its window (then to the next
# drain): enough for several decode blocks and a hundred prefill chunks,
# while reading the trace stays well inside a run's time limit.
TRACE_SECONDS = 4.0


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.spec = load_json(self.root / "BENCHMARK.json")
        self.dir = self.root / "chipbench"

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return load_json(self.dir / "configs" / f"{name}.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.dir / "traffic" / f"{name}.json")

    def limits(self, cell: str) -> dict:
        return load_json(self.dir / "limits" / f"{cell}.json")

    def metrics(self, cell: str, trace: bool) -> list:
        """The metric entries a run of ``cell`` reports, in order."""
        group = self.spec["per_layer"] if trace else self.spec["end_to_end"]
        return [m for m in group
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        return load_module(self.dir / "metrics" / f"{metric}.py",
                           f"chipbench_metric_{metric.replace('.', '_')}"
                           ).read

    def family(self, conf: dict):
        """The module of a configuration's model family
        (``chipbench/families/__init__.py`` says what it gives); unknown
        is an error."""
        name = conf.get("family", "dense")
        fams = self.dir / "families"
        known = sorted(p.stem for p in fams.glob("[!_]*.py"))
        if name not in known:
            raise KeyError(f"model family {name!r} has no file in "
                           f"{fams.name}/ (known: {known})")
        return load_module(fams / f"{name}.py",
                           f"chipbench_family_{name.replace('.', '_')}")


def peaks_for(kind: str, path: Path = HERE / "peaks.json") -> dict:
    """The peak table's entry for a ``device_kind``; unknown is an error."""
    table = load_json(path)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {path.name} "
                       f"(known: {sorted(table)})")
    return table[kind]


class CompileStats:
    """Backend compile seconds and persistent-cache hits / misses, from
    JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.events = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.events["compiles"] += 1

    def _event(self, event, **_):
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def snapshot(self) -> tuple:
        return (self.seconds, self.events["compiles"],
                self.events["cache_hits"], self.events["cache_misses"])


# ---------------------------------------------------------------------------
# Clients
# ---------------------------------------------------------------------------

class Clients:
    """A closed loop: ``concurrency`` clients, each sending its next
    request as soon as its last one finished (at the drain that
    delivered its last token)."""

    def __init__(self, eng, reqs: list, concurrency: int):
        self.eng = eng
        self.queue = reqs             # an iterator of traffic.Req
        self.concurrency = concurrency
        self.live: dict = {}          # uid -> record
        self.done: list = []          # finished records
        self.sent: list = []          # every record, in send order

    def _send(self, now: float) -> None:
        import jax
        from repro.serving import Request
        q = next(self.queue)
        r = Request(uid=q.uid, tokens=q.prompt, max_new_tokens=q.max_new)
        with jax.profiler.TraceAnnotation("client.add_request"):
            self.eng.add_request(r)
        rec = {"req": r, "sent": now, "seen": 0, "deliveries": [],
               "prompt": len(q.prompt)}
        self.live[q.uid] = rec
        self.sent.append(rec)

    def start(self, now: float) -> None:
        for _ in range(self.concurrency):
            self._send(now)

    def pump(self, now: float, finished: list) -> list:
        """Record what the last drain delivered and resend for the
        finished.  Returns [(record, tokens delivered, whether they hold
        the request's first token)]."""
        got = []
        for rec in self.live.values():
            n = len(rec["req"].out)
            if n > rec["seen"]:
                rec["deliveries"].append((now, n - rec["seen"]))
                got.append((rec, n - rec["seen"], rec["seen"] == 0))
                rec["seen"] = n
        for r in finished:
            rec = self.live.pop(r.uid)
            rec["finished"] = now
            self.done.append(rec)
            self._send(now)
        return got

    def records(self) -> list:
        return self.done + list(self.live.values())


# ---------------------------------------------------------------------------
# Dispatch bookkeeping (traced runs only)
# ---------------------------------------------------------------------------

class Dispatches:
    """The engine's dispatches, told from what the benchmark sees: each
    heartbeat's counters (the engine's public ``horizon_hist``,
    ``prefill_tokens``, ``prefill_dispatches`` and the scheduler's
    preemptions) and the tokens each request got at its drain.

    * Decode: at most one fused dispatch per heartbeat, its scan length
      the ``horizon_hist`` entry that grew.  A request that got ``k``
      decode tokens emitted them at scan steps 0 .. k-1, and its t-th
      token's query saw P + n + t positions (prompt P, n tokens before
      the dispatch).
    * Prefill: the engine spends prompt tokens on admitted requests
      oldest first, so a heartbeat's ``prefill_tokens`` are the next
      ones of the requests in send order.  Each request's share is cut
      into chunks of the largest power of two up to ``prefill_chunk``.

    Where that reading disagrees with the counters (another number of
    chunks than ``prefill_dispatches``, a first token before its prompt
    is done, more tokens than scan steps) or a request was preempted,
    ``records`` is None and the readers that need it report nothing."""

    def __init__(self, eng, clients, max_batch: int, prefill_chunk: int):
        self.eng, self.clients = eng, clients
        self.rows, self.chunk = max_batch, prefill_chunk
        self.on = False
        self.calls: list = []
        self.bad: list = []
        self.queue: list = []         # [record, prompt tokens prefilled]
        self.n_sent = 0
        self.c = self._counters()
        self._enqueue()

    def _counters(self) -> dict:
        e = self.eng
        return {"hist": dict(e.horizon_hist), "tokens": e.prefill_tokens,
                "dispatches": e.prefill_dispatches,
                "preempted": e.sched.stats.preempted}

    def _enqueue(self) -> None:
        new = self.clients.sent[self.n_sent:]
        self.queue += [[rec, 0] for rec in new]
        self.n_sent += len(new)

    def _prefill(self, n: int) -> list:
        out = []
        while n > 0 and self.queue:
            item = self.queue[0]
            rec, p = item
            m = min(n, rec["prompt"] - p)
            left = m
            while left:
                c = 1 << (min(self.chunk, left).bit_length() - 1)
                out.append({"kind": "prefill", "start": p, "chunk": c})
                p, left = p + c, left - c
            n -= m
            if p == rec["prompt"]:
                self.queue.pop(0)
            else:
                item[1] = p
        if n:
            self.bad.append("prefill tokens with no prompt left")
        return out

    def heartbeat(self, got: list) -> None:
        """After a drain: ``got`` is what ``Clients.pump`` returned."""
        c = self._counters()
        old, self.c = self.c, c
        if c["preempted"] != old["preempted"]:
            self.bad.append("preemption")
        calls = self._prefill(c["tokens"] - old["tokens"])
        if len(calls) != c["dispatches"] - old["dispatches"]:
            self.bad.append(f"{len(calls)} prefill chunks told, "
                            f"{c['dispatches'] - old['dispatches']} counted")
        waiting = {id(rec) for rec, _ in self.queue}
        if any(first and id(rec) in waiting for rec, _, first in got):
            self.bad.append("a first token before its prompt was done")
        grew = [h for h, k in c["hist"].items() if k > old["hist"].get(h, 0)]
        if len(grew) > 1:
            self.bad.append("two decode dispatches in one heartbeat")
        elif grew:
            h = grew[0]
            steps = [[] for _ in range(h)]
            for rec, n, first in got:
                k = n - 1 if first else n
                if k > h:
                    self.bad.append(f"{k} decode tokens from {h} steps")
                    k = h
                for t in range(k):
                    steps[t].append(rec["prompt"] + rec["seen"] - k + t)
            calls.append({"kind": "decode", "rows": self.rows,
                          "steps": steps})
        if self.on:
            self.calls += calls
        self._enqueue()

    def records(self):
        """Dispatch records as a family's ``call_costs`` takes them, or
        None where they cannot be trusted."""
        if self.bad:
            log(f"dispatch records left out: {sorted(set(self.bad))}")
            return None
        return self.calls


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def _engine(params, cfg, eng_conf: dict, backend):
    from repro.serving import PagedServingEngine
    return PagedServingEngine(
        params, cfg, max_batch=eng_conf["max_batch"],
        page_size=eng_conf["page_size"], n_pages=eng_conf["n_pages"],
        max_pages_per_slot=eng_conf["max_pages_per_slot"],
        prefill_chunk=eng_conf["prefill_chunk"],
        decode_horizon=eng_conf["decode_horizon"], backend=backend)


def warm(eng, eng_conf: dict) -> None:
    """Serve throwaway requests, one at a time, through ``add_request``
    and ``step``, so that every program the traffic can reach runs once:
    a prompt of 2 C - 1 tokens runs the prefill chunks C, C/2, ..., 1,
    and a request of 1 + h tokens runs the fused decode of scan length h,
    for h = H, H/2, ..., 1 (C = prefill_chunk, H = decode_horizon)."""
    from repro.serving import Request

    C, h = eng_conf["prefill_chunk"], eng_conf["decode_horizon"]
    uid = -1
    while h >= 1:
        n = 2 * C - 1 if uid == -1 else 1
        r = Request(uid=uid, tokens=np.zeros(n, np.int32),
                    max_new_tokens=1 + h)
        eng.add_request(r)
        while not r.done:
            eng.step()
        uid, h = uid - 1, h // 2


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: float, backend=None, require_tpu: bool = True,
        fault=None, control: bool = False, dump=None):
    """One run of one cell.  Returns the result dict (the last line)."""
    import jax

    from chipbench import trace as tr, traffic, weights
    from repro.exec import PallasBackend

    bench = Bench(root)
    cell = bench.cell(workload)
    conf = bench.config(cell["config"])
    fam = bench.family(conf)
    mix = bench.traffic(cell["traffic"])
    limits = bench.limits(workload)
    dev = jax.devices()[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise SystemExit(f"no TPU: JAX platform {dev.platform!r}")
        if len(jax.devices()) < cell["chips"]:
            raise SystemExit(f"{cell['chips']} chips needed, "
                             f"{len(jax.devices())} found")
    peak = peaks_for(dev.device_kind) if require_tpu else None
    if backend is None:
        backend = PallasBackend(interpret=False)
    stats = CompileStats()
    split = {"imports_s": time.perf_counter() - t_start}

    # -- weights -----------------------------------------------------------
    t0 = time.perf_counter()
    cfg = weights.model_config(conf)
    dims = fam.dims(conf)
    fam.check(dims, cfg)
    abstract = fam.deployed_shapes(cfg)
    params = weights.make_params(seed, abstract, fam.mean_abs_in,
                                 fam.float_init)
    jax.block_until_ready(params)
    split["weights_s"] = time.perf_counter() - t0

    # -- engine, programs --------------------------------------------------
    t0 = time.perf_counter()
    c0 = stats.snapshot()
    eng = _engine(params, cfg, conf["engine"], backend)
    if fault is not None:
        fault(eng)
    warm(eng, conf["engine"])
    c1 = stats.snapshot()
    split["compile_or_load_s"] = time.perf_counter() - t0
    split["backend_compile_s"] = c1[0] - c0[0]
    split["compiles"] = c1[1] - c0[1]
    split["cache_hits"], split["cache_misses"] = c1[2] - c0[2], c1[3] - c0[3]

    # -- sessions ----------------------------------------------------------
    t0 = time.perf_counter()
    reqs = traffic.stream(mix, seed, cfg.vocab)
    clients = Clients(eng, reqs, mix["concurrency"])
    clients.start(time.perf_counter())
    disp = (Dispatches(eng, clients, conf["engine"]["max_batch"],
                       conf["engine"]["prefill_chunk"]) if trace else None)

    def beat() -> float:
        """One heartbeat and its bookkeeping; returns the drain's time."""
        with jax.profiler.TraceAnnotation("engine.step"):
            fin = eng.step()
        now = time.perf_counter()
        with jax.profiler.TraceAnnotation("client.bookkeeping"):
            got = clients.pump(now, fin)
            if disp is not None:
                disp.heartbeat(got)
        return now

    first = set(clients.live)
    while any(len(clients.live[u]["req"].out) == 0 for u in first
              if u in clients.live):
        beat()
    t_open = time.perf_counter()
    split["session_fill_s"] = t_open - t0
    setup_s = t_open - t_start
    log("setup split: " + ", ".join(
        f"{k} {v:.4f}" if isinstance(v, float) else f"{k} {v}"
        for k, v in split.items()) + f"; setup_s {setup_s:.4f}")

    # -- window ------------------------------------------------------------
    def counters():
        return {"decode_device_steps": eng.decode_device_steps,
                "decode_dispatches": eng.decode_dispatches,
                "prefill_dispatches": eng.prefill_dispatches,
                "prefill_tokens": eng.prefill_tokens,
                "preempted": eng.sched.stats.preempted}

    cw0 = counters()
    comp0 = stats.snapshot()
    t_close = t_open + seconds
    tracing = None
    red = None
    trace_dir = root / ".chipbench" / "trace"
    now = t_open
    while True:
        if trace and tracing is None and red is None:
            jax.block_until_ready(eng.state)
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
            span = jax.profiler.TraceAnnotation("chipbench.window")
            span.__enter__()
            disp.on = True
            tracing = {"t0": time.perf_counter(), "c0": counters()}
        now = beat()
        if tracing is not None and (now >= tracing["t0"] + TRACE_SECONDS
                                    or now >= t_close):
            jax.block_until_ready(eng.state)
            disp.on = False
            span.__exit__(None, None, None)
            tracing["c1"] = counters()
            jax.profiler.stop_trace()
            red = tracing
            tracing = None
        if now >= t_close:
            break
    t_close = now
    cw1 = counters()
    comp1 = stats.snapshot()
    log(f"window {t_close - t_open:.4f} s; compiles inside it "
        f"{comp1[1] - comp0[1]} ({comp1[0] - comp0[0]:.4f} s backend "
        f"compile); preemptions {cw1['preempted'] - cw0['preempted']}")

    recs = clients.records()
    win = {"t0": t_open, "t1": t_close, "requests": recs,
           "counters": {k: cw1[k] - cw0[k] for k in cw0},
           "max_batch": conf["engine"]["max_batch"]}
    first_in = sum(1 for r in recs if r["deliveries"]
                   and t_open < r["deliveries"][0][0] <= t_close)
    delivered = sum(n for r in recs for t, n in r["deliveries"]
                    if t_open < t <= t_close)
    win["decode_tokens"] = delivered - first_in
    ctx = {"setup_s": setup_s, "window": win, "dims": dims, "peak": peak}
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    stats_mem = dev.memory_stats() or {}
    result_device["memory_peak_bytes"] = int(
        stats_mem.get("peak_bytes_in_use", 0))
    breakdown = None
    if trace:
        calls = disp.records()
        rtrace = tr.extract(str(trace_dir))
        red_s = tr.reduce(rtrace)
        if require_tpu and not red_s["kernels"]:
            raise SystemExit("the traced window ran no Mosaic kernel")
        ctx["trace"] = red_s
        ctx["calls"] = calls
        ctx["call_costs"] = (None if calls is None or peak is None else
                             [fam.call_costs(dims, c, peak) for c in calls])
        ctx["trace_counters"] = {k: red["c1"][k] - red["c0"][k]
                                 for k in red["c0"]}
        result_device["busy_s"] = red_s["busy_s"]
        result_device["window_s"] = red_s["window_s"]
        breakdown = tr.breakdown(red_s)
        log(f"trace: window {red_s['window_s']:.4f} s, busy "
            f"{red_s['busy_s']:.4f} s, {red_s['n_ops']} device ops, "
            f"{len(calls or ())} dispatches; programs {red_s['modules']}; "
            f"kernels {red_s['kernels']}")
    metrics = {}
    for m in bench.metrics(workload, trace):
        val = bench.reader(m["name"])(ctx)
        if isinstance(val, dict):
            log(f"{m['name']}: {val['value']:.6g} {m['unit']} over "
                f"{val['samples']} samples")
            val = val["value"]
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}

    # -- the check -----------------------------------------------------------
    done = [r for r in clients.done if r["finished"] <= t_close]
    max_len = (traffic.max_len(mix["prompt"])
               + traffic.max_len(mix["output"]))
    del eng, params, clients, disp
    gc.collect()
    check = check_served(seed, fam, cfg, dims, abstract, done, mix, limits,
                         max_len, control, dump)
    correct = all(v["value"] is not None and v["value"] <= v["limit"]
                  for v in check.values())
    for k, v in check.items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    out = {"correct": correct, "attempted": win_attempted(recs, t_close),
           "failed": 0, "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    return out


def win_attempted(recs: list, t_close: float) -> int:
    """Requests sent before the window closed."""
    return sum(1 for r in recs if r["sent"] <= t_close)


def sample_done(done: list, k: int, seed: int) -> list:
    """The longest finished request and ``k - 1`` others drawn from the
    seed."""
    if not done:
        return []
    by_len = sorted(done, key=lambda r: (r["prompt"] + len(r["req"].out),
                                         r["req"].uid))
    pick = [by_len[-1]]
    rest = by_len[:-1]
    rng = np.random.default_rng([seed, 2])
    idx = rng.permutation(len(rest))[:max(0, k - 1)]
    return pick + [rest[i] for i in sorted(idx)]


# A token whose gap passes this lies beyond sound rounding: 1-3% of a
# sound run's tokens do, nearly all of the control's.
GAP_OVER = 1.5


def gap_numbers(per: list) -> dict:
    """The numbers the check can compare, from each sampled request's
    per-token gaps: the largest of the requests' mean gaps (a request
    served wrong reads high however many sound ones sit beside it), the
    share of all tokens whose gap passes ``GAP_OVER`` (a fault spread
    thinly over every request, such as lost cache writes, raises it), the
    mean over every token and the widest single gap."""
    allg = np.concatenate(per)
    return {"worst_request_gap": float(max(g.mean() for g in per)),
            "gap_share": float((allg > GAP_OVER).mean()),
            "mean_logit_gap": float(allg.mean()),
            "max_logit_gap": float(allg.max())}


def check_served(seed, fam, cfg, dims, abstract, done, mix, limits,
                 max_len, control=False, dump=None):
    """Compare the served tokens of a sample of finished requests with
    the family's plain reference: per token, the gap by which its
    reference logit lies below the reference's best.  The numbers named
    in the limits file's ``compare`` are held to their limits.

    With ``control`` the reference at the lower precision
    (``control_bits``) takes the program's place: at each position of
    the same prompts and served tokens, its first token is held to the
    same limits, and the run must come out not correct.  A control run
    also logs the served tokens' numbers and those of the sample with
    one request's tokens altered (each shifted by one), the fault of a
    token altered in one slot, planted in the reference's place."""
    from chipbench import reference, weights

    sample = sample_done(done, mix["check_requests"], seed)
    if not sample:
        return {k: {"value": None, "limit": v, "requests": 0, "tokens": 0}
                for k, v in limits["compare"].items()}
    ref = fam.reference(weights.seed_key(seed), cfg, dims, abstract,
                        max_len)
    seqs, rows, toks = [], [], []
    for r in sample:
        out = np.asarray(r["req"].out, np.int32)
        prompt = np.asarray(r["req"].tokens, np.int32)
        seqs.append(np.concatenate([prompt, out[:-1]]))
        rows.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(out)))
        toks.append(out)
    t0 = time.perf_counter()
    lg = ref.logits(seqs, rows)
    per = [reference.served_gaps(l, t) for l, t in zip(lg, toks)]
    for r, g in zip(sample, per):
        log(f"request {r['req'].uid}: prompt {r['prompt']}, served "
            f"{len(g)}, gaps > 0: {int((g > 0).sum())}, mean "
            f"{g.mean():.6g}, widest {g.max():.6g}")
    got = gap_numbers(per)
    n_tok = sum(g.size for g in per)
    log(f"reference over {len(sample)} requests, {n_tok} served tokens: "
        f"{time.perf_counter() - t0:.2f} s; " + ", ".join(
            f"{k} {v:.6g}" for k, v in got.items()))
    saved = {"served": [g.tolist() for g in per],
             "prompt": [r["prompt"] for r in sample]}
    if control:
        vocab = dims["vocab"]
        alt = [reference.served_gaps(lg[0], (toks[0] + 1) % vocab)] + per[1:]
        log("one request altered: " + ", ".join(
            f"{k} {v:.6g}" for k, v in gap_numbers(alt).items()))
        clg = ref.logits(seqs, rows, bits=limits["control_bits"])
        per = [reference.served_gaps(l, c.argmax(-1))
               for l, c in zip(lg, clg)]
        got = gap_numbers(per)
        log(f"control (the reference at {limits['control_bits']} bits in "
            "the program's place): " + ", ".join(
                f"{k} {v:.6g}" for k, v in got.items()))
        saved["control"] = [g.tolist() for g in per]
        saved["altered"] = alt[0].tolist()
    if dump is not None:
        Path(dump).parent.mkdir(parents=True, exist_ok=True)
        Path(dump).write_text(json.dumps(saved))
    return {k: {"value": got[k], "limit": v, "requests": len(sample),
                "tokens": n_tok}
            for k, v in limits["compare"].items()}

"""tpot_p90_ms: 90th percentile, over requests with at least two
deliveries in the window, of (last delivery - first delivery) divided
by the tokens delivered after the first delivery, counting only
deliveries inside the window.  Tokens come in fused blocks, so the
first delivery's tokens arrive at once and are not counted."""
from chipbench import stats


def read(ctx):
    w = ctx["window"]
    xs = []
    for r in w["requests"]:
        d = [(t, k) for t, k in r["deliveries"] if w["t0"] < t <= w["t1"]]
        if len(d) >= 2:
            xs.append((d[-1][0] - d[0][0]) * 1e3 / sum(k for _, k in d[1:]))
    v = stats.percentile(xs, 90)
    return None if v is None else {"value": v, "samples": len(xs)}

"""ttft_p90_ms: 90th percentile, over every request whose first token
arrived in the window, of the time from the client's send to the drain
that delivered that token."""
from chipbench import stats


def read(ctx):
    w = ctx["window"]
    xs = [(r["deliveries"][0][0] - r["sent"]) * 1e3
          for r in w["requests"]
          if r["deliveries"] and w["t0"] < r["deliveries"][0][0] <= w["t1"]]
    v = stats.percentile(xs, 90)
    return None if v is None else {"value": v, "samples": len(xs)}

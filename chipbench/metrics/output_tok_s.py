"""output_tok_s: every output token delivered in the window, over the
window's length (host clock, drain to drain)."""


def read(ctx):
    w = ctx["window"]
    n = sum(k for r in w["requests"] for t, k in r["deliveries"]
            if w["t0"] < t <= w["t1"])
    return n / (w["t1"] - w["t0"])

"""setup_s: process start to the window's opening drain: imports, the
weights made from the seed, the engine with every program compiled or
loaded from the cache, and the first sessions admitted and prefilled."""


def read(ctx):
    return ctx["setup_s"]

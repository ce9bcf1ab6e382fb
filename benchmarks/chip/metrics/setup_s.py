"""setup_s: process start to window start (loading, weights, warm-up,
compiles or compile-cache loads)."""


def read(rec):
    return rec.setup_s

"""Process start to window open: imports, weights, warm-up, compiles."""


def read(ctx):
    return ctx['setup_s']

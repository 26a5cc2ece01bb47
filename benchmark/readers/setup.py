"""Process start to the first measured request."""


def read(ctx, params):
    return ctx["setup_s"]

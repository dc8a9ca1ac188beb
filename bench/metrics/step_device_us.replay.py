"""Device busy time per scan step in the traced job, in microseconds: one
scan step per invocation of the single lane."""


def read(ctx):
    p = ctx["profile"]
    if p is None:
        return None
    return 1e6 * sum(p["busy_s"]) / len(p["busy_s"]) / ctx["steps"]

"""Share of the traced job during which the device ran no program, in %:
100 x (1 - busy / window), busy being the union of program executions."""


def read(ctx):
    p = ctx["profile"]
    if p is None:
        return None
    return 100.0 * (1.0 - sum(p["busy_s"]) / len(p["busy_s"])
                    / p["window_s"])

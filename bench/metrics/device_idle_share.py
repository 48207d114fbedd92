"""Device: the share of the traced window in which no operation ran on
the chip, in % (profiler trace)."""


def read(run: dict):
    tr = run.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["n_ops"] == 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

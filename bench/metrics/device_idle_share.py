"""Device: the share of the traced slice in which no operation ran on the
chip (1 - busy / window), in percent."""


def read(run):
    trace = run.get("trace")
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

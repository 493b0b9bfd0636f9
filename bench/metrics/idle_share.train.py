"""Share of the traced stretch in which the chips ran no operation:
1 - busy / window, busy being the union of the ops' intervals, averaged
over the chips (bench/trace.py)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

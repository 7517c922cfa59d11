"""Share of the traced window in which no operation ran on the device.

layer: device; source: device_trace; moves train_tok_s.
"""


def read(r):
    t = r.get("trace")
    if r.get("kind") != "train" or not t or not t["window_s"] \
            or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

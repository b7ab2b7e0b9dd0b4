"""Share of the traced window in which no operation ran on the device; in
percent."""


def read(rec):
    t = rec.get("trace")
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None

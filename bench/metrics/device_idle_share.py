"""1 - device busy time / traced time, over the traced slice of the
window.  A TPU trace holds a device plane only where an operation ran,
so a slice without one was idle throughout."""


def read(rec):
    s = (rec.get("trace") or {}).get("window")
    if not s or s["window_s"] <= 0 or rec.get("platform") != "tpu":
        return None
    return 1.0 - s["busy_s"] / s["window_s"]

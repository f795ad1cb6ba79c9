"""1 - the union of the device's operation intervals over the traced
window, averaged over the chips used."""


def read(facts, suffix):
    t = facts["trace"]
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

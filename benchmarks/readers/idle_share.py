"""The device's idle share of the traced window, in percent: 1 - the
union of the device's operation intervals over the window."""


def read(facts: dict, args: dict):
    trace = facts.get("trace")
    if not trace or trace.get("idle_share") is None:
        return None
    return 100.0 * trace["idle_share"]

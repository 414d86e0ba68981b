"""A share of the device's busy time by named scope, in percent: the
self seconds of the scope x phase table (`scope_reduce.reduce_scopes`,
kept as `facts["scopes"]`) whose outer name is one of `outer` (every
scope where left out), over `phases` (every phase where left out)."""
import scope_reduce


def read(facts: dict, args: dict):
    reduced = facts.get("scopes")
    if not reduced or not reduced.get("busy_s"):
        return None
    seconds = scope_reduce.seconds_of(
        reduced["scopes"], outer=args.get("outer"),
        phases=args.get("phases", scope_reduce.PHASES))
    return 100.0 * seconds / reduced["busy_s"]

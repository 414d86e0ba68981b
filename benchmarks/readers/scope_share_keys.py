"""A share of the device's busy time by WHOLE scope keys, in percent: the
self seconds, all phases, of the cells of the scope x phase table
(`scope_reduce.reduce_scopes`, kept as `facts["scopes"]`) whose key is one
of `keys` (`cca_attn/conv_mix`: an outer name with its inner one, which
`scope_share` cannot select). None where the table holds none of them."""


def read(facts: dict, args: dict):
    reduced = facts.get("scopes")
    if not reduced or not reduced.get("busy_s"):
        return None
    cells = [reduced["scopes"][key] for key in args["keys"] if key in reduced["scopes"]]
    if not cells:
        return None
    return 100.0 * sum(sum(cell.values()) for cell in cells) / reduced["busy_s"]

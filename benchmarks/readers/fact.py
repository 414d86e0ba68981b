"""A number the run already holds: `key` of its facts, times `scale`."""


def read(facts: dict, args: dict):
    value = facts.get(args["key"])
    if value is None:
        return None
    return value * args.get("scale", 1.0)

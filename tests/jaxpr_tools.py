"""What the tests read out of a jaxpr."""


def kernel_call_sites(jaxpr, counts=None):
    """{kernel function: `pallas_call` equations}, the sub-jaxprs of scan,
    a checkpoint and a custom_vjp included."""
    from jax._src import core

    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["jaxpr"].debug_info.func_name
            counts[name] = counts.get(name, 0) + 1
        for sub in core.jaxprs_in_params(eqn.params):
            kernel_call_sites(sub, counts)
    return counts

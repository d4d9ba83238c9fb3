"""Walking a jaxpr: a leaf utility (imports jax alone) shared by the static
analysis (analysis/jaxpr_lint.py) and the model registry's sizing of its
tagged activations (models/registry.named_activation_bytes)."""


def _sub_jaxprs(value):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for item in value:
            yield from _sub_jaxprs(item)


def iter_eqns(closed):
    """Every eqn in a ClosedJaxpr, recursing into scan/pjit/shard_map/cond
    sub-jaxprs (each counted once — a scan body's collectives are per-
    program, not per-iteration)."""
    stack = [closed.jaxpr]
    while stack:
        jaxpr = stack.pop()
        for eqn in jaxpr.eqns:
            yield eqn
            for v in eqn.params.values():
                stack.extend(_sub_jaxprs(v))

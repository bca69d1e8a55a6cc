"""Device time in one train step of the operations that the program's op
map (``ray_tpu.util.tracing.op_maps``) puts under one of ``scopes``, in
milliseconds: their share of the traced window x the step time of the
whole window (as op_ms_per_step, which knows its operations by a
substring of their trace name).

``program``: the name the step registered under (``train.step``).
``scopes``: exact components of an operation's scope (``mlp`` counts
``mlp`` and ``mlp/cast_weights``, forward and backward); ``pass``
(``fwd`` | ``bwd``) narrows it.  With ``unscoped`` instead: the operations
whose scope has no component but those in ``ignore``, and those the map
does not hold: what the map fails to say.  None where there is no map to
join (the parent of the PR that added it, an untraced run, the CPU)."""

from perfbench import op_scopes


def reduce(facts: dict, params: dict):
    if not facts.get("steps"):
        return None
    joined = op_scopes.of_run(facts)
    if joined is None:
        return None
    start, end = joined["window"]
    share = op_scopes.seconds(joined, params) / (end - start)
    return share * 1e3 * facts["window_s"] / facts["steps"]

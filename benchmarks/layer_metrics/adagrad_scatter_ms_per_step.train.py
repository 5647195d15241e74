"""Device ms per training step under the two row-wise Adagrad updates: the
self time of the operations whose scope is ``twotower.adagrad_user`` or
``twotower.adagrad_item`` (the program's map from instruction to
``named_scope``, ``obs/jaxmon.SCOPE_MAPS``), over the traced steps."""

SCOPES = ("twotower.adagrad_user", "twotower.adagrad_item")


def read(ctx):
    traced = ctx.get("traced")
    spans = ctx["bench"].lib("program_spans")
    trace = spans.trace_of(ctx)
    if trace is None or not traced or not traced.get("steps"):
        return None
    if not spans.ops_in_scope(trace, SCOPES):
        return None
    ns = spans.self_ns_of_ops(trace, lambda o: o.scope in SCOPES)
    return ns / 1e6 / len(trace.ops) / traced["steps"]

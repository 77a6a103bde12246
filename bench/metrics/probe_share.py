"""probe_share.<split>: share of the fused segment's device time spent in
its Lookups' hash-probe loops.

The program names the compiled segment's top-level ops by their
``jax.named_scope`` in ``("program", "scopes")`` instant events
(``{"program", "layout", "ops": {op: scope}}``); the probe loop of Lookup
``<dim>`` runs under ``lookup.<dim>/probe``.  The share is the device time
(``ctx.trace.ops_ns``) of the ops whose scope is a probe, over the device
time of the segment program (``program_ns("segment")``).  Only top-level
ops carry a scope, so a ``while`` counts once with the ops inside it.

None without scope events, and where two layouts give one op name
different scopes: the trace sums an op's time over layouts by name."""
import re

SCOPES = ("program", "scopes")
PROBE = re.compile(r"^lookup\.[^/]+/probe(/|$)")


def scopes(spans):
    """``{"<program>/<op>": scope}`` from the program's scope events, or
    None where two of them disagree on an op."""
    out = {}
    for e in spans:
        if (e.get("ph"), e.get("cat"), e.get("name")) != ("i",) + SCOPES:
            continue
        args = e.get("args") or {}
        for op, scope in (args.get("ops") or {}).items():
            key = f"{args.get('program')}/{op}"
            if out.setdefault(key, scope) != scope:
                return None
    return out


def read(ctx):
    if ctx.trace is None or ctx.spans is None:
        return None
    ops = scopes(ctx.spans)
    segment_ns = ctx.trace.program_ns("segment")
    if not ops or segment_ns <= 0:
        return None
    probe_ns = sum(ctx.trace.ops_ns.get(op, 0.0) for op, scope in ops.items()
                   if PROBE.search(scope))
    return 100.0 * probe_ns / segment_ns

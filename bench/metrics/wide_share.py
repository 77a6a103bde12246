"""wide_share.<split>: share of the fused segment's device time spent in
its wide integer expressions.

The program runs an expression that can leave int32 in two 32-bit words,
under ``jax.named_scope("wide.<column>")`` (a filter's under
``wide.filter.<i>``), and names the compiled segment's top-level ops by
their scope in ``("program", "scopes")`` instant events, as
``probe_share`` reads them.  The share is the device time of the ops whose
scope holds a ``wide.`` part, over the device time of the segment program
(``program_ns("segment")``).  None without scope events, or where no op
of the window's programs is scoped ``wide.`` (a program that computes no
wide integer)."""
import re

SCOPES = ("program", "scopes")
WIDE = re.compile(r"(^|/)wide\.")


def _scopes(spans):
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
    ops = _scopes(ctx.spans)
    segment_ns = ctx.trace.program_ns("segment")
    if not ops or segment_ns <= 0:
        return None
    wide = [op for op, scope in ops.items() if WIDE.search(scope)]
    if not wide:
        return None
    return 100.0 * sum(ctx.trace.ops_ns.get(op, 0.0)
                       for op in wide) / segment_ns

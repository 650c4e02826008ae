"""dispatch_ms: host milliseconds per step spent inside the calls into
``Croft3D`` (the benchmark's ``bench.call.*`` spans, from call to return,
before the wait), read from the profiler trace."""


def read(ctx):
    if ctx.trace is None or not ctx.steps:
        return None
    ns = ctx.trace.span_ns("bench.call.")
    return ns / 1e6 / ctx.steps if ns > 0 else None

"""Every name the trace reduction and the readers match, in one table.

Where the program renames a jitted function, a kernel or a span, a later
benchmark change repoints the metric here, in one line.
"""

#: profiler planes: one per chip, and the host's
DEVICE_PLANE = r"^/device:TPU:(\d+)$"
HOST_PLANE = "/host:CPU"

#: lines of a device plane: single operations, and whole XLA programs
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

#: XLA programs (events of ``MODULES_LINE``) by the part of the program
#: they belong to, as regular expressions over the event name
PROGRAMS = {
    # the fused row-synchronized segment: jax.jit(_JaxSegmentRunner._kernel)
    "segment": r"^jit__kernel\b",
    # the group-by kernels' jitted wrappers (kernels/radix_groupby,
    # kernels/segment_sum): the Pallas call and its padding
    "groupby": r"^jit_(radix_groupby|segment_sum)\b",
}

#: the harness's own profiler annotations (jax.profiler.TraceAnnotation)
#: (``bench.window`` around the measured loop, ``bench.run`` or
#: ``bench.tick`` around each request)
WINDOW = "bench.window"
ANNOTATION_PREFIX = "bench."

#: host events that say nothing of what the host was doing
HOST_NOISE = r"^(ThreadpoolListener|end: )"

#: the program's own spans (repro.obs trace events): category and name
H2D_SPAN = ("transfer", "h2d")

#: jax.monitoring events: one XLA program that jit's in-memory cache
#: missed (compiled, or loaded from the persistent cache), and one hit of
#: the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

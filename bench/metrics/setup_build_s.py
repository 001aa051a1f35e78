"""Engine partitions, program build: wall seconds of the first call of
every partition program built in set-up (trace, lower, compile or load from
the persistent cache, dispatch), the program's
``CompiledPlanCache.build_s`` read at the end of set-up (s)."""


def read(run):
    return getattr(run, "build_s", None)

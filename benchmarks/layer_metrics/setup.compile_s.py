"""Layer: benchmark.  Seconds of set-up that JAX spent tracing, lowering
and compiling or reading its persistent cache (CompileClock)."""


def read(obs):
    c = obs["clock"]["compile"]
    return c["trace_s"] + c["backend_s"]

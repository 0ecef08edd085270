"""Where JAX's persistent compilation cache lives.

Entry points (chip_smoke.py, bench.py, tools/gen_bench.py,
tools/op_bench.py) call `enable_compile_cache()` before their first
compile; `import paddle_tpu` does not.  A cold start of the training and
serving paths on a TPU v5e is minutes of XLA and Mosaic compilation, and
the chip machine keeps nothing between runs but what is under a known
directory.
"""
import os

import jax

# <checkout>/.jax_cache: the path is part of the cache key, so it is fixed —
# never a temp dir, a pid or a timestamp
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache():
    """Returns the cache directory in force.  JAX_COMPILATION_CACHE_DIR,
    when set, is the directory (JAX reads it itself; no path is set
    here); otherwise the cache goes to DEFAULT_CACHE_DIR."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # JAX stores only programs that took over 1 s to compile.  The eager
    # paths (dygraph, the engine's host-side ops, greedy_reference) compile
    # ~950 sub-second ones: under that threshold a warm chip_smoke.py still
    # spent 95 of its 154 s compiling them (chip run, PR 21).  Store
    # everything, wherever the cache is, unless the threshold too was
    # placed from outside.
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir

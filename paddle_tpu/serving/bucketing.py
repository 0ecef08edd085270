"""Shape bucketing + per-bucket AOT-compiled executable cache.

Production TPU serving lives or dies on compile reuse: XLA compiles one
executable PER SHAPE, so free-form request shapes mean a compile storm.
The fix (Ragged Paged Attention, arxiv 2604.15464; the Gemma-on-TPU
report, arxiv 2605.25645, attributes most serving throughput to batching
+ AOT compile reuse) is a small fixed menu of shapes:

- `ShapeBucketer` rounds every request up to the next (batch, length)
  bucket and pads with a constant; outputs are sliced back to real rows;
- `CompiledModelCache` keeps ONE ahead-of-time compiled executable per
  padded shape signature (jax.jit().lower().compile(), the AOT analogue
  of the reference's warmed AnalysisPredictor), so steady-state serving
  never traces or compiles again.
"""
import re
import threading

import numpy as np

from .admission import RequestTooLargeError
from .metrics import ServingMetrics


def _check_buckets(name, buckets):
    bs = tuple(int(b) for b in buckets)
    if not bs or any(b < 1 for b in bs) or list(bs) != sorted(set(bs)):
        raise ValueError(
            f"{name} must be strictly increasing positive ints, got "
            f"{buckets!r}")
    return bs


class ShapeBucketer:
    """Pads request shapes to a fixed bucket menu.

    batch_buckets: allowed padded batch sizes (axis 0 of every input).
    length_buckets: optional allowed padded lengths for axis 1 of every
        input with ndim >= 2 (token/sequence inputs); None disables
        length bucketing (trailing dims must then match the bucket key
        exactly).
    pad_value: fill for padding rows/positions (0 works for both token
        ids and dense features).
    """

    def __init__(self, batch_buckets=(1, 2, 4, 8), length_buckets=None,
                 pad_value=0):
        self.batch_buckets = _check_buckets("batch_buckets", batch_buckets)
        self.length_buckets = None if length_buckets is None else \
            _check_buckets("length_buckets", length_buckets)
        self.pad_value = pad_value

    @staticmethod
    def geometric_menu(limit, start=8):
        """A power-of-two bucket menu covering [1, limit]: (start,
        2*start, ..., first power >= limit).  log2(limit) buckets bound
        the compile count while wasting at most 2x padding — the
        standard serving trade (docs/SERVING.md)."""
        limit = max(int(limit), 1)
        start = max(int(start), 1)
        menu = [start]
        while menu[-1] < limit:
            menu.append(menu[-1] * 2)
        return tuple(menu)

    @property
    def max_batch(self):
        return self.batch_buckets[-1]

    def batch_bucket(self, rows):
        """Smallest batch bucket >= rows; typed rejection past the menu."""
        for b in self.batch_buckets:
            if rows <= b:
                return b
        raise RequestTooLargeError(
            f"request rows={rows} exceed the largest batch bucket "
            f"{self.batch_buckets[-1]}")

    def length_bucket(self, length):
        if self.length_buckets is None:
            return int(length)
        for b in self.length_buckets:
            if length <= b:
                return b
        raise RequestTooLargeError(
            f"sequence length {length} exceeds the largest length bucket "
            f"{self.length_buckets[-1]}")

    def bucket_key(self, arrays):
        """Coalescing key: per-input (bucketed trailing shape, dtype).
        Two requests coalesce into one dispatch iff their keys match —
        after length padding they then share every non-batch dim."""
        key = []
        for a in arrays:
            a = np.asarray(a)
            trail = list(a.shape[1:])
            if trail and self.length_buckets is not None:
                trail[0] = self.length_bucket(trail[0])
            key.append((tuple(trail), str(a.dtype)))
        return tuple(key)

    def pad_request(self, arrays):
        """Pad axis 1 of each input to its length bucket (axis 0 — batch —
        is padded later, once per coalesced dispatch)."""
        out = []
        for a in arrays:
            a = np.asarray(a)
            if a.ndim >= 2 and self.length_buckets is not None:
                want = self.length_bucket(a.shape[1])
                if want != a.shape[1]:
                    widths = [(0, 0)] * a.ndim
                    widths[1] = (0, want - a.shape[1])
                    a = np.pad(a, widths, constant_values=self.pad_value)
            out.append(a)
        return out

    def pad_token_batch(self, seqs, dtype=np.int32):
        """Pad ragged token-id sequences into one bucketed batch:
        returns ``(tokens [batch_bucket, length_bucket], lengths [B])``
        — the prefill-side entry point (generation's batched prefill
        and any token-in serving model share this menu)."""
        lens = np.asarray([len(s) for s in seqs], np.int32)
        if len(seqs) == 0:
            raise ValueError("pad_token_batch needs at least one sequence")
        bb = self.batch_bucket(len(seqs))
        lb = self.length_bucket(int(lens.max()))
        out = np.full((bb, lb), self.pad_value, dtype)
        for i, s in enumerate(seqs):
            out[i, :len(s)] = s
        return out, lens

    def pad_batch(self, arrays, rows):
        """Pad axis 0 from `rows` to the batch bucket; returns (padded
        arrays, bucket_rows)."""
        bucket = self.batch_bucket(rows)
        if bucket == rows:
            return list(arrays), bucket
        out = []
        for a in arrays:
            widths = [(0, 0)] * a.ndim
            widths[0] = (0, bucket - rows)
            out.append(np.pad(a, widths, constant_values=self.pad_value))
        return out, bucket

    @staticmethod
    def unpad_outputs(outs, row_counts):
        """Scatter a padded batch output back per-request: slices rows
        [offset, offset+rows) for each request in dispatch order."""
        per_request = [[] for _ in row_counts]
        for o in outs:
            o = np.asarray(o)
            off = 0
            for i, rows in enumerate(row_counts):
                per_request[i].append(o[off:off + rows])
                off += rows
        return per_request


def _default_name(fn):
    kind = getattr(fn, "__name__", "program")

    def name(args):
        dims = "x".join(map(str, getattr(args[0], "shape", ()))) \
            if args else ""
        return f"{kind}_{dims}" if dims else kind

    return name


def _named(fn, name):
    """`fn` under the name its jitted program and module take."""
    def program(*args):
        return fn(*args)

    program.__name__ = program.__qualname__ = re.sub(r"\W", "_", name)
    return program


class CompiledModelCache:
    """(shapes, dtypes) -> ahead-of-time compiled executable.

    Wraps any positional array function (a Predictor's exported module
    call, a CompiledBlock-style jitted fn, or a plain jax callable).  The
    first request into a bucket pays lower+compile ONCE (counted in
    `serving.compiles_total`); every later request is a cache hit that
    goes straight to the executable — the compile-reuse contract the
    bucket menu exists to enable.

    ``aot=False`` keeps the per-signature cache and its counters but
    skips jax.jit: every signature "compiles" to the raw fn, dispatched
    eagerly.  Callers needing BITWISE parity with an unbatched eager
    path use this — XLA whole-program fusion reassociates float
    reductions at the ulp level, which generation's zero-tolerance
    token-identity oracle cannot absorb (docs/GENERATION.md).
    compile_count then still means "distinct shape signatures
    dispatched" — the number the bucket menu exists to bound.

    ``name(args)`` names the program of a signature after its kind and
    its bucket (``ragged_step_p128``): the module a device profile and
    `profiler.device_op_scopes()` know it by (``jit_ragged_step_p128``).
    By default the function's own name and the leading argument's shape.
    Every executable compiled is registered with the profiler, which
    reads its text only when asked.
    """

    def __init__(self, fn, metrics=None, aot=True, donate_argnums=(),
                 name=None):
        self._fn = fn
        self._name = name or _default_name(fn)
        self._metrics = metrics or ServingMetrics()
        self._aot = bool(aot)
        # buffer-donation plan forwarded to jax.jit: generation's fused
        # decode step donates its KV pool arguments so XLA updates them
        # in place (ignored when aot=False — the raw fn never donates)
        self._donate = tuple(donate_argnums)
        self._cache = {}
        self._lock = threading.Lock()
        self.compile_count = 0

    @staticmethod
    def _key(args):
        return tuple((tuple(a.shape), str(a.dtype)) for a in args)

    def _compile(self, args):
        import jax

        from ..profiler import RecordEvent, register_program

        if not self._aot:
            return self._fn

        from jax.sharding import NamedSharding

        def aval(a):
            # mesh-sharded callers (generation's sharded fused decode)
            # hand committed NamedSharding arrays — or prewarm
            # ShapeDtypeStructs carrying the same shardings — and the
            # AOT executable must be lowered against those shardings or
            # it would reject the very arrays it is dispatched with.
            # Plain numpy args (and single-device jax arrays) keep the
            # historical sharding-free aval: placement stays the
            # compiler's choice, exactly as before.
            sh = getattr(a, "sharding", None)
            if isinstance(sh, NamedSharding):
                return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        avals = [aval(a) for a in args]
        with RecordEvent("serving::compile"):
            try:
                exe = jax.jit(_named(self._fn, self._name(args)),
                              donate_argnums=self._donate) \
                    .lower(*avals).compile()
            except Exception:
                # fns that resist lowering (host callbacks, non-jax code)
                # still serve, just without the AOT guarantee
                return self._fn
        register_program(exe)
        return exe

    def get(self, args):
        """Executable for this exact shape signature (compiling once)."""
        key = self._key(args)
        with self._lock:
            exe = self._cache.get(key)
            hit = exe is not None
        self._metrics.count_cache(hit)
        if hit:
            return exe
        # compile OUTSIDE the lock: buckets compile concurrently and a
        # 30 s XLA compile must not block cache hits on other buckets
        exe = self._compile(args)
        with self._lock:
            # a racing compile of the same bucket: first one in wins so
            # every caller runs the SAME executable (and the compile
            # counter keeps meaning 'one per cached bucket')
            exist = self._cache.get(key)
            if exist is None:
                self._cache[key] = exe
                self.compile_count += 1
                won = True
            else:
                exe = exist
                won = False
        if won:
            self._metrics.count_compile()
        return exe

    def __call__(self, args):
        outs = self.get(args)(*[np.asarray(a) for a in args])
        if not isinstance(outs, (list, tuple)):
            outs = (outs,)
        return [np.asarray(o) for o in outs]

    def warmup(self, shape_sets, dtype="float32"):
        """Pre-compile buckets before traffic: shape_sets is an iterable
        of per-input shape lists, e.g. [[(8, 16)], [(4, 16)]]."""
        for shapes in shape_sets:
            args = [np.zeros(s, dtype=dtype) for s in shapes]
            self.get(args)

    def cached_buckets(self):
        with self._lock:
            return sorted(self._cache)

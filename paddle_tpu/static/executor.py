"""Static executor: whole-block XLA lowering.

Reference parity: framework/executor.cc (Executor::Run :166/292, Prepare :368,
per-op loop :485-491) and python executor.py:916 (Executor.run feed/fetch,
program cache keyed on feed/fetch).  TPU-native design (SURVEY §7.1): instead
of a per-op dispatch loop, the executor lowers the WHOLE block into one jitted
XLA computation (feed vars + parameters -> fetch vars), cached per
(program id, feed names, fetch names, shapes).  Parameters live in a Scope
(name -> jax array), the analogue of framework/scope.h:52.
"""
import collections

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core.device import current_place
from .program import Program, default_main_program, Variable


class Scope:
    """name -> value store (framework/scope.h:52 parity, flat)."""

    def __init__(self):
        self._vars = {}

    def var(self, name):
        return self._vars.setdefault(name, None)

    def find_var(self, name):
        return self._vars.get(name)

    def set(self, name, value):
        self._vars[name] = value

    def get(self, name):
        return self._vars.get(name)

    def names(self):
        return list(self._vars)

    def drop_kids(self):
        pass


_global_scope = Scope()


def global_scope():
    return _global_scope


def coerce_feeds(feed_names, feed):
    """Validate + convert a feed dict to jnp arrays (shared by the
    whole-block and pipelined execution paths)."""
    feeds = {}
    for n in feed_names:
        if n not in feed:
            from ..core.errors import NotFoundError

            raise NotFoundError(
                f"feed variable {n!r} missing from feed dict "
                f"(declared feeds: {list(feed_names)})")
        v = feed[n]
        if isinstance(v, Tensor):
            v = v._data
        if isinstance(v, jax.Array):
            # already on device: hand it to jit as-is (jit device_puts /
            # reshards per in_shardings).  np.asarray here would pull the
            # buffer back to host and re-upload it every step.
            feeds[n] = v
        else:
            feeds[n] = jnp.asarray(np.asarray(v))
    return feeds


# Static AMP (reference: contrib/mixed_precision/decorator.py:37 +
# cast_model_to_fp16): a lowering-time dtype policy applied while the block
# is traced into ONE jit — XLA folds/fuses every convert.  Params stay f32
# in the Scope (master weights); bf16 ops cast their >=2-D float operands at
# the use site, so weight buffers are f32 but compute and activation
# buffers are bf16.  1-D floats (BN scale/bias/stats, lr) stay f32.
_AMP_BF16_OPS = frozenset({
    "conv2d", "conv2d_grad", "conv2d_bias", "conv2d_bias_grad",
    "conv3d", "conv3d_grad", "fc", "fc_grad", "matmul", "matmul_grad",
    "mul", "mul_grad", "pool2d", "pool2d_grad", "relu", "relu_grad",
    "elementwise_add", "elementwise_add_grad", "flatten", "flatten_grad",
    "sum", "batch_norm", "batch_norm_grad", "dropout", "dropout_grad",
})
_AMP_F32_OPS = frozenset({
    "softmax", "softmax_grad", "softmax_with_cross_entropy",
    "softmax_with_cross_entropy_grad", "cross_entropy", "cross_entropy_grad",
    "reduce_mean", "reduce_mean_grad", "reduce_sum", "reduce_sum_grad",
    "mean", "mean_grad", "fill_constant_grad",
    "momentum", "sgd", "adam", "adamw", "lars_momentum", "rmsprop",
})


def _amp_cast_args(op_type, args):
    if op_type in _AMP_BF16_OPS:
        return [a.astype(jnp.bfloat16)
                if (hasattr(a, "dtype") and a.dtype == jnp.float32
                    and getattr(a, "ndim", 0) >= 2) else a
                for a in args]
    if op_type in _AMP_F32_OPS:
        return [a.astype(jnp.float32)
                if (hasattr(a, "dtype") and a.dtype == jnp.bfloat16) else a
                for a in args]
    return args


class CompiledBlock:
    """One lowered block: pure function (feeds, params) -> fetches.

    Lowering order, dead-op pruning and feed-donation decisions come from the
    native planner (native/src/scheduler.cc — the executor_gc_helper /
    memory_optimize_pass role); XLA then owns scheduling and memory *inside*
    the compiled computation.
    """

    def __init__(self, program, feed_names, fetch_names, scope, mesh=None):
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        # GSPMD mode (ParallelExecutor role, parallel_executor.h:51): with a
        # mesh, the block jits with in/out shardings from each var's
        # dist_spec + batch-sharded feeds; XLA partitions the global-
        # semantics program and inserts the ICI collectives the fleet
        # marker ops (c_allreduce_sum/c_broadcast/...) stand for.
        self.mesh = mesh
        self._in_shardings = None
        block = program.global_block()
        self.param_names = [
            n for n, v in block.vars.items()
            if v.persistable and scope.get(n) is not None
        ]
        from ..framework import _FLAGS

        # FLAGS_check_nan_inf (operator.cc:1183 parity): thread a per-op
        # finite-mask through the compiled block; run() raises fetch-side
        # with the op name.  Captured at compile time (Executor.run's cache
        # key includes the flag, so flips build a fresh CompiledBlock).
        self._check_nan = bool(_FLAGS.get("FLAGS_check_nan_inf"))
        self._amp_bf16 = bool(getattr(program, "_amp_bf16", False))
        self._rng_steps = list(getattr(program, "_rng_step_vars", ()))
        self._chained = {}
        self._checked_ops = []
        self._op_order, self._donate_feeds = self._plan(block)
        self._jitted = None
        self._donated = False

    def _ensure_jitted(self, feeds, params):
        """Build the jitted callable on first run, when concrete feed/param
        avals are known.  Feeds are donated (inplace-pass analogue) only
        when every feed buffer can actually be aliased into some output —
        XLA warns on (and on TPU double-allocates for) donations it can't
        use, so a shape/dtype multiset check gates the donation plan."""
        if self._jitted is not None:
            return
        if self.mesh is not None:
            in_sh, out_sh = self._build_shardings(feeds, params)
            self._in_shardings = in_sh
            self._jitted = jax.jit(self._run_block, in_shardings=in_sh,
                                   out_shardings=out_sh)
            return
        donate = False
        if self._donate_feeds and feeds:
            try:
                out_sds = jax.eval_shape(self._run_block, feeds, params)
                avail = collections.Counter(
                    (tuple(s.shape), str(s.dtype))
                    for s in jax.tree_util.tree_leaves(out_sds))
                donate = True
                for v in feeds.values():
                    k = (tuple(v.shape), str(v.dtype))
                    if avail.get(k, 0) <= 0:
                        donate = False
                        break
                    avail[k] -= 1
            except Exception:
                donate = False
        if donate:
            self._jitted = jax.jit(self._run_block, donate_argnums=(0,))
            self._donated = True
        else:
            self._jitted = jax.jit(self._run_block)

    def _build_shardings(self, feeds, params):
        """GSPMD placement: feeds shard their batch dim over the data-like
        axes; every persistable var follows its dist_spec (TP column/row
        specs from `distributed.split` call sites, ZeRO range-sharding from
        the sharding meta-opt); fetches come back replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.hybrid import _clean_spec

        mesh = self.mesh
        batch_axes = tuple(a for a in ("data", "sharding")
                           if a in mesh.axis_names and mesh.shape[a] > 1)
        bsize = int(np.prod([mesh.shape[a] for a in batch_axes])) \
            if batch_axes else 1
        block = self.program.global_block()
        feed_sh = {}
        for n, v in feeds.items():
            if batch_axes and v.ndim >= 1 and v.shape[0] % bsize == 0:
                spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0])
            else:
                spec = P()
            feed_sh[n] = NamedSharding(mesh, spec)
        param_sh = {}
        for n, v in params.items():
            var = block.vars.get(n)
            spec = _clean_spec(getattr(var, "dist_spec", None), mesh,
                               tuple(getattr(v, "shape", ())))
            param_sh[n] = NamedSharding(mesh, spec)
        rep = NamedSharding(mesh, P())
        out_sh = (tuple(rep for _ in self.fetch_names), dict(param_sh), rep)
        return (feed_sh, param_sh), out_sh

    def _plan(self, block):
        """(op order, donate feeds?): the native planner's pruned
        schedule, or program order when the native library could not be
        built (native.get_lib logs why, once)."""
        from ..native import NativeProgram, available

        ops = list(block.ops)
        if not available():
            return list(range(len(ops))), False
        nprog = NativeProgram()
        var_ids = {}

        def vid(name):
            if name not in var_ids:
                v = block.vars.get(name)
                persistable = bool(v is not None and v.persistable)
                var_ids[name] = nprog.add_var(name, persistable)
            return var_ids[name]

        # NOTE: c_broadcast is intentionally NOT here — param broadcasts
        # survive pruning via writes_state, and TP input broadcasts must
        # stay dead-code-prunable for partial-feed runs
        side_effect_ops = {
            "c_allreduce_sum", "c_allgather", "barrier",
            "send_v2", "recv_v2", "send", "recv", "listen_and_serv",
            "save", "load", "print", "assert", "py_func",
        }
        for op in ops:
            in_names = getattr(op, "in_order", op.input_names())
            out_names = getattr(op, "out_order", op.output_names())
            # writers of persistable state (optimizer updates, BN running
            # stats) are roots: they matter even when only loss is fetched
            writes_state = any(
                (v := block.vars.get(n)) is not None and v.persistable
                for n in out_names)
            nprog.add_op(op.type, [vid(n) for n in in_names],
                         [vid(n) for n in out_names],
                         side_effect=op.type in side_effect_ops
                         or writes_state)
        feed_ids = [vid(n) for n in self.feed_names]
        fetch_ids = [var_ids[n] for n in self.fetch_names if n in var_ids]
        plan = nprog.build_plan(feed_ids, fetch_ids)
        order = plan.order
        donatable = set(plan.donatable_feeds)
        donate = bool(feed_ids) and all(f in donatable for f in feed_ids)
        if plan.has_cycle:
            return list(range(len(ops))), False
        return order, donate

    def _run_block(self, feeds, params):
        env = {}
        env.update(params)
        env.update(feeds)
        block = self.program.global_block()
        all_ops = list(block.ops)
        nonfinite = []
        if self._check_nan:
            from ..core import sanitizer

            self._checked_ops = []
        for idx in self._op_order:
            op = all_ops[idx]
            if op.fn is None:
                continue  # structural ops (feed/fetch/init markers)
            in_names = getattr(op, "in_order", op.input_names())
            out_names = getattr(op, "out_order", op.output_names())
            args = [env[n] for n in in_names]
            if self._amp_bf16:
                args = _amp_cast_args(op.type, args)
            res = op.fn(*args)
            if not isinstance(res, tuple):
                res = (res,)
            for n, v in zip(out_names, res):
                env[n] = v
                if self._check_nan:
                    nonfinite.append(sanitizer.nonfinite_flag(v))
                    self._checked_ops.append((op.type, n))
        mask = jnp.stack(nonfinite) if nonfinite else jnp.zeros((0,), bool)
        return tuple(env[n] for n in self.fetch_names), {
            n: env[n] for n in self.param_names if n in env
        }, mask

    def _coerce_feeds(self, feed):
        return coerce_feeds(self.feed_names, feed)

    @staticmethod
    def _caller_owned(v):
        """True for feeds handed to us as live device arrays: donating
        those buffers would invalidate the CALLER's array (deleted-buffer
        errors on the next use), unlike the fresh arrays jnp.asarray makes
        from host feeds."""
        if isinstance(v, Tensor):
            v = v._data
        return isinstance(v, jax.Array)

    def _place_inputs(self, feeds, params):
        """Place inputs on the mesh (committed single-device arrays from
        startup would otherwise conflict with the jit's in_shardings);
        after step 1 the scope holds jit outputs already placed by
        out_shardings, so matching arrays pass through untouched."""
        if self._in_shardings is None:
            return feeds, params
        feed_sh, param_sh = self._in_shardings
        feeds = {n: jax.device_put(v, feed_sh[n])
                 for n, v in feeds.items()}
        params = {n: v if getattr(v, "sharding", None) == param_sh[n]
                  else jax.device_put(v, param_sh[n])
                  for n, v in params.items()}
        return feeds, params

    def run(self, feed, scope):
        feeds = self._coerce_feeds(feed)
        params = {n: scope.get(n) for n in self.param_names}
        self._ensure_jitted(feeds, params)
        if self._donated:
            # the donation plan aliases feed buffers into outputs; give it
            # an on-device copy of caller-owned arrays so the caller's
            # buffers stay alive (host feeds are already private copies)
            feeds = {n: jnp.copy(v) if self._caller_owned(feed[n]) else v
                     for n, v in feeds.items()}
        feeds, params = self._place_inputs(feeds, params)
        try:
            outs, updated, nonfinite = self._jitted(feeds, params)
        except KeyError as e:
            from ..core.errors import NotFoundError

            raise NotFoundError(
                f"variable {e.args[0]!r} is needed by the fetch targets "
                "but was neither fed nor produced by any op") from e
        if self._check_nan:
            mask = np.asarray(nonfinite)
            if mask.any():
                bad = [f"{op}->{var}"
                       for (op, var), hit in zip(self._checked_ops, mask)
                       if hit]
                raise FloatingPointError(
                    "FLAGS_check_nan_inf: non-finite outputs in compiled "
                    f"block from op(s): {', '.join(bad[:8])}"
                    + (f" (+{len(bad) - 8} more)" if len(bad) > 8 else ""))
        # write back persistable updates (e.g. optimizer/global-stat vars)
        for n, v in updated.items():
            scope.set(n, v)
        return [np.asarray(o) for o in outs]

    def run_chained(self, feed, scope, n_steps):
        """n dependent train steps in ONE dispatch: lax.scan over the block
        with every persistable (params, optimizer state, BN running stats,
        RNG counters) as the carry.  The host-free inner training loop —
        reference DeviceWorker::TrainFiles role (trainer.h) — which also
        spreads one dispatch and one host sync over the chain.
        Returns each fetch stacked over steps (leading n_steps axis)."""
        feeds = self._coerce_feeds(feed)
        params = {n: scope.get(n) for n in self.param_names}
        jitted = self._chained.get(n_steps)
        if jitted is None:
            def multi(feeds, params):
                def body(p, _):
                    outs, new_p, mask = self._run_block(feeds, p)
                    for n in self._rng_steps:
                        if n in new_p:
                            # dropout-mask counters advance per STEP (the
                            # host-side bump in Executor.run is skipped for
                            # chained runs)
                            new_p[n] = new_p[n] + 1
                    return new_p, (outs, mask)

                last_p, (outs, masks) = jax.lax.scan(
                    body, params, None, length=n_steps)
                return outs, last_p, masks

            if self.mesh is not None:
                # GSPMD programs keep their partitioning across the chain:
                # same in-shardings as run(); fetches stack over steps but
                # stay replicated, and params keep their dist_spec layout,
                # so out_shardings carries over structurally unchanged
                in_sh, out_sh = self._build_shardings(feeds, params)
                self._in_shardings = self._in_shardings or in_sh
                jitted = jax.jit(multi, in_shardings=in_sh,
                                 out_shardings=out_sh,
                                 donate_argnums=(1,))
            else:
                jitted = jax.jit(multi, donate_argnums=(1,))
            self._chained[n_steps] = jitted
        if self.mesh is not None:
            feeds, params = self._place_inputs(feeds, params)
        outs, last_p, masks = jitted(feeds, params)
        if self._check_nan:
            mask = np.asarray(masks).any(axis=0)
            if mask.any():
                bad = [f"{op}->{var}"
                       for (op, var), hit in zip(self._checked_ops, mask)
                       if hit]
                raise FloatingPointError(
                    "FLAGS_check_nan_inf: non-finite outputs in chained "
                    f"block from op(s): {', '.join(bad[:8])}")
        for n, v in last_p.items():
            scope.set(n, v)
        return [np.asarray(o) for o in outs]

    def cost_analysis(self, feed, scope):
        """XLA cost analysis of the lowered block ('flops', 'bytes
        accessed', ...), or None where JAX cannot analyse a lowering (a
        TPU one under jax 0.9.0); bench.py prefers it to a hand FLOPs
        model (op_tester.cc role)."""
        feeds = self._coerce_feeds(feed)
        params = {n: scope.get(n) for n in self.param_names}
        self._ensure_jitted(feeds, params)
        return self._jitted.lower(feeds, params).cost_analysis()


class Executor:
    def __init__(self, place=None):
        self.place = place or current_place()
        self._cache = {}
        self._meshes = {}

    def _resolve_mesh(self, program):
        """Build the device mesh a fleet-rewritten program asked for
        (`program._mesh_axes`, set via record_mesh_axis).  Degree-None
        axes absorb the devices no fixed axis claims.  When the fixed
        degrees don't fit the visible devices the program degrades to
        single-device execution — the math is global-semantics either
        way, only the partitioning changes."""
        axes = getattr(program, "_mesh_axes", None)
        if not axes:
            return None
        n = len(jax.devices())
        fixed = {k: int(v) for k, v in axes.items() if v}
        prod = int(np.prod(list(fixed.values()))) if fixed else 1
        if prod > n or n % prod:
            return None
        resolved = dict(fixed)
        free = [k for k, v in axes.items() if not v]
        if free:
            resolved[free[0]] = n // prod
            for k in free[1:]:
                resolved[k] = 1
        if int(np.prod(list(resolved.values()))) <= 1:
            return None
        key = tuple(sorted(resolved.items()))
        mesh = self._meshes.get(key)
        if mesh is None:
            from ..parallel.env import build_mesh

            # batch-like axes lead so model/pipe land on adjacent chips
            rank = {"data": 0, "sharding": 1, "pipe": 2, "model": 3}
            order = sorted(resolved, key=lambda k: (rank.get(k, 4), k))
            mesh = build_mesh({k: resolved[k] for k in order})
            self._meshes[key] = mesh
        return mesh

    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, use_program_cache=True):
        program = program or default_main_program()
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope or _global_scope

        if getattr(program, "_is_start_up_run", False) or _is_startup(program):
            self._run_startup(program, scope)
            return []

        cb = self._get_block(program, feed, fetch_list, scope)
        outs = cb.run(feed, scope)
        # advance RNG step counters (dropout masks etc.) once per run —
        # host-side so the value is CONSTANT within a run and the vjp
        # grad replay reconstructs the exact forward randomness
        for n in getattr(program, "_rng_step_vars", ()):
            v = scope.get(n)
            if v is not None:
                scope.set(n, v + 1)
        if return_numpy:
            return outs
        return [Tensor(o) for o in outs]

    def run_chained(self, program=None, feed=None, fetch_list=None,
                    n_steps=1, scope=None, return_numpy=True):
        """Run `n_steps` DEPENDENT steps of `program` in one device
        dispatch (see CompiledBlock.run_chained).  Fetches come back with
        a leading n_steps axis (e.g. the loss curve of the chain)."""
        program = program or default_main_program()
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope or _global_scope
        cb = self._get_block(program, feed, fetch_list, scope)
        if not hasattr(cb, "run_chained"):  # pipelined blocks: host loop
            outs = None
            for _ in range(int(n_steps)):
                outs = cb.run(feed, scope)
                # per-step RNG bump, as the scan path does in its carry —
                # otherwise every chained step reuses one dropout mask
                for n in getattr(program, "_rng_step_vars", ()):
                    v = scope.get(n)
                    if v is not None:
                        scope.set(n, v + 1)
            if return_numpy:
                return outs
            return [Tensor(o) for o in outs]
        outs = cb.run_chained(feed, scope, int(n_steps))
        if return_numpy:
            return outs
        return [Tensor(o) for o in outs]

    @staticmethod
    def _feed_shape(v):
        # shape WITHOUT materializing: np.asarray on a device array would
        # pull the whole buffer to host on every run() just for the key
        if isinstance(v, Tensor):
            v = v._data
        s = getattr(v, "shape", None)
        return tuple(s) if s is not None else np.asarray(v).shape

    def _cache_key(self, program, feed, fetch_names):
        feed_names = tuple(sorted(feed.keys()))
        shapes = tuple(self._feed_shape(v) for _, v in sorted(feed.items()))
        from ..framework import _FLAGS

        # _version: program-rewriting passes that mutate ops in place
        # (quant convert, ...) bump it so stale compiled blocks miss
        return (id(program), getattr(program, "_version", 0), feed_names,
                tuple(fetch_names), shapes,
                bool(getattr(program, "_amp_bf16", False)),
                bool(_FLAGS.get("FLAGS_check_nan_inf")))

    def _get_block(self, program, feed, fetch_list, scope):
        fetch_names = [
            f.name if isinstance(f, Variable) else str(f)
            for f in (fetch_list or [])
        ]
        popt = getattr(program, "_pipeline_opt", None)
        if popt and int(popt.get("num_stages", 1)) > 1 \
                and len(jax.local_devices()) >= int(popt["num_stages"]):
            # pipelined path (executor.py:1134 _run_pipeline role): stage
            # chunks on their own devices + micro-batch schedule
            from .pipeline_exec import PipelinedBlock

            key = self._cache_key(program, feed, fetch_names) + ("pipe",)
            cb = self._cache.get(key)
            if cb is None:
                cb = PipelinedBlock(program, feed.keys(), fetch_names,
                                    scope)
                self._cache[key] = cb
            return cb
        mesh = self._resolve_mesh(program)
        key = self._cache_key(program, feed, fetch_names) + (
            tuple(mesh.shape.items()) if mesh is not None else None,)
        cb = self._cache.get(key)
        if cb is None:
            cb = CompiledBlock(program, feed.keys(), fetch_names, scope,
                               mesh=mesh)
            self._cache[key] = cb
        return cb

    def cost_analysis(self, program=None, feed=None, fetch_list=None,
                      scope=None):
        """Cost stats of the block run() would execute for these args
        (compiles it if this exact (program, feed, fetch) wasn't run yet)."""
        program = program or default_main_program()
        feed = feed or {}
        scope = scope or _global_scope
        cb = self._get_block(program, feed, fetch_list, scope)
        return cb.cost_analysis(feed, scope)

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Dataset-path training (executor.py:1402 _run_from_dataset ->
        TrainerFactory -> MultiTrainer over the native DataFeed)."""
        from .trainer import TrainerDesc, TrainerFactory

        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        desc = TrainerDesc()
        if thread:
            desc.set_thread(thread)
            dataset.set_thread(thread)
        desc.set_debug(debug)
        desc.set_fetch_var_and_info(fetch_list, fetch_info, print_period)
        trainer = TrainerFactory().create_trainer(desc)
        trainer.set_program(program or default_main_program())
        trainer.set_dataset(dataset)
        steps, last = trainer.run(self, scope or _global_scope)
        return last

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Like train_from_dataset but parameters never update (the
        device worker's infer flag): backward/update/PS ops are stripped
        from a cloned program before the batch loop."""
        from .trainer import inference_program

        program = program or default_main_program()
        prog = program.__dict__.get("_infer_clone")
        if prog is None:  # cache: the executor compiles per program object
            prog = inference_program(program)
            program.__dict__["_infer_clone"] = prog
        return self.train_from_dataset(prog, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period)

    def _run_startup(self, program, scope):
        block = program.global_block()
        for op in block.ops:
            if op.type == "init" and op.fn is not None:
                out_name = op.outputs["Out"][0]
                if scope.get(out_name) is None:
                    scope.set(out_name, jnp.asarray(op.fn()))

    def close(self):
        pass


def _is_startup(program):
    ops = program.global_block().ops
    return bool(ops) and all(
        op.type in ("init", "c_comm_init", "c_gen_nccl_id",
                    "listen_and_serv")  # PS bootstrap marker (pscore)
        for op in ops)

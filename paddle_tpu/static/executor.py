"""Executor: lowers a captured Program to ONE compiled XLA computation.

Parity: python/paddle/fluid/executor.py (+ paddle/fluid/framework/executor.cc
per-op dispatch; ParallelExecutor SSA-graph scheduling). TPU-first: instead of
dispatching 1 kernel per op, the whole fetch-pruned op list is interpreted
once under jax.jit — XLA fuses/schedules it. Training programs (after
optimizer.minimize) compile forward+backward+update into the same program,
with jax.grad providing what append_backward provides in the reference.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, Parameter
from ..core.dtypes import convert_dtype
from .graph import Program, Variable, default_main_program
from .. import observability as _obs


def _program_params(program):
    """Ordered parameter Variables a program's ops read."""
    seen, out = set(), []
    for op in program.global_block.ops:
        for v in op.inputs:
            if v.concrete is not None and isinstance(v.concrete, Parameter) \
                    and id(v) not in seen:
                seen.add(id(v))
                out.append(v)
    return out


def _interpret_ops(ops, env):
    """Run a Program op list over an id(var)->payload environment.

    Ops whose inputs are unavailable are skipped (fetch-pruning happens
    implicitly); constants come from each Variable's concrete payload.
    Shared by Executor compilation and the portable jax.export path so the
    two can never diverge.
    """
    for op in ops:
        args = []
        ok = True
        for v in op.inputs:
            if id(v) in env:
                args.append(env[id(v)])
            elif v.concrete is not None:
                args.append(v.concrete._value)
            else:
                ok = False
                break
        if not ok:
            continue
        res = op.fn(*args)
        if op.n_outputs == 1:
            env[id(op.outputs[0])] = res
        else:
            for ov, r in zip(op.outputs, res):
                env[id(ov)] = r
    return env


def _fetch_outs(fetch_vars, env):
    outs = []
    for fv in fetch_vars:
        if id(fv) in env:
            outs.append(env[id(fv)])
        elif fv.concrete is not None:
            outs.append(fv.concrete._value)
        else:
            raise RuntimeError(
                f"fetch var {fv.name} not computed — check feeds")
    return outs


def _unshard_committed(tree):
    """Pull leaves that are still committed to a non-trivial mesh sharding
    back to host (a sharding-config toggle leaves the previous plan's
    placements in the param concretes / optimizer slots; a replicated-
    pinned dp jit rejects them). The next step's output re-places them,
    so the host round-trip happens once per toggle."""
    def fix(v):
        if getattr(getattr(v, 'sharding', None), 'spec', None):
            return np.asarray(v)
        return v
    return jax.tree_util.tree_map(fix, tree)


class Executor:
    def __init__(self, place=None):
        self.place = place
        self._cache = {}

    def close(self):
        self._cache.clear()

    def run(self, program=None, feed=None, fetch_list=None, feed_var_name='feed',
            fetch_var_name='fetch', scope=None, return_numpy=True,
            use_program_cache=True, verify=None):
        program = program or default_main_program()
        feed = feed or {}
        fetch_list = fetch_list or []
        if not isinstance(fetch_list, (list, tuple)):
            fetch_list = [fetch_list]

        from .fluid_format import FluidProgram
        if isinstance(program, FluidProgram):
            # a translated reference-format (Paddle 1.8) inference program:
            # run its jitted forward with the canonical exe.run signature
            return program.run(feed, fetch_list=fetch_list or None)

        # startup program: params were initialized eagerly at creation — no-op
        if not program.global_block.ops and not fetch_list:
            return []

        # static verification before compilation (analysis engine 2):
        # explicit verify=True/False wins, else PADDLE_TPU_VERIFY=1 or
        # analysis.set_always_verify(True) turns it on. Malformed programs
        # raise ProgramVerificationError with op-indexed findings instead of
        # a KeyError deep inside the jitted interpreter.
        from ..analysis.verify import assert_verified, verify_enabled
        if verify_enabled(verify):
            with _obs.timer('executor.verify'):
                assert_verified(program, fetch_list=fetch_list)

        fetch_vars = [self._resolve(program, f) for f in fetch_list]
        feed_items = sorted(feed.items())
        feed_names = [k for k, _ in feed_items]
        feed_vals = []
        for k, v in feed_items:
            if isinstance(v, Tensor):
                feed_vals.append(v._value)
            else:
                arr = np.asarray(v)
                var = program.global_block.vars.get(k)
                if var is not None:
                    arr = arr.astype(np.dtype(var.dtype))
                feed_vals.append(jnp.asarray(arr))

        train_spec = program._train_spec
        params = self._program_params(program)
        param_names = [v.name for v in params]
        param_vals = [v.concrete._value for v in params]

        dp = bool(getattr(program, '_dp', False))
        # the live sharding config is part of the compiled program's
        # identity: toggling fleet sharding between runs must recompile,
        # not silently reuse the other plan's cached step
        from ..distributed.strategy import current_config
        sharding_cfg = current_config() if dp else None
        key = (program._fingerprint, tuple(feed_names),
               tuple((tuple(v.shape), str(v.dtype)) for v in feed_vals),
               tuple(v.name for v in fetch_vars), train_spec is not None,
               sharding_cfg, dp)
        telemetry = _obs.enabled()
        if key not in self._cache:
            if telemetry:
                _obs.counter('executor.program_cache.misses').inc()
            with _obs.timer('executor.build'):
                self._cache[key] = self._compile(program, feed_names,
                                                 fetch_vars, param_names,
                                                 train_spec, dp=dp)
            # persistent tier (paddle_tpu.compilecache): a bound cache dir
            # turns this in-memory miss into a deserialize instead of a
            # compile (or an AOT compile-once + commit on true miss)
            attach = getattr(self._cache[key], 'attach_disk_cache', None)
            attached = bool(attach is not None
                            and attach(feed_vals, param_vals))
            if attach is None:
                # donated train steps are not serialized: counted bypass
                from .. import compilecache as _cc
                _cc.note_bypass(
                    getattr(self._cache[key], 'cost_label',
                            f'executor.train.p{program._fingerprint}'),
                    reason='donated_train_step')
            if telemetry and not attached:
                # cost explorer: ledger this program's FLOPs/bytes/peak
                # memory once, at build time (train steps capture
                # themselves at first dispatch — see TrainStep; attached
                # entries are ledgered by the persistent tier without the
                # extra capture compile)
                cap = getattr(self._cache[key], 'capture_costs', None)
                if cap is not None:
                    cap(feed_vals, param_vals)
        elif telemetry:
            _obs.counter('executor.program_cache.hits').inc()
            lbl = getattr(self._cache[key], 'cost_label', None)
            if lbl:
                _obs.costs.mark_hit(lbl)
        compiled = self._cache[key]
        # sampled sync: the run span blocks on the fetched outputs only on
        # sampled occurrences, so timing the step never adds a host sync the
        # steady-state pipeline would not have had
        outs = None
        with _obs.timer('executor.run', sync=lambda: outs):
            if train_spec is not None:
                optimizer = train_spec[1]
                pv = {v.name: val for v, val in zip(params, param_vals)}
                if getattr(optimizer, '_static_state', None) is None:
                    optimizer._static_state = \
                        optimizer.init_state_values(pv)
                # the engine step owns the whole functional state (and
                # donates it where the backend honors donation); params
                # stay authoritative in the Variables' concrete payloads
                if getattr(compiled, 'sharding', None) is not None:
                    # fleet sharding config live: init_state compiles the
                    # sharded program (first run) and places params +
                    # opt-state on the mesh per the FSDP/TP plan
                    state = compiled.init_state(
                        pv, {}, opt_state=optimizer._static_state)
                else:
                    state = {'params': pv, 'buffers': {},
                             'opt': optimizer._static_state}
                    if dp:
                        # a previous sharded run leaves committed sharded
                        # params/slots in the concretes; the replicated-
                        # pinned dp jit rejects those — pull the
                        # stragglers once (the step output re-places them)
                        state = _unshard_committed(state)
                state, result = compiled(state, feed_vals)
                optimizer._static_state = state['opt']
                outs = result.outputs
                new_param_vals = [state['params'][v.name] for v in params]
            else:
                if dp and sharding_cfg is None:
                    param_vals = _unshard_committed(param_vals)
                outs, new_param_vals = compiled(feed_vals, param_vals)
        if new_param_vals is not None:
            for v, nv in zip(params, new_param_vals):
                v.concrete._inplace_value(nv)
        if return_numpy:
            fetched = [np.asarray(jax.device_get(o)) for o in outs]
            if telemetry:
                _obs.record_host_transfer(
                    sum(a.nbytes for a in fetched), kind='executor.fetch')
            return fetched
        return [Tensor(o) for o in outs]

    # -- dataset-driven training (the reference's train/ device-worker
    # trainers: fluid/executor.py train_from_dataset -> C++ Hogwild/
    # Section trainers over a DataFeed) --------------------------------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Run a training Program over a fleet dataset
        (DatasetFactory.create_dataset + MultiSlot files).

        TPU-first divergence: the reference spawns `thread` host workers
        each driving per-op kernels (Hogwild async updates); here every
        batch is ONE XLA computation that already saturates the chip, so
        batches run sequentially on-device while the MultiSlot text
        parsing runs through the native csrc parser. `thread` is accepted
        for API parity.
        """
        return self._run_from_dataset(program, dataset, fetch_list,
                                      fetch_info, print_period,
                                      debug=debug, train=True)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        return self._run_from_dataset(program, dataset, fetch_list,
                                      fetch_info, print_period,
                                      debug=debug, train=False)

    def _run_from_dataset(self, program, dataset, fetch_list, fetch_info,
                          print_period, debug=False, train=True):
        from .._native import multislot
        program = program or default_main_program()
        if dataset is None:
            raise ValueError("train_from_dataset: dataset is required")
        use_vars = list(getattr(dataset, 'use_vars', []))
        if not use_vars:
            raise ValueError(
                "train_from_dataset: dataset.set_use_var([...]) must name "
                "the feed Variables (in MultiSlot slot order)")
        records = list(dataset)
        if not records:
            dataset.load_into_memory()
            records = list(dataset)
        bs = max(int(getattr(dataset, 'batch_size', 1)), 1)
        n_slots = len(use_vars)
        step = 0
        for start in range(0, len(records), bs):
            batch_lines = [ln.strip() for ln in records[start:start + bs]
                           if ln.strip()]
            if not batch_lines:
                continue
            values, counts = multislot.parse_batch(batch_lines, n_slots)
            feed = {}
            pos = 0
            # slice the flat value stream line-major into per-slot padded
            # dense arrays
            per_slot = [[] for _ in range(n_slots)]
            for li in range(counts.shape[0]):
                for s in range(n_slots):
                    c = int(counts[li, s])
                    per_slot[s].append(values[pos:pos + c])
                    pos += c
            for s, var in enumerate(use_vars):
                rows = per_slot[s]
                width = max((len(r) for r in rows), default=1)
                arr = np.zeros((len(rows), width), np.float64)
                for i, r in enumerate(rows):
                    arr[i, :len(r)] = r
                dt = np.dtype(var.dtype)
                name = getattr(var, 'name', str(var))
                want = tuple(getattr(var, 'shape', ()) or ())
                if len(want) == 1:
                    if width != 1:
                        raise ValueError(
                            f"train_from_dataset: slot {s} feeds 1-D "
                            f"variable '{name}' (shape {list(want)}) but a "
                            f"line carries {width} values per instance; "
                            f"declare the variable as [-1, {width}] or fix "
                            f"the slot arity in the data file")
                    arr = arr.reshape(len(rows))
                elif len(want) == 2 and want[-1] == 1 and width == 1:
                    arr = arr.reshape(len(rows), *want[1:])
                feed[name] = arr.astype(dt)
            outs = self.run(program, feed=feed,
                            fetch_list=list(fetch_list or []))
            if fetch_list and print_period and step % print_period == 0:
                labels = fetch_info or [getattr(f, 'name', str(f))
                                        for f in fetch_list]
                msg = ", ".join(f"{n}={np.asarray(o).ravel()[:4]}"
                                for n, o in zip(labels, outs))
                print(f"[dataset step {step}] {msg}")
            step += 1
        return None

    # -- internals ----------------------------------------------------------
    def _resolve(self, program, f):
        if isinstance(f, Variable):
            return f
        if isinstance(f, str):
            name = f.split('@')[0]
            return program.global_block.var(name)
        if isinstance(f, Tensor):
            # concrete tensor (e.g. a create_global_var Parameter a Switch
            # branch assigns into): fetch through its cached block Variable
            # so in-graph writes to its slot are visible
            return program.global_block.concrete_var(f)
        raise TypeError(f"bad fetch entry {f!r}")

    def _program_params(self, program):
        return _program_params(program)

    def _compile(self, program, feed_names, fetch_vars, param_names,
                 train_spec, dp=False):
        ops = program.global_block.ops

        def interpret(env):
            return _interpret_ops(ops, env)

        block = program.global_block
        feed_vars = [block.var(n) for n in feed_names]
        params = self._program_params(program)

        # data-parallel compile (CompiledProgram.with_data_parallel): feeds
        # shard over a 1-D 'data' mesh, params/opt-state replicate; XLA
        # derives the grad all-reduce from the shardings — numerics match
        # the single-device run on the concatenated batch exactly. When a
        # fleet sharding config is live (DistributedStrategy.sharding/
        # tensor_parallel resolved by fleet.init), the train path upgrades
        # to the full FSDP/TP plan through the same engine builder.
        from ..distributed.strategy import current_config
        sharding_cfg = current_config() if dp else None
        dp_shardings = None
        jit_kwargs = {}
        sharded_feed = None
        if dp:
            from jax.sharding import (Mesh, NamedSharding,
                                      PartitionSpec as P)
            if sharding_cfg is not None:
                # feeds go to the config mesh; params keep whatever
                # placement they carry (FSDP/TP training left them
                # committed sharded — pinning them to replicated
                # in_shardings would raise on the first call)
                sharded_feed = sharding_cfg.batch_sharding()
            else:
                mesh = Mesh(np.asarray(jax.devices()), ('data',))
                feed_sh = NamedSharding(mesh, P('data'))
                repl = NamedSharding(mesh, P())
                n_feed = len(feed_vars)
                n_param = len(params)
                jit_kwargs['in_shardings'] = ([feed_sh] * n_feed,
                                              [repl] * n_param)
                # engine step signature (state, batch): replicate the whole
                # state pytree (sharding-as-prefix), shard the feeds
                dp_shardings = (repl, [feed_sh] * n_feed)

        if train_spec is None:
            from ..engine.builder import kernel_mesh_of
            kernel_scope = functools.partial(
                kernel_mesh_of, sharding_cfg,
                (None,) + tuple(jit_kwargs.get('in_shardings', ())))

            @functools.partial(jax.jit, **jit_kwargs)
            def run_jit(feed_vals, param_vals):
                env = {}
                for v, val in zip(feed_vars, feed_vals):
                    env[id(v)] = val
                for v, val in zip(params, param_vals):
                    env[id(v)] = val
                with kernel_scope():    # data-parallel: see kernel_mesh
                    env = interpret(env)
                return _fetch_outs(fetch_vars, env), None

            fp = program._fingerprint
            state = {}          # persistent-tier executable, if attached
            if sharded_feed is None:
                def run(feed_vals, param_vals):
                    exe = state.get('exe')
                    if exe is not None:
                        comp, from_cache = exe
                        try:
                            return comp(feed_vals, param_vals)
                        except Exception as e:
                            # a deserialized executable the runtime rejects
                            # at dispatch: evict + count, recover live
                            state.pop('exe', None)
                            if from_cache:
                                from .. import compilecache as _cc
                                _cc.note_incompat(
                                    getattr(run, 'cost_label', f'p{fp}'),
                                    reason=repr(e)[:200])
                    return run_jit(feed_vals, param_vals)
            else:
                def run(feed_vals, param_vals):
                    feed_vals = [jax.device_put(v, sharded_feed)
                                 for v in feed_vals]
                    return run_jit(feed_vals, param_vals)

            def capture_costs(feed_vals, param_vals):
                """AOT cost/memory capture into the observability cost
                ledger (one extra compile, once per cache entry)."""
                from ..observability import costs as _costs
                fv = feed_vals
                if sharded_feed is not None:
                    fv = [jax.device_put(v, sharded_feed)
                          for v in feed_vals]
                sig = ','.join(
                    'x'.join(str(d) for d in np.shape(v)) or '()'
                    for v in fv)
                run.cost_label = f'executor.p{fp}[{sig}]'
                _costs.capture(run.cost_label, run_jit, fv, param_vals,
                               kind='executor.infer',
                               meta={'fingerprint': fp, 'dp': dp})
            run.capture_costs = capture_costs

            def attach_disk_cache(feed_vals, param_vals):
                """Install this entry's executable from the persistent
                compile tier (load-or-AOT-compile-once, see
                ``paddle_tpu.compilecache``). True means the run path now
                dispatches an AOT executable and the cost ledger is
                already populated — skip capture_costs (and its extra
                compile) for this entry."""
                from .. import compilecache as _cc
                if _cc.active() is None:
                    return False
                sig = ','.join(
                    'x'.join(str(d) for d in np.shape(v)) or '()'
                    for v in feed_vals)
                run.cost_label = f'executor.p{fp}[{sig}]'
                if dp:
                    # sharded-feed programs carry mesh placements a
                    # serialized executable cannot re-derive portably:
                    # deliberate, counted bypass
                    _cc.note_bypass(run.cost_label, reason='dp_sharded')
                    return False
                comp, src = _cc.fetch_or_compile(
                    run.cost_label, run_jit, (feed_vals, param_vals),
                    kind='executor.infer',
                    meta={'fingerprint': fp, 'dp': dp})
                if comp is None:
                    return False
                state['exe'] = (comp, src == 'hit')
                return True
            run.attach_disk_cache = attach_disk_cache
            return run

        # train path: ONE compiled step through the unified engine builder
        # (buffer donation where supported, shared update/clip/decay rule)
        # — the same step hapi Model.fit(jit=True) and engine.fit run
        from ..engine import build_train_step
        loss_var, optimizer = train_spec
        trainable = {v.name for v in params if not v.stop_gradient}
        meta = {v.name: v.concrete for v in params}

        def program_loss_fn(pvals, buffers, feed_vals, key):
            env = {}
            for v, val in zip(feed_vars, feed_vals):
                env[id(v)] = val
            for v in params:
                env[id(v)] = pvals[v.name]
            env = interpret(env)
            loss = jnp.sum(env[id(loss_var)])
            outs = []
            for fv in fetch_vars:
                if id(fv) in env:
                    outs.append(env[id(fv)])
                else:
                    outs.append(fv.concrete._value)
            return loss, tuple(outs), buffers

        step = build_train_step(loss_fn=program_loss_fn,
                                optimizer=optimizer, params_meta=meta,
                                trainable=trainable, with_key=False,
                                in_shardings=dp_shardings,
                                sharding=sharding_cfg)
        step.cost_label = f'executor.train.p{program._fingerprint}'
        return step


def program_infer_fn(program, feed_names, fetch_vars):
    """Standalone pure inference function over a Program.

    Returns ``(fn, params)`` where ``fn(feed_vals, param_vals) -> list`` of
    fetch payloads and ``params`` is the ordered list of parameter
    Variables the function takes positionally. Used by save_inference_model
    to jax.export the fetch subgraph so a Predictor can run it in a fresh
    process with no Program rebuild. Shares _interpret_ops/_fetch_outs with
    Executor._compile, so the two execution paths cannot diverge.
    """
    ops = program.global_block.ops
    block = program.global_block
    feed_vars = [block.var(n) for n in feed_names]
    params = _program_params(program)

    def fn(feed_vals, param_vals):
        env = {}
        for v, val in zip(feed_vars, feed_vals):
            env[id(v)] = val
        for v, val in zip(params, param_vals):
            env[id(v)] = val
        env = _interpret_ops(ops, env)
        return _fetch_outs(fetch_vars, env)

    return fn, params

"""AOT executable caching + Predictor engine."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.inference import (AOTCompiledFunction, Predictor,
                                  enable_compilation_cache)


class TestAOTCompiledFunction:
    def test_trace_and_call(self):
        m = nn.Linear(4, 3)
        m.eval()
        w = m.weight.numpy()
        b = m.bias.numpy()

        def fn(x):
            import jax.numpy as jnp
            return jnp.tanh(x @ w + b)

        x = np.ones((2, 4), 'float32')
        aot = AOTCompiledFunction.trace(fn, x)
        out = aot(paddle.to_tensor(x))
        np.testing.assert_allclose(out.numpy(), np.tanh(x @ w + b),
                                   rtol=1e-5)
        assert aot.cost_analysis() is not None

    def test_serialize_roundtrip_skips_tracing(self, tmp_path):
        traces = []

        def fn(x):
            traces.append(1)
            return (x * 2.0).sum()

        x = np.arange(6, dtype='float32').reshape(2, 3)
        aot = AOTCompiledFunction.trace(fn, x)
        p = str(tmp_path / 'fn.aotx')
        aot.save(p)
        assert os.path.getsize(p) > 0
        n_traces = len(traces)
        loaded = AOTCompiledFunction.load(p)
        out = loaded(x)
        assert float(out.numpy()) == 30.0
        assert len(traces) == n_traces   # no retrace on load/run

    def test_backend_mismatch_raises(self, tmp_path):
        import pickle
        aot = AOTCompiledFunction.trace(lambda x: x + 1,
                                        np.ones(3, 'float32'))
        p = str(tmp_path / 'fn.aotx')
        aot.save(p)
        blob = pickle.load(open(p, 'rb'))
        blob['backend'] = 'gpu'
        pickle.dump(blob, open(p, 'wb'))
        with pytest.raises(RuntimeError, match="backend"):
            AOTCompiledFunction.load(p)


class TestPersistentCompilationCache:
    def test_cache_dir_populated(self, tmp_path):
        import jax
        cache = str(tmp_path / 'xla_cache')
        enable_compilation_cache(cache)
        try:
            @jax.jit
            def f(x):
                return (x ** 2 + x).sum()

            f(np.arange(1000, dtype='float32')).block_until_ready()
            entries = os.listdir(cache)
            assert entries, "persistent cache has no entries"
        finally:
            # hand the worker back WITHOUT a live persistent cache: an
            # initialized one keeps serving (and writing) entries for every
            # later test in this process, and an XLA:CPU executable loaded
            # back from it can fail at run time ("Function ... not found")
            from jax.experimental.compilation_cache import (
                compilation_cache as cc)
            jax.config.update('jax_compilation_cache_dir', None)
            jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
            jax.config.update('jax_persistent_cache_min_compile_time_secs',
                              1.0)
            cc.reset_cache()


class TestPredictor:
    def _export(self, dirname):
        import paddle_tpu.static as static
        paddle.enable_static()
        try:
            main = static.Program()
            startup = static.Program()
            with static.program_guard(main, startup):
                x = static.data('x', [None, 4], 'float32')
                lin = nn.Linear(4, 2)
                y = lin(x)
            exe = static.Executor()
            exe.run(startup)
            from paddle_tpu.static.io import save_inference_model
            save_inference_model(dirname, ['x'], [y], exe, main_program=main)
            ref_w = lin.weight.numpy().copy()
            ref_b = lin.bias.numpy().copy()
        finally:
            paddle.disable_static()
        return ref_w, ref_b

    def test_export_load_run_standalone(self, tmp_path):
        """Predictor runs from the model dir alone — no Program, no static
        mode, fresh-process semantics (symbolic batch dim re-specializes)."""
        d = str(tmp_path / 'model')
        ref_w, ref_b = self._export(d)
        pred = Predictor(d)
        assert pred.feed_names == ['x']
        x = np.random.default_rng(0).standard_normal(
            (3, 4)).astype('float32')
        out, = pred.run({'x': x})
        np.testing.assert_allclose(np.asarray(out), x @ ref_w + ref_b,
                                   rtol=1e-5)
        # a different batch size re-specializes the symbolic dim
        x2 = np.random.default_rng(1).standard_normal(
            (7, 4)).astype('float32')
        out2, = pred.run({'x': x2})
        np.testing.assert_allclose(np.asarray(out2),
                                   x2 @ ref_w + ref_b, rtol=1e-5)

    def test_missing_feed_raises(self, tmp_path):
        d = str(tmp_path / 'model')
        self._export(d)
        pred = Predictor(d)
        with pytest.raises(ValueError, match="missing feeds"):
            pred.run({})


class TestMultiFeedExport:
    def test_two_feeds_shared_batch_dim(self, tmp_path):
        """Feeds that interact (x + y) must export: dim-0 shares one
        'batch' symbol across feeds."""
        import paddle_tpu.static as static
        d = str(tmp_path / 'model2')
        paddle.enable_static()
        try:
            main, startup = static.Program(), static.Program()
            with static.program_guard(main, startup):
                x = static.data('x', [None, 4], 'float32')
                y = static.data('y', [None, 4], 'float32')
                z = (x + y) * 2.0
            exe = static.Executor()
            exe.run(startup)
            from paddle_tpu.static.io import save_inference_model
            save_inference_model(d, ['x', 'y'], [z], exe, main_program=main)
        finally:
            paddle.disable_static()
        pred = Predictor(d)
        a = np.ones((3, 4), 'float64')      # float64: run() must cast
        b = np.full((3, 4), 2.0)            # python-float list semantics
        out, = pred.run({'x': a, 'y': b})
        np.testing.assert_allclose(out, np.full((3, 4), 6.0, 'float32'))

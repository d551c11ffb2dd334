"""incubate.complex namespace, fluid.contrib utilities, real spawn."""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.static as static


class TestIncubateComplex:
    def test_namespace_ops(self):
        import paddle_tpu.incubate.complex as C
        a = paddle.to_tensor(np.array([1 + 2j, 3 - 1j], np.complex64))
        b = paddle.to_tensor(np.array([2 - 1j, 1 + 1j], np.complex64))
        out = C.elementwise_mul(a, b).numpy()
        np.testing.assert_allclose(
            out, np.array([1 + 2j, 3 - 1j]) * np.array([2 - 1j, 1 + 1j]),
            rtol=1e-6)
        m = paddle.to_tensor(
            np.array([[1 + 1j, 0], [0, 2 - 1j]], np.complex64))
        np.testing.assert_allclose(C.trace(m).numpy(), 3 + 0j, rtol=1e-6)
        mm = C.matmul(m, m).numpy()
        np.testing.assert_allclose(mm, m.numpy() @ m.numpy(), rtol=1e-6)


class TestContrib:
    def test_memory_usage_and_stats(self):
        from paddle_tpu.fluid import contrib
        paddle.enable_static()
        try:
            p = static.Program()
            with static.program_guard(p):
                x = static.data('x', [None, 4], 'float32')
                h = static.nn.fc(x, 8)
                y = static.nn.fc(h, 2)
            mb = contrib.memory_usage(p, batch_size=32)
            assert mb > 0
            rows = contrib.summary(p)
            total_params = sum(r[1] for r in rows)
            assert total_params == (4 * 8 + 8) + (8 * 2 + 2)
            uni, adj = contrib.op_freq_statistic(p)
            assert sum(uni.values()) == len(p.global_block.ops)
        finally:
            paddle.disable_static()

    def test_extend_with_decoupled_weight_decay(self):
        from paddle_tpu.fluid import contrib
        import paddle_tpu.optimizer as opt
        from paddle_tpu.core.tensor import Parameter
        SGDW = contrib.extend_with_decoupled_weight_decay(opt.SGD)
        p = Parameter(np.ones(3, np.float32))
        o = SGDW(learning_rate=0.1, parameters=[p], weight_decay=0.01)
        (p * p).sum().backward()
        o.step()
        expect = (1 - 0.1 * 2) * (1 - 0.1 * 0.01)
        np.testing.assert_allclose(p.numpy(), expect, rtol=1e-5)


def _rank_fn(scale):
    rank = int(os.environ.get('PADDLE_TRAINER_ID', '0'))
    return rank * scale


def _cli_env(*extra_path):
    """Subprocess env for script/module children: repo (and extras) on
    PYTHONPATH, CPU backend."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.pathsep.join(list(map(str, extra_path)) + [repo]
                           + ([os.environ['PYTHONPATH']]
                              if os.environ.get('PYTHONPATH') else []))
    return dict(os.environ, JAX_PLATFORMS='cpu',
                PYTHONPATH=path)


class TestSpawn:
    def test_inprocess_default(self):
        import paddle_tpu.distributed as dist
        ctx = dist.spawn(lambda: 41 + 1)
        assert ctx.join() == 42

    @pytest.mark.skipif(sys.platform == 'win32', reason='posix only')
    def test_multiprocess_real_ranks(self):
        import paddle_tpu.distributed as dist
        ctx = dist.spawn(_rank_fn, args=(10,), nprocs=2, backend='cpu')
        results = ctx.join()
        assert results == [0, 10]

    def test_multiprocess_error_propagates(self):
        import paddle_tpu.distributed as dist
        with pytest.raises(RuntimeError, match="spawn"):
            dist.spawn(_boom, nprocs=2, backend='cpu')

    @pytest.mark.skipif(sys.platform == 'win32', reason='posix only')
    def test_script_main_classes_roundtrip(self, tmp_path):
        # func AND a result class defined in a plain `python script.py`
        # __main__: the worker must preload the script to unpickle func,
        # and the parent must unpickle the '__spawn_main__'-module result
        script = tmp_path / "train_script.py"
        script.write_text(
            "import os, json\n"
            "import paddle_tpu.distributed as dist\n\n"
            "class Cfg:\n"
            "    def __init__(self, scale):\n"
            "        self.scale = scale\n\n"
            "def rank_fn(cfg):\n"
            "    r = int(os.environ.get('PADDLE_TRAINER_ID', '0'))\n"
            "    out = Cfg(r * cfg.scale)\n"
            "    return out\n\n"
            "if __name__ == '__main__':\n"
            "    ctx = dist.spawn(rank_fn, args=(Cfg(7),), nprocs=2,\n"
            "                     backend='cpu')\n"
            "    res = ctx.join()\n"
            "    print(json.dumps([c.scale for c in res]))\n")
        import subprocess as sp
        out = sp.run([sys.executable, str(script)], env=_cli_env(),
                     capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        import json
        assert json.loads(out.stdout.strip().splitlines()[-1]) == [0, 7]

    @pytest.mark.skipif(sys.platform == 'win32', reason='posix only')
    def test_module_main_spawn(self, tmp_path):
        # parent launched `python -m mytrain`: workers must resolve func
        # defined in that module-style __main__ (init_main_from_name)
        mod = tmp_path / "mytrain_mod.py"
        mod.write_text(
            "import os, json\n"
            "import paddle_tpu.distributed as dist\n\n"
            "def rank_fn(off):\n"
            "    return off + int(os.environ.get('PADDLE_TRAINER_ID',"
            " '0'))\n\n"
            "if __name__ == '__main__':\n"
            "    res = dist.spawn(rank_fn, args=(5,), nprocs=2,\n"
            "                     backend='cpu').join()\n"
            "    print(json.dumps(res))\n")
        import subprocess as sp
        out = sp.run([sys.executable, '-m', 'mytrain_mod'],
                     env=_cli_env(tmp_path),
                     capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        import json
        assert json.loads(out.stdout.strip().splitlines()[-1]) == [5, 6]


def _boom():
    raise ValueError("worker failure")


class TestLaunchCLI:
    @pytest.mark.skipif(sys.platform == 'win32', reason='posix only')
    def test_launch_module_two_ranks(self, tmp_path):
        # `python -m paddle_tpu.distributed.launch --nproc_per_node 2 s.py`
        # must run the script once per rank with the trainer env set
        script = tmp_path / "train_cli.py"
        script.write_text(
            "import os, json, pathlib\n"
            "rank = os.environ['PADDLE_TRAINER_ID']\n"
            "world = os.environ['PADDLE_TRAINERS_NUM']\n"
            "out = pathlib.Path(__file__).parent / ('rank_%s.json' % rank)\n"
            "out.write_text(json.dumps({'rank': rank, 'world': world}))\n")
        import subprocess as sp
        out = sp.run([sys.executable, '-m', 'paddle_tpu.distributed.launch',
                      '--nproc_per_node', '2', str(script)],
                     env=_cli_env(),
                     capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        import json
        recs = [json.loads((tmp_path / ('rank_%d.json' % r)).read_text())
                for r in range(2)]
        assert sorted(r['rank'] for r in recs) == ['0', '1']
        assert all(r['world'] == '2' for r in recs)

"""chip_smoke.py rehearsed on the CPU, and the refusals that keep a run
without a chip from looking like a result.

The rehearsals drive ``chip_smoke.run(size, rehearsal=True)`` — the
test-only entry: tiny sizes, kernels in interpret mode, no platform refusal
— in a fresh interpreter each (``--chips 4`` needs its own 4 virtual devices,
and a run turns on process-wide telemetry and the compile cache). Everything
else here is what replaced the deleted fallbacks' tests: without a TPU
``chip_smoke.py`` exits non-zero and prints no metric, a phase that raises fails the run, one helper places the compile cache, and an
unknown device has no roofline.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(
    layers=2, hidden=128, heads=4, ffn=256, vocab=512, max_pos=64,
    train=((16, 4, 12), (64, 4, 12)), lr=1e-3, fall=0.5,
    serve_len=16, serve_requests=9, serve_buckets=(1, 4),
    lm=dict(vocab=64, embed=32, num_heads=4, max_seq=64, max_batch=4,
            prompt_buckets=(8, 16)),
    lm_prompts=(3, 3, 9, 12), lm_new_tokens=4,
    kernel_shape=(2, 2, 128, 16),
    sharded=(16, 8, 12), sharded_kernels=(4, 4, 128, 16),
    hybrid=dict(
        config=dict(vocab_size=64, hidden_size=32, num_hidden_layers=5,
                    num_attention_heads=2, head_dim=16, intermediate_size=48,
                    moe_intermediate_size=16, num_experts=16,
                    num_experts_per_token=4, experts_held=(4, 8),
                    kv_lora_rank=8, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=16, recompute=True, kda_chunk=16,
                    moe_block=8),
        seq=64, rows=2, steps=12, lr=3e-3, fall=0.3),
)
_CHILD = ("import json, sys, chip_smoke; "
          "chip_smoke.run(json.loads(sys.argv[1]), chips=int(sys.argv[2]), "
          "rehearsal=True)")


def _run(argv, tmp_path, devices=1, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'jax_cache'),
               XLA_FLAGS='--xla_force_host_platform_device_count=%d'
                         % devices)
    return subprocess.run([sys.executable] + argv, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _phases(stdout):
    return [json.loads(line) for line in stdout.splitlines()
            if line.startswith('{"') and '"phase"' in line]


def test_rehearsal_one_chip(tmp_path):
    out = _run(['-c', _CHILD, json.dumps(TINY), '1'], tmp_path)
    assert out.returncode == 0, out.stderr[-3000:]
    by = {}
    for p in _phases(out.stdout):
        by.setdefault(p['phase'], []).append(p)
    assert by['start'][0]['compile_cache_dir'] == str(tmp_path / 'jax_cache')
    assert set(by['kernels'][0]['max_abs_err']) == {'causal', 'key_padding'}
    assert [t['seq'] for t in by['train']] == [16, 64]
    for t in by['train']:
        assert t['compiles_after_first_step'] == 0
        assert len(t['losses']) == 12    # check_losses held them (rc 0)
        assert t['losses'][-1] < t['losses'][0] - TINY['fall']
    assert 0.9 < by['train'][0]['first_update_over_lr'] < 1.01
    assert by['serve'][0]['ok'] == TINY['serve_requests']
    assert by['serve'][0]['compiles_after_warmup'] == 0
    assert by['generate'][0]['tokens_equal_reference'] is True
    assert by['generate'][0]['compiles_after_warmup'] == 0
    assert 'NOT a real model' in by['generate'][0]['spec']
    hybrid = by['hybrid'][0]
    assert hybrid['compiles_after_first_step'] == 0
    assert hybrid['losses'][-1] < hybrid['losses'][0] - 0.3
    assert hybrid['counters']['moe.dropped'] == 0
    assert hybrid['counters']['moe.assignments'] == 4 * 2 * 64 * 4
    assert 'done' in by


def test_rehearsal_four_chips_runs_only_the_sharded_path(tmp_path):
    out = _run(['-c', _CHILD, json.dumps(TINY), '4'], tmp_path, devices=4)
    assert out.returncode == 0, out.stderr[-3000:]
    phases = _phases(out.stdout)
    assert [p['phase'] for p in phases] == [
        'start', 'partitioned_kernels', 'replicated_one_device', 'fsdp',
        'sharded_vs_replicated', 'done']
    assert phases[0]['device']['count'] == 4
    assert set(phases[1]['max_rel_diff_vs_one_device']) == {
        'flash', 'dropout_add_norm'}
    assert phases[3]['losses'][-1] < phases[3]['losses'][0] - TINY['fall']
    assert phases[4]['param_bytes_ratio'] < 0.3
    assert phases[4]['max_rel_loss_diff_first_steps'] < 2e-2


def test_chip_smoke_refuses_without_a_tpu(tmp_path):
    out = _run(['chip_smoke.py'], tmp_path)
    assert out.returncode != 0
    assert 'needs a TPU' in out.stderr
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        'ok': False,
        'device': {'platform': 'cpu', 'kind': 'cpu', 'count': 1}}
    assert not _phases(out.stdout)          # no phase ran, nothing measured


def test_a_phase_that_raises_fails_the_run(monkeypatch, capsys):
    def boom(*a, **k):
        raise RuntimeError('the chip said no')
    monkeypatch.setattr(chip_smoke, 'run', boom)
    assert chip_smoke.main([]) == 1
    captured = capsys.readouterr()
    assert json.loads(captured.out.strip().splitlines()[-1])['ok'] is False
    assert 'the chip said no' in captured.err


class TestCompileCachePlacement:
    """One helper decides where JAX's persistent cache lives."""

    @pytest.fixture
    def config_updates(self, monkeypatch):
        from jax.experimental.compilation_cache import compilation_cache as cc
        from paddle_tpu import inference
        updates = {}
        monkeypatch.setattr(jax.config, 'update',
                            lambda k, v: updates.__setitem__(k, v))
        monkeypatch.setattr(cc, 'reset_cache', lambda: None)
        monkeypatch.setattr(inference, '_env_override_said', [False])
        monkeypatch.setattr(os, 'makedirs', lambda *a, **k: None)
        return updates

    def test_env_places_it_and_no_code_sets_another(self, monkeypatch,
                                                    config_updates):
        from paddle_tpu import inference
        monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/placed/outside')
        assert inference.enable_compilation_cache() == '/placed/outside'
        with pytest.warns(UserWarning, match='left alone'):
            assert inference.enable_compilation_cache('/mine') == \
                '/placed/outside'
        assert 'jax_compilation_cache_dir' not in config_updates

    def test_unset_it_is_one_fixed_path_in_the_checkout(self, monkeypatch,
                                                        config_updates):
        from paddle_tpu import inference
        monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
        want = os.path.join(REPO, '.jax_cache')
        assert inference.DEFAULT_CACHE_DIR == want
        assert inference.enable_compilation_cache() == want
        assert config_updates['jax_compilation_cache_dir'] == want

    def test_no_temporary_name_in_any_cache_path(self):
        import re
        for rel in ('chip_smoke.py', 'paddle_tpu/inference/__init__.py'):
            src = open(os.path.join(REPO, rel)).read()
            for m in re.finditer(r'jax_compilation_cache_dir', src):
                near = src[max(0, m.start() - 400):m.end() + 200]
                assert not re.search(r'mkdtemp|getpid|time\.time', near), rel
            assert 'PADDLE_TPU_XLA_CACHE' not in src


class TestDevicePeaks:
    def test_v5e_peaks_are_keyed_by_device_kind(self):
        from paddle_tpu.observability import costs
        assert costs.device_peaks('TPU v5 lite') == (197e12, 819e9)
        r = costs.roofline(197e12, 819e9, 'TPU v5 lite')
        assert r['est_ms'] == 1000.0 and r['bound'] == 'compute'

    def test_unknown_device_kind_is_an_error(self):
        from paddle_tpu.observability import costs
        with pytest.raises(KeyError, match='no published peaks'):
            costs.device_peaks('TPU v4')
        with pytest.raises(KeyError, match='no published peaks'):
            costs.roofline(1e9, 1e9)        # this process: the CPU


def test_tpu_place_does_not_wrap_around():
    import paddle_tpu as paddle
    n = jax.device_count()
    assert paddle.TPUPlace(n - 1).jax_device() == jax.devices()[n - 1]
    with pytest.raises(ValueError, match='device'):
        paddle.TPUPlace(n).jax_device()

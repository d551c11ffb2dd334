"""paddle_tpu.analysis: AST lint rules (GL) + Program verifier (GV).

Acceptance anchor: >= 10 distinct rule IDs fire on seeded fixtures
(>= 5 AST rules, >= 5 verifier checks), each with file:line findings and
JSON reporter output; Executor.run(verify=True) turns structural errors
into actionable ProgramVerificationError before compilation.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.static as static
from paddle_tpu import analysis
from paddle_tpu.analysis import (Finding, ProgramVerificationError,
                                 lint_paths, lint_source, render_json,
                                 verify_program)
from paddle_tpu.analysis.config import (Config, load_config, parse_toml_min)
from paddle_tpu.analysis.testing import KINDS, malform, well_formed_program

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Engine 1: AST rules on seeded fixtures
# ---------------------------------------------------------------------------

# one fixture snippet per rule: (rule id, source, substring of the flagged
# line) — the line number assertion pins findings to real locations
AST_FIXTURES = {
    'GL001': ("import jax, numpy as np\n"
              "@jax.jit\n"
              "def f(x):\n"
              "    return np.asarray(x)\n", "np.asarray"),
    'GL002': ("import jax\n"
              "@jax.jit\n"
              "def f(x):\n"
              "    return float(x)\n", "float(x)"),
    'GL003': ("import jax\n"
              "@jax.jit\n"
              "def f(x):\n"
              "    return jax.device_get(x)\n", "jax.device_get"),
    'GL004': ("import jax\n"
              "@jax.jit\n"
              "def f(x, opts=[]):\n"
              "    return x\n", "opts=[]"),
    'GL005': ("import jax\n"
              "def g(x):\n"
              "    return x\n"
              "fast = jax.jit(g)\n"
              "def use():\n"
              "    return fast([1, 2])\n", "fast([1, 2])"),
    'GL006': ("import jax\n"
              "@jax.jit\n"
              "def f(x):\n"
              "    if x:\n"
              "        return x\n"
              "    return x\n", "if x:"),
    'GL007': ("import jax, time\n"
              "@jax.jit\n"
              "def f(x):\n"
              "    return x + time.time()\n", "time.time"),
    'GL008': ("import jax\n"
              "import numpy as np\n"
              "@jax.jit\n"
              "def f(x):\n"
              "    return x + np.random.rand(3)\n", "np.random.rand"),
    'GL009': ("import jax\n"
              "def f(x):\n"
              "    jax.debug.print('x={}', x)\n"
              "    return x\n", "jax.debug.print"),
    'GL010': ("def save(path, blob):\n"
              "    with open(path, 'wb') as f:\n"
              "        f.write(blob)\n", "open(path, 'wb')"),
    'GL011': ("import time\n"
              "def run_step(fn):\n"
              "    t0 = time.perf_counter()\n"
              "    fn()\n"
              "    return time.perf_counter() - t0\n", "time.perf_counter"),
    'GL012': ("import queue\n"
              "def consume():\n"
              "    q = queue.Queue()\n"
              "    return q.get()\n", "q.get()"),
    'GL013': ("import jax\n"
              "import numpy as np\n"
              "def model(x):\n"
              "    return x * 2\n"
              "predict = jax.jit(model)\n"
              "def serve(batch):\n"
              "    n = len(batch)\n"
              "    arr = np.zeros((n, 8), np.float32)\n"
              "    return predict(arr)\n", "predict(arr)"),
    'GL014': ("def train_step(loss, step_ms):\n"
              "    print(f'step loss {loss:.4f} in {step_ms:.1f} ms')\n",
              "print(f'step loss"),
    'GL015': ("import jax\n"
              "def train_step(params, opt_state, batch):\n"
              "    return params, opt_state\n"
              "step = jax.jit(train_step)\n", "jax.jit(train_step)"),
    'GL016': ("import jax\n"
              "def place(params):\n"
              "    return jax.device_put(params)\n",
              "jax.device_put(params)"),
    'GL017': ("import jax\n"
              "@jax.jit\n"
              "def f(x):\n"
              "    mask = x > 0\n"
              "    return x[mask].sum()\n", "x[mask]"),
    'GL018': ("import jax\n"
              "def trace_step(fn):\n"
              "    jax.profiler.start_trace('/tmp/x')\n"
              "    fn()\n"
              "    jax.profiler.stop_trace()\n", "start_trace"),
    'GL019': ("def dispatch_all(replicas, req):\n"
              "    for r in replicas:\n"
              "        try:\n"
              "            return r.submit(req)\n"
              "        except Exception:\n"
              "            pass\n", "except Exception"),
    'GL020': ("_LOG = []\n"
              "def poll(events):\n"
              "    for e in events:\n"
              "        _LOG.append(e)\n", "_LOG.append(e)"),
    'GL022': ("import time\n"
              "def wait_ready(client):\n"
              "    while not client.ready():\n"
              "        time.sleep(0.5)\n", "time.sleep(0.5)"),
}


@pytest.mark.parametrize('rule_id', sorted(AST_FIXTURES))
def test_ast_rule_fires_with_location(rule_id, tmp_path):
    source, needle = AST_FIXTURES[rule_id]
    # GL010 is scoped to checkpoint-path modules: use a matching filename
    name = 'framework.py' if rule_id == 'GL010' else 'fix.py'
    path = tmp_path / name
    path.write_text(source)
    findings, n = lint_paths([str(path)], scan_root=str(tmp_path))
    assert n == 1
    hits = [f for f in findings if f.rule == rule_id]
    assert hits, f"{rule_id} did not fire; got {[f.rule for f in findings]}"
    f = hits[0]
    assert f.path == str(path) and f.line >= 1
    # the finding points at the line containing the anti-pattern
    assert needle in source.splitlines()[f.line - 1]
    assert f.source == 'ast' and f.severity == 'error'


def test_traced_scope_excludes_host_code():
    # the same host-sync calls OUTSIDE traced code are legal
    src = ("import numpy as np\n"
           "def loader(batch):\n"
           "    return np.asarray(batch)\n")
    findings = lint_source('loader.py', src)
    assert [f for f in findings if f.rule == 'GL001'] == []


def test_local_traced_value_is_tainted():
    # GL002 must catch casts on LOCALS derived from traced params, not just
    # the params themselves (the float(loss) pattern)
    src = ("import jax\n"
           "import jax.numpy as jnp\n"
           "@jax.jit\n"
           "def step(params, batch):\n"
           "    logits = batch @ params\n"
           "    loss = jnp.mean(logits)\n"
           "    return float(loss)\n")
    findings = lint_source('step.py', src)
    assert any(f.rule == 'GL002' and f.line == 7 for f in findings)


def test_is_none_flag_is_static_not_tainted():
    # `w is not None` is a host bool — branching on it is the sanctioned
    # static-specialization idiom, not GL006
    src = ("import jax\n"
           "@jax.jit\n"
           "def norm(x, w):\n"
           "    has_w = w is not None\n"
           "    if has_w:\n"
           "        x = x * w\n"
           "    return x\n")
    findings = lint_source('norm.py', src)
    assert [f for f in findings if f.rule == 'GL006'] == []


def test_transitive_traced_helper_is_flagged():
    src = ("import jax\n"
           "def helper(v):\n"
           "    return float(v)\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return helper(x)\n")
    findings = lint_source('helper.py', src)
    assert any(f.rule == 'GL002' and f.line == 3 for f in findings)


def test_host_callback_is_sanctioned_escape():
    src = ("import jax\n"
           "import numpy as np\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    def report(v):\n"
           "        print(np.asarray(v))\n"
           "    jax.debug.callback(report, x)\n"
           "    return x\n")
    findings = lint_source('cb.py', src)
    assert [f for f in findings if f.rule == 'GL001'] == []


def test_inline_waiver_suppresses_and_records_reason(tmp_path):
    p = tmp_path / 'fix.py'
    p.write_text("import jax, time\n"
                 "@jax.jit\n"
                 "def f(x):\n"
                 "    # graftlint: disable=GL007 — trace-time stamp wanted\n"
                 "    return x + time.time()\n")
    findings, _ = lint_paths([str(p)])
    hits = [f for f in findings if f.rule == 'GL007']
    assert len(hits) == 1 and hits[0].waived
    # waived findings don't count as active
    from paddle_tpu.analysis.finding import active
    assert active(hits) == []


def test_multiline_waiver_comment_block(tmp_path):
    p = tmp_path / 'fix.py'
    p.write_text("import jax, time\n"
                 "@jax.jit\n"
                 "def f(x):\n"
                 "    # graftlint: disable=GL007 — a justification that\n"
                 "    # wraps over two comment lines\n"
                 "    return x + time.time()\n")
    findings, _ = lint_paths([str(p)])
    assert all(f.waived for f in findings if f.rule == 'GL007')


def test_waiver_typos_do_not_blanket_waive(tmp_path):
    # 'disabled' is not a waiver; 'disable=<garbage>' waives nothing;
    # lowercase ids are normalized, not silently widened
    src = ("import jax, time\n@jax.jit\ndef f(x):\n"
           "    {}\n    return x + time.time()\n")
    for comment, waived in [
            ('# graftlint: disabled for now', False),
            ('# graftlint: disable=GL0x7', False),
            ('# graftlint: disable=gl007 — ok lowercase', True),
            ('# graftlint: disable', True)]:
        p = tmp_path / 'fix.py'
        p.write_text(src.format(comment))
        findings, _ = lint_paths([str(p)])
        hits = [f for f in findings if f.rule == 'GL007']
        assert len(hits) == 1 and hits[0].waived is waived, comment


def test_gl010_scope_without_config(tmp_path):
    # GL010's checkpoint scope must survive config-less runs: the scope
    # root defaults to the parent of the path argument
    pkg = tmp_path / 'paddle_tpu' / 'hapi'
    pkg.mkdir(parents=True)
    (pkg / 'model.py').write_text(
        "def save(p):\n    with open(p, 'wb') as f:\n        f.write(b'x')\n")
    findings, _ = lint_paths([str(tmp_path / 'paddle_tpu')])
    assert any(f.rule == 'GL010' for f in findings)


TIMING_SRC = ("import time\n"
              "def f():\n"
              "    return time.perf_counter()\n")


def test_gl011_exempts_tests_tools_bench_and_observability(tmp_path):
    # tests/tools/bench harnesses and the telemetry package itself may read
    # raw clocks; library code may not
    for sub in ('tests', 'tools', 'paddle_tpu/observability'):
        d = tmp_path / sub
        d.mkdir(parents=True, exist_ok=True)
        (d / 'mod.py').write_text(TIMING_SRC)
        findings, _ = lint_paths([str(d / 'mod.py')],
                                 scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL011'] == [], sub
    (tmp_path / 'bench_thing.py').write_text(TIMING_SRC)
    findings, _ = lint_paths([str(tmp_path / 'bench_thing.py')],
                             scan_root=str(tmp_path))
    assert [f for f in findings if f.rule == 'GL011'] == []
    lib = tmp_path / 'paddle_tpu'
    (lib / 'mod.py').write_text(TIMING_SRC)
    findings, _ = lint_paths([str(lib / 'mod.py')],
                             scan_root=str(tmp_path))
    hits = [f for f in findings if f.rule == 'GL011']
    assert len(hits) == 1 and hits[0].line == 3
    assert 'observability.timer' in hits[0].message


def test_gl011_allows_monotonic_deadlines(tmp_path):
    # timeout/deadline math is not duration measurement
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'deadline.py').write_text(
        "import time\n"
        "def wait(timeout):\n"
        "    deadline = time.monotonic() + timeout\n"
        "    return deadline\n")
    findings, _ = lint_paths([str(lib / 'deadline.py')],
                             scan_root=str(tmp_path))
    assert [f for f in findings if f.rule == 'GL011'] == []


_WAIT_SRC = ("import queue, threading, subprocess\n"
             "def pipeline():\n"
             "    q = queue.Queue()\n"
             "    q.get()\n"                          # flagged
             "    q.get(timeout=1)\n"                 # bounded: fine
             "    q.get_nowait()\n"                   # non-blocking: fine
             "    threads = [threading.Thread(target=print)\n"
             "               for _ in range(2)]\n"
             "    for t in threads:\n"
             "        t.join()\n"                     # flagged (container)
             "    p = subprocess.Popen(['ls'])\n"
             "    p.wait()\n"                         # flagged
             "    p.wait(5)\n")                       # bounded: fine


def test_gl012_flags_only_unbounded_waits(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'pipe.py').write_text(_WAIT_SRC)
    findings, _ = lint_paths([str(lib / 'pipe.py')],
                             scan_root=str(tmp_path))
    hits = sorted(f.line for f in findings if f.rule == 'GL012')
    lines = _WAIT_SRC.splitlines()
    assert len(hits) == 3, [(f.rule, f.line) for f in findings]
    assert 'q.get()' in lines[hits[0] - 1]
    assert 't.join()' in lines[hits[1] - 1]
    assert 'p.wait()' in lines[hits[2] - 1]
    msg = [f for f in findings if f.rule == 'GL012'][0].message
    assert 'watchdog' in msg     # fix-it points at the bounded helpers


def test_gl012_exempts_tests_tools_and_watchdog(tmp_path):
    # harnesses and the watchdog module itself may use raw waits
    for rel in ('tests/mod.py', 'tools/mod.py',
                'paddle_tpu/resilience/watchdog.py'):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(_WAIT_SRC)
        findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL012'] == [], rel
    # ...but sibling resilience modules may not
    p = tmp_path / 'paddle_tpu/resilience/other.py'
    p.write_text(_WAIT_SRC)
    findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
    assert [f for f in findings if f.rule == 'GL012'] != []


_DYNSHAPE_SRC = (
    "import jax\n"
    "import numpy as np\n"
    "def model(x):\n"
    "    return x * 2\n"
    "predict = jax.jit(model)\n"
    "def serve_ctor(batch):\n"
    "    n = len(batch)\n"
    "    arr = np.zeros((n, 8), np.float32)\n"
    "    return predict(arr)\n"                       # flagged (dyn ctor)
    "def serve_slice(batch, buf):\n"
    "    return predict(buf[:len(batch)])\n"          # flagged (dyn slice)
    "def serve_scalar(batch, arr):\n"
    "    return predict(arr, len(batch))\n")          # scalar len(): fine


def test_gl013_flags_dynamic_shapes_not_scalars(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'serve.py').write_text(_DYNSHAPE_SRC)
    findings, _ = lint_paths([str(lib / 'serve.py')],
                             scan_root=str(tmp_path))
    hits = sorted(f.line for f in findings if f.rule == 'GL013')
    lines = _DYNSHAPE_SRC.splitlines()
    assert len(hits) == 2, [(f.rule, f.line) for f in findings]
    assert 'predict(arr)' in lines[hits[0] - 1]
    assert 'predict(buf[:len(batch)])' in lines[hits[1] - 1]
    msg = [f for f in findings if f.rule == 'GL013'][0].message
    # fix-it points at the serving bucketing helpers
    assert 'serving.bucketing' in msg and 'pad_to_bucket' in msg


def test_gl013_bucketed_code_is_sanctioned(tmp_path):
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from paddle_tpu.serving.bucketing import (pad_to_bucket,\n"
        "    select_bucket, stack_examples)\n"
        "def model(x):\n"
        "    return x * 2\n"
        "predict = jax.jit(model)\n"
        "def serve(batch):\n"
        "    b = select_bucket(len(batch), (1, 2, 4))\n"
        "    arr = stack_examples(batch, b)\n"
        "    return predict(arr)\n"
        "def serve2(batch):\n"
        "    padded = pad_to_bucket(np.stack(batch), 4)\n"
        "    return predict(padded)\n")
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'bucketed.py').write_text(src)
    findings, _ = lint_paths([str(lib / 'bucketed.py')],
                             scan_root=str(tmp_path))
    assert [f for f in findings if f.rule == 'GL013'] == []


def test_gl013_exempts_tests_and_tools(tmp_path):
    for rel in ('tests/mod.py', 'tools/mod.py', 'bench_load.py'):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(_DYNSHAPE_SRC)
        findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL013'] == [], rel


_EMIT_SRC = (
    "import logging\n"
    "logger = logging.getLogger(__name__)\n"
    "def report(loss, qps, epoch):\n"
    "    print(f'loss {loss:.4f}')\n"                   # flagged (f-string)
    "    logger.info('qps %.2f', qps)\n"                # flagged (%-format)
    "    print('epoch', epoch)\n"                       # narrative: fine
    "    print('done: {} items'.format(epoch))\n")      # no float spec: fine


def test_gl014_flags_metrics_shaped_emission_only(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'emit.py').write_text(_EMIT_SRC)
    findings, _ = lint_paths([str(lib / 'emit.py')],
                             scan_root=str(tmp_path))
    hits = sorted(f.line for f in findings if f.rule == 'GL014')
    assert hits == [4, 5], [(f.rule, f.line) for f in findings]
    msg = [f for f in findings if f.rule == 'GL014'][0].message
    # fix-it points at the telemetry spine
    assert 'observability.event' in msg


def test_gl014_exempts_tests_tools_bench_and_waiver(tmp_path):
    for rel in ('tests/mod.py', 'tools/mod.py', 'bench_load.py',
                'paddle_tpu/observability/exporter.py'):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(_EMIT_SRC)
        findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL014'] == [], rel
    # inline waiver honored
    lib = tmp_path / 'paddle_tpu'
    (lib / 'waived.py').write_text(
        "def report(loss):\n"
        "    # graftlint: disable=GL014 — user-facing verbose output\n"
        "    print(f'loss {loss:.4f}')\n")
    findings, _ = lint_paths([str(lib / 'waived.py')],
                             scan_root=str(tmp_path))
    live = [f for f in findings
            if f.rule == 'GL014' and not getattr(f, 'waived', False)]
    assert live == []


def test_unresolvable_fetch_does_not_flood_gv006():
    prog, _final = well_formed_program(seed=9)
    fs = verify_program(prog, fetch_list=['typo_name'])
    assert {f.rule for f in fs if f.severity == 'error'} == {'GV008'}
    assert [f for f in fs if f.rule == 'GV006'] == []


def test_toml_config_waiver_and_exclude(tmp_path):
    (tmp_path / 'graftlint.toml').write_text(
        '[graftlint]\n'
        'exclude = ["skipme/*"]\n'
        '[[graftlint.waiver]]\n'
        'rule = "GL007"\n'
        'path = "timed.py"\n'
        'reason = "benchmark stub"\n')
    skip = tmp_path / 'skipme'
    skip.mkdir()
    (skip / 'bad.py').write_text("import jax, time\n@jax.jit\n"
                                 "def f(x):\n    return x + time.time()\n")
    (tmp_path / 'timed.py').write_text("import jax, time\n@jax.jit\n"
                                       "def f(x):\n"
                                       "    return x + time.time()\n")
    cfg = load_config(str(tmp_path / 'graftlint.toml'))
    findings, n = lint_paths([str(tmp_path)], config=cfg)
    assert n == 1   # skipme/bad.py never scanned
    hits = [f for f in findings if f.rule == 'GL007']
    assert len(hits) == 1 and hits[0].waived
    assert hits[0].waive_reason == 'benchmark stub'


def test_toml_waiver_requires_reason(tmp_path):
    from paddle_tpu.analysis.config import ConfigError
    (tmp_path / 'graftlint.toml').write_text(
        '[[graftlint.waiver]]\nrule = "GL001"\npath = "x.py"\n')
    with pytest.raises(ConfigError):
        load_config(str(tmp_path / 'graftlint.toml'))


def test_parse_toml_min_subset():
    data = parse_toml_min('# c\n[a]\nx = "s"  # trailing\n'
                          'y = ["p", "q"]\nz = true\n'
                          '[[a.w]]\nr = "1"\n[[a.w]]\nr = "2"\n')
    assert data == {'a': {'x': 's', 'y': ['p', 'q'], 'z': True,
                          'w': [{'r': '1'}, {'r': '2'}]}}


# ---------------------------------------------------------------------------
# Engine 2: verifier on seeded malformed Programs
# ---------------------------------------------------------------------------

ERROR_KINDS = ['dangling_input', 'duplicate_var', 'dtype_mismatch',
               'shape_mismatch', 'undeclared_output', 'bad_fetch']
WARNING_KINDS = ['dead_op', 'unused_var']


def _run_malform(kind, seed):
    res = malform(kind, seed=seed)
    if kind == 'bad_fetch':
        prog, fetch, expect = res
        return verify_program(prog, fetch_list=fetch), expect
    prog, expect = res
    return verify_program(prog), expect


@pytest.mark.parametrize('kind', ERROR_KINDS)
@pytest.mark.parametrize('seed', [0, 7])
def test_verifier_error_kinds_fire_exactly(kind, seed):
    findings, expect = _run_malform(kind, seed)
    errs = [f for f in findings if f.severity == 'error']
    assert {f.rule for f in errs} == {expect}, \
        f"{kind}: expected only {expect}, got {[f.rule for f in errs]}"
    # findings are op-indexed and actionable
    assert all(f.source == 'ir' and f.path == '<program>' for f in errs)
    assert any('block 0' in f.message or 'fetch target' in f.message
               for f in errs)


@pytest.mark.parametrize('kind', WARNING_KINDS)
@pytest.mark.parametrize('seed', [0, 7])
def test_verifier_warning_kinds_fire_exactly(kind, seed):
    findings, expect = _run_malform(kind, seed)
    assert {f.rule for f in findings} == {expect}
    assert all(f.severity == 'warning' for f in findings)


def test_well_formed_program_verifies_clean():
    prog, final = well_formed_program(seed=5)
    assert verify_program(prog, fetch_list=[final]) == []
    assert prog.verify(fetch_list=[final]) == []


_UNDONATED_SRC = (
    "import jax\n"
    "import functools\n"
    "def train_step(params, opt_state, batch):\n"
    "    return params, opt_state\n"
    "step = jax.jit(train_step)\n"                            # flagged
    "donated = jax.jit(train_step, donate_argnums=(0, 1))\n"  # donated: fine
    "@jax.jit\n"
    "def update_step(params, opt_state):\n"                   # flagged
    "    return params, opt_state\n"
    "@functools.partial(jax.jit, donate_argnums=(0,))\n"
    "def third_step(params, opt_state):\n"                    # donated: fine
    "    return params, opt_state\n"
    "def eval_step(params, opt_state):\n"
    "    return params\n"
    "ev = jax.jit(eval_step)\n"                               # name-exempt
    "def forward(params, batch):\n"
    "    return params\n"
    "fw = jax.jit(forward)\n"                 # no opt-state pytree: fine
    "def scan_step(params, opt_state):\n"
    "    return params, opt_state\n"
    "ps = functools.partial(jax.jit, static_argnums=())(scan_step)\n")
    # ^ flagged: the partial(jax.jit, ...) wrapper spelling


def test_gl015_flags_undonated_train_steps(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'steps.py').write_text(_UNDONATED_SRC)
    findings, _ = lint_paths([str(lib / 'steps.py')],
                             scan_root=str(tmp_path))
    hits = sorted(f.line for f in findings if f.rule == 'GL015')
    lines = _UNDONATED_SRC.splitlines()
    assert len(hits) == 3, [(f.rule, f.line) for f in findings]
    assert 'jax.jit(train_step)' in lines[hits[0] - 1]
    assert '@jax.jit' in lines[hits[1] - 1]
    assert 'functools.partial(jax.jit' in lines[hits[2] - 1]
    msg = [f for f in findings if f.rule == 'GL015'][0].message
    # the fix-it points at the unified step builder
    assert 'engine.build_train_step' in msg and 'donate_argnums' in msg


def test_gl015_exempts_engine_tests_tools(tmp_path):
    # the engine package is the sanctioned builder (donation decided at
    # runtime behind the backend gate); harnesses measure, they don't ship
    for rel in ('paddle_tpu/engine/builder.py', 'tests/mod.py',
                'tools/mod.py', 'bench_x.py'):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(_UNDONATED_SRC)
        findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL015'] == [], rel
    # ...but sibling library packages may not roll their own
    p = tmp_path / 'paddle_tpu/kernels/steps.py'
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(_UNDONATED_SRC)
    findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
    assert [f for f in findings if f.rule == 'GL015'] != []


_DEVICE_PUT_SRC = (
    "import jax\n"
    "from jax.sharding import NamedSharding, PartitionSpec as P\n"
    "def replicate_all(params):\n"
    "    return jax.device_put(params)\n"                  # flagged
    "def pin_one(opt_state):\n"
    "    return jax.device_put(opt_state, jax.devices()[0])\n"  # flagged
    "def upload(state, mesh):\n"
    "    sh = NamedSharding(mesh, P('data'))\n"
    "    return jax.device_put(state, sh)\n"               # sanctioned
    "def upload_batch(x):\n"
    "    return jax.device_put(x)\n")                      # not a pytree


def test_gl016_flags_unsharded_param_device_put(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'place.py').write_text(_DEVICE_PUT_SRC)
    findings, _ = lint_paths([str(lib / 'place.py')],
                             scan_root=str(tmp_path))
    hits = sorted(f.line for f in findings if f.rule == 'GL016')
    lines = _DEVICE_PUT_SRC.splitlines()
    assert len(hits) == 2, [(f.rule, f.line) for f in findings]
    assert 'jax.device_put(params)' in lines[hits[0] - 1]
    assert 'jax.devices()[0]' in lines[hits[1] - 1]
    msg = [f for f in findings if f.rule == 'GL016'][0].message
    # fix-it points at the sharding surface
    assert 'shard_tensor' in msg and 'fsdp_pspecs' in msg
    assert 'build_train_step' in msg


def test_gl016_exempts_harnesses(tmp_path):
    for rel in ('tests/mod.py', 'tools/mod.py', 'bench_x.py'):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(_DEVICE_PUT_SRC)
        findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL016'] == [], rel


_MASK_INDEX_SRC = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "@jax.jit\n"
    "def inline_mask(x):\n"
    "    return x[x > 0]\n"                                 # flagged
    "@jax.jit\n"
    "def named_mask(x, lo):\n"
    "    keep = x > lo\n"
    "    return x[keep]\n"                                  # flagged
    "@jax.jit\n"
    "def dyn_nonzero(x):\n"
    "    return jnp.nonzero(x)\n"                           # flagged
    "@jax.jit\n"
    "def one_arg_where(x):\n"
    "    return jnp.where(x > 0)\n"                         # flagged
    "@jax.jit\n"
    "def sized_nonzero(x):\n"
    "    return jnp.nonzero(x, size=8)\n"                   # size= pins shape
    "@jax.jit\n"
    "def three_arg_where(x):\n"
    "    return jnp.where(x > 0, x, 0.0)\n"                 # in-place select
    "@jax.jit\n"
    "def page_gather(cache, block_tables):\n"
    "    return cache[block_tables]\n"                      # fixed-shape gather
    "@jax.jit\n"
    "def where_gather(x, i, j):\n"
    "    return x[jnp.where(x > 0, i, j)]\n"   # the fix-it's OWN pattern
    "@jax.jit\n"
    "def where_gather_named(x, i, j):\n"
    "    idx = jnp.where(x > 0, i, j)\n"
    "    return x[idx]\n"                      # same, via a name
    "def host_filter(x):\n"
    "    return x[x > 0]\n")                                # not traced


def test_gl017_flags_mask_indexing_and_nonzero_in_traced_code(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'masks.py').write_text(_MASK_INDEX_SRC)
    findings, _ = lint_paths([str(lib / 'masks.py')],
                             scan_root=str(tmp_path))
    hits = sorted(f.line for f in findings if f.rule == 'GL017')
    lines = _MASK_INDEX_SRC.splitlines()
    assert len(hits) == 4, [(f.rule, f.line) for f in findings]
    assert 'x[x > 0]' in lines[hits[0] - 1]
    assert 'x[keep]' in lines[hits[1] - 1]
    assert 'jnp.nonzero(x)' in lines[hits[2] - 1]
    assert 'jnp.where(x > 0)' in lines[hits[3] - 1]
    msg = [f for f in findings if f.rule == 'GL017'][0].message
    # fix-it points at the fixed-shape gather / page-index pattern
    assert 'paged_kv' in msg and 'jnp.where' in msg


def test_gl017_exempts_harnesses_and_host_code(tmp_path):
    for rel in ('tests/mod.py', 'tools/mod.py', 'bench_x.py'):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(_MASK_INDEX_SRC)
        findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL017'] == [], rel
    # the same mask indexing outside any traced function never fires
    host_only = ("import numpy as np\n"
                 "def pick(x):\n"
                 "    mask = x > 0\n"
                 "    return x[mask]\n")
    p = tmp_path / 'lib.py'
    p.write_text(host_only)
    findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
    assert [f for f in findings if f.rule == 'GL017'] == []


def test_gl017_inline_waiver(tmp_path):
    src = ("import jax\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    # graftlint: disable=GL017 — eager-only debug helper\n"
           "    return x[x > 0]\n")
    p = tmp_path / 'lib.py'
    p.write_text(src)
    findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
    hits = [f for f in findings if f.rule == 'GL017']
    assert len(hits) == 1 and hits[0].waived
    from paddle_tpu.analysis.finding import active
    assert active(hits) == []


_PROFILER_SRC = (
    "import jax\n"
    "from paddle_tpu import observability\n"
    "def leaky_trace(fn):\n"
    "    jax.profiler.start_trace('/tmp/x')\n"            # flagged: stop not
    "    fn()\n"                                          # in a finally
    "    jax.profiler.stop_trace()\n"
    "def owned_trace(fn):\n"
    "    jax.profiler.start_trace('/tmp/x')\n"            # sanctioned
    "    try:\n"
    "        fn()\n"
    "    finally:\n"
    "        jax.profiler.stop_trace()\n"
    "def serve_profiler():\n"
    "    jax.profiler.start_server(9999)\n"               # flagged always
    "def leaky_span(fn):\n"
    "    s = observability.span('step')\n"
    "    s.__enter__()\n"                                 # flagged: exit not
    "    fn()\n"                                          # exception-safe
    "    s.__exit__(None, None, None)\n"
    "def owned_span(fn):\n"
    "    s = observability.span('step')\n"
    "    s.__enter__()\n"                                 # sanctioned
    "    try:\n"
    "        fn()\n"
    "    finally:\n"
    "        s.__exit__(None, None, None)\n"
    "def with_span(fn):\n"
    "    with observability.span('step'):\n"              # the fix-it itself
    "        fn()\n")


def test_gl018_flags_unpaired_profiler_and_span_starts(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'prof.py').write_text(_PROFILER_SRC)
    findings, _ = lint_paths([str(lib / 'prof.py')],
                             scan_root=str(tmp_path))
    hits = sorted(f.line for f in findings if f.rule == 'GL018')
    lines = _PROFILER_SRC.splitlines()
    assert len(hits) == 3, [(f.rule, f.line) for f in findings]
    assert 'start_trace' in lines[hits[0] - 1]
    assert 'start_server' in lines[hits[1] - 1]
    assert '__enter__' in lines[hits[2] - 1]
    msg = [f for f in findings if f.rule == 'GL018'][0].message
    # fix-it points at the with-span spelling
    assert 'observability.span' in msg and 'finally' in msg


def test_gl018_exempts_harnesses_and_profiler_wrappers(tmp_path):
    for rel in ('tests/mod.py', 'tools/mod.py', 'bench_x.py',
                'paddle_tpu/observability/mod.py',
                'paddle_tpu/utils/profiler.py'):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(_PROFILER_SRC)
        findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL018'] == [], rel


def test_gl018_inline_waiver(tmp_path):
    src = ("import jax\n"
           "def trace_window(fn):\n"
           "    # graftlint: disable=GL018 — harness owns the stop\n"
           "    jax.profiler.start_trace('/tmp/x')\n"
           "    fn()\n")
    p = tmp_path / 'lib.py'
    p.write_text(src)
    findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
    hits = [f for f in findings if f.rule == 'GL018']
    assert len(hits) == 1 and hits[0].waived
    from paddle_tpu.analysis.finding import active
    assert active(hits) == []


_SWALLOW_SRC = (
    "from paddle_tpu import observability as obs\n"
    "def silent_failover(replicas, req):\n"
    "    for r in replicas:\n"
    "        try:\n"
    "            return r.submit(req)\n"
    "        except Exception:\n"                # flagged: nothing recorded
    "            pass\n"
    "def silent_bare(queue):\n"
    "    while True:\n"
    "        try:\n"
    "            queue.drain()\n"
    "        except:\n"                          # flagged: bare + continue
    "            continue\n"
    "def counted_failover(replicas, req):\n"
    "    for r in replicas:\n"
    "        try:\n"
    "            return r.submit(req)\n"
    "        except Exception:\n"                # sanctioned: emits a counter
    "            obs.counter('dispatch.failed').inc()\n"
    "def narrow_failover(replicas, req):\n"
    "    for r in replicas:\n"
    "        try:\n"
    "            return r.submit(req)\n"
    "        except ConnectionError:\n"          # sanctioned: narrow type
    "            pass\n"
    "def fallback_loop(items):\n"
    "    out = []\n"
    "    for it in items:\n"
    "        try:\n"
    "            v = it.decode()\n"
    "        except Exception:\n"                # sanctioned: fallback assign
    "            v = None\n"
    "        out.append(v)\n"
    "    return out\n"
    "def reraise_last(replicas, req):\n"
    "    for r in replicas:\n"
    "        try:\n"
    "            return r.submit(req)\n"
    "        except Exception:\n"                # sanctioned: re-raises
    "            raise\n"
    "def outside_loop(r, req):\n"
    "    try:\n"
    "        return r.submit(req)\n"
    "    except Exception:\n"                    # sanctioned: not in a loop
    "        pass\n")


def test_gl019_flags_silent_swallow_in_loops(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'disp.py').write_text(_SWALLOW_SRC)
    findings, _ = lint_paths([str(lib / 'disp.py')],
                             scan_root=str(tmp_path))
    hits = sorted(f.line for f in findings if f.rule == 'GL019')
    lines = _SWALLOW_SRC.splitlines()
    assert len(hits) == 2, [(f.rule, f.line) for f in findings]
    assert 'except Exception' in lines[hits[0] - 1]
    assert 'except:' in lines[hits[1] - 1]
    msg = [f for f in findings if f.rule == 'GL019'][0].message
    # fix-it points at the sanctioned retry helper
    assert 'resilience.retry' in msg


def test_gl019_exempts_harnesses(tmp_path):
    for rel in ('tests/mod.py', 'tools/mod.py', 'bench_x.py'):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(_SWALLOW_SRC)
        findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL019'] == [], rel


def test_gl019_inline_waiver(tmp_path):
    src = ("def sweep(items):\n"
           "    for it in items:\n"
           "        try:\n"
           "            it.close()\n"
           "        # graftlint: disable=GL019 — best-effort cleanup\n"
           "        except Exception:\n"
           "            pass\n")
    p = tmp_path / 'lib.py'
    p.write_text(src)
    findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
    hits = [f for f in findings if f.rule == 'GL019']
    assert len(hits) == 1 and hits[0].waived
    from paddle_tpu.analysis.finding import active
    assert active(hits) == []


# ---------------------------------------------------------------------------
# GL020: unbounded in-memory accumulation in library code
# ---------------------------------------------------------------------------

_ACCUM_SRC = (
    "_LOG = []\n"                                  # firing: module global
    "_REG = {}\n"                                  # firing: dict-of-lists
    "def poll(events):\n"
    "    for e in events:\n"
    "        _LOG.append(e)\n"
    "        _REG.setdefault(e, []).append(e)\n"
    "class Hook:\n"
    "    def __init__(self):\n"
    "        self._hist = []\n"
    "    def on_batch_end(self, logs):\n"          # firing: per-step hook
    "        self._hist.append(logs)\n")


def test_gl020_flags_unbounded_accumulation(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'acc.py').write_text(_ACCUM_SRC)
    findings, _ = lint_paths([str(lib / 'acc.py')],
                             scan_root=str(tmp_path))
    hits = sorted(f.line for f in findings if f.rule == 'GL020')
    lines = _ACCUM_SRC.splitlines()
    assert len(hits) == 3, [(f.rule, f.line) for f in findings]
    assert '_LOG.append' in lines[hits[0] - 1]
    # setdefault(...).append(...) is two grow tails on one container —
    # a single finding, not two
    assert '_REG.setdefault' in lines[hits[1] - 1]
    assert 'self._hist.append' in lines[hits[2] - 1]
    msg = [f for f in findings if f.rule == 'GL020'][0].message
    # fix-it points at the bounded spellings
    assert 'deque(maxlen' in msg


def test_gl020_sanctioned_bounded_spellings(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    src = (
        "import collections\n"
        "_RING = collections.deque(maxlen=10)\n"   # structural bound
        "_CAP = []\n"
        "class Hook:\n"
        "    def __init__(self):\n"
        "        self._hist = []\n"
        "    def on_batch_end(self, logs):\n"
        "        self._hist.append(logs)\n"
        "        self._hist[:] = self._hist[-100:]\n"  # slice rotation
        "class Builder:\n"
        "    def __init__(self, items):\n"
        "        self.rows = []\n"
        "        for it in items:\n"               # workload-proportional
        "            self.rows.append(it)\n"
        "def poll(events):\n"
        "    for e in events:\n"
        "        _RING.append(e)\n"
        "        if len(_CAP) < 100:\n"            # len() guard
        "            _CAP.append(e)\n")
    (lib / 'ok.py').write_text(src)
    findings, _ = lint_paths([str(lib / 'ok.py')],
                             scan_root=str(tmp_path))
    assert [f for f in findings if f.rule == 'GL020'] == [], \
        [(f.rule, f.line) for f in findings]


def test_gl020_exempts_harnesses_and_waiver(tmp_path):
    for rel in ('tests/mod.py', 'tools/mod.py', 'bench_x.py'):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(_ACCUM_SRC)
        findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL020'] == [], rel
    # inline waiver honored and excluded from the active set
    p = tmp_path / 'lib.py'
    p.write_text(
        "_LOG = []\n"
        "def poll(events):\n"
        "    for e in events:\n"
        "        _LOG.append(e)"
        "  # graftlint: disable=GL020 — drained by caller each round\n")
    findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
    hits = [f for f in findings if f.rule == 'GL020']
    assert len(hits) == 1 and hits[0].waived
    from paddle_tpu.analysis.finding import active
    assert active(hits) == []


# ---------------------------------------------------------------------------
# GL021: cache-blind serving warmup (raw jax.jit under a warmup class)
# ---------------------------------------------------------------------------

_CACHE_BLIND_SRC = (
    "import jax\n"
    "class Runner:\n"
    "    def __init__(self, spec, jit_compile=True):\n"
    "        self._prefill = jax.jit(spec.prefill)\n"          # flagged
    "        self._decode = jax.jit(spec.decode) if jit_compile \\\n"
    "            else spec.decode\n"                           # flagged
    "        self.helper = spec.helper\n"       # not a serving program
    "    def warmup(self):\n"
    "        return 0\n"
    "class NotARunner:\n"                       # no warmup(): out of shape
    "    def __init__(self, spec):\n"
    "        self._prefill = jax.jit(spec.prefill)\n")


def test_gl021_flags_cache_blind_warmup(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'runner.py').write_text(_CACHE_BLIND_SRC)
    findings, _ = lint_paths([str(lib / 'runner.py')],
                             scan_root=str(tmp_path))
    hits = sorted(f.line for f in findings if f.rule == 'GL021')
    lines = _CACHE_BLIND_SRC.splitlines()
    assert len(hits) == 2, [(f.rule, f.line) for f in findings]
    assert 'self._prefill' in lines[hits[0] - 1]
    assert 'self._decode' in lines[hits[1] - 1]
    msg = [f for f in findings if f.rule == 'GL021'][0].message
    # fix-it points at the persistent compile tier surfaces
    assert 'CachedJit' in msg and 'artifact_dir' in msg


def test_gl021_cache_aware_module_is_sanctioned(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    src = (
        "import jax\n"
        "from paddle_tpu import compilecache as _cc\n"
        "class Runner:\n"
        "    def __init__(self, spec):\n"
        "        self._prefill = _cc.CachedJit(spec.prefill)\n"
        "        self._decode = jax.jit(spec.aux)\n"  # cache-aware module
        "    def warmup(self):\n"
        "        return self._prefill.warm('x')\n")
    (lib / 'ok.py').write_text(src)
    findings, _ = lint_paths([str(lib / 'ok.py')],
                             scan_root=str(tmp_path))
    assert [f for f in findings if f.rule == 'GL021'] == [], \
        [(f.rule, f.line) for f in findings]


def test_gl021_exempts_harnesses_and_waiver(tmp_path):
    for rel in ('tests/mod.py', 'tools/mod.py', 'bench_x.py',
                'paddle_tpu/compilecache/wrap.py'):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(_CACHE_BLIND_SRC)
        findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL021'] == [], rel
    # inline waiver honored and excluded from the active set
    p = tmp_path / 'lib.py'
    p.write_text(
        "import jax\n"
        "class R:\n"
        "    def __init__(self, spec):\n"
        "        self._decode = jax.jit(spec.d)"
        "  # graftlint: disable=GL021 — one-off tool runner\n"
        "    def warmup(self):\n"
        "        return 0\n")
    findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
    hits = [f for f in findings if f.rule == 'GL021']
    assert len(hits) == 1 and hits[0].waived
    from paddle_tpu.analysis.finding import active
    assert active(hits) == []


def test_gl021_repo_serving_runners_lint_clean():
    """The real runners route through CachedJit — the rule must agree."""
    targets = [os.path.join(REPO, 'paddle_tpu', 'serving', f)
               for f in ('runners.py', 'paged_runner.py')]
    findings, n = lint_paths(targets, scan_root=REPO)
    assert n == 2
    assert [f for f in findings if f.rule == 'GL021'] == [], \
        [(f.path, f.line) for f in findings if f.rule == 'GL021']


# ---------------------------------------------------------------------------
# GL022: bare time.sleep retry/poll loop (unbounded, no backoff)
# ---------------------------------------------------------------------------

_BARE_SLEEP_SRC = (
    "import time\n"
    "def wait_ready(client):\n"
    "    while not client.ready():\n"
    "        time.sleep(0.5)\n"                          # flagged
    "def poll_file(path, items):\n"
    "    for _ in range(10):\n"
    "        time.sleep(1.0)\n"                          # flagged too
    "def once():\n"
    "    time.sleep(0.5)\n")                 # not in a loop: out of shape


def test_gl022_flags_bare_sleep_loops(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    (lib / 'mod.py').write_text(_BARE_SLEEP_SRC)
    findings, _ = lint_paths([str(lib / 'mod.py')],
                             scan_root=str(tmp_path))
    hits = sorted(f.line for f in findings if f.rule == 'GL022')
    assert len(hits) == 2, [(f.rule, f.line) for f in findings]
    lines = _BARE_SLEEP_SRC.splitlines()
    assert all('time.sleep' in lines[ln - 1] for ln in hits)
    msg = [f for f in findings if f.rule == 'GL022'][0].message
    # fix-it points at the bounded machinery
    assert 'resilience.retry' in msg and 'WatchdogTimeout' in msg


def test_gl022_deadline_bounded_loop_is_sanctioned(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    src = (
        "import time\n"
        "def wait_ready(client, timeout=5.0):\n"
        "    deadline = time.monotonic() + timeout\n"
        "    while not client.ready():\n"
        "        if time.monotonic() >= deadline:\n"
        "            raise TimeoutError('never became ready')\n"
        "        time.sleep(0.1)\n")
    (lib / 'ok.py').write_text(src)
    findings, _ = lint_paths([str(lib / 'ok.py')],
                             scan_root=str(tmp_path))
    assert [f for f in findings if f.rule == 'GL022'] == [], \
        [(f.rule, f.line) for f in findings]


def test_gl022_backoff_and_retry_aware_are_sanctioned(tmp_path):
    lib = tmp_path / 'paddle_tpu'
    lib.mkdir(exist_ok=True)
    # backoff-shaped delay: arithmetic — it grows, the fix's whole point
    (lib / 'backoff.py').write_text(
        "import time\n"
        "def wait_ready(client):\n"
        "    delay = 0.05\n"
        "    while not client.ready():\n"
        "        time.sleep(delay * 2)\n")
    # module routes retries through the sanctioned machinery
    (lib / 'aware.py').write_text(
        "import time\n"
        "from paddle_tpu.resilience import retry\n"
        "def wait_ready(client):\n"
        "    while not client.ready():\n"
        "        time.sleep(0.5)\n")
    for name in ('backoff.py', 'aware.py'):
        findings, _ = lint_paths([str(lib / name)],
                                 scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL022'] == [], name


def test_gl022_exempts_harnesses_and_waiver(tmp_path):
    for rel in ('tests/mod.py', 'tools/mod.py', 'bench_x.py',
                'paddle_tpu/resilience/mod.py'):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(_BARE_SLEEP_SRC)
        findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        assert [f for f in findings if f.rule == 'GL022'] == [], rel
    # inline waiver honored and excluded from the active set
    p = tmp_path / 'lib.py'
    p.write_text(
        "import time\n"
        "def wait_ready(client):\n"
        "    while not client.ready():\n"
        "        time.sleep(0.5)"
        "  # graftlint: disable=GL022 — caller holds the deadline\n")
    findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
    hits = [f for f in findings if f.rule == 'GL022']
    assert len(hits) == 1 and hits[0].waived
    from paddle_tpu.analysis.finding import active
    assert active(hits) == []


def test_gl022_repo_lints_clean():
    """Every in-tree sleep loop is deadline-bounded (router drain/response
    waits, launch joins, process-pool error drain) — the rule must agree."""
    findings, _ = lint_paths([os.path.join(REPO, 'paddle_tpu')],
                             scan_root=REPO)
    active_hits = [f for f in findings
                   if f.rule == 'GL022' and not f.waived]
    assert active_hits == [], \
        [(f.path, f.line) for f in active_hits]


def test_ten_distinct_rule_ids_on_seeded_fixtures(tmp_path):
    """The acceptance criterion, asserted directly: >=5 AST + >=5 verifier
    rule IDs fire, each finding carrying a location, and the JSON reporter
    round-trips all of them."""
    all_findings = []
    for rule_id, (source, _) in AST_FIXTURES.items():
        name = 'framework.py' if rule_id == 'GL010' else f"{rule_id}.py"
        p = tmp_path / name
        p.write_text(source)
        fs, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        all_findings.extend(fs)
    for kind in KINDS:
        fs, _expect = _run_malform(kind, seed=11)
        all_findings.extend(fs)
    ast_ids = {f.rule for f in all_findings if f.source == 'ast'}
    ir_ids = {f.rule for f in all_findings if f.source == 'ir'}
    assert len(ast_ids) >= 5, ast_ids
    assert len(ir_ids) >= 5, ir_ids
    assert len(ast_ids | ir_ids) >= 10
    assert all(f.line >= 1 for f in all_findings if f.source == 'ast')
    payload = json.loads(render_json(all_findings))
    assert payload['version'] == 1
    assert len(payload['findings']) == len(all_findings)
    got = {f['rule'] for f in payload['findings']}
    assert ast_ids | ir_ids <= got


# ---------------------------------------------------------------------------
# Executor integration: verify-then-run
# ---------------------------------------------------------------------------

@pytest.fixture
def static_mode():
    paddle.enable_static()
    yield
    paddle.disable_static()


def test_executor_run_verify_true_on_malformed():
    prog, expect = malform('dangling_input', seed=2)
    exe = static.Executor()
    fetch = prog.global_block.ops[-1].outputs[0]
    with pytest.raises(ProgramVerificationError) as ei:
        exe.run(prog, feed={}, fetch_list=[fetch], verify=True)
    msg = str(ei.value)
    assert 'GV001' in msg and 'dangling' in msg
    assert 'PADDLE_TPU_VERIFY' in msg     # tells the user how to bypass


def test_executor_run_verify_env_default(monkeypatch):
    prog, expect = malform('dangling_input', seed=2)
    exe = static.Executor()
    fetch = prog.global_block.ops[-1].outputs[0]
    monkeypatch.setenv('PADDLE_TPU_VERIFY', '1')
    with pytest.raises(ProgramVerificationError):
        exe.run(prog, feed={}, fetch_list=[fetch])
    monkeypatch.setenv('PADDLE_TPU_VERIFY', '0')
    # explicit verify=False always wins
    prog2, final2 = well_formed_program(seed=3)
    xvar = prog2.global_block.vars['x_3']
    exe.run(prog2, feed={'x_3': np.ones(xvar.shape, np.float32)},
            fetch_list=[final2], verify=False)


def test_set_always_verify_flag():
    prog, _ = malform('undeclared_output', seed=4)
    exe = static.Executor()
    fetch = prog.global_block.ops[-1].outputs[0]
    old = analysis.set_always_verify(True)
    try:
        with pytest.raises(ProgramVerificationError):
            exe.run(prog, feed={}, fetch_list=[fetch])
    finally:
        analysis.set_always_verify(old)


def test_verified_run_of_real_program_passes(static_mode):
    main = static.Program()
    with static.program_guard(main):
        x = static.data('x', [4, 8], 'float32')
        y = x * 2.0 + 1.0
    exe = static.Executor()
    xv = np.random.RandomState(0).rand(4, 8).astype(np.float32)
    out = exe.run(main, feed={'x': xv}, fetch_list=[y], verify=True)[0]
    np.testing.assert_allclose(out, xv * 2.0 + 1.0, rtol=1e-6)


def test_verify_accepts_string_and_missing_fetch(static_mode):
    main = static.Program()
    with static.program_guard(main):
        x = static.data('x', [2, 2], 'float32')
        y = x + 1.0
    fs = main.verify(fetch_list=[y.name])
    assert [f for f in fs if f.severity == 'error'] == []
    fs = main.verify(fetch_list=['definitely_not_there'])
    assert any(f.rule == 'GV008' for f in fs)


# ---------------------------------------------------------------------------
# Reporters / Finding
# ---------------------------------------------------------------------------

def test_finding_render_and_location():
    f = Finding(rule='GL001', message='m', path='a.py', line=3, col=1)
    assert f.location == 'a.py:3'
    assert 'GL001' in f.render() and 'a.py:3' in f.render()
    g = Finding(rule='GV001', message='m', source='ir')
    assert g.location == '<program>'


def test_render_text_tally_and_waived_hidden():
    fs = [Finding(rule='GL001', message='a', path='x.py', line=1),
          Finding(rule='GL007', message='b', path='x.py', line=2,
                  waived=True, waive_reason='why')]
    txt = analysis.render_text(fs)
    assert '1 error(s)' in txt and '1 waived' in txt
    assert 'GL007' not in txt
    assert 'GL007' in analysis.render_text(fs, show_waived=True)


# ---------------------------------------------------------------------------
# Engine 3: concurrency rules (GC001..GC006) on seeded fixtures
# ---------------------------------------------------------------------------

from paddle_tpu.analysis.testing import (CONCURRENCY_KINDS,
                                         concurrency_fixture)


@pytest.mark.parametrize('kind', sorted(CONCURRENCY_KINDS))
def test_concurrency_rule_fires_with_location(kind, tmp_path):
    source, rule, line = concurrency_fixture(kind, seed=5)
    p = tmp_path / 'fabric.py'
    p.write_text(source)
    findings, n = lint_paths([str(p)], scan_root=str(tmp_path))
    assert n == 1
    gc = [f for f in findings if f.rule.startswith('GC')]
    hits = [f for f in gc if f.rule == rule]
    assert hits, f"{rule} did not fire; got {[f.rule for f in findings]}"
    # the fixture trips exactly its own rule, nothing else in the family
    assert {f.rule for f in gc} == {rule}
    f = hits[0]
    assert f.path == str(p) and f.source == 'ast' and f.severity == 'error'
    if line is not None:   # GC002 anchors on whichever acquire closes
        assert any(h.line == line for h in hits), \
            f"{rule} anchored at {[h.line for h in hits]}, wanted {line}"
    else:
        assert all(h.line >= 1 for h in hits)


@pytest.mark.parametrize('kind', sorted(CONCURRENCY_KINDS))
def test_concurrency_sanctioned_variant_is_clean(kind, tmp_path):
    source, _, _ = concurrency_fixture(kind, seed=5, sanctioned=True)
    p = tmp_path / 'fabric.py'
    p.write_text(source)
    findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
    assert [f for f in findings if f.rule.startswith('GC')] == [], \
        [f.render() for f in findings]


@pytest.mark.parametrize('kind', sorted(CONCURRENCY_KINDS))
def test_concurrency_inline_waiver(kind, tmp_path):
    source, rule, line = concurrency_fixture(kind, seed=5)
    lines = source.splitlines()
    if line is None:
        # GC002: waive every acquire line in the cycle-closing function
        lines = [ln + f'  # graftlint: disable={rule} — fixture'
                 if 'with lock_' in ln else ln for ln in lines]
    else:
        lines[line - 1] += f'  # graftlint: disable={rule} — fixture'
    p = tmp_path / 'fabric.py'
    p.write_text('\n'.join(lines) + '\n')
    findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
    hits = [f for f in findings if f.rule == rule]
    assert hits and all(f.waived for f in hits), \
        [(f.rule, f.line, f.waived) for f in findings]
    assert all(f.waive_reason == 'inline disable' for f in hits)
    from paddle_tpu.analysis.finding import active
    assert [f for f in active(findings) if f.rule.startswith('GC')] == []


def test_concurrency_exempts_tests_tools_bench(tmp_path):
    source, _, _ = concurrency_fixture('unguarded_counter', seed=5)
    for rel in ('tests/fix.py', 'tools/fix.py', 'bench_fabric.py'):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(source)
        findings, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        assert [f for f in findings if f.rule.startswith('GC')] == [], rel


def test_select_gc_family_expansion(tmp_path):
    """--select GC expands to the whole family; exact ids still work;
    unknown families stay a usage error."""
    from paddle_tpu.analysis.cli import main
    from paddle_tpu.analysis.rules import expand_select
    expanded, unknown = expand_select({'GC'})
    assert expanded == {'GC001', 'GC002', 'GC003', 'GC004', 'GC005',
                        'GC006'} and unknown == set()
    expanded, unknown = expand_select({'GC003', 'GL007'})
    assert expanded == {'GC003', 'GL007'} and unknown == set()
    _, unknown = expand_select({'GX'})
    assert unknown == {'GX'}
    source, _, _ = concurrency_fixture('sleep_under_lock', seed=5)
    p = tmp_path / 'fabric.py'
    p.write_text(source)
    assert main(['--no-config', '--select', 'GC', str(p)]) == 1
    assert main(['--no-config', '--select', 'GL', str(p)]) == 0
    assert main(['--no-config', '--select', 'GX', str(p)]) == 2


def test_concurrency_json_reporter(tmp_path, capsys):
    source, rule, line = concurrency_fixture('unjoined_thread', seed=5)
    p = tmp_path / 'fabric.py'
    p.write_text(source)
    from paddle_tpu.analysis.cli import main
    rc = main(['--json', '--no-config', '--select', 'GC', str(p)])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1 and payload['errors'] == 1
    f = payload['findings'][0]
    assert f['rule'] == rule and f['line'] == line
    assert f['path'] == str(p) and f['severity'] == 'error'


def test_all_six_concurrency_rules_on_seeded_fixtures(tmp_path):
    """Engine-3 acceptance: GC001..GC006 each demonstrated (firing +
    sanctioned) and the JSON reporter round-trips the lot."""
    all_findings = []
    for kind in CONCURRENCY_KINDS:
        src, rule, _ = concurrency_fixture(kind, seed=9)
        p = tmp_path / f'{kind}.py'
        p.write_text(src)
        fs, _ = lint_paths([str(p)], scan_root=str(tmp_path))
        all_findings.extend(fs)
    fired = {f.rule for f in all_findings if f.rule.startswith('GC')}
    assert fired == set(CONCURRENCY_KINDS.values())
    payload = json.loads(render_json(all_findings))
    assert fired <= {f['rule'] for f in payload['findings']}

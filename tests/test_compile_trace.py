"""Set-up from inside the program (``bigdl_tpu/obs/compile.py``): jax's trace,
lowering and backend-compile events become always-on registry totals that
count the outermost event only, a table by program, spans when tracing is on
and nothing when it is off; the persistent cache's misses and hits across
two processes; the cost-analysis lowering of ``obs/program_flops``; the run
report's and ``/statusz``'s block; one listener in the package."""

import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.obs import compile as obs_compile
from bigdl_tpu.obs import exporter, mfu, report, trace
from bigdl_tpu.obs.registry import registry

pytestmark = pytest.mark.obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("compile/trace", "compile/lower", "compile/backend")


def _totals():
    hists = registry.snapshot()["histograms"]
    return {p: (hists.get(p, {}).get("count", 0), hists.get(p, {}).get("total", 0.0))
            for p in PHASES}


def _program():
    """A jitted function that calls a nested jitted one and eager-traced
    operations, new to this process at every call of this helper."""
    scale = float(time.perf_counter_ns() % 1000 + 2)

    @jax.jit
    def inner(x):
        return jnp.sin(x) * scale

    @jax.jit
    def outer_step(x):
        return inner(x) + jnp.cos(x).sum()

    x = jnp.ones((16, 16))
    x.block_until_ready()
    return outer_step, x


def test_nested_traces_count_once_and_within_the_call():
    step, x = _program()
    before = _totals()
    t0 = time.perf_counter()
    step(x).block_until_ready()
    wall = time.perf_counter() - t0
    after = _totals()
    added = {p: (after[p][0] - before[p][0], after[p][1] - before[p][1]) for p in PHASES}
    # one outermost trace (`outer_step`), not one for `inner`, `sin`, `cos`, ...
    assert {p: n for p, (n, _) in added.items()} == {p: 1 for p in PHASES}
    assert added["compile/trace"][1] <= wall
    assert sum(s for _, s in added.values()) <= wall
    programs = {(p, f) for p, f, _, _ in obs_compile.largest(10 ** 6)}
    assert ("trace", "outer_step") in programs and ("trace", "inner") not in programs
    assert ("backend", "jit(outer_step)") in programs


def test_a_second_call_adds_nothing():
    step, x = _program()
    step(x).block_until_ready()
    snap = registry.snapshot()
    for _ in range(3):
        step(x).block_until_ready()
    again = registry.snapshot()
    assert {k: v for k, v in again["histograms"].items() if k.startswith("compile/")} \
        == {k: v for k, v in snap["histograms"].items() if k.startswith("compile/")}
    assert again["counters"] == snap["counters"]


def test_program_flops_hits_the_lowering_cache():
    step, x = _program()
    step(x).block_until_ready()
    before = _totals()
    n0 = registry.snapshot()["histograms"].get("obs/program_flops", {}).get("count", 0)
    assert mfu.program_flops(step, x) > 0
    after = _totals()
    assert after["compile/lower"] == before["compile/lower"]
    assert after["compile/backend"] == before["compile/backend"]
    assert registry.snapshot()["histograms"]["obs/program_flops"]["count"] == n0 + 1


def test_spans_with_tracing_on_and_none_with_it_off(tmp_path):
    trace.configure(enabled=False)
    made0 = trace._SPANS_CREATED
    step, x = _program()
    step(x).block_until_ready()
    assert trace._SPANS_CREATED == made0 and trace.spans_between() == []

    trace.configure(enabled=True, trace_dir=str(tmp_path))
    step, x = _program()
    t0 = time.time_ns()
    step(x).block_until_ready()
    t1 = time.time_ns()
    with open(trace.export_chrome()) as f:
        chrome = json.load(f)
    zero = chrome["otherData"]["ts_zero_unix_ns"]
    spans = [e for e in chrome["traceEvents"]
             if e.get("ph") == "X" and e["name"] in PHASES]
    assert {e["name"] for e in spans} == set(PHASES)
    assert {e["args"]["fun"] for e in spans} == {"outer_step", "jit(outer_step)"}
    for e in spans:     # on the Unix clock, inside the call (a microsecond of rounding)
        start = zero + e["ts"] * 1e3
        assert t0 - 1e3 <= start and start + e["dur"] * 1e3 <= t1 + 1e3


def test_the_span_nests_under_the_span_open_on_its_thread():
    trace.configure(enabled=True)
    step, x = _program()
    with trace.span("outer"):
        step(x).block_until_ready()
    parents = {r.name: r.parent for r in trace.spans_between()}
    assert parents == {"outer": None, **{p: "outer" for p in PHASES}}


def _child(code, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_ENABLE_COMPILATION_CACHE")}
    env.update(PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", **env_over)
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_the_persistent_cache_misses_then_hits(tmp_path):
    code = ("import json, jax, jax.numpy as jnp, bigdl_tpu\n"
            "from bigdl_tpu.obs.registry import registry\n"
            "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((32, 32))).block_until_ready()\n"
            "print(json.dumps(registry.snapshot()['counters']))")
    env = dict(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    cold = _child(code, **env)
    warm = _child(code, **env)
    assert cold.get("compile/cache_misses", 0) > 0
    assert warm.get("compile/cache_hits", 0) > 0 and warm.get("compile/cache_misses", 0) == 0
    assert warm["compile/cache_requests"] == cold["compile/cache_requests"]


def test_the_run_report_and_statusz_name_the_largest_programs():
    step, x = _program()
    step(x).block_until_ready()
    rep = report.build_report({}, registry.snapshot())
    block = rep["compile"]
    assert block["trace_count"] >= 1 and block["backend_s"] > 0
    assert len(block["programs"]) <= 8
    seconds = [s for _, _, s, _ in block["programs"]]
    assert seconds == sorted(seconds, reverse=True)
    text = report.format_report(rep)
    assert "compile since process start: trace" in text and "largest compiles: " in text
    assert exporter.render_statusz()["compile"]["programs"]
    assert json.loads(json.dumps(rep)) == rep


def test_install_registers_once():
    obs_compile.install()
    obs_compile.install()
    from jax._src import monitoring
    assert monitoring.get_event_time_span_listeners().count(obs_compile._on_span) == 1
    assert monitoring.get_scalar_listeners().count(obs_compile._on_start) == 1


def test_one_listener_in_the_package():
    """jax's monitoring events have one listener in the program: the
    package and the chip smoke test read the registry it fills."""
    found = set()
    files = [os.path.join(ROOT, "chip_smoke.py")]
    for dirpath, _, names in os.walk(os.path.join(ROOT, "bigdl_tpu")):
        files += [os.path.join(dirpath, n) for n in names if n.endswith(".py")]
    for path in files:
        with open(path) as f:
            if re.search(r"monitoring\.register_|register_\w*listener", f.read()):
                found.add(os.path.relpath(path, ROOT))
    assert found == {os.path.join("bigdl_tpu", "obs", "compile.py")}

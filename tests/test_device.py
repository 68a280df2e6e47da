"""The platform probe (repro/device.py) and the host-fallback counter.

The probe decides the engine of every plan, so it must not depend on
import order, must pin the device engine when JAX reports a TPU, and must
refuse to carry on on the host when a TPU was asked for.  Phases planned
for the device that run on the host are counted by reason, and the
``phase:summarize`` span names the engine that actually ran.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import device
from repro.core.api import GraphicalJoin
from repro.core.gfjs import desummarize
from repro.obs import Tracer
from repro.plan import executor as executor_mod
from repro.plan.executor import Executor
from repro.relational.synth import figure1, lastfm_like
from repro.summary.service import JoinService

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")
DEVICE = {"summarize": "jax", "desummarize": "jax"}
HOST = {"summarize": "numpy", "desummarize": "numpy"}


def _plan_in_child(import_jax_first: bool) -> dict:
    code = (
        "import json, sys\n"
        + ("import jax, repro.core.engine_jax\n" if import_jax_first else "")
        + "from repro.relational.synth import figure1\n"
        "from repro.summary.service import JoinService\n"
        "from repro.core.api import GraphicalJoin\n"
        "cat, q = figure1()\n"
        "svc = JoinService(cat)\n"
        "print(json.dumps({'service': svc.compile(q).backends,\n"
        "                  'library': GraphicalJoin(cat, q).plan().backends,\n"
        "                  'jax': 'jax' in sys.modules}))\n")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_backends_do_not_depend_on_import_order():
    jax_first = _plan_in_child(import_jax_first=True)
    jax_free = _plan_in_child(import_jax_first=False)
    assert jax_first["service"] == jax_free["service"] == HOST
    assert jax_first["library"] == jax_free["library"] == HOST
    assert not jax_free["jax"], "planning under JAX_PLATFORMS=cpu imported jax"


@pytest.fixture
def steered_tpu(monkeypatch):
    """Make the probe see a TPU: JAX itself reports "tpu" in this test.

    The probe's cache and JAX's compilation-cache settings are restored
    afterwards, and its compile listener is kept out of JAX's registry, so
    no later test sees the steered answer or hears its compiles."""
    jax = pytest.importorskip("jax")
    from jax.experimental.compilation_cache import compilation_cache
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        lambda fn: None)
    device.platform.cache_clear()
    device._listen_for_compiles.cache_clear()
    yield jax
    device.platform.cache_clear()
    device._listen_for_compiles.cache_clear()
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_probe_reporting_tpu_pins_the_device_engine(steered_tpu):
    from repro.kernels import ops
    cat, q = figure1()
    svc = JoinService(cat)
    assert svc.engine == "jax"
    assert svc.compile(q).backends == DEVICE
    assert GraphicalJoin(cat, q).plan().backends == DEVICE
    assert not ops.default_interpret()
    # the compile cache: a fixed directory inside the checkout
    assert steered_tpu.config.jax_compilation_cache_dir == str(
        device.CACHE_DIR)
    assert steered_tpu.config.jax_persistent_cache_min_compile_time_secs \
        == 0.0
    assert (device.CACHE_DIR.parent / "src" / "repro").is_dir()


def test_probe_defers_to_the_cache_dir_environment(steered_tpu, monkeypatch,
                                                   tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device.platform() == "tpu"
    assert steered_tpu.config.jax_compilation_cache_dir == str(tmp_path)


def test_probe_on_a_tpu_listens_for_compiles(steered_tpu, monkeypatch):
    heard = []
    monkeypatch.setattr(steered_tpu.monitoring,
                        "register_event_duration_secs_listener", heard.append)
    assert device.platform() == "tpu"
    assert heard == [device.on_compile]


def test_compile_listener_records_a_span_and_counts(monkeypatch):
    # one span per compile event, none for other events or with no tracer;
    # clock reads: the tracer's epoch, the span's start, the listener's
    # "now", the span's end
    tracer = Tracer(clock=iter([0.0, 0.0, 10.0, 11.0]).__next__)
    with tracer.span("request") as root:
        device.on_compile(device.COMPILE_EVENT, 0.25)
        device.on_compile("/jax/core/compile/jaxpr_trace_duration", 9.0)
    (sp,) = tracer.find("jax:compile")
    assert (sp.t0, sp.t1, sp.cat) == (9.75, 10.0, "jax")
    assert sp.parent_id == root.span_id
    device.on_compile(device.COMPILE_EVENT, 0.5)    # no tracer: nothing
    assert len(tracer.find("jax:compile")) == 1


def test_probe_refuses_a_missing_tpu(monkeypatch):
    pytest.importorskip("jax")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    device.platform.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="asks for a TPU"):
            device.platform()
    finally:
        device.platform.cache_clear()


def _summarize_span(tracer: Tracer):
    (sp,) = [s for s in tracer.spans if s.name == "phase:summarize"]
    return sp


def test_ungenerable_generator_counts_a_host_fallback(monkeypatch):
    pytest.importorskip("jax")
    from repro.core import engine_jax
    monkeypatch.setattr(engine_jax, "jax_generable", lambda gen: False)
    cat, q = figure1()
    before = device.host_fallbacks().get("not_generable", 0)
    tr = Tracer()
    ex = Executor(cat, q, generation_backend="jax", tracer=tr)
    got = ex.run()
    assert device.host_fallbacks()["not_generable"] == before + 1
    assert _summarize_span(tr).args["backend"] == "numpy"
    # the engine's own guard counts callers that skip the executor
    engine_jax.generate_gfjs_jax(ex.generator, ex.enc.domains)
    assert device.host_fallbacks()["not_generable"] == before + 2
    want = GraphicalJoin(cat, q, generation_backend="numpy").run()
    assert got.join_size == want.join_size


@pytest.mark.parametrize("record_trace", [False, True])
def test_summarize_span_names_the_engine_that_ran(record_trace):
    pytest.importorskip("jax")
    cat, qs = lastfm_like(n_users=30, n_artists=20, artists_per_user=3,
                          friends_per_user=2)
    before = device.host_fallbacks().get("record_trace", 0)
    tr = Tracer()
    Executor(cat, qs["lastfm_A1"], generation_backend="jax",
             record_trace=record_trace, tracer=tr).run()
    ran = "numpy" if record_trace else "jax"
    assert _summarize_span(tr).args["backend"] == ran
    assert device.host_fallbacks().get("record_trace", 0) \
        == before + int(record_trace)


def test_numpy_plans_count_no_fallback():
    cat, q = figure1()
    before = device.host_fallbacks()
    tr = Tracer()
    ex = Executor(cat, q, record_trace=True, tracer=tr)
    ex.desummarize(ex.run())
    assert device.host_fallbacks() == before
    assert _summarize_span(tr).args["backend"] == "numpy"


def test_desummarize_past_the_kernel_range_counts_a_fallback(monkeypatch):
    pytest.importorskip("jax")
    cat, q = figure1()
    ex = Executor(cat, q)
    g = ex.run()
    ex.plan.backends["desummarize"] = "jax"
    monkeypatch.setattr(executor_mod, "_I32_MAX", g.join_size - 1)
    before = device.host_fallbacks().get("join_past_int32", 0)
    got = ex.desummarize(g, decode=False)
    assert device.host_fallbacks()["join_past_int32"] == before + 1
    want = desummarize(g, decode=False)
    for v in g.column_order:
        np.testing.assert_array_equal(got[v], want[v])

"""Profiler spans (``repro.obs.span``) and the counters at the same
boundaries.

With no profiler session, ``span`` is one shared no-op object and loads no
JAX backend. In a session on the CPU, one v3 request through
``PredictionServer`` -> ``ClusterFrontend`` -> ``ForestEngine`` ->
``pad_pow2`` writes every named span, each nested in the layer above it,
each carrying its rows. ``FrontendStats.wait_s``/``waited`` and
``EngineStats.padded_rows`` count what they say."""
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.cluster import (ClusterFrontend, PredictionServer, RemoteReplica,
                           ReplicaPool)
from repro.cluster.remote import demo_estimator
from repro.obs import span
from repro.serve import ForestEngine
from repro.serve.backend import pow2_padding

N_F = 6
SRC = Path(__file__).resolve().parents[1] / "src"

#: each span and the program span around it on its thread (None: none)
PARENT = {
    "wire.decode": None,
    "frontend.admit": None,
    "frontend.pop": None,
    "frontend.dispatch": None,
    "frontend.stack": "frontend.dispatch",
    "engine.batch": "frontend.dispatch",
    "engine.lookup": "engine.batch",
    "engine.writeback": "engine.batch",
    "backend.pad": "engine.batch",
    "backend.launch": "engine.batch",
    "backend.wait": "engine.batch",
    # written from the future's done-callback, which runs inside the
    # dispatch, or on the connection thread if the answer came first
    "wire.encode": ("frontend.dispatch", None),
}


@pytest.fixture(scope="module")
def est():
    return demo_estimator(seed=5, n_features=N_F, n_trees=8)


def rows(n, seed):
    return np.random.default_rng(seed).lognormal(
        1.0, 1.5, size=(n, N_F)).astype(np.float32)


def test_span_without_a_profiler_is_one_no_op_and_starts_no_backend():
    code = """
from jax._src import xla_bridge
from repro.obs import span
a, b = span("wire.decode", rows=3), span("engine.batch")
with a as s:
    s.set_metadata(rows=4)
print(a is b, xla_bridge.backends_are_initialized())
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env={"PYTHONPATH": str(SRC),
                                                      "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["True", "False"]


def test_span_in_a_profiler_session_is_a_trace_annotation(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        on = span("engine.batch", rows=2)
    finally:
        jax.profiler.stop_trace()
    assert isinstance(on, jax.profiler.TraceAnnotation)
    assert span("engine.batch") is span("wire.decode")


def nesting(pd):
    """[(name, stats, enclosing program span's name)] of every program span,
    thread by thread."""
    out = []
    layers = {n.split(".")[0] for n in PARENT}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted((e for e in line.events
                          if e.name.split(".")[0] in layers),
                         key=lambda e: (e.start_ns, -e.duration_ns))
            opened = []
            for e in evs:
                while opened and (opened[-1].start_ns + opened[-1].duration_ns
                                  <= e.start_ns):
                    opened.pop()
                out.append((e.name, dict(e.stats),
                            opened[-1].name if opened else None))
                opened.append(e)
    return out


@pytest.fixture(scope="module")
def traced(est, tmp_path_factory):
    """Three v3 requests of 5, 7 and 9 fresh rows, traced on the CPU."""
    import jax
    from jax.profiler import ProfileData
    engine = ForestEngine(est, backend="flat-jax")
    for n in (1, 2, 4, 8, 16):                    # compile before the trace
        engine.predictor(rows(n, 0))
    pool = ReplicaPool({"r0": engine}, check_interval_s=60.0)
    frontend = ClusterFrontend(pool, max_queue=256)
    server = PredictionServer(frontend, port=0).start()
    client = RemoteReplica(server.address)
    sizes = (5, 7, 9)
    out = tmp_path_factory.mktemp("spans")
    try:
        client.predict(rows(1, 1))                # connect; probe answered
        before = engine.stats_snapshot()
        jax.profiler.start_trace(str(out))
        try:
            for i, n in enumerate(sizes):
                client.predict(rows(n, 10 + i))
            # the answer leaves before its dispatch span closes: let the
            # last dispatch end inside the trace
            frontend.close(close_pool=False)
        finally:
            jax.profiler.stop_trace()
        after = engine.stats_snapshot()
    finally:
        client.close()
        server.close()
        engine.close()
    pd = ProfileData.from_file(str(next(out.rglob("*.xplane.pb"))))
    return nesting(pd), sizes, before, after


def test_a_v3_request_writes_each_span_nested_in_its_layer(traced):
    spans, sizes, before, after = traced
    assert {name for name, _, _ in spans} == set(PARENT)
    for name, stats, parent in spans:
        want = PARENT[name]
        assert parent in (want if isinstance(want, tuple) else (want,)), (
            name, parent)
        assert "rows" in stats, name
    assert any(p == "frontend.dispatch" for n, _, p in spans
               if n == "wire.encode")
    engine_rows = sum(s["rows"] for n, s, _ in spans if n == "engine.batch")
    assert engine_rows == after.predictions - before.predictions
    # the rows the v3 requests carried; the pool's probes (answered by the
    # cache, so no backend call) may add a few
    mine = sorted(s["rows"] for n, s, _ in spans if n == "wire.decode")
    assert mine == sorted(sizes)
    pads = [s for n, s, _ in spans if n == "backend.pad"]
    assert sum(s["padded"] for s in pads) == (after.padded_rows
                                              - before.padded_rows)
    assert sum(s["rows"] for s in pads) == (after.backend_rows
                                            - before.backend_rows)


@pytest.mark.parametrize("backend,pads", [("flat-jax", True),
                                          ("flat-numpy", False)])
def test_engine_counts_the_rows_padding_appended(est, backend, pads):
    with ForestEngine(est, backend=backend) as engine:
        for n in (5, 8, 1, 12):
            engine.predict(rows(n, n))
        engine.predict(rows(5, 5))                # all cached: no call
        st = engine.stats_snapshot()
    assert st.backend_rows == 5 + 8 + 1 + 12
    assert st.padded_rows == (3 + 0 + 0 + 4 if pads else 0)
    assert [pow2_padding(n) for n in (1, 2, 3, 5, 8, 1000)] == [
        0, 0, 1, 3, 0, 24]


def test_frontend_counts_each_dispatched_request_s_wait(est):
    engine = ForestEngine(est, backend="flat-numpy")
    pool = ReplicaPool({"r0": engine}, check_interval_s=60.0)
    frontend = ClusterFrontend(pool, max_queue=256, auto_start=False)
    try:
        futs = [frontend.submit_batch(rows(3, i)) for i in range(4)]
        futs.append(frontend.submit(rows(1, 9)[0]))
        t0 = time.monotonic()
        time.sleep(0.05)
        frontend.start()
        for f in futs:
            f.result(timeout=30)
        held = time.monotonic() - t0
        st = frontend.stats_snapshot()
    finally:
        frontend.close()
    assert st.waited == 5
    assert 5 * 0.05 <= st.wait_s <= 5 * held + 1.0


def test_wait_and_padding_counters_reach_the_registry(est):
    from repro.obs import Observability
    obs = Observability.default()
    engine = ForestEngine(est, backend="flat-jax")
    engine.register_metrics(obs.registry, replica="r0")
    pool = ReplicaPool({"r0": engine}, check_interval_s=60.0)
    frontend = ClusterFrontend(pool, max_queue=256, obs=obs)
    try:
        frontend.submit_batch(rows(5, 1)).result(timeout=30)
    finally:
        frontend.close()
    got = {r["name"]: r for r in obs.registry.snapshot()}
    assert got["frontend.waited"]["value"] == 1
    assert got["frontend.waited"]["kind"] == "counter"
    assert got["frontend.wait_s_total"]["value"] >= 0.0
    assert got["frontend.wait_s"]["kind"] == "histogram"
    assert got["engine.padded_rows"]["value"] >= 3      # and the probes'


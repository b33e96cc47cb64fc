"""The observability plane (PR 7): `repro.obs` itself, plus the
acceptance contract — an instrumented end-to-end run whose counters and
phase breakdown match what the code actually did, and an ingest
overhead guard for the <5% budget.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


@pytest.fixture(autouse=True)
def fresh_obs():
    """Every test starts from an empty registry/ring with obs enabled,
    and leaves the process back on the environment's setting."""
    obs.set_enabled(True)
    obs.reset_all()
    yield
    obs.reset_all()
    obs.set_enabled(None)


# ------------------------------------------------------------- metrics ---

def test_counter_and_gauge_basics():
    c = obs.counter("t.c")
    c.add()
    c.add(2.5)
    assert obs.counter("t.c") is c          # registry: same series
    assert c.value == 3.5
    g = obs.gauge("t.g")
    g.set(7)
    g.set(3)
    assert g.value == 3 and g.max == 7


def test_counter_labels_are_independent_series():
    obs.counter("t.lc", be="jnp").add(1)
    obs.counter("t.lc", be="pallas").add(5)
    snap = obs.metrics_snapshot()["counters"]
    assert snap["t.lc{be=jnp}"] == 1
    assert snap["t.lc{be=pallas}"] == 5


def test_counter_thread_safety_under_producer_threads():
    c = obs.counter("t.mt")
    n_threads, n_adds = 8, 2000

    def work():
        for _ in range(n_adds):
            c.add(1)

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * n_adds    # exact: no lost updates


def test_histogram_quantiles_match_numpy_within_bucket_ratio():
    h = obs.histogram("t.h")
    rng = np.random.default_rng(0)
    # lognormal latencies spanning ~3 decades — the regime the log
    # buckets are built for
    vals = np.exp(rng.normal(loc=-6.0, scale=1.5, size=5000))
    for v in vals:
        h.observe(float(v))
    ratio = 10.0 ** (1.0 / obs_metrics.PER_DECADE)
    for q in (0.5, 0.9, 0.99):
        exact = float(np.percentile(vals, q * 100))
        est = h.quantile(q)
        assert exact / ratio <= est <= exact * ratio, \
            f"p{int(q * 100)}: {est} vs exact {exact}"
    assert h.quantile(0.0) == float(vals.min())
    assert h.quantile(1.0) == float(vals.max())


def test_histogram_underflow_overflow_answer_min_max():
    h = obs.histogram("t.h2")
    h.observe(1e-9)                          # below lo: underflow bucket
    h.observe(5e4)                           # above hi: overflow bucket
    assert h.quantile(0.01) == 1e-9
    assert h.quantile(0.99) == 5e4


def test_kill_switch_compiles_to_noops():
    obs.set_enabled(False)
    obs.counter("t.off").add(5)
    obs.gauge("t.off.g").set(1)
    obs.histogram("t.off.h").observe(0.5)
    obs.event("t.off.ev")
    with obs.span("t.off.span"):
        pass
    assert obs.counter("t.off").value == 0
    assert obs.histogram("t.off.h").count == 0
    assert obs.ring_events() == []
    snap = obs.metrics_snapshot()
    assert snap["histograms"]["t.off.h"]["count"] == 0


# --------------------------------------------------------------- spans ---

def test_spans_nest_and_record_parent_and_feed_histograms():
    with obs.span("outer"):
        with obs.span("inner"):
            pass
    evs = obs.ring_events()
    by = {e["name"]: e for e in evs}
    assert by["inner"]["parent"] == "outer"
    assert by["outer"]["parent"] is None
    assert by["inner"]["ts"] <= by["outer"]["ts"] + by["outer"]["dur_s"]
    snap = obs.metrics_snapshot()["histograms"]
    assert snap["span.outer"]["count"] == 1
    assert snap["span.inner"]["count"] == 1


def test_span_stack_isolated_per_thread():
    seen = {}

    def work():
        with obs.span("threaded"):
            pass
        seen["done"] = True

    with obs.span("main_scope"):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    ev = [e for e in obs.ring_events() if e["name"] == "threaded"][0]
    assert ev["parent"] is None              # not "main_scope"
    assert seen["done"]


@pytest.mark.parametrize("enabled", [True, False], ids=["on", "off"])
def test_span_is_a_profiler_event(tmp_path, enabled):
    """Once jax is imported, an enabled span is a host event of the
    profiler's trace, under its bare name (labels and fields stay out
    of it), nested as the spans are; REPRO_OBS=0 leaves none."""
    import jax  # noqa: F401 — the annotation needs jax imported
    from conftest import host_profile

    def run():
        with obs.span("x.y"):
            with obs.span("x.inner", labels={"replica": "r0"}, rows=3):
                time.sleep(0.001)

    obs.set_enabled(enabled)
    events = host_profile(run, tmp_path)
    mine = {name: (line, s, d) for line, name, s, d in events
            if name.startswith("x.")}
    if not enabled:
        assert mine == {}
        return
    assert sorted(mine) == ["x.inner", "x.y"]
    (lo, so, do), (li, si, di) = mine["x.y"], mine["x.inner"]
    assert lo == li and so <= si and si + di <= so + do
    assert di >= 1e6                          # the sleep, in ns
    assert obs.metrics_snapshot()["histograms"]["span.x.y"]["count"] == 1


def test_obs_imports_no_jax():
    """`repro.obs` stays pure stdlib: spans work, and stay off the
    profiler, in a process that never imports jax."""
    import subprocess
    import sys

    code = ("import sys; from repro import obs\n"
            "with obs.span('a'):\n    pass\n"
            "assert obs.ring_events()[0]['name'] == 'a'\n"
            "assert 'jax' not in sys.modules, 'obs imported jax'\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr


def test_span_histograms_resolved_once_and_after_reset():
    hs = obs_metrics.span_histograms("t.sh", {"replica": "r0"})
    assert [h.name for h in hs] == ["span.t.sh", "span.t.sh"]
    assert hs[1].labels == (("replica", "r0"),)
    assert obs_metrics.span_histograms("t.sh", {"replica": "r0"}) is hs
    assert obs_metrics.span_histograms("t.sh", {})[0] is hs[0]
    obs.reset_metrics()               # a fresh registry: resolved again
    with obs.span("t.sh", labels={"replica": "r0"}):
        pass
    snap = obs.metrics_snapshot()["histograms"]
    assert snap["span.t.sh"]["count"] == 1
    assert snap["span.t.sh{replica=r0}"]["count"] == 1
    assert hs[0].count == 0


def test_handles_bind_once_and_again_after_reset():
    calls = []

    def bind():
        calls.append(1)
        return obs.counter("t.bound", replica="r0")

    handles = obs.Handles(bind)
    c = handles.get()
    c.add(2)
    assert handles.get() is c and len(calls) == 1
    obs.reset_metrics()
    handles.get().add(3)
    assert len(calls) == 2 and handles.get() is not c
    assert obs.metrics_snapshot()["counters"]["t.bound{replica=r0}"] == 3


def test_ring_buffer_evicts_oldest_first():
    obs.set_ring_size(5)
    try:
        for i in range(9):
            obs.event("tick", i=i)
        evs = obs.ring_events()
        assert [e["i"] for e in evs] == [4, 5, 6, 7, 8]
    finally:
        obs.set_ring_size(obs_trace._ring_size())


def test_warn_once_dedupes_but_keeps_payload():
    obs_trace._reset_warned()
    with pytest.warns(RuntimeWarning, match="probe blew up"):
        assert obs.warn_once("t_probe", "probe blew up", error="E1")
    assert not obs.warn_once("t_probe", "probe blew up again")
    warns = [e for e in obs.ring_events()
             if e["name"] == "warn.t_probe"]
    assert len(warns) == 1 and warns[0]["error"] == "E1"
    obs_trace._reset_warned()


# ---------------------------------------------------------- JSONL sink ---

def test_jsonl_round_trip_and_snapshot_line(tmp_path):
    obs.counter("t.rt").add(3)
    with obs.span("t.rt.span"):
        pass
    obs.event("t.rt.ev", detail="x")
    path = str(tmp_path / "events.jsonl")
    assert obs.flush_jsonl(path) == path
    evs = obs.load_jsonl(path)
    kinds = [e["kind"] for e in evs]
    assert kinds.count("span") == 1 and kinds.count("event") == 1
    assert kinds[-1] == "snapshot"
    assert evs[-1]["metrics"]["counters"]["t.rt"] == 3
    # the renderer consumes the same file
    from repro.obs import report
    text = report.render_report(evs)
    assert "t.rt.span" in text and "t.rt" in text


def test_jsonl_tolerates_corrupt_lines(tmp_path):
    path = str(tmp_path / "events.jsonl")
    good = {"kind": "span", "name": "ok", "ts": 1.0, "dur_s": 0.5}
    with open(path, "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write("{truncated json li\n")
        f.write("[1, 2, 3]\n")             # valid JSON, not an event dict
        f.write(json.dumps(dict(good, name="ok2")) + "\n")
    evs = obs.load_jsonl(path)
    assert [e["name"] for e in evs] == ["ok", "ok2"]
    assert obs.load_jsonl(str(tmp_path / "missing.jsonl")) == []


def test_report_main_renders_phase_table(tmp_path, capsys):
    with obs.span("demo.phase"):
        pass
    path = str(tmp_path / "events.jsonl")
    obs.flush_jsonl(path)
    from repro.obs.report import main
    assert main(["--jsonl", path]) == 0
    out = capsys.readouterr().out
    assert "demo.phase" in out and "p99_ms" in out


def test_phase_breakdown_live_vs_jsonl_agree(tmp_path):
    for _ in range(4):
        with obs.span("agree.phase"):
            pass
    live = {r["phase"]: r for r in obs.phase_breakdown()}
    path = str(tmp_path / "events.jsonl")
    obs.flush_jsonl(path)
    sunk = {r["phase"]: r
            for r in obs.phase_breakdown(obs.load_jsonl(path))}
    assert live["agree.phase"]["count"] == 4
    assert sunk["agree.phase"]["count"] == 4
    assert sunk["agree.phase"]["total_s"] == \
        pytest.approx(live["agree.phase"]["total_s"], rel=1e-6)


# ------------------------------------------------- end-to-end contract ---

def test_e2e_report_matches_actual_behavior(tmp_path):
    """The ISSUE's acceptance run: ChunkStore ingest → bigfcm_fit_store
    → assign_store, with the cache counters cross-checked against a
    ground-truth count of actual `chunk()` calls and serve latency
    quantiles coming out of the span histogram."""
    from repro.core.bigfcm import BigFCMConfig, bigfcm_fit_store
    from repro.data.cache import ChunkStore
    from repro.serve.cluster import assign_store

    rng = np.random.default_rng(0)
    x = rng.normal(size=(1200, 3)).astype(np.float32)
    store = ChunkStore.ingest(x, chunk_rows=300,
                              cache_dir=str(tmp_path / "cache"))

    # ground truth: count chunk() calls ourselves, independently of obs
    calls = {"n": 0}
    orig_chunk = ChunkStore.chunk

    def counting_chunk(self, i):
        calls["n"] += 1
        return orig_chunk(self, i)

    ChunkStore.chunk = counting_chunk
    try:
        obs.reset_all()                  # drop the ingest-phase telemetry
        cfg = BigFCMConfig(n_clusters=3, max_iter=15, sample_size=128,
                           use_driver=False, backend="jnp")
        res = bigfcm_fit_store(store, cfg)
        outs = list(assign_store(store, res.centers, backend="jnp"))
    finally:
        ChunkStore.chunk = orig_chunk

    snap = obs.metrics_snapshot()
    # cache counters match what the store actually served
    assert snap["counters"]["data.cache.chunk_reads"] == calls["n"]
    assert snap["counters"]["data.cache.warm_mmap_bytes"] > 0
    assert "data.cache.warm_mem_bytes" not in snap["counters"]

    # per-phase breakdown covers the fit pipeline + scoring
    phases = {r["phase"] for r in obs.phase_breakdown()}
    assert {"engine.fit_store", "engine.combiner", "engine.sweep",
            "engine.merge", "serve.assign"} <= phases

    # serve latency quantiles from the log buckets, one span per chunk
    h = snap["histograms"]["span.serve.assign"]
    assert h["count"] == store.n_chunks == len(outs)
    assert 0 < h["p50"] <= h["p99"]

    # the host-orchestrated fit emitted its per-iteration series
    iters = [e for e in obs.ring_events()
             if e["name"] == "engine.fit.iter"]
    assert len(iters) >= 1
    assert all("objective" in e and "shift" in e for e in iters)
    done = [e for e in obs.ring_events()
            if e["name"] == "engine.fit.done"]
    assert done and done[-1]["backend"] == "jnp"

    # the renderer turns all of it into a non-empty report
    text = obs.render_report()
    assert "engine.fit_store" in text and "data.cache.chunk_reads" in text


def test_open_or_ingest_hit_miss_counters(tmp_path):
    from repro.data.cache import ChunkStore
    x = np.random.default_rng(1).normal(size=(100, 2)).astype(np.float32)
    d = str(tmp_path / "c")
    ChunkStore.open_or_ingest(d, x, chunk_rows=50)     # cold: miss
    ChunkStore.open_or_ingest(d, x, chunk_rows=50)     # warm: hit
    snap = obs.metrics_snapshot()["counters"]
    assert snap["data.cache.open_misses"] == 1
    assert snap["data.cache.open_hits"] == 1
    assert snap["data.cache.chunks_written"] == 2
    assert snap["data.cache.cold_parse_bytes"] == x.nbytes


def test_streaming_ingest_counters():
    from repro.stream import StreamConfig, StreamingBigFCM
    rng = np.random.default_rng(2)
    cfg = StreamConfig(n_clusters=3, window=4, max_iter=30,
                       driver_sample=128, seed=0)
    model = StreamingBigFCM(cfg)
    for _ in range(3):
        model.ingest(rng.normal(size=(256, 4)).astype(np.float32))
    snap = obs.metrics_snapshot()
    assert snap["counters"]["stream.records"] == 3 * 256
    assert snap["histograms"]["span.stream.ingest"]["count"] == 3
    assert snap["gauges"]["stream.n_centers"]["value"] == 3


def test_checkpoint_save_restore_instrumented(tmp_path):
    import jax.numpy as jnp
    from repro.ft.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    tree = {"v": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)}
    mgr.save(1, tree)
    out = mgr.restore(tree)
    np.testing.assert_array_equal(np.asarray(out["v"]),
                                  np.asarray(tree["v"]))
    snap = obs.metrics_snapshot()
    assert snap["counters"]["ft.checkpoint.saves"] == 1
    assert snap["counters"]["ft.checkpoint.restores"] == 1
    assert snap["histograms"]["span.ft.checkpoint.save"]["count"] == 1


# ------------------------------------------------------ overhead guard ---

def test_ingest_overhead_within_budget():
    """REPRO_OBS on-vs-off on streaming ingest stays within 5% (plus a
    small absolute slack for timer noise on a loaded 1-core host —
    per-batch obs cost is a few µs against ~ms of batch compute)."""
    from repro.stream import StreamConfig, StreamingBigFCM
    import time

    rng = np.random.default_rng(3)
    chunks = [rng.normal(size=(2048, 8)).astype(np.float32)
              for _ in range(6)]
    cfg = StreamConfig(n_clusters=4, window=4, max_iter=50,
                       driver_sample=256, seed=0)

    def run_once(enabled: bool) -> float:
        obs.set_enabled(enabled)
        obs.reset_all()
        model = StreamingBigFCM(cfg)
        model.ingest(chunks[0])              # compile warm-up
        t0 = time.perf_counter()
        for x in chunks[1:]:
            model.ingest(x)
        return time.perf_counter() - t0

    run_once(True)                           # shared warm-up pass
    # interleaved min-of-N: min is the load-robust estimator of the
    # true cost (a background GC/scheduler spike inflates any single
    # run, and the suite shares this host with other tests)
    on = min(run_once(True) for _ in range(7))
    off = min(run_once(False) for _ in range(7))
    obs.set_enabled(True)
    slack = 2e-3                             # 2 ms absolute timer noise
    assert on <= off * 1.05 + slack, \
        f"obs overhead {(on - off) / off * 100:.1f}% (on={on:.4f}s " \
        f"off={off:.4f}s) exceeds the 5% budget"

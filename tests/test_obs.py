"""repro.obs: histogram percentile accuracy, exact cross-shard merge,
span nesting/exception safety, the disabled-mode zero-cost guard, the
metrics_snapshot/v1 export contract, and the span log: profiler
annotations, parents and keys, compile counters, the serve fold."""

import glob
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import FQuantConfig
from repro.core import qat_store as qs
from repro.core.tiers import TierConfig
from repro.obs import trace as obs_trace
from repro.obs.registry import NUM_BUCKETS, Histogram, Registry
from repro.obs.trace import SPAN_LOG_SIZE
from repro.serve import OnlineConfig, OnlineServer

_SCHEMA_TOOL = (pathlib.Path(__file__).resolve().parents[1]
                / "tools" / "check_bench_schema.py")
_spec = importlib.util.spec_from_file_location("check_bench_schema",
                                               _SCHEMA_TOOL)
check_bench_schema = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_bench_schema)


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test starts (and leaves) the default registry disabled,
    empty and sink-less — the process-global state must never leak."""
    obs.disable()
    obs.get_registry().reset()
    obs.set_sink(None)
    yield
    obs.disable()
    obs.get_registry().reset()
    obs.set_sink(None)


# -- histogram ---------------------------------------------------------

def _rel_err(est, ref):
    return abs(est - ref) / max(abs(ref), 1e-12)


@pytest.mark.parametrize("draw", [
    lambda rng: rng.uniform(5.0, 5e4, 4000),
    lambda rng: rng.lognormal(7.0, 1.5, 4000),     # heavy tail, ~us scale
])
def test_histogram_percentiles_track_numpy(draw):
    rng = np.random.default_rng(0)
    vals = draw(rng)
    h = Histogram()
    h.record_many(vals)
    for q in (50, 95, 99):
        ref = float(np.percentile(vals, q))
        # log-bucket resolution bound: RATIO - 1 ~ 7.5% relative
        assert _rel_err(h.percentile(q), ref) < 0.075, (q, ref)
    assert h.count == vals.size
    assert h.vmin == vals.min() and h.vmax == vals.max()
    assert np.isclose(h.total, vals.sum())


def test_histogram_exact_on_constant_stream():
    h = Histogram()
    h.record_many(np.full(100, 1234.5))
    for q in (50, 95, 99):
        assert h.percentile(q) == 1234.5    # clamped to [min, max]


def test_histogram_merge_is_exact_and_associative():
    rng = np.random.default_rng(1)
    parts = [rng.lognormal(6.0, 2.0, n) for n in (300, 700, 50)]
    hs = []
    for p in parts:
        h = Histogram()
        h.record_many(p)
        hs.append(h)

    union = Histogram()
    union.record_many(np.concatenate(parts))

    ab_c = Histogram().merge(hs[0]).merge(hs[1]).merge(hs[2])
    c_ab = Histogram().merge(hs[2]).merge(hs[0]).merge(hs[1])
    for merged in (ab_c, c_ab):
        np.testing.assert_array_equal(merged.counts, union.counts)
        assert merged.count == union.count
        assert merged.vmin == union.vmin and merged.vmax == union.vmax
        for q in (50, 95, 99):
            assert merged.percentile(q) == union.percentile(q)
        assert np.isclose(merged.total, union.total)


def test_histogram_snapshot_round_trip():
    rng = np.random.default_rng(2)
    h = Histogram()
    h.record_many(rng.uniform(0.1, 1e6, 500))    # incl. underflow bucket
    back = Histogram.from_snapshot(
        json.loads(json.dumps(h.snapshot())))    # via actual JSON
    np.testing.assert_array_equal(back.counts, h.counts)
    assert back.count == h.count
    assert back.vmin == h.vmin and back.vmax == h.vmax
    for q in (50, 95, 99):
        assert back.percentile(q) == h.percentile(q)
    empty = Histogram.from_snapshot(Histogram().snapshot())
    assert empty.count == 0 and empty.percentile(99) == 0.0
    assert len(h.counts) == NUM_BUCKETS


# -- registry gating ---------------------------------------------------

def test_disabled_registry_records_nothing():
    obs.inc("a")
    obs.gauge("b", 1.0)
    obs.observe("c", 2.0)
    obs.ensure_histograms(["d_us"])
    with obs.span("e"):
        pass
    reg = obs.get_registry()
    assert not reg.counters and not reg.gauges and not reg.histograms
    assert obs.span("e") is obs.span("f")      # shared no-op singleton


def test_enabled_registry_records_and_merges():
    obs.enable()
    obs.inc("req", 3)
    obs.inc("req")
    obs.gauge("occ", 0.5)
    obs.observe("lat_us", 100.0)
    reg = obs.get_registry()
    assert reg.counters["req"] == 4
    assert reg.gauges["occ"] == 0.5
    assert reg.histograms["lat_us"].count == 1

    other = Registry()
    other.inc("req", 10)
    other.gauge("occ", 0.9)
    other.observe("lat_us", 200.0)
    reg.merge(other)
    assert reg.counters["req"] == 14
    assert reg.gauges["occ"] == 0.9            # last write wins
    assert reg.histograms["lat_us"].count == 2


# -- spans / timeblock -------------------------------------------------

def test_span_nesting_paths_and_exception_safety():
    obs.enable()
    with obs.span("outer") as so:
        assert so.path == "outer"
        with obs.span("inner") as si:
            assert si.path == "outer/inner"
            assert obs.current_path() == "outer/inner"
        assert obs.current_path() == "outer"
    assert obs.current_path() == ""

    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    assert obs.current_path() == ""            # stack popped on raise
    reg = obs.get_registry()
    for name in ("outer_us", "inner_us", "boom_us"):
        assert reg.histograms[name].count == 1  # recorded despite raise


def test_timeblock_always_measures_records_only_when_enabled():
    with obs.timeblock("t") as tb:
        tb.sync(jnp.arange(8) * 2)
    assert tb.seconds > 0.0                    # wall clock is always on
    assert not obs.get_registry().histograms   # ... recording is not

    obs.enable()
    tb = obs.timeblock("t").start()
    tb.stop()                                  # explicit protocol
    assert obs.get_registry().histograms["t_us"].count == 1


# -- export ------------------------------------------------------------

def test_snapshot_validates_and_statsd_lines(tmp_path):
    obs.enable()
    obs.inc("serve.requests", 7)
    obs.gauge("store.hot_rows", 42.0)
    obs.observe("serve.request_us", 1500.0)
    obs.ensure_histograms(["store.migrate_us"])   # count-0 histogram
    snap = obs.snapshot()
    assert snap["schema"] == "metrics_snapshot/v1"
    assert check_bench_schema.validate(snap) == []
    assert snap["histograms"]["store.migrate_us"]["count"] == 0

    lines = obs.statsd_lines()
    assert "serve.requests:7|c" in lines
    assert "store.hot_rows:42|g" in lines
    assert any(ln.startswith("serve.request_us.p99:") for ln in lines)


def test_jsonl_sink_tick_cadence_and_flush(tmp_path):
    path = tmp_path / "m.jsonl"
    obs.enable()
    obs.set_sink(obs.JsonlSink(str(path), every=3))
    for _ in range(7):
        obs.inc("n")
        obs.tick()
    obs.flush()
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(recs) == 3                      # ticks 3, 6 + final flush
    assert [r["seq"] for r in recs] == [1, 2, 3]
    assert recs[-1]["ticks"] == 7
    assert recs[-1]["counters"]["n"] == 7
    for r in recs:
        assert check_bench_schema.validate(r) == []


def test_tick_and_flush_noop_when_disabled(tmp_path):
    path = tmp_path / "m.jsonl"
    obs.set_sink(obs.JsonlSink(str(path), every=1))
    for _ in range(5):
        obs.tick()
    obs.flush()
    assert path.read_text() == ""              # no snapshot when off
    assert obs.get_registry().ticks == 0


# -- instrumented serving ----------------------------------------------

V, D = 160, 24
CFG = FQuantConfig(tiers=TierConfig(t8=5.0, t16=50.0), stochastic=False)


def _store(seed=0):
    rng = np.random.default_rng(seed)
    st = qs.init(jax.random.PRNGKey(seed), V, D, scale=0.05)
    pri = jnp.asarray((rng.pareto(1.2, V) * 20).astype(np.float32))
    st = st._replace(priority=pri)
    return st._replace(table=qs.snap(
        st.table, qs.current_tiers(st, CFG), CFG))


def test_eager_lookup_valid_excludes_padding_from_accounting():
    st = _store(5)
    srv = OnlineServer(st, CFG,
                       OnlineConfig(cache_rows=24, retier_every=0))
    hot = np.asarray(srv.cache.ids)[:2]
    idx = np.stack([np.array([hot[0], hot[1]]),
                    np.array([0, 0])]).astype(np.int32)  # row 2 = pad
    valid = np.array([True, False])[:, None]

    ref = OnlineServer(st, CFG,
                       OnlineConfig(cache_rows=24, retier_every=0))
    out_m = srv.lookup(jnp.asarray(idx), valid=valid, count=1)
    out_p = ref.lookup(jnp.asarray(idx[:1]), count=1)
    # masking fixes the books, never the rows
    np.testing.assert_array_equal(np.asarray(out_m)[:1],
                                  np.asarray(out_p))
    assert srv.stats.lookups == ref.stats.lookups == 2
    assert srv.stats.hits == ref.stats.hits == 2
    assert srv.stats.hit_rate == 1.0           # padding no longer dilutes
    np.testing.assert_array_equal(np.asarray(srv.store.priority),
                                  np.asarray(ref.store.priority))


def test_serving_bit_identical_with_metrics_on(tmp_path):
    """The disabled-mode overhead guard: turning the registry on must
    not change a single served byte, and turning it off must leave no
    snapshot behind."""
    idx = np.arange(8, dtype=np.int32).reshape(4, 2)

    def serve_once():
        srv = OnlineServer(_store(6), CFG,
                           OnlineConfig(cache_rows=16, retier_every=2))
        out = [np.asarray(srv.lookup(jnp.asarray(idx), count=1))
               for _ in range(4)]
        return np.stack(out)

    off = serve_once()
    assert not obs.get_registry().histograms

    obs.enable()
    path = tmp_path / "m.jsonl"
    obs.set_sink(obs.JsonlSink(str(path), every=2))
    on = serve_once()
    obs.flush()

    np.testing.assert_array_equal(on, off)     # bit-identical service
    reg = obs.get_registry()
    assert reg.counters["serve.requests"] == 4
    assert reg.histograms["serve.retier_us"].count == 2
    assert reg.gauges["serve.cache.rows"] == 16.0
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert recs and all(
        check_bench_schema.validate(r) == [] for r in recs)


# -- span log: profiler annotations, parents and keys, compiles --------

def _logged(name, since=0):
    return [e for e in obs.span_log().spans
            if e[0] == name and e[3] >= since]


def test_span_log_records_only_when_enabled_or_profiling(tmp_path):
    with obs.span("log.off"):
        pass
    assert not _logged("log.off")              # untraced, disabled

    obs.enable()
    with obs.span("log.enabled"):
        pass
    assert len(_logged("log.enabled")) == 1

    obs.disable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("log.profiling") as sp:
            pass
    finally:
        jax.profiler.stop_trace()
    [(_, parent, key, t0, t1)] = _logged("log.profiling")
    assert parent is None and key is None
    assert t1 - t0 == pytest.approx(sp.seconds * 1e9, abs=1)
    # the log, not the disabled registry
    assert "log.profiling_us" not in obs.get_registry().histograms


def test_timeblock_always_records():
    t = time.perf_counter_ns()
    with obs.timeblock("log.tb") as tb:
        pass
    tb2 = obs.timeblock("log.tb").start()
    tb2.stop()
    got = _logged("log.tb", since=t)
    assert len(got) == 2 and not obs.enabled()
    assert got[0][4] - got[0][3] == pytest.approx(tb.seconds * 1e9, abs=1)
    with obs.timeblock():                      # unnamed: a clock only
        pass
    assert all(e[0] is not None for e in obs.span_log().spans)


def test_span_log_bounded_with_parents_keys_and_exceptions():
    obs.enable()
    t = time.perf_counter_ns()
    with obs.timeblock("log.outer"):
        with obs.span("log.mid", key=7):
            with obs.span("log.inner"):
                pass
        with pytest.raises(RuntimeError):
            with obs.span("log.boom", key=9):
                raise RuntimeError("x")
    assert obs.current_path() == ""
    [outer] = _logged("log.outer", t)
    [mid] = _logged("log.mid", t)
    [inner] = _logged("log.inner", t)
    [boom] = _logged("log.boom", t)
    assert outer[1:3] == (None, None)
    assert mid[1:3] == ("log.outer", 7)
    assert inner[1:3] == ("log.mid", 7)        # key inherited
    assert boom[1:3] == ("log.outer", 9)       # recorded despite raise
    assert outer[3] <= mid[3] <= inner[3] <= inner[4] <= mid[4] \
        <= boom[3] <= boom[4] <= outer[4]

    for _ in range(SPAN_LOG_SIZE + 10):
        with obs.timeblock("log.fill"):
            pass
    spans = obs.span_log().spans
    assert len(spans) == SPAN_LOG_SIZE
    assert spans[-1][0] == "log.fill" and not _logged("log.outer", t)


def test_untraced_span_costs_two_flag_checks(monkeypatch):
    """Disabled registry, no profiler: ``span`` reads the registry's
    flag and the profiler's once each, then hands out the shared no-op
    without reading a clock, building a span or touching the log."""
    class Refuse:
        def __init__(self, what):
            self.what = what

        def __getattr__(self, name):
            raise AssertionError(f"{self.what}.{name} used")

        def __call__(self, *args, **kwargs):
            raise AssertionError(f"{self.what} called")

    class Flag:
        @property
        def enabled(self):
            checks.append("registry")
            return False

    checks = []
    tracing = obs_trace._tracing
    monkeypatch.setattr(obs_trace, "_tracing",
                        lambda: checks.append("profiler") or tracing())
    monkeypatch.setattr(obs_trace, "_reg",
                        SimpleNamespace(get_registry=Flag))
    for name in ("time", "Span", "_log", "_stack"):
        monkeypatch.setattr(obs_trace, name, Refuse(name))
    with obs.span("cost") as sp:
        pass
    assert checks == ["registry", "profiler"]
    assert sp is obs_trace._NULL_SPAN


def test_program_spans_land_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("serve.fold", key=0):
            with obs.span("serve.fold.priority"):
                jnp.arange(8).sum().block_until_ready()
        with obs.timeblock("store.snap"):
            pass
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    host = {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert {"serve.fold", "serve.fold.priority", "store.snap"} <= host


def test_compiles_and_cache_loads_are_counted(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    c = float(np.random.default_rng().integers(1, 1 << 30))

    def fresh(x):
        return x * c + 1.0

    t = time.perf_counter_ns()
    before = obs.span_log().compiles
    jax.jit(fresh)(jnp.float32(2.0)).block_until_ready()
    after = obs.span_log().compiles
    assert after["jax.compile"][0] >= before["jax.compile"][0] + 1
    assert after["jax.compile"][1] > before["jax.compile"][1]
    assert _logged("jax.compile", t)

    # the same program again, from a persistent cache: a cache load
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs,
            jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        g = lambda x: x * c - 1.0  # noqa: E731
        jax.jit(g)(jnp.float32(2.0)).block_until_ready()
        jax.clear_caches()
        mid = obs.span_log().compiles
        jax.jit(g)(jnp.float32(2.0)).block_until_ready()
        end = obs.span_log().compiles
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          prev[2])
        compilation_cache.reset_cache()
    assert end["jax.cache_load"][0] >= mid["jax.cache_load"][0] + 1
    assert end["jax.cache_load"][1] > mid["jax.cache_load"][1]


def test_train_set_up_counts_its_own_compiles():
    """A process that only trains (no serving, no ``repro.obs`` import
    of its own) still has the compile listener before its first
    compile."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.train.setup import build_recsys_training\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.ones(3)).block_until_ready()\n"
            "from repro import obs\n"
            "print(obs.span_log().compiles['jax.compile'][0])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(pathlib.Path(__file__).resolve().parents[1]
                              / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert int(out.stdout.split()[-1]) >= 1


def test_observe_logs_serve_fold_keyed_by_micro_batch():
    obs.enable()
    srv = OnlineServer(_store(3), CFG,
                       OnlineConfig(cache_rows=0, retier_every=8))
    idx = jnp.asarray((np.arange(16) % V).astype(np.int32).reshape(4, 4))
    valid = np.array([True, True, True, False])[:, None]
    t = time.perf_counter_ns()
    for _ in range(3):
        srv.observe(idx, 0, valid=valid, count=3)
    log = [e for e in obs.span_log().spans if e[3] >= t]
    folds = [e for e in log if e[0] == "serve.fold"]
    assert [e[2] for e in folds] == [0, 3, 6]   # requests before each
    for name, parent, key, s, e in folds:
        kids = {n: (p, k, a, b) for n, p, k, a, b in log
                if p == "serve.fold" and k == key}
        assert set(kids) == {"serve.fold.mask", "serve.fold.priority",
                             "serve.fold.retier"}
        assert all(s <= a <= b <= e for _, _, a, b in kids.values())
    # the third batch crosses request 8: its re-tier is under the fold
    [retier] = [e for e in log if e[0] == "serve.retier"]
    assert retier[1:3] == ("serve.fold.retier", 6)

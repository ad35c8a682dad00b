"""The readers of the program's span log, on the trace recorded on one
TPU v5e (``data/serve.trace.json.gz``) with program spans placed by hand
inside its ``observe`` spans, on a clock offset by a known amount."""

import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench.lib import program_spans as ps
from bench.lib import registry
from bench.lib import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "serve.trace.json.gz")
SPANS = {"window", "feed", "forward", "observe"}
OFFSET = -987_654_321_012     # the profiler's clock minus perf_counter_ns
JITTER = 3_000                # ns, each harness record's own error


def _window(profile):
    return next((e.start_ns, e.start_ns + e.duration_ns)
                for p in profile.planes if p.name.startswith("/host:")
                for ln in p.lines for e in ln.events if e.name == "window")


@pytest.fixture(scope="module")
def recorded():
    profile = tr.load(DATA)
    return tr.reduce(profile, SPANS, [0]), _window(profile)


def _ctx(recorded):
    """The harness's records as a run on ``perf_counter_ns`` would hold
    them: the window, the spans inside it (each off by up to JITTER),
    and a set-up batch long before it, which the trace never saw."""
    red, (w0, w1) = recorded
    rng = np.random.default_rng(0)
    recs = [("window", w0 - OFFSET, w1 - OFFSET)]
    for n, s, e in red.spans:
        if s >= w0 and e <= w1:
            j = int(rng.integers(-JITTER, JITTER + 1))
            recs.append((n, s - OFFSET + j, e - OFFSET + j))
    early = w0 - OFFSET - 10 ** 10     # a set-up batch, untraced
    recs += [(n, early + i, early + i + 5)
             for i, n in enumerate(("feed", "forward", "observe"))]
    return SimpleNamespace(spans=SimpleNamespace(records=recs),
                           trace_data=red, t_start=(w0 - OFFSET - 2e10)
                           * 1e-9)


def _program_log(recorded):
    """``serve.fold`` over the middle of each ``observe`` inside the
    window, its three children, and a compile under its priority child
    (not the fold's own time); all on ``perf_counter_ns``."""
    red, (w0, w1) = recorded
    log = []
    for key, (n, s, e) in enumerate(x for x in red.spans
                                    if x[0] == "observe"):
        if s < w0 or e > w1:
            continue
        s, e = s - OFFSET, e - OFFSET
        d = (e - s) // 20
        fs, fe = s + d, e - d
        log.append(("serve.fold", None, key, fs, fe))
        log.append(("serve.fold.mask", "serve.fold", key, fs, fs + 2 * d))
        log.append(("serve.fold.priority", "serve.fold", key, fs + 3 * d,
                    fs + 12 * d))
        log.append(("jax.compile", "serve.fold.priority", key, fs + 5 * d,
                    fs + 7 * d))
        log.append(("serve.fold.retier", "serve.fold", key, fs + 12 * d,
                    fs + 13 * d))
    return log


def _fold_idle_by_points(recorded, log):
    """The same share, another way: every elementary interval between
    consecutive boundaries is idle or busy, and under the fold or not,
    by its midpoint."""
    red, (w0, w1) = recorded
    ops = sorted((s, e) for _, s, e, _ in red.ops)
    spans = [(n, s + OFFSET, e + OFFSET) for n, _, _, s, e in log]
    cuts = sorted({w0, w1} | {t for s, e in ops for t in (s, e)}
                  | {t for _, s, e in spans for t in (s, e)})
    cuts = np.asarray([c for c in cuts if w0 <= c <= w1], np.float64)
    mid = 0.5 * (cuts[1:] + cuts[:-1])
    busy = np.zeros(mid.shape, bool)
    for s, e in ops:
        busy |= (mid >= s) & (mid < e)
    st = np.asarray([s for _, s, _ in spans], np.float64)
    en = np.asarray([e for _, _, e in spans], np.float64)
    open_ = (st[None, :] <= mid[:, None]) & (mid[:, None] < en[None, :])
    latest = np.where(open_, st[None, :], -np.inf).argmax(axis=1)
    fold = open_.any(axis=1) & np.asarray(
        [spans[i][0].startswith("serve.fold") for i in latest])
    idle = np.sum((cuts[1:] - cuts[:-1])[fold & ~busy])
    return idle * 1e-9 / red.window_s * 100.0


def test_offset_recovered_from_the_harness_spans(recorded):
    off, residual = ps.offset_ns(_ctx(recorded))
    assert abs(off - OFFSET) <= JITTER
    assert residual <= 2 * JITTER


def test_fold_idle_and_fold_ms_read_the_recorded_trace(recorded,
                                                       monkeypatch):
    log = _program_log(recorded)
    monkeypatch.setattr(ps, "log", lambda: log)
    ctx = _ctx(recorded)
    idle = registry.metric_reader("fold_idle.serve").read(ctx)
    want = _fold_idle_by_points(recorded, log)
    assert want > 1.0                      # the fold does hold it idle
    # the reader's alignment may be off by the jitter, at 4 edges a span
    edges = 4 * len([e for e in log if e[0] == "serve.fold"])
    assert idle == pytest.approx(want, abs=edges * 2 * JITTER
                                 / (recorded[0].window_s * 1e9) * 100)
    assert idle <= recorded[0].idle_share * 100.0

    folds = [e - s for n, _, _, s, e in log if n == "serve.fold"]
    ms = registry.metric_reader("fold_ms.serve").read(ctx)
    assert ms == pytest.approx(np.mean(folds) * 1e-6, rel=1e-12)


def test_set_up_and_window_counts(recorded, monkeypatch):
    ctx = _ctx(recorded)
    w0, w1 = ps.window_ns(ctx)
    t0 = int(ctx.t_start * 1e9)
    log = [("store.snap", None, None, t0 - 50, t0 + 10),   # another run
           ("store.plan", None, None, t0 + 100, t0 + 2 * 10 ** 9),
           ("store.pack", None, None, t0 + 3 * 10 ** 9, t0 + 4 * 10 ** 9),
           ("jax.compile", "store.pack", None, t0 + 3 * 10 ** 9,
            t0 + 3 * 10 ** 9 + 5 * 10 ** 8),
           ("jax.cache_load", None, None, w0 - 10 ** 9, w0 - 1),
           ("jax.compile", "serve.fold", 3, w0 + 10, w0 + 20),
           ("jax.compile", None, None, w1 + 10, w1 + 20)]   # the check
    monkeypatch.setattr(ps, "log", lambda: log)
    read = {m: registry.metric_reader(m).read(ctx) for m in (
        "store_build_s.serve", "compile_s.serve", "compile_s.train",
        "window_compiles.serve", "window_compiles.train")}
    # the compile inside store.pack is compile time, not store time
    assert read["store_build_s.serve"] == pytest.approx(2.0 - 1e-7 + 0.5)
    assert read["compile_s.serve"] == read["compile_s.train"] == \
        pytest.approx(0.5 + 1.0 - 1e-9)
    assert read["window_compiles.serve"] == \
        read["window_compiles.train"] == 1


def test_innermost_cuts_nested_spans_by_hand():
    spans = [("p", 0, 10), ("a", 1, 3), ("b", 5, 7), ("c", 5, 6),
             ("q", 12, 14)]
    assert sorted(ps.innermost(spans), key=lambda x: x[1]) == [
        ("p", 0, 1), ("a", 1, 3), ("p", 3, 5), ("c", 5, 6), ("b", 6, 7),
        ("p", 7, 10), ("q", 12, 14)]


NEW = ("fold_ms.serve", "fold_idle.serve", "store_build_s.serve",
       "compile_s.serve", "compile_s.train", "window_compiles.serve",
       "window_compiles.train")


@pytest.mark.parametrize("metric", NEW)
def test_reader_reads_nothing_without_a_span_log(recorded, monkeypatch,
                                                 metric):
    """A program that keeps no span log gives no reading and no error."""
    monkeypatch.setattr(ps, "log", lambda: None)
    assert registry.metric_reader(metric).read(_ctx(recorded)) is None

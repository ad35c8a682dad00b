"""The trace reduction, on a trace recorded on one TPU v5e (a short
traced window of ``dlrm-rm2.serve-zipf.sat``) and on hand-made
intervals."""

import os

import numpy as np
import pytest

from bench.lib import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "serve.trace.json.gz")
SPANS = {"window", "feed", "forward", "observe"}


def test_union_and_innermost_by_hand():
    total, merged = tr._union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert total == 6 and merged == [(0, 3), (5, 8)]
    spans = [("forward", 0, 10), ("inner", 2, 4), ("observe", 10, 20)]
    starts = [s[1] for s in spans]
    assert tr._innermost(spans, starts, 3) == "inner"
    assert tr._innermost(spans, starts, 5) == "forward"
    assert tr._innermost(spans, starts, 15) == "observe"
    assert tr._innermost(spans, starts, 25) == tr.NO_SPAN


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(tr.load(DATA), SPANS)


def test_recorded_trace_busy_and_idle(reduced):
    r = reduced
    assert r.devices == 1
    assert 0 < r.busy_s <= r.window_s
    # busy is the union of the operations' intervals
    iv = sorted((s, e) for _, s, e, _ in r.ops)
    covered, end = 0.0, -np.inf
    for s, e in iv:
        if e > end:
            covered += e - max(s, end)
            end = e
    assert covered * 1e-9 == pytest.approx(r.busy_s, rel=1e-9)
    # every idle nanosecond is attributed to some span (or to none)
    assert sum(r.gap_s.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    # per-name sums cover every operation once
    assert sum(r.op_s.values()) == pytest.approx(
        sum(e - s for _, s, e, _ in r.ops) * 1e-9, rel=1e-9)


def test_recorded_trace_names_the_kernels(reduced):
    """Three gather kernels per micro-batch, one per tier, all inside
    the forward's jitted program; the relayout dominates it."""
    from bench.lib import registry
    gather = registry.metric_reader("gather_roofline.serve").is_kernel
    relayout = registry.metric_reader("relayout_ms.serve")
    kernels = {tr.head(n) for n in reduced.op_s if gather(n)}
    assert len(kernels) == 3, kernels
    fwd = sum(e - s for _, s, e, m in reduced.ops if m == "jit_fwd")
    assert reduced.op_time(gather, module="jit_fwd") > 0
    assert reduced.op_time(relayout.is_relayout, module="jit_fwd") \
        > 0.8 * fwd * 1e-9
    assert set(reduced.gap_s) <= SPANS | {tr.NO_SPAN}


def test_leaves_drop_loop_events():
    ops = [("while.1", 0, 10), ("body.a", 0, 4), ("body.b", 5, 10),
           ("next", 12, 13)]
    assert [o[0] for o in tr._leaves(ops)] == ["body.a", "body.b", "next"]


def test_short_names():
    n = ("%fusion.42 = (s8[4050848,64]{1,0:T(8,128)(4,1)}, "
         "s8[4050848,64]{1,0:T(8,128)(4,1)}) fusion(s8[8101663,64]"
         "{1,0:T(8,128)(4,1)} %copy.31), kind=kLoop")
    assert tr.short(n) == \
        "fusion.42 = (s8[4050848,64], s8[4050848,64]) fusion"
    assert tr.head(n) == "fusion.42"


def test_breakdown_shape(reduced):
    b = reduced.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10
    assert len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)

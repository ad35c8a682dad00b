"""The trace reduction, on a trace recorded on one TPU v5e (a short
traced window of ``dlrm-rm2.serve-zipf.sat``) and on hand-made
intervals."""

import os
from types import SimpleNamespace

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
    return tr.reduce(tr.load(DATA), SPANS, [0])


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


# the excerpt's busy and window seconds as the reduction read every
# device plane of a profile, before it took the cell's device ids
BUSY_S, WINDOW_S = 0.212959897, 0.25


def _with_idle_second_device(profile):
    """The excerpt as a two-chip host's profile would hold it for a
    one-chip cell: a second device plane on which nothing ran."""
    idle = tr._Plane(tr.DEVICE_PREFIX + "1", [tr._Line(tr.OPS_LINE, [])])
    return SimpleNamespace(planes=list(profile.planes) + [idle])


def test_reduce_reads_only_the_cells_devices(reduced):
    assert (reduced.busy_s, reduced.window_s) == pytest.approx(
        (BUSY_S, WINDOW_S), rel=1e-12)
    assert reduced.idle_share == pytest.approx(1 - BUSY_S / WINDOW_S,
                                               rel=1e-12)
    host = _with_idle_second_device(tr.load(DATA))
    own = tr.reduce(host, SPANS, [0])
    assert (own.devices, own.busy_s, own.window_s, own.op_s, own.gap_s) \
        == (1, reduced.busy_s, reduced.window_s, reduced.op_s,
            reduced.gap_s)
    both = tr.reduce(host, SPANS, [0, 1])
    assert both.devices == 2
    assert both.busy_s == pytest.approx(reduced.busy_s / 2, rel=1e-12)
    assert both.op_s == reduced.op_s


def test_reduce_refuses_a_device_the_trace_lacks():
    with pytest.raises(ValueError, match="TPU:1"):
        tr.reduce(tr.load(DATA), SPANS, [0, 1])


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

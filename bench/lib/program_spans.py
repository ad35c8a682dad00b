"""The program's own span log (``repro.obs.span_log``) read against the
harness's records.

Both are kept on ``time.perf_counter_ns`` in the one process, so the
harness's ``window`` record selects the program's spans and compiles
of the measured window, or of the set-up before it, with no alignment.
Only the device trace runs on the profiler's clock: ``offset_ns`` finds
that clock's offset from the harness spans that both record.

A program without a span log (``log`` returns None) reads as nothing:
every reader then returns None.
"""

from __future__ import annotations

import statistics

FOLD = "serve.fold"
STORE = ("store.plan", "store.snap", "store.pack", "store.place")
COMPILES = ("jax.compile", "jax.cache_load")
# harness spans recorded both in ctx.spans.records and in the trace
ALIGN = ("feed", "forward", "observe")


def log() -> list | None:
    """The program's span entries ``(name, parent, key, start_ns,
    end_ns)``, or None where the program keeps no span log."""
    try:
        from repro.obs import span_log
    except ImportError:
        return None
    return span_log().spans


def window_ns(ctx) -> tuple[int, int]:
    """The measured window on ``perf_counter_ns``: the harness's
    ``window`` record."""
    return next((s, e) for n, s, e in ctx.spans.records if n == "window")


def before_window(ctx, names) -> list | None:
    """Entries of ``names`` that ended after this run started and before
    its window opened."""
    spans = log()
    if spans is None:
        return None
    t0, (w0, _) = int(ctx.t_start * 1e9), window_ns(ctx)
    return [sp for sp in spans
            if sp[0] in names and sp[3] >= t0 and sp[4] <= w0]


def in_window(ctx, names) -> list | None:
    """Entries of ``names`` that ended inside the window."""
    spans = log()
    if spans is None:
        return None
    w0, w1 = window_ns(ctx)
    return [sp for sp in spans if sp[0] in names and w0 < sp[4] <= w1]


def seconds(entries) -> float:
    return sum(e - s for _, _, _, s, e in entries) * 1e-9


def self_seconds(entries, inner) -> float:
    """Seconds of ``entries`` less those of the ``inner`` entries that
    lie inside one of them."""
    return seconds(entries) - seconds(
        [i for i in inner
         if any(s <= i[3] and i[4] <= e for _, _, _, s, e in entries)])


def _pair(perf, prof) -> list:
    """Pair the same spans as two clocks recorded them, in order; one
    list may hold a few more at its ends (spans cut by the window's
    edges): take the shift whose start differences spread least."""
    flip = len(perf) > len(prof)
    short, long_ = (prof, perf) if flip else (perf, prof)
    spread = []
    for k in range(len(long_) - len(short) + 1):
        d = [b[0] - a[0] for a, b in zip(short, long_[k:])]
        spread.append(max(d) - min(d))
    k = spread.index(min(spread))
    pairs = list(zip(short, long_[k:]))
    return [(b, a) for a, b in pairs] if flip else pairs


def offset_ns(ctx) -> tuple[float, float] | None:
    """(offset, residual): the profiler's clock minus ``perf_counter_ns``,
    the median over the window's harness spans that both the records and
    the trace hold, and the largest distance of a pair's start or end
    from it."""
    w0, w1 = window_ns(ctx)
    diffs = []
    for name in ALIGN:
        perf = sorted((s, e) for n, s, e in ctx.spans.records
                      if n == name and w0 <= s and e <= w1)
        prof = sorted((s, e) for n, s, e in ctx.trace_data.spans
                      if n == name)
        if perf and prof:
            for (ps, pe), (ts, te) in _pair(perf, prof):
                diffs += [ts - ps, te - pe]
    if not diffs:
        return None
    off = statistics.median(diffs)
    return off, max(abs(d - off) for d in diffs)


def innermost(spans) -> list:
    """Cut properly nested ``(name, start, end)`` spans into the pieces
    in which each is the innermost one open: ``(name, start, end)``."""
    pieces, stack = [], []      # stack of [name, end, resumed-at]
    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            pieces.append((top[0], top[2], top[1]))
            if stack:
                stack[-1][2] = top[1]
        if stack:
            pieces.append((stack[-1][0], stack[-1][2], s))
        stack.append([name, e, s])
    while stack:
        top = stack.pop()
        pieces.append((top[0], top[2], top[1]))
        if stack:
            stack[-1][2] = top[1]
    return [p for p in pieces if p[2] > p[1]]


def _merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def idle_under(ctx, prefix: str) -> float | None:
    """Share of the traced window, in %, in which the device ran no
    operation while a program span named ``prefix`` or ``prefix.*`` was
    the innermost program span open.  One-chip cells only: the trace's
    operations carry no device number."""
    spans = log()
    if spans is None or ctx.trace_data.devices != 1:
        return None
    aligned = offset_ns(ctx)
    if aligned is None:
        return None
    off = aligned[0]
    w0, w1 = window_ns(ctx)
    inside = [(n, max(s, w0) + off, min(e, w1) + off)
              for n, _, _, s, e in spans if e > w0 and s < w1]
    mine = _merge((s, e) for n, s, e in innermost(inside)
                  if n == prefix or n.startswith(prefix + "."))
    busy = _merge((s, e) for _, s, e, _ in ctx.trace_data.ops)
    idle, j = 0.0, 0
    for s, e in mine:
        covered = 0.0
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < e:
            covered += min(e, busy[k][1]) - max(s, busy[k][0])
            k += 1
        idle += (e - s) - covered
    return idle * 1e-9 / ctx.trace_data.window_s * 100.0

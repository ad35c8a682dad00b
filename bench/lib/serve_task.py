"""Serve cells: set-up through the program's entries, the measured
window, and the comparison with the plain reference.

Set-up: weights from the seed (``weights``), the store through
``launch.serve.build_serving_store`` with the priority profile the
config states (its own fixed seed, so every run has the same tier
sizes), ``serve.OnlineServer`` with the
default ``OnlineConfig`` (no hot-row cache, no re-tier; the Eq. 7 fold
runs on every ``observe``), and one short ``serve.serve_forward`` call,
which compiles the forward and returns it jitted
(``LoopResult.forward``).  Then one harness micro-batch warms the same
program and the fold at the window's shapes.

Chips: a cell on more than one chip passes its ``"model"`` mesh
(``Ctx.mesh``) to ``OnlineServer``, so the store is row-sharded over
the cell's devices and served through the program's sharded lookup;
the weights are drawn with the table row-sharded and every other leaf
replicated.  On one chip there is no mesh and every call is the
one-device call.

Window: per micro-batch, the few lines of ``serve_forward``'s glue,
copied: the batch dict, the jitted forward with ``block_until_ready``,
and ``OnlineServer.observe``.  The requests come from
``bench/traffic`` and the seed alone, made before the window.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench.lib import shark_ref, traffic, weights
from bench.lib.context import Ctx, build_model, global_ids

_REF_CHUNK = 4096


class Served:
    """The program's serving state and the window's record."""

    def __init__(self):
        self.outs = []         # (first request, count, device logits)
        self.lat = []          # per request: due -> output ready, s
        self.queue = []        # per request: due -> dispatch, s
        self.batch = []        # per request: its batch's dispatch ->
                               # output ready, s


def setup(ctx: Ctx):
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import build_serving_store
    from repro.serve import OnlineConfig, OnlineServer, serve_forward

    sizes = ctx.sizes
    model = build_model(ctx.cfg, sizes)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make(shapes, ctx.cfg["init"], ctx.seed, ctx.mesh)
    table = params.pop(ctx.cfg["program"]["table"])
    store, fq = build_serving_store(
        model.spec, table, seed=ctx.cfg["serving_store"]["priority"]["seed"])
    del table
    server = OnlineServer(store, fq, online=OnlineConfig(), mesh=ctx.mesh)
    del store
    nd = int(sizes.get("num_dense", 0))
    mb = int(ctx.mix["micro_batch"])
    res = serve_forward(server, model, model.spec, params,
                        serve_batch=mb, requests=mb, num_dense=nd,
                        seed=ctx.seed)
    fwd = res.forward[0]
    del res
    if "forward" in ctx.hooks:                 # tests: break the path
        fwd = ctx.hooks["forward"](fwd)

    st = Served()
    st.model, st.server, st.params, st.fwd = model, server, params, fwd
    st.nd, st.mb = nd, mb
    st.cards = list(model.spec.cardinalities)
    st.pool = traffic.serve_pool(ctx.mix, st.cards, nd, ctx.seed)
    st.npool = st.pool["indices"].shape[0]
    st.labels = jnp.zeros((mb,))
    # the window's shapes, once: forward and fold
    serve_batch(ctx, st, 0, mb)
    jax.block_until_ready(st.server.store.priority)
    st.outs.clear()
    return st


def serve_batch(ctx: Ctx, st: Served, first: int, count: int):
    """One micro-batch of requests first..first+count-1 (pool order,
    cycled), padded to the micro-batch as ``MicroBatcher`` pads."""
    import jax
    import jax.numpy as jnp

    sp = ctx.spans
    mb = st.mb
    with sp("feed"):
        rows = (first + np.arange(mb)) % st.npool
        idx = st.pool["indices"][rows]
        valid_np = np.arange(mb) < count
        if count < mb:
            idx = np.where(valid_np[:, None], idx, 0).astype(np.int32)
        b = {"indices": jnp.asarray(idx), "labels": st.labels}
        if st.nd:
            b["dense"] = jnp.asarray(st.pool["dense"][rows])
        valid = jnp.asarray(valid_np)
    with sp("forward"):
        out, hits, gidx = st.fwd(st.server.packed, st.server.cache,
                                 st.params, b, valid)
        jax.block_until_ready(out)
    with sp("observe"):
        st.server.observe(gidx, int(hits), valid=valid_np[:, None],
                          count=count)
    st.outs.append((first, count, out))
    return out


def window(ctx: Ctx, st: Served) -> None:
    import jax
    if ctx.mix["arrivals"] == "closed":
        _closed(ctx, st)
    else:
        _open(ctx, st)
    jax.block_until_ready(st.server.store.priority)


def _closed(ctx: Ctx, st: Served) -> None:
    """Back to back: one micro-batch in flight, ``micro_batch`` waiting
    clients."""
    mb, n = st.mb, 0
    t0 = time.perf_counter()
    end = t0 + ctx.window_seconds
    with ctx.spans("window"):
        while True:
            serve_batch(ctx, st, n, mb)
            n += mb
            if time.perf_counter() >= end:
                break
    elapsed = time.perf_counter() - t0
    ctx.attempted = n
    ctx.counts.update(requests=n, batches=n // mb, window_s=elapsed)
    ctx.e2e["serve_qps"] = n / elapsed


def _open(ctx: Ctx, st: Served) -> None:
    """Open loop: single requests due at Poisson times; whenever the
    device is free, every due request (up to a micro-batch) goes as one
    padded batch.  Requests due in the window are all served, late
    ones after it closes; latency runs from the due time."""
    due = traffic.due_times(ctx.mix, ctx.seed, ctx.window_seconds)
    mb, n, total = st.mb, 0, len(due)
    t0 = time.perf_counter()
    with ctx.spans("window"):
        while n < total:
            now = time.perf_counter() - t0
            if due[n] > now:
                with ctx.spans("wait"):
                    while time.perf_counter() - t0 < due[n]:
                        pass
                now = time.perf_counter() - t0
            k = min(mb, int(np.searchsorted(due, now, "right")) - n)
            t_disp = time.perf_counter() - t0
            serve_batch(ctx, st, n, k)
            t_ready = time.perf_counter() - t0
            st.lat.extend(t_ready - due[n:n + k])
            st.queue.extend(t_disp - due[n:n + k])
            st.batch.extend([t_ready - t_disp] * k)
            n += k
    elapsed = time.perf_counter() - t0
    ctx.attempted = total
    ctx.counts.update(requests=n, batches=len(st.outs), window_s=elapsed)
    lat = np.asarray(st.lat)
    ctx.e2e["serve_p99_ms"] = float(np.quantile(lat, 0.99)) * 1e3
    ctx.extra["lat_ms"] = lat * 1e3
    ctx.extra["queue_ms"] = np.asarray(st.queue) * 1e3
    ctx.extra["batch_ms"] = np.asarray(st.batch) * 1e3


def footprint(ctx: Ctx, st: Served) -> None:
    """Served bytes on the fullest of the cell's devices once the window
    closed, with the harness's own set-up arrays dropped: what decides
    whether the store fits its chips."""
    from bench.lib.chip import bytes_in_use
    st.labels = None
    gc.collect()
    ctx.e2e["serve_hbm_gib"] = max(
        bytes_in_use(d) for d in ctx.devices) / 2 ** 30


def check(ctx: Ctx, st: Served) -> None:
    """Compare a sample of the window's answers, drawn from the seed,
    with the plain reference.  Runs after the program's state is
    freed."""
    import jax

    rng = traffic.rng_for(ctx.seed, 7)
    want = int(ctx.mix["sample_requests"])
    order = rng.permutation(len(st.outs))
    picked = [len(st.outs) - 1] + [i for i in order
                                   if i != len(st.outs) - 1]
    reqs, outs, n = [], [], 0
    for i in picked:
        first, count, out = st.outs[i]
        reqs.append(first + np.arange(count))
        outs.append(np.asarray(jax.device_get(out))[:count])
        n += count
        if n >= want:
            break
    req = np.concatenate(reqs)
    got = np.concatenate(outs).astype(np.float32)
    rows = req % st.npool
    idx = st.pool["indices"][rows]
    dense = st.pool["dense"][rows] if st.nd else None
    shapes = jax.eval_shape(st.model.init, jax.random.PRNGKey(0))
    cards = st.cards
    prog_rows = _program_rows(st, idx) if ctx.control else None
    # the program's state goes before the reference runs
    st.server = st.params = st.fwd = st.outs = None
    gc.collect()

    import jax.numpy as jnp
    vocab, dim = shapes[ctx.cfg["program"]["table"]].shape
    tiers = reference_tiers(ctx, vocab, dim)
    if ctx.trace:
        # rows by tier of every slot the traced window served
        served = np.arange(ctx.counts["requests"]) % st.npool
        per_req = np.asarray(jnp.take(tiers, jnp.asarray(global_ids(
            cards, st.pool["indices"]))))
        ctx.counts["slots_by_tier"] = np.bincount(
            per_req[served].ravel(), minlength=3)[:3].tolist()
    prec = shark_ref.default_precision(ctx.platform)
    ref = reference_logits(ctx, shapes, tiers, cards, idx, dense, prec)
    ctx.readings["logit_gap"] = _gap(got, ref)
    ctx.counts["checked_requests"] = int(req.size)
    if ctx.control:
        ctrl = reference_logits(ctx, shapes, tiers, cards, idx, dense,
                                shark_ref.BELOW[prec])
        ctx.readings["control.logit_gap"] = _gap(ctrl, ref)
        # where a gap comes from: the rows, or the head's arithmetic
        ref_rows = reference_rows(ctx, shapes, tiers, cards, idx)
        ctx.readings["diag.row_gap"] = _gap(prog_rows, ref_rows)
        same = reference_logits(ctx, shapes, tiers, cards, idx, dense,
                                prec, rows=prog_rows)
        ctx.readings["diag.head_gap"] = _gap(got, same)


def _gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape or not np.isfinite(a).all():
        return float("inf")
    return float(np.max(np.abs(a - b)))


def _program_rows(st: Served, idx):
    """The rows the program's gather serves for these requests."""
    import jax
    import jax.numpy as jnp
    look = jax.jit(st.server.lookup_fn())
    gidx = jnp.asarray(global_ids(st.cards, idx))
    return np.concatenate([
        np.asarray(look(st.server.packed, gidx[i:i + _REF_CHUNK]))
        for i in range(0, gidx.shape[0], _REF_CHUNK)])


def take_rows(mesh):
    """``jnp.take`` of table rows.  Under a mesh the table is
    row-sharded: each device reads the rows of its own shard, zeros for
    the rest, and one psum assembles them (exact: the other devices add
    zeros), so no device ever holds the whole table."""
    import jax
    import jax.numpy as jnp
    if mesh is None:
        return lambda t, g: jnp.take(t, g, axis=0)
    from jax.sharding import PartitionSpec as P
    axis = mesh.axis_names[0]

    def local(t, g):
        v = t.shape[0]
        loc = g - jax.lax.axis_index(axis) * v
        mine = (loc >= 0) & (loc < v)
        rows = jnp.take(t, jnp.clip(loc, 0, v - 1), axis=0)
        return jax.lax.psum(jnp.where(mine[..., None], rows, 0.0), axis)

    return jax.shard_map(local, mesh=mesh, in_specs=(P(axis, None), P()),
                         out_specs=P())


def reference_rows(ctx: Ctx, shapes, tiers, cards, idx):
    import jax
    import jax.numpy as jnp
    table = weights.make(shapes, ctx.cfg["init"], ctx.seed, ctx.mesh)[
        ctx.cfg["program"]["table"]]
    gidx = jnp.asarray(global_ids(cards, idx))
    take = take_rows(ctx.mesh)
    rows = jax.jit(lambda t, g: shark_ref.served_rows(
        take(t, g), jnp.take(tiers, g, axis=0)))
    return np.concatenate([np.asarray(rows(table, gidx[i:i + _REF_CHUNK]))
                           for i in range(0, gidx.shape[0], _REF_CHUNK)])


def reference_logits(ctx: Ctx, shapes, tiers, cards, idx, dense,
                     precision, rows=None):
    """The plain reference's logits for these requests: weights and
    tiers rebuilt from the seed, rows served as their tier holds them,
    the config's reference head at ``precision``."""
    import jax
    import jax.numpy as jnp

    ref = ctx.reference()
    params = weights.make(shapes, ctx.cfg["init"], ctx.seed, ctx.mesh)
    table = params.pop(ctx.cfg["program"]["table"])
    gidx = global_ids(cards, idx)
    dot = shark_ref.make_dot(precision)
    take = take_rows(ctx.mesh)

    @jax.jit
    def head(params, table, tiers, gidx, dense, emb):
        if emb is None:
            emb = shark_ref.served_rows(take(table, gidx),
                                        jnp.take(tiers, gidx, axis=0))
        batch = {"gidx": gidx}
        if dense is not None:
            batch["dense"] = dense
        return ref.logits(params, emb, batch, dot)

    out = []
    for i in range(0, gidx.shape[0], _REF_CHUNK):
        sl = slice(i, i + _REF_CHUNK)
        out.append(np.asarray(head(
            params, table, tiers, jnp.asarray(gidx[sl]),
            None if dense is None else jnp.asarray(dense[sl]),
            None if rows is None else jnp.asarray(rows[sl]))))
    return np.concatenate(out)


def reference_tiers(ctx: Ctx, vocab: int, dim: int):
    import jax.numpy as jnp
    s = ctx.cfg["serving_store"]
    w = jnp.asarray(shark_ref.priority_profile(s["priority"], vocab,
                                               s["priority"]["seed"]))
    t8, t16 = shark_ref.plan_thresholds(w, dim, s["memory_ratio"],
                                        s["half_share"])
    return shark_ref.tiers_of(w, t8, t16)


"""Measured autotune cache: persistence, fallback and precedence.

The contract under test (repro.kernels.autotune + the resolve_*
layers): the serving path only ever READS the cache; anything wrong
with the file — missing, corrupt, wrong schema, malformed entry,
foreign key — degrades to the analytic pick, never to an error; and
explicit/env overrides always beat a cache hit."""

import importlib.util
import json
import pathlib

import pytest

from repro.kernels import autotune
from repro.kernels.dequant_bag.ops import resolve_block_b


@pytest.fixture
def cache(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    return path


def test_store_lookup_roundtrip_preserves_entries(cache):
    autotune.store("dequant_bag", "int8", 64, 8, 64, {"block_b": 16},
                   123.4)
    assert autotune.lookup_cached("dequant_bag", "int8",
                                  64, 8, 64) == (16,)
    doc = json.loads(cache.read_text())
    assert doc["schema"] == "autotune_cache/v1"
    # a second store merges: the first entry survives
    autotune.store("dequant_bag", "int8", 32, 4, 96, {"block_b": 8}, 50.0)
    assert autotune.lookup_cached("dequant_bag", "int8",
                                  64, 8, 64) == (16,)
    assert autotune.lookup_cached("dequant_bag", "int8",
                                  32, 4, 96) == (8,)


def test_resolve_serves_cache_hit(cache):
    b, k, d = 64, 8, 64
    analytic = resolve_block_b(b, k, d, 1)
    tuned = max(8, analytic // 2)
    assert tuned != analytic
    autotune.store("dequant_bag", "int8", b, k, d, {"block_b": tuned}, 1.0)
    assert resolve_block_b(b, k, d, 1) == tuned


def test_key_mismatch_is_a_miss_not_a_stale_hit(cache):
    b, k, d = 64, 8, 64
    analytic = resolve_block_b(b, k, d, 1)
    autotune.store("dequant_bag", "int8", b, k, d, {"block_b": 8}, 1.0)
    assert analytic != 8
    # different shape / kind / dtype: every probe misses and the
    # resolver re-derives the analytic pick instead of serving 8
    assert autotune.lookup_cached("dequant_bag", "int8",
                                  b, k, d + 1) is None
    assert autotune.lookup_cached("bag_grad", "float32", b, k, d) is None
    assert autotune.lookup_cached("dequant_bag", "bfloat16",
                                  b, k, d) is None
    assert resolve_block_b(b, k, d + 64, 1) != 8
    assert resolve_block_b(b, k, d, 1, kind="bag_grad") == analytic


@pytest.mark.parametrize("content", [
    "not json {",
    json.dumps({"schema": "autotune_cache/v999", "entries": {}}),
    json.dumps(["a", "list"]),
    json.dumps({"schema": "autotune_cache/v1", "entries": "nope"}),
])
def test_corrupt_or_stale_cache_falls_back(cache, content):
    b, k, d = 64, 8, 64
    analytic = resolve_block_b(b, k, d, 1)
    cache.write_text(content)
    assert autotune.lookup_cached("dequant_bag", "int8", b, k, d) is None
    assert resolve_block_b(b, k, d, 1) == analytic


def test_malformed_entry_is_a_miss(cache):
    b, k, d = 64, 8, 64
    analytic = resolve_block_b(b, k, d, 1)
    key = autotune.cache_key("dequant_bag", "int8", b, k, d)
    for bad in ({"block_b": "four"}, {"block_b": 0}, {"us": 1.0}):
        cache.write_text(json.dumps({"schema": "autotune_cache/v1",
                                     "entries": {key: bad}}))
        assert autotune.lookup_cached("dequant_bag", "int8",
                                      b, k, d) is None
        assert resolve_block_b(b, k, d, 1) == analytic


def test_env_override_wins_over_cache(cache, monkeypatch):
    b, k, d = 64, 8, 64
    autotune.store("dequant_bag", "int8", b, k, d, {"block_b": 16}, 1.0)
    assert resolve_block_b(b, k, d, 1) == 16
    monkeypatch.setenv("REPRO_DEQUANT_BLOCK_B", "32")
    assert resolve_block_b(b, k, d, 1) == 32
    monkeypatch.delenv("REPRO_DEQUANT_BLOCK_B")
    assert resolve_block_b(b, k, d, 1) == 16


def test_explicit_args_win_over_everything(cache, monkeypatch):
    b, k, d = 64, 8, 64
    autotune.store("dequant_bag", "int8", b, k, d, {"block_b": 16}, 1.0)
    monkeypatch.setenv("REPRO_DEQUANT_BLOCK_B", "32")
    assert resolve_block_b(b, k, d, 1, block_b=8) == 8


def test_empty_env_disables_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "")
    assert autotune.cache_path() is None
    assert autotune.store("dequant_bag", "int8", 8, 2, 32,
                          {"block_b": 8}, 1.0) is None
    assert autotune.lookup_cached("dequant_bag", "int8", 8, 2,
                                  32) is None


def test_external_write_picked_up_without_restart(cache):
    """A sweep seeded by another process (direct file write) is served
    after the mtime changes — no in-process store() call needed."""
    b, k, d = 64, 8, 64
    assert autotune.lookup_cached("dequant_bag", "int8", b, k, d) is None
    key = autotune.cache_key("dequant_bag", "int8", b, k, d)
    cache.write_text(json.dumps({
        "schema": "autotune_cache/v1",
        "entries": {key: {"block_b": 24, "us": 9.0}},
    }))
    assert autotune.lookup_cached("dequant_bag", "int8",
                                  b, k, d) == (24,)
    assert resolve_block_b(b, k, d, 1) == 24


def test_bag_matmul_key_folds_output_width(cache):
    from repro.kernels.bag_matmul.ops import resolve_bm_block_sizes
    b, k, d, h = 64, 8, 64, 32
    autotune.store("bag_matmul", "int8", b, k, d,
                   {"block_b": 8, "block_h": 16}, 1.0, extra=f"|h={h}")
    assert resolve_bm_block_sizes(b, k, d, h, 1) == (8, 16)
    # same (b, k, d) with a different H is a distinct key: miss
    analytic = resolve_bm_block_sizes(b, k, d, 2 * h, 1)
    assert analytic != (8, 16)


def test_candidate_tilings_lead_with_analytic(cache):
    for b, k, d, itemsize in [(64, 8, 64, 1), (5, 3, 96, 4),
                              (4096, 26, 64, 2)]:
        cands = autotune.candidate_block_b(b, k, d, itemsize)
        assert cands[0] == resolve_block_b(b, k, d, itemsize)
        assert len(cands) == len(set(cands))
        # every candidate is a block that runs as named: the 8-row
        # tile rule, never past the 8-padded batch
        assert all(bb % 8 == 0 and 8 <= bb <= -(-b // 8) * 8
                   for bb in cands), cands


def test_sweep_skips_failing_candidates():
    calls = []

    def run(bb, bh):
        def thunk():
            calls.append((bb, bh))
            if bb == 16:
                raise ValueError("backend rejected tiling")
            import jax.numpy as jnp
            return jnp.zeros(())
        return thunk

    res = autotune.sweep(run, [(8, 128), (16, 128), (32, 128)], iters=1)
    assert res["best"] in {(8, 128), (32, 128)}
    failed = [r for r in res["sweep"] if r["us"] is None]
    assert [r["blocks"] for r in failed] == [[16, 128]]


def test_kernel_bench_record_validates(cache):
    """benchmarks/kernels.py end to end at a tiny shape: the emitted
    record passes the bench_kernel/v1 validator, holds the
    measured<=analytic invariant, and --seed-cache entries resolve."""
    root = pathlib.Path(__file__).resolve().parent.parent

    def _load(name, rel):
        spec = importlib.util.spec_from_file_location(name, root / rel)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    bench = _load("bench_kernels", "benchmarks/kernels.py")
    checker = _load("check_bench_schema", "tools/check_bench_schema.py")

    rec = bench.run(shapes=((8, 2, 32, 8),), iters=1, seed_cache=True)
    assert checker.validate(rec) == []
    kinds = {e["kernel"] for e in rec["sweep"]}
    assert kinds == {"dequant_bag_rowgrid", "dequant_bag", "bag_grad",
                     "unfused_bag_matmul", "bag_matmul"}
    for e in rec["sweep"]:
        assert e["measured_us"] <= e["analytic_us"] * (1 + 1e-6)
    # the seeded entries are served back by the resolvers
    assert autotune.lookup_cached("dequant_bag", "int8",
                                  8, 2, 32) is not None
    assert autotune.lookup_cached("bag_matmul", "int8", 8, 2, 32,
                                  extra="|h=8") is not None

"""Compilecache registry structure: the declared program set must cover
every trace entry a proofs-on survey dispatches (BUCKETED_OPS + the raw
Pallas flat kernels + the fused service jits). Trace-free by default —
only the two driver smoke tests lower anything, and only the cheapest
scalar-field programs."""
import sys

import pytest

from drynx_tpu import compilecache as cc
from drynx_tpu.compilecache.stats import CompileStats


@pytest.fixture(scope="module")
def registry():
    return cc.build_registry(cc.BENCH)


def test_registry_covers_every_bucketed_op(registry):
    """Every named bucketed op (including the lazy range-proof wrappers
    force-built by aot_register_bucketed) has at least one registered
    program — a new `name=`d bucketed() call site without a registry
    entry fails here."""
    from drynx_tpu.crypto import batching as B

    covered = {s.op for s in registry if s.kind == "bucketed"}
    missing = set(B.BUCKETED_OPS) - covered
    assert not missing, (
        f"BUCKETED_OPS entries without a compilecache program: {missing} "
        f"— add them to registry._B_SCHEMAS")
    # the Pallas-only lazy wrappers are registered even when the current
    # backend never builds them (they are skipped, not absent)
    assert {"gt_pow_fixed_multi", "gt_pow_gtb"} <= covered


def test_registry_covers_pallas_and_fused_families(registry):
    ops = {(s.kind, s.op) for s in registry}
    for op in ("miller_flat", "f12_wpow_flat", "f12_mulreduce8_flat"):
        assert ("pallas", op) in ops
    for op in ("enc", "agg", "ks", "dec"):
        assert ("fused", op) in ops


def test_registry_names_unique_and_thunks_wellformed(registry):
    names = [s.name for s in registry]
    assert len(names) == len(set(names))
    for s in registry:
        assert callable(s.lower) and callable(s.dispatched), s.name
        assert s.call is None or callable(s.call), s.name
        assert s.kind in ("bucketed", "pallas", "fused", "pool",
                          "wire", "pane"), s.name


def test_registry_scales_with_profile():
    small = cc.Profile(n_cns=2, n_dps=2, n_values=2, u=4, l=2,
                       dlog_limit=100)
    specs = cc.build_registry(small)
    # smaller survey -> smaller buckets -> at least as few programs, and
    # every bucketed name stays within the wrapper's max_bucket cap
    for s in specs:
        if s.kind == "bucketed":
            bucket = int(s.name.rsplit("@", 1)[1])
            assert bucket <= 2048


def test_registry_sharded_program_set():
    """Profile.n_shards > 1 must add the proof-plane per-shard programs
    (the smaller buckets each mesh device dispatches) on BOTH phases —
    creation and verification — and must only ever ADD programs: the
    single-shard registry is a strict subset, so sharding can never
    silently drop AOT coverage of the fallback path."""
    base = cc.BENCH
    sharded = cc.build_registry(
        cc.Profile(n_cns=base.n_cns, n_dps=base.n_dps,
                   n_values=base.n_values, u=base.u, l=base.l,
                   dlog_limit=base.dlog_limit, n_shards=8))
    flat = cc.build_registry(base)
    flat_names = {s.name for s in flat}
    sharded_names = {s.name for s in sharded}
    assert flat_names <= sharded_names
    extra = [s for s in sharded if s.name not in flat_names]
    assert extra, "n_shards=8 must add per-shard programs"
    phases = {s.phase for s in extra}
    assert phases <= {"RangeProofVerifyShard", "RangeProofCreateShard"}
    assert "RangeProofVerifyShard" in phases
    assert "RangeProofCreateShard" in phases
    # the verify shard's pairing programs at the per-shard bucket
    ops = {s.op for s in extra}
    assert {"miller", "gt_pow64"} <= ops
    # per-shard buckets are smaller than the full flat batch
    for s in extra:
        if s.kind == "bucketed":
            assert int(s.name.rsplit("@", 1)[1]) <= 2048


def test_registry_bucket_grid_program_set():
    """Profile.n_buckets above the tile threshold must add the bucket-tile
    programs (tile-derived creation shards + fused enc at tile slab
    widths) and must only ever ADD: the plain registry at the same grid
    shape is a strict subset, mirroring the n_shards / n_queue
    contracts."""
    grid = cc.Profile(n_values=65536, u=2, l=1, n_buckets=65536)
    plain = cc.Profile(n_values=65536, u=2, l=1)
    grid_names = {s.name for s in cc.build_registry(grid)}
    plain_names = {s.name for s in cc.build_registry(plain)}
    assert plain_names <= grid_names
    extra = [s for s in cc.build_registry(grid)
             if s.name not in plain_names]
    assert extra, "n_buckets=65536 must add bucket-tile programs"
    phases = {s.phase for s in extra}
    assert phases <= {"RangeProofCreateTile", "DataCollectionTile"}
    assert "RangeProofCreateTile" in phases
    # the chunked-encrypt slab program at the tile width
    assert any(s.name.startswith("fused:enc@") for s in extra)


def test_registry_bucket_grid_below_threshold_is_identity():
    """A grid at or below the tile threshold never tiles, so n_buckets
    must add nothing — the existing program set is exactly preserved."""
    with_b = cc.build_registry(
        cc.Profile(n_values=256, u=2, l=1, n_buckets=256))
    without = cc.build_registry(cc.Profile(n_values=256, u=2, l=1))
    assert {s.name for s in with_b} == {s.name for s in without}


def test_registry_n_buckets_zero_is_identity():
    base = cc.BENCH
    zero = cc.build_registry(dataclasses_replace(base, n_buckets=0))
    assert {s.name for s in zero} == {s.name
                                      for s in cc.build_registry(base)}


def dataclasses_replace(p, **kw):
    import dataclasses

    return dataclasses.replace(p, **kw)


def test_registry_n_shards_one_is_identity():
    base = cc.BENCH
    one = cc.build_registry(
        cc.Profile(n_cns=base.n_cns, n_dps=base.n_dps,
                   n_values=base.n_values, u=base.u, l=base.l,
                   dlog_limit=base.dlog_limit, n_shards=1))
    assert {s.name for s in one} == {s.name for s in cc.build_registry(base)}


def test_driver_lower_smoke_cheap_program():
    """spec.lower() on the cheapest scalar-field program returns an AOT
    Lowered (compile()-able); the driver records it as 'lowered'."""
    stats = CompileStats()
    specs = [s for s in cc.build_registry(cc.BENCH)
             if s.op in ("fn_add", "int_to_scalar") and s.dispatched()]
    assert specs, "scalar-field programs must dispatch on every backend"
    lowered = specs[0].lower()
    assert hasattr(lowered, "compile")
    stats.record(specs[0].name, "lowered", lower_s=0.1)
    assert stats.count("lowered") == 1


def test_stats_headline_keys_and_totals():
    stats = CompileStats()
    stats.record("a", "compiled", lower_s=1.0, compile_s=2.0, cache="miss")
    stats.record("b", "executed", lower_s=0.5, cache="hit")
    stats.record("c", "skipped")
    stats.record("d", "error", detail="boom")
    t = stats.totals()
    assert t["programs"] == 4 and t["errors"] == 1
    assert t["persistent_hits"] == 1 and t["persistent_misses"] == 1
    h = stats.headline()
    assert h["compile_cache_programs"] == 4
    assert h["compile_cache_compiled"] == 2      # compiled + executed
    assert h["compile_cache_skipped"] == 1
    assert h["compile_cache_trace_lower_seconds"] == 1.5
    assert h["compile_cache_persistent_hits"] == 1
    assert h["compile_cache_persistent_misses"] == 1
    assert "a" in stats.table() and "error" in stats.table()


def test_trace_guard_raises_recursion_limit():
    before = sys.getrecursionlimit()
    cc.trace_guard(min_recursion=max(before, 20000))
    assert sys.getrecursionlimit() >= 20000


def test_cli_list_exits_zero(capsys):
    from drynx_tpu import precompile as cli

    assert cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "bucketed:fn_add" in out and "fused:dec" in out
    assert "programs" in out


def test_cli_list_shards_includes_shard_programs(capsys):
    from drynx_tpu import precompile as cli

    assert cli.main(["--list", "--shards", "8"]) == 0
    out = capsys.readouterr().out
    assert "RangeProofVerifyShard" in out
    assert "RangeProofCreateShard" in out
    # and forcing a single shard removes them again
    assert cli.main(["--list", "--shards", "1"]) == 0
    out = capsys.readouterr().out
    assert "RangeProofVerifyShard" not in out


def test_cli_list_buckets_includes_tile_programs(capsys):
    from drynx_tpu import precompile as cli

    assert cli.main(["--list", "--buckets", "65536", "--values", "65536",
                     "--range-u", "2", "--range-l", "1"]) == 0
    out = capsys.readouterr().out
    assert "RangeProofCreateTile" in out
    assert "fused:enc@" in out
    # no grid axis -> no tile programs
    assert cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "RangeProofCreateTile" not in out


def test_registry_pool_program_set():
    """Profile.n_noise > 0 must add the DRO pool/slab programs (the three
    stored programs the noise encryption, the precompute refill and the
    shuffle dispatch) at exactly the dro.slab_widths chunk widths — and
    must only ever ADD programs: the non-diffp registry stays a strict
    subset, so pooling can never silently drop AOT coverage."""
    from drynx_tpu.parallel import dro

    base = cc.BENCH
    pooled = cc.build_registry(dataclasses_replace(base, n_noise=10000))
    base_names = {s.name for s in cc.build_registry(base)}
    pooled_names = {s.name for s in pooled}
    assert base_names <= pooled_names
    extra = [s for s in pooled if s.name not in base_names]
    assert extra, "n_noise must add pool programs"
    assert {s.phase for s in extra} == {"DROPool"}
    assert {s.kind for s in extra} == {"pool"}
    # every slab width the chunked path dispatches is certified
    widths = set(dro.slab_widths(10000))
    assert widths == {4096, 10000 - 2 * 4096}
    for op in ("dro_noise_enc", "dro_zero_enc", "dro_permute_add"):
        got = {int(s.name.rsplit("@", 1)[1]) for s in extra if s.op == op}
        assert got == widths, (op, got, widths)
    # pool programs always dispatch (plain device jits, no backend gate)
    assert all(s.dispatched() for s in extra)


def test_registry_wire_widen_complete(registry):
    """Every (narrow, wide) dtype pair the v2 encoder can ship has a
    registered on-device widen program — a new _NARROW entry in
    transport without a registry program fails here. Wire programs are
    profile-independent: present in every registry, always dispatched."""
    from drynx_tpu.service import transport as T

    wire = [s for s in registry if s.kind == "wire"]
    names = {s.name for s in wire}
    for narrow, orig in T.widen_pairs():
        assert f"wire:widen@{narrow}->{orig}" in names, (narrow, orig)
    assert len(wire) == len(T.widen_pairs())
    assert {s.phase for s in wire} == {"WireDecode"}
    assert all(s.dispatched() for s in wire)
    # profile-independence: the smallest profile certifies the same set
    small = cc.build_registry(cc.Profile(n_cns=2, n_dps=2, n_values=2,
                                         u=4, l=2, dlog_limit=100))
    assert {s.name for s in small if s.kind == "wire"} == names


def test_cli_list_includes_wire_programs(capsys):
    from drynx_tpu import precompile as cli

    assert cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "wire:widen@uint16->uint32" in out
    assert "WireDecode" in out


def test_registry_n_noise_zero_is_identity():
    base = cc.BENCH
    zero = cc.build_registry(dataclasses_replace(base, n_noise=0))
    assert {s.name for s in zero} == {s.name
                                      for s in cc.build_registry(base)}


def test_cli_list_noise_includes_pool_programs(capsys):
    from drynx_tpu import precompile as cli

    assert cli.main(["--list", "--noise", "10000"]) == 0
    out = capsys.readouterr().out
    assert "pool:dro_zero_enc@4096" in out
    assert "DROPool" in out
    # no diffp axis -> no pool programs
    assert cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "DROPool" not in out


def test_registry_pane_program_set():
    """Profile.n_pane > 1 must add the streaming pane-delta programs —
    the RAW ct_add/ct_sub jits at the (V,) window-aggregate shape plus
    the first advance's bucketed pane-stack fold — and must only ever
    ADD programs: the one-shot registry stays a strict subset, mirroring
    the n_fold / n_noise contracts."""
    base = cc.BENCH
    paned = cc.build_registry(dataclasses_replace(base, n_pane=16))
    base_names = {s.name for s in cc.build_registry(base)}
    paned_names = {s.name for s in paned}
    assert base_names <= paned_names
    extra = [s for s in paned if s.name not in base_names]
    assert extra, "n_pane=16 must add pane-delta programs"
    phases = {s.phase for s in extra}
    assert phases <= {"PaneDelta", "PaneFold"}
    assert "PaneDelta" in phases
    # the raw delta jits at the window shape, both directions
    assert f"pane:ct_add@{base.n_values}" in paned_names
    assert f"pane:ct_sub@{base.n_values}" in paned_names
    # pane programs always dispatch (plain device jits, no backend gate)
    assert all(s.dispatched() for s in extra if s.kind == "pane")


def test_registry_n_pane_zero_and_one_are_identity():
    """n_pane in {0, 1} means no delta chain (a 1-pane window re-folds
    from scratch), so the registry must be exactly the one-shot set."""
    base = cc.BENCH
    base_names = {s.name for s in cc.build_registry(base)}
    for n in (0, 1):
        same = cc.build_registry(dataclasses_replace(base, n_pane=n))
        assert {s.name for s in same} == base_names, n


def test_cli_list_panes_includes_pane_programs(capsys):
    from drynx_tpu import precompile as cli

    assert cli.main(["--list", "--panes", "16"]) == 0
    out = capsys.readouterr().out
    assert "pane:ct_sub@9" in out
    assert "PaneDelta" in out
    # no streaming axis -> no pane programs
    assert cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "PaneDelta" not in out

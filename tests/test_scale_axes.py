"""Reference-scale axes (PR 8): the bucket-tile planner's memory bound,
tiled-vs-monolithic bit identity for the grid encoders and the range-proof
transcripts, chunked-vs-unchunked DRO byte identity, the vectorized noise
generator against its loop reference, and sparse-grid decode semantics.

Fast by default: only the two crypto round-trip tests compile kernels and
carry the `slow` mark."""
import numpy as np
import pytest

from drynx_tpu.encoding import stats as st
from drynx_tpu.encoding import tiles


# ---------------------------------------------------------------------------
# Tile planner: balance, coverage, and the 65k-bucket memory bound
# ---------------------------------------------------------------------------

def test_plan_tiles_balanced_and_covering():
    for n in (1, 5, 4096, 4097, 8193, 65536, 1_000_000):
        plan = tiles.plan_tiles(n, 4096)
        assert plan.covers(), n
        widths = {b - a for a, b in plan.tiles}
        assert max(widths) <= 4096
        assert max(widths) - min(widths) <= 1, (n, widths)
        assert plan.n_tiles == -(-n // 4096)


def test_plan_tiles_monolithic_cases():
    assert tiles.plan_tiles(100, 0).tiles == ((0, 100),)
    assert tiles.plan_tiles(100, 200).tiles == ((0, 100),)
    assert tiles.plan_tiles(0, 4096).tiles == ()


def test_65k_bucket_peak_mask_bounded_by_tile():
    """The acceptance bound: at 65536 buckets the largest row-by-grid
    mask any single tiled encode dispatch materializes is rows x tile,
    NOT rows x buckets."""
    R, rows = 65536, 600
    t = tiles.auto_tile(R)
    assert t == tiles.tile_width()          # tiling is the DEFAULT here
    plan = tiles.plan_tiles(R, t)
    assert plan.covers()
    assert plan.max_tile_width <= tiles.tile_width()
    assert plan.peak_mask_elems(rows) == rows * plan.max_tile_width
    assert plan.peak_mask_elems(rows) <= rows * tiles.DEFAULT_TILE
    assert plan.peak_mask_elems(rows) < rows * R / 10


def test_auto_tile_policy_and_env_override(monkeypatch):
    assert tiles.auto_tile(tiles.TILE_THRESHOLD) == 0
    assert tiles.auto_tile(tiles.TILE_THRESHOLD + 1) == tiles.DEFAULT_TILE
    monkeypatch.setenv(tiles.ENV_TILE, "512")
    assert tiles.tile_width() == 512
    assert tiles.auto_tile(tiles.TILE_THRESHOLD + 1) == 512
    monkeypatch.setenv(tiles.ENV_TILE, "garbage")
    assert tiles.tile_width() == tiles.DEFAULT_TILE


def test_proof_tile_shards():
    assert tiles.proof_tile_shards(100, 0) == 1
    assert tiles.proof_tile_shards(100, 200) == 1
    assert tiles.proof_tile_shards(4097, 4096) == 2
    assert tiles.proof_tile_shards(65536, 4096) == 16


# ---------------------------------------------------------------------------
# Tiled encode: bit-identical to the monolithic grid encoders
# ---------------------------------------------------------------------------

GRID_CASES = [(op, rows, R) for op in st.GRID_OPS
              for rows, R in ((50, 300), (7, 64))]


@pytest.mark.parametrize("op,rows,R", GRID_CASES)
def test_tiled_encode_bit_identical(op, rows, R):
    rng = np.random.default_rng(3)
    data = rng.integers(0, R, rows)
    mono = np.asarray(st.encode_clear(op, data, 0, R - 1))  # below
    # threshold -> the dense monolithic path
    tiled = np.asarray(st.encode_clear_tiled(op, data, 0, R - 1, tile=33))
    assert np.array_equal(mono, tiled), op


def test_encode_clear_auto_tiles_above_threshold():
    """Above TILE_THRESHOLD encode_clear dispatches the tiled path by
    default, and the result equals a single-tile (monolithic) pass."""
    R = tiles.TILE_THRESHOLD + 5
    rng = np.random.default_rng(4)
    data = rng.integers(0, R, 40)
    auto = np.asarray(st.encode_clear("min", data, 0, R - 1))
    one_tile = np.asarray(
        st.encode_clear_tiled("min", data, 0, R - 1, tile=R))
    assert np.array_equal(auto, one_tile)
    assert auto.shape == (R,)


def test_encode_clear_tiles_offsets_partition():
    offs = [(off, np.asarray(enc).shape[0]) for off, enc
            in st.encode_clear_tiles("union", np.asarray([1, 2]), 0, 99,
                                     tile=16)]
    pos = 0
    for off, w in offs:
        assert off == pos
        pos += w
    assert pos == 100


# ---------------------------------------------------------------------------
# Sparse-grid decode semantics (empty-group sentinels, max ambiguity)
# ---------------------------------------------------------------------------

def _dec(values):
    v = np.asarray(values, dtype=np.int64)
    return st.DecryptedVector(values=v, found=np.ones(v.shape, bool),
                              is_zero=(v == 0))


def test_decode_min_max_large_sparse_grid():
    R, lo, hit = 65536, 10, 12345
    v = np.zeros(R, dtype=np.int64)
    v[hit:] = 1                       # min: OR bits from the min upward
    assert st.decode("min", _dec(v), lo, lo + R - 1) == lo + hit
    c = np.zeros(R, dtype=np.int64)
    c[:hit] = 1                       # max: complement bits below the max
    assert st.decode("max", _dec(c), lo, lo + R - 1) == lo + hit


def test_decode_min_empty_is_none_max_empty_is_query_min():
    """No data: min's all-zero OR bits decode to the None sentinel; max's
    AND-complement neutral element is indistinguishable from a genuine
    max of query_min (the documented reference ambiguity)."""
    z = np.zeros(100, dtype=np.int64)
    assert st.decode("min", _dec(z), 7, 106) is None
    assert st.decode("max", _dec(z), 7, 106) == 7


def test_decode_union_inter_frequency_sparse():
    v = np.zeros(1000, dtype=np.int64)
    v[[3, 997]] = 2
    assert st.decode("union", _dec(v), 5, 1004) == [8, 1002]
    inter = st.decode("inter", _dec(v), 5, 1004)
    assert 8 not in inter and 1002 not in inter and len(inter) == 998
    freq = st.decode("frequency_count", _dec(v), 5, 1004)
    assert freq[8] == 2 and freq[9] == 0 and len(freq) == 1000


def test_decode_grouped_empty_group_sentinels():
    R, gvals = 64, [(), ()]
    g0 = np.zeros(R, dtype=np.int64)
    g0[20:] = 1
    g1 = np.zeros(R, dtype=np.int64)  # empty group
    vec = _dec(np.concatenate([g0, g1]))
    grid = np.asarray([[0], [1]])
    out = st.decode_grouped("min", vec, grid, 0, R - 1)
    assert out[(0,)] == 20 and out[(1,)] is None
    out = st.decode_grouped("max", vec, grid, 0, R - 1)
    # g0's complement encoding is all-zero-above -> decodes to 0 here;
    # the empty group hits the documented query_min ambiguity
    assert out[(1,)] == 0


# ---------------------------------------------------------------------------
# Vectorized noise generation == loop reference (golden)
# ---------------------------------------------------------------------------

NOISE_CASES = [
    (100, 0.0, 30.0, 100.0, 1.0, 0.0),
    (1000, 0.0, 30.0, 100.0, 1.0, 0.0),
    (512, 5.0, 2.0, 10.0, 1.0, 0.0),
    (256, -3.0, 1.0, 1.0, 2.0, 0.0),       # sharp density
    (300, 0.0, 50.0, 0.5, 1.0, 0.0),        # tiny quanta
    (200, 0.0, 30.0, 100.0, 1.0, 400.0),    # aggressive limit
    (1, 0.0, 30.0, 100.0, 1.0, 0.0),
    (10000, 1.5, 12.0, 7.0, 0.5, 0.0),
]


@pytest.mark.parametrize("size,mean,b,quanta,scale,limit", NOISE_CASES)
def test_noise_values_match_loop_reference(size, mean, b, quanta, scale,
                                           limit):
    from drynx_tpu.parallel import dro

    got = dro.generate_noise_values(size, mean, b, quanta, scale, limit)
    want = dro._generate_noise_values_ref(size, mean, b, quanta, scale,
                                          limit)
    assert got.dtype == want.dtype
    assert np.array_equal(got, want)


def test_noise_values_order_and_size():
    from drynx_tpu.parallel import dro

    out = dro.generate_noise_values(7, 0.0, 30.0, 100.0)
    assert len(out) == 7
    # order is [m, m+q, m-q, m+2q, m-2q, ...] expanded by repetition
    assert out[0] == 0


# ---------------------------------------------------------------------------
# DRO API convention: FixedBase at the encryption boundary, raw tables in
# the shuffle layer — mixing them is a TypeError, not a silent reshape
# ---------------------------------------------------------------------------

def test_dro_table_convention_typeerrors():
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.parallel import dro

    fb = eg.BASE_TABLE                  # FixedBase wrapper
    raw = eg.BASE_TABLE.table
    with pytest.raises(TypeError):
        dro.encrypt_noise(None, raw, None)
    with pytest.raises(TypeError):
        dro.precompute_rerandomization(None, fb, 4)
    with pytest.raises(TypeError):
        dro.shuffle_rerandomize(None, None, fb)
    with pytest.raises(TypeError):
        dro.dro_pipeline(None, raw, 4, 0.0, 30.0, 100.0)


# ---------------------------------------------------------------------------
# Crypto round trips (compile-heavy -> slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_tiled_range_proof_transcript_byte_identical():
    """Forced tiling at small V: the Fiat-Shamir transcript (to_bytes)
    must be byte-equal to the monolithic path, and still verify."""
    import jax
    import jax.numpy as jnp

    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.proofs import range_proof as rp

    rng = np.random.default_rng(7)
    U, L, V = 2, 1, 12
    sigs = [rp.init_range_sig(U, rng) for _ in range(2)]
    _, ca_pub = eg.keygen(rng)
    tbl = eg.pub_table(ca_pub)
    secrets = np.asarray(rng.integers(0, U, V), dtype=np.int64)
    cts, rs = eg.encrypt_ints(jax.random.PRNGKey(3), tbl,
                              jnp.asarray(secrets))
    mono = rp.create_range_proofs(jax.random.PRNGKey(5), secrets, rs, cts,
                                  sigs, U, L, tbl.table, tile=0,
                                  shard=False)
    tiled = rp.create_range_proofs(jax.random.PRNGKey(5), secrets, rs,
                                   cts, sigs, U, L, tbl.table, tile=5,
                                   shard=False)
    assert mono.to_bytes() == tiled.to_bytes()
    ok = rp.verify_range_proofs(tiled, [s.public for s in sigs], tbl.table)
    assert np.asarray(ok).all()


@pytest.mark.slow
def test_chunked_dro_byte_identical():
    """Chunked precompute + shuffle at a forced small chunk must be
    byte-identical to the monolithic path for the same key."""
    import jax
    import numpy as np

    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.parallel import dro

    rng = np.random.default_rng(7)
    _, pub = eg.keygen(rng)
    tbl = eg.pub_table(pub)
    S = 8
    key = jax.random.PRNGKey(1)
    z_mono, r_mono = dro.precompute_rerandomization(key, tbl.table, S,
                                                    chunk=0)
    z_chnk, r_chnk = dro.precompute_rerandomization(key, tbl.table, S,
                                                    chunk=3)
    assert np.array_equal(np.asarray(r_mono), np.asarray(r_chnk))
    assert np.array_equal(np.asarray(z_mono), np.asarray(z_chnk))

    k2 = jax.random.PRNGKey(2)
    cts = z_mono  # any ciphertext pool works
    a, pa, ra = dro.shuffle_rerandomize(k2, cts, tbl.table,
                                        precomp=(z_mono, r_mono), chunk=0)
    b, pb, rb = dro.shuffle_rerandomize(k2, cts, tbl.table,
                                        precomp=(z_mono, r_mono), chunk=3)
    assert np.array_equal(np.asarray(pa), np.asarray(pb))
    assert np.array_equal(np.asarray(ra), np.asarray(rb))
    assert np.array_equal(np.asarray(a), np.asarray(b))

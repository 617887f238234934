"""AOT kernel precompile registry: the finite program set of a proofs-on
survey, declared as (kernel, bucket shape, dtype) entries.

A cold process used to discover every program lazily, mid-survey, from
whichever thread touched it first — tens of minutes of serialized trace +
compile inside the timed bench window, and (worse) first-touch TRACING on
`_async_proof` / dp_lists worker threads, whose default 8 MB C stacks
overflow under partial_eval's recursion on the pairing kernels (the r05
segfault class, service.py:500). This registry makes the program set
explicit so it can be driven SERIALLY, on the MAIN thread, before any
survey starts:

  * the `bucketed()` crypto family (crypto/batching.py BUCKETED_OPS) at
    the bucket sizes a proofs-on survey dispatches,
  * the raw Pallas pairing entry points (miller / windowed-pow /
    mulreduce8) at their flat dispatch shapes,
  * the range-proof create/verify compositions (covered through the
    bucketed primitives they dispatch — _commit_kernel, _response_kernel,
    _verify_kernel and the RLC prelude are pure compositions),
  * the fused exec pipeline (service._fused_enc/_agg/_ks/_dec).

`jax.jit(...).lower(...).compile()` on each entry feeds the persistent XLA
cache (utils/cache.py), so the next process pays lowering only. On CPU,
`--dry-run` traces + lowers exactly the programs the CPU backend would
dispatch (host-oracle detours and Pallas-only kernels are enumerated but
skipped) — a fast structural check that every registered program still
traces.

Batch sizes derive from a Profile (defaults = the flagship bench survey:
3 CNs, 10 DPs, V=9 logreg coefficients, (u=16, l=5) ranges). They are the
canonical POST-bucketing shapes, so nearby survey configurations land on
the same executables.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Callable

from .stats import STATS, CompileStats, install_cache_listener

NL = 16  # limbs per field element (crypto/params.py)


@dataclasses.dataclass(frozen=True)
class Profile:
    """Survey shape parameters the program set derives from."""

    n_cns: int = 3
    n_dps: int = 10
    n_values: int = 9       # V: logreg num_coeffs for pima d=8
    u: int = 16             # range-proof base
    l: int = 5              # range-proof digits
    dlog_limit: int = 10000
    n_shards: int = 1       # proof-plane shards (parallel/proof_plane.py);
                            # >1 adds the per-shard program set
    n_queue: int = 1        # cross-survey batch width (drynx_tpu/server):
                            # >1 adds the cross-survey verify program set
                            # at n_queue-concatenated batch sizes
    n_buckets: int = 0      # bucket-grid width of a grid-op survey
                            # (min/max/frequency_count/union/inter:
                            # n_values == n_buckets, ranges (u=2, l=1)).
                            # Above encoding/tiles.TILE_THRESHOLD the
                            # tiled dispatch path engages and adds the
                            # tile-shard program set (_bucket_schemas)
                            # plus fused enc at tile slab widths. 0 (the
                            # default) = non-grid survey, no extra
                            # programs, so plain registries stay a subset
                            # of bucket-grid ones (test_precompile.py).
    n_noise: int = 0        # DRO noise-list size of a diffp survey: > 0
                            # adds the pool/DRO slab program set
                            # (_pool_specs) at parallel/dro.slab_widths —
                            # the stored programs the noise encryption,
                            # the precompute/refill and the shuffle
                            # run. 0 (default) = no
                            # diffp, no extra programs, so plain
                            # registries stay a subset of pooled ones
                            # (test_precompile.py enforces both
                            # directions, mirroring n_buckets).
    n_fold: int = 0         # tree-overlay fold stack height
                            # (service/topology.fold_cts): the LARGEST
                            # (k, V) ciphertext stack one node folds —
                            # 1 + tree fanout at a relay hop, or the
                            # root's top-level partial count. > 1 adds
                            # ct_add at the halving fold widths plus the
                            # canon g1_normalize batch (_fold_schemas).
                            # 0 (default) = star dispatch, no extra
                            # programs, so star registries stay a subset
                            # of tree ones (test_precompile.py pattern).
    n_pane: int = 0         # streaming-survey window width in PANES
                            # (service/streaming.StreamEngine): > 1 adds
                            # the pane-delta program set — the raw
                            # ct_add/ct_sub jits of the window delta
                            # chain at the (V,) window shape
                            # (_pane_specs) plus the first advance's
                            # pane-stack fold, bucketed ct_add at the
                            # halving widths of n_pane (_pane_schemas).
                            # 0 (default) = one-shot survey, no extra
                            # programs, so one-shot registries stay a
                            # subset of streaming ones
                            # (test_precompile.py enforces both
                            # directions, mirroring n_fold).


BENCH = Profile()


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One AOT program: zero-arg lower()/call() thunks + dispatch metadata.

    lower() returns a jax.stages.Lowered (AOT: .compile() feeds the
    persistent cache WITHOUT executing — but does NOT warm the jit's own
    dispatch cache). call() dispatches the program the way runtime does —
    it is the only way to guarantee later calls at these shapes re-use a
    cached trace instead of retracing (LocalCluster warmup uses it)."""

    name: str               # e.g. "bucketed:pair@2048"
    op: str                 # registry family key (BUCKETED_OPS name, ...)
    kind: str               # "bucketed" | "pallas" | "fused" | "pool" |
                            # "wire" | "pane"
    phase: str              # survey phase that dispatches it (doc only)
    lower: Callable[[], object]
    dispatched: Callable[[], bool]
    call: Callable[[], object] | None = None
    family: str = ""        # gate family: "device" | "g1" | "pairing" |
                            # "pallas" (the server's compile lane executes
                            # just the cheap device family on CPU)


# ---------------------------------------------------------------------------
# Backend dispatch predicates (must mirror crypto/batching.py host_dispatch)
# ---------------------------------------------------------------------------

def _pallas_on() -> bool:
    from ..crypto import pallas_ops as po

    return po.available()


def _kernel_route_pairing() -> bool:
    """True iff the pairing-family bucketed kernels actually dispatch
    (host_dispatch detours them to the host oracle on CPU)."""
    from ..crypto import host_oracle as ho

    return not (ho.ENABLED and not _pallas_on())


def _kernel_route_g1() -> bool:
    """G1/G2 family: detours to host only when the NATIVE library built
    (gate=npair.available in batching._build)."""
    from ..crypto import host_oracle as ho
    from ..crypto import native_pairing as npair

    return not (ho.ENABLED and not _pallas_on() and npair.available())


_GATES = {
    "device": lambda: True,
    "pairing": _kernel_route_pairing,
    "g1": _kernel_route_g1,
    "pallas": _pallas_on,
}


# ---------------------------------------------------------------------------
# Example-argument templates (zeros: trace/lower/compile never execute)
# ---------------------------------------------------------------------------

def _z(shape, dtype=None):
    import jax.numpy as jnp

    return jnp.zeros(shape, dtype or jnp.uint32)


def _scalar(b):
    return _z((b, NL))


def _g1(b):
    return _z((b, 3, NL))


def _g2(b):
    return _z((b, 3, 2, NL))


def _gt(b):
    return _z((b, 6, 2, NL))


def _ct(b):
    return _z((b, 2, 3, NL))


def _coord(b):
    return _z((b, NL))


def _fp2c(b):
    return _z((b, 2, NL))


def _i64(b):
    import jax.numpy as jnp
    import numpy as np

    # canonicalized like service.run_survey's jnp.asarray(dp_stats)
    return jnp.asarray(np.zeros((b,), dtype=np.int64))


def _fb_table():
    return _z((64, 16, 3, NL))          # eg.FixedBase.table


def _pow_tables(p: Profile):
    return _z((p.n_cns * p.u, 64, 16, 6, 2, NL))  # sig_gt_pow_tables


# Each bucketed entry: op -> (args builder(profile, B), batch exprs, phase,
# gate). Batch exprs are evaluated on the profile; the wrapper's bucket_of
# canonicalizes them, and entries landing on the same bucket dedupe.
_B_SCHEMAS: list = [
    # --- DataCollection / DRO / keyswitch helpers (device everywhere) ---
    ("encrypt", lambda p, b: (_fb_table(), _fb_table(), _scalar(b),
                              _scalar(b)),
     [lambda p: p.n_dps * p.n_values], "DataCollection", "device"),
    ("int_to_scalar", lambda p, b: (_i64(b),),
     [lambda p: p.n_dps * p.n_values * p.l], "RangeProofCreate", "device"),
    ("ct_add", lambda p, b: (_ct(b), _ct(b)),
     [lambda p: p.n_values], "Aggregation", "device"),
    ("ct_scalar_mul", lambda p, b: (_ct(b), _scalar(b)),
     [lambda p: p.n_values], "Obfuscation", "device"),
    ("decrypt_point", lambda p, b: (_ct(b), _scalar(b)),
     [lambda p: p.n_values], "Decryption", "device"),
    ("is_infinity", lambda p, b: (_g1(b),),
     [lambda p: p.n_values], "Decryption", "device"),
    ("table_lookup",
     lambda p, b: (_z((2 * p.dlog_limit,)), _z((2 * p.dlog_limit, NL)),
                   _z((2 * p.dlog_limit,)),
                   _z((2 * p.dlog_limit,), "int32"), _g1(b)),
     [lambda p: p.n_values], "Decryption", "device"),
    # --- scalar-field (mod n) family: creation + response + RLC weights ---
    ("fn_add", lambda p, b: (_scalar(b), _scalar(b)),
     [lambda p: p.n_dps * p.n_values,
      lambda p: p.n_dps * p.n_values * p.l,
      lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "device"),
    ("fn_sub", lambda p, b: (_scalar(b), _scalar(b)),
     [lambda p: p.n_dps * p.n_values,
      lambda p: p.n_dps * p.n_values * p.l,
      lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "device"),
    ("fn_neg", lambda p, b: (_scalar(b),),
     [lambda p: p.n_dps * p.n_values * p.l,
      lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "device"),
    ("fn_mul_plain", lambda p, b: (_scalar(b), _scalar(b)),
     [lambda p: p.n_dps * p.n_values,
      lambda p: p.n_dps * p.n_values * p.l,
      lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "device"),
    ("fn_mont_mul", lambda p, b: (_scalar(b), _scalar(b)),
     [lambda p: p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "device"),
    # --- canonical byte encoders (wire format, proofs/encoding.py) ---
    ("from_mont_p", lambda p, b: (_scalar(b),),
     [lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofWire", "device"),
    ("to_mont_p", lambda p, b: (_scalar(b),),
     # encode batch, plus the per-payload DECODE shapes (_g1/_g2/_gt
     # _from_bytes): commit x|y at 2V, d at V, each G2 component at
     # ns*V*l, the GT response at 12*ns*V*l — the verify worker of
     # drynx_tpu/server deserializes payloads off the main thread, so
     # these buckets must be registry-warmable
     [lambda p: p.n_cns * p.n_dps * p.n_values * p.l,
      lambda p: 2 * p.n_values, lambda p: p.n_values,
      lambda p: p.n_cns * p.n_values * p.l,
      lambda p: 12 * p.n_cns * p.n_values * p.l],
     "RangeProofWire", "device"),
    # --- G1/G2 family (host-native detour on CPU when the lib built) ---
    ("g1_add", lambda p, b: (_g1(b), _g1(b)),
     [lambda p: p.n_dps * p.n_values,
      lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "g1"),
    ("g1_neg", lambda p, b: (_g1(b),),
     [lambda p: p.n_dps * p.n_values], "RangeProofVerify", "g1"),
    ("g1_scalar_mul", lambda p, b: (_g1(b), _scalar(b)),
     [lambda p: p.n_dps * p.n_values,
      lambda p: p.n_cns * p.n_dps * p.n_values],
     "RangeProofVerify", "g1"),
    ("g1_scalar_mul64", lambda p, b: (_g1(b), _scalar(b)),
     [lambda p: p.n_dps * p.n_values], "RangeProofVerify", "g1"),
    ("g1_eq", lambda p, b: (_g1(b), _g1(b)),
     [lambda p: p.n_dps * p.n_values], "RangeProofVerify", "g1"),
    ("g1_normalize", lambda p, b: (_g1(b),),
     [lambda p: p.n_dps * p.n_values * p.l,
      lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "g1"),
    # canonical aggregate (topology.canon_points): the root normalizes the
    # folded (V, 2, 3, NL) ciphertext sum — 2*V flattened points — in
    # BOTH dispatch topologies, so this is a base program, not a tree one
    ("g1_normalize", lambda p, b: (_g1(b),),
     [lambda p: 2 * p.n_values], "Aggregation", "g1"),
    ("fixed_base_mul", lambda p, b: (_fb_table(), _scalar(b)),
     [lambda p: p.n_dps * p.n_values,
      lambda p: p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "g1"),
    ("g2_scalar_mul", lambda p, b: (_g2(b), _scalar(b)),
     [lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "g1"),
    ("g2_normalize", lambda p, b: (_g2(b),),
     [lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "g1"),
    # --- pairing family (host-oracle detour on CPU) ---
    ("pair", lambda p, b: (_coord(b), _coord(b), _fp2c(b), _fp2c(b)),
     [lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofVerify", "pairing"),
    ("miller", lambda p, b: (_coord(b), _coord(b), _fp2c(b), _fp2c(b)),
     [lambda p: p.n_cns * p.u], "SigTableSetup", "pairing"),
    ("gt_pow", lambda p, b: (_gt(b), _scalar(b)),
     [lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "pairing"),
    ("gt_pow64", lambda p, b: (_gt(b), _scalar(b)),
     [lambda p: p.n_dps * p.n_values], "RangeProofVerify", "pairing"),
    ("gt_pow128", lambda p, b: (_gt(b), _scalar(b)),
     [lambda p: 1], "GTOrderGate", "pairing"),
    ("final_exp", lambda p, b: (_gt(b),),
     [lambda p: 1], "RangeProofVerify", "pairing"),
    ("gt_mul", lambda p, b: (_gt(b), _gt(b)),
     [lambda p: p.n_dps * p.n_values * p.l,
      lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "pairing"),
    # --- pure-device GT helpers ---
    ("gt_eq", lambda p, b: (_gt(b), _gt(b)),
     [lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofVerify", "device"),
    ("gt_frob1", lambda p, b: (_gt(b),),
     [lambda p: 1], "GTMembershipGate", "device"),
    ("gt_frob2", lambda p, b: (_gt(b),),
     [lambda p: 1], "GTMembershipGate", "device"),
    # --- Pallas-only bucketed ops (lazy wrappers in proofs/range_proof) ---
    ("gt_pow_fixed_multi",
     lambda p, b: (_pow_tables(p), _z((b,), "int32"), _scalar(b)),
     [lambda p: p.n_cns * p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "pallas"),
    ("gt_pow_gtb", lambda p, b: (_scalar(b),),
     [lambda p: p.n_dps * p.n_values * p.l],
     "RangeProofCreate", "pallas"),
]

def _shard_schemas(p: Profile) -> list:
    """The per-shard program set of the mesh proof plane — the SAME bucketed
    ops as the full-batch schemas, at the smaller per-shard batch sizes the
    chunked dispatch hits (parallel/proof_mesh.rlc_total_shards slices the
    flat ns*V*l digit batch; proofs/range_proof._commit_kernel_sharded
    slices the dp-flattened value axis V = n_dps*n_values). Empty when the
    profile is single-shard, so single-device registries are a subset of
    sharded ones (test_precompile.py enforces both directions)."""
    if p.n_shards <= 1:
        return []

    def cdiv(a, k):
        return -(-a // k)

    # verify shard: slice of the flattened ns*V*l joint digit batch
    vs = lambda p: cdiv(p.n_cns * p.n_dps * p.n_values * p.l, p.n_shards)
    # creation shard: slice of the dp-flattened value axis
    cs = lambda p: cdiv(p.n_dps * p.n_values, p.n_shards)
    csl = lambda p: cs(p) * p.l
    ncsl = lambda p: p.n_cns * cs(p) * p.l
    return [
        # --- rlc_total_shards per-shard body ---
        ("miller", lambda p, b: (_coord(b), _coord(b), _fp2c(b), _fp2c(b)),
         [vs], "RangeProofVerifyShard", "pairing"),
        ("gt_pow64", lambda p, b: (_gt(b), _scalar(b)),
         [vs], "RangeProofVerifyShard", "pairing"),
        # --- _commit_kernel per-shard body (D / V_pts / a stages) ---
        ("fn_add", lambda p, b: (_scalar(b), _scalar(b)),
         [cs], "RangeProofCreateShard", "device"),
        ("fn_neg", lambda p, b: (_scalar(b),),
         [csl, ncsl], "RangeProofCreateShard", "device"),
        ("fn_mul_plain", lambda p, b: (_scalar(b), _scalar(b)),
         [ncsl], "RangeProofCreateShard", "device"),
        ("fn_mont_mul", lambda p, b: (_scalar(b), _scalar(b)),
         [csl], "RangeProofCreateShard", "device"),
        ("fixed_base_mul", lambda p, b: (_fb_table(), _scalar(b)),
         [cs, csl], "RangeProofCreateShard", "g1"),
        ("g1_add", lambda p, b: (_g1(b), _g1(b)),
         [cs], "RangeProofCreateShard", "g1"),
        ("g1_normalize", lambda p, b: (_g1(b),),
         [csl], "RangeProofCreateShard", "g1"),
        ("g2_scalar_mul", lambda p, b: (_g2(b), _scalar(b)),
         [ncsl], "RangeProofCreateShard", "g1"),
        ("g2_normalize", lambda p, b: (_g2(b),),
         [ncsl], "RangeProofCreateShard", "g1"),
        ("pair", lambda p, b: (_coord(b), _coord(b), _fp2c(b), _fp2c(b)),
         [ncsl], "RangeProofCreateShard", "pairing"),
        ("gt_pow", lambda p, b: (_gt(b), _scalar(b)),
         [ncsl], "RangeProofCreateShard", "pairing"),
        ("gt_mul", lambda p, b: (_gt(b), _gt(b)),
         [ncsl], "RangeProofCreateShard", "pairing"),
        ("gt_pow_fixed_multi",
         lambda p, b: (_pow_tables(p), _z((b,), "int32"), _scalar(b)),
         [ncsl], "RangeProofCreateShard", "pallas"),
        ("gt_pow_gtb", lambda p, b: (_scalar(b),),
         [csl], "RangeProofCreateShard", "pallas"),
    ]


def _fold_schemas(p: Profile) -> list:
    """The tree-overlay fold program set (service/topology.fold_cts): a
    relay — or the tree root — folds a (k, V) ciphertext stack with
    tree_reduce_add, dispatching ct_add at the halving widths of k, then
    canonicalizes via g1_normalize over the flattened 2*V point batch.
    ``n_fold`` is the largest such k the deployment folds (1 + tree
    fanout at a relay hop, or the root's partial count). Empty when
    n_fold <= 1, so star registries stay a subset of tree ones
    (tests/test_precompile.py pattern for optional axes)."""
    if p.n_fold <= 1:
        return []
    widths = []
    n = p.n_fold
    while n > 1:
        widths.append(n // 2)        # batch of one tree_reduce_add level
        n = n // 2 + (n % 2)
    batches = sorted({w * p.n_values for w in widths})
    return [
        ("ct_add", lambda p, b: (_ct(b), _ct(b)),
         [(lambda p, bb=bb: bb) for bb in batches], "TreeFold", "device"),
        ("g1_normalize", lambda p, b: (_g1(b),),
         [lambda p: 2 * p.n_values], "TreeFold", "g1"),
    ]


def _pane_schemas(p: Profile) -> list:
    """The window-fold program set of a streaming survey's FIRST advance
    (service/streaming.StreamEngine): before the delta chain takes over,
    the initial window aggregate folds the (n_pane, V) pane stack with
    topology.fold_cts — bucketed ct_add at the halving widths of n_pane
    (the canon g1_normalize at 2*V is already a base program). Empty when
    n_pane <= 1, so one-shot registries stay a subset of streaming ones
    (tests/test_precompile.py enforces both directions)."""
    if p.n_pane <= 1:
        return []
    widths = []
    n = p.n_pane
    while n > 1:
        widths.append(n // 2)        # batch of one tree_reduce_add level
        n = n // 2 + (n % 2)
    batches = sorted({w * p.n_values for w in widths})
    return [
        ("ct_add", lambda p, b: (_ct(b), _ct(b)),
         [(lambda p, bb=bb: bb) for bb in batches], "PaneFold", "device"),
    ]


def _bucket_schemas(p: Profile) -> list:
    """The bucket-tile program set of a grid-op survey (min/max/
    frequency_count/union/inter). Above encoding/tiles.TILE_THRESHOLD the
    create path tiles its commit stage: proofs/range_proof.
    create_range_proofs dispatches _commit_kernel_sharded with
    k = max(n_shards, tiles.proof_tile_shards(V, tiles.tile_width()))
    over the dp-flattened value axis V = n_dps * n_buckets (for grid ops
    every bucket is one (u=2, l=1) value, so n_values == n_buckets).
    Same bucketed ops as the creation-shard family, at the tile-derived
    per-shard batch sizes. Empty when n_buckets <= 0 or the grid sits
    below the tile threshold, so plain registries are a subset of
    bucket-grid ones (tests/test_precompile.py enforces both
    directions, mirroring the n_shards / n_queue contracts)."""
    if p.n_buckets <= 0:
        return []
    from ..encoding import tiles as _tiles

    V = p.n_dps * p.n_buckets
    t = _tiles.auto_tile(V)
    if not t:
        return []
    k = max(p.n_shards, _tiles.proof_tile_shards(V, t))
    if k <= 1:
        return []

    def cdiv(a, kk):
        return -(-a // kk)

    # tile shard: slice of the dp-flattened bucket-value axis
    ts = lambda p: cdiv(p.n_dps * p.n_buckets, k)
    tsl = lambda p: ts(p) * p.l
    ntsl = lambda p: p.n_cns * ts(p) * p.l
    return [
        ("fn_add", lambda p, b: (_scalar(b), _scalar(b)),
         [ts], "RangeProofCreateTile", "device"),
        ("fn_neg", lambda p, b: (_scalar(b),),
         [tsl, ntsl], "RangeProofCreateTile", "device"),
        ("fn_mul_plain", lambda p, b: (_scalar(b), _scalar(b)),
         [ntsl], "RangeProofCreateTile", "device"),
        ("fn_mont_mul", lambda p, b: (_scalar(b), _scalar(b)),
         [tsl], "RangeProofCreateTile", "device"),
        ("int_to_scalar", lambda p, b: (_i64(b),),
         [tsl], "RangeProofCreateTile", "device"),
        ("fixed_base_mul", lambda p, b: (_fb_table(), _scalar(b)),
         [ts, tsl], "RangeProofCreateTile", "g1"),
        ("g1_add", lambda p, b: (_g1(b), _g1(b)),
         [ts], "RangeProofCreateTile", "g1"),
        ("g1_normalize", lambda p, b: (_g1(b),),
         [tsl], "RangeProofCreateTile", "g1"),
        ("g2_scalar_mul", lambda p, b: (_g2(b), _scalar(b)),
         [ntsl], "RangeProofCreateTile", "g1"),
        ("g2_normalize", lambda p, b: (_g2(b),),
         [ntsl], "RangeProofCreateTile", "g1"),
        ("pair", lambda p, b: (_coord(b), _coord(b), _fp2c(b), _fp2c(b)),
         [ntsl], "RangeProofCreateTile", "pairing"),
        ("gt_pow", lambda p, b: (_gt(b), _scalar(b)),
         [ntsl], "RangeProofCreateTile", "pairing"),
        ("gt_mul", lambda p, b: (_gt(b), _gt(b)),
         [ntsl], "RangeProofCreateTile", "pairing"),
        ("gt_pow_fixed_multi",
         lambda p, b: (_pow_tables(p), _z((b,), "int32"), _scalar(b)),
         [ntsl], "RangeProofCreateTile", "pallas"),
        ("gt_pow_gtb", lambda p, b: (_scalar(b),),
         [tsl], "RangeProofCreateTile", "pallas"),
    ]


def _queue_schemas(p: Profile) -> list:
    """The cross-survey verify program set of the standing survey server
    (drynx_tpu/server): `n_queue` equal-shape surveys' joint digit batches
    concatenated along the value axis verify in ONE RLC dispatch
    (proofs/range_proof.verify_cross_survey_payloads_joint ->
    parallel/proof_mesh.rlc_total_shards at phase CrossSurveyVerifyShard).
    Same bucketed ops as the verify schemas, at the n_queue-scaled batch
    sizes. Empty when n_queue <= 1, so single-survey registries are a
    subset of queued ones (tests/test_precompile.py enforces both
    directions, mirroring the n_shards contract)."""
    if p.n_queue <= 1:
        return []

    def cdiv(a, k):
        return -(-a // k)

    # value axis of the cross-survey concatenation, and its digit batch
    qv = lambda p: p.n_queue * p.n_dps * p.n_values
    qd = lambda p: p.n_cns * qv(p) * p.l
    # per-shard slice of the concatenated digit batch (chunked dispatch)
    qs = lambda p: cdiv(qd(p), max(1, p.n_shards))
    return [
        # --- rlc_prelude over the concatenation (D eq, challenge, weights)
        ("fn_add", lambda p, b: (_scalar(b), _scalar(b)),
         [qv, lambda p: qv(p) * p.l, qd], "CrossSurveyVerify", "device"),
        ("fn_sub", lambda p, b: (_scalar(b), _scalar(b)),
         [qd], "CrossSurveyVerify", "device"),
        ("fn_neg", lambda p, b: (_scalar(b),),
         [lambda p: qv(p) * p.l, qd], "CrossSurveyVerify", "device"),
        ("fn_mul_plain", lambda p, b: (_scalar(b), _scalar(b)),
         [qd], "CrossSurveyVerify", "device"),
        # rlc weights + challenge recompute over the concatenation (the
        # only family the verify worker dispatches as jits on CPU — the
        # g1/pairing families host-detour there, so warming these is what
        # keeps the pipeline's verify thread trace-free)
        ("int_to_scalar", lambda p, b: (_i64(b),),
         [qv, lambda p: qv(p) * p.l, qd], "CrossSurveyVerify", "device"),
        ("to_mont_p", lambda p, b: (_scalar(b),),
         [qv, lambda p: qv(p) * p.l, qd], "CrossSurveyVerify", "device"),
        ("from_mont_p", lambda p, b: (_scalar(b),),
         [qv, lambda p: qv(p) * p.l, qd], "CrossSurveyVerify", "device"),
        # --- _g1_prep + the single-device fallback verifier ---
        ("g1_neg", lambda p, b: (_g1(b),),
         [qv], "CrossSurveyVerify", "g1"),
        ("g1_scalar_mul", lambda p, b: (_g1(b), _scalar(b)),
         [qv, lambda p: p.n_cns * qv(p)], "CrossSurveyVerify", "g1"),
        ("g1_scalar_mul64", lambda p, b: (_g1(b), _scalar(b)),
         [qv, qd], "CrossSurveyVerify", "g1"),
        ("g1_add", lambda p, b: (_g1(b), _g1(b)),
         [qd], "CrossSurveyVerify", "g1"),
        ("g1_normalize", lambda p, b: (_g1(b),),
         [qd], "CrossSurveyVerify", "g1"),
        ("g2_normalize", lambda p, b: (_g2(b),),
         [qd], "CrossSurveyVerify", "g1"),
        ("fixed_base_mul", lambda p, b: (_fb_table(), _scalar(b)),
         [lambda p: qv(p) * p.l], "CrossSurveyVerify", "g1"),
        ("pair", lambda p, b: (_coord(b), _coord(b), _fp2c(b), _fp2c(b)),
         [qd], "CrossSurveyVerify", "pairing"),
        ("gt_pow64", lambda p, b: (_gt(b), _scalar(b)),
         [qv], "CrossSurveyVerify", "pairing"),
        # --- rlc_total_shards per-shard body over the concatenation ---
        ("miller", lambda p, b: (_coord(b), _coord(b), _fp2c(b), _fp2c(b)),
         [qs], "CrossSurveyVerifyShard", "pairing"),
        ("gt_pow64", lambda p, b: (_gt(b), _scalar(b)),
         [qs], "CrossSurveyVerifyShard", "pairing"),
    ]


# Raw Pallas flat entry points the bucketed family dispatches internally on
# TPU. Registered explicitly so their Mosaic compiles land in the
# persistent cache even for call sites outside bucketed wrappers
# (g2.scalar_mul, fp12 pow paths, gt_pow_fixed's mulreduce passes).
_FLAT = 2048  # the pairing family's max_bucket: every big batch chunks to it


def _pallas_specs(p: Profile) -> list:
    def miller(do="lower"):
        from ..crypto import pallas_pairing as pp

        args = (_coord(_FLAT), _coord(_FLAT), _fp2c(_FLAT), _fp2c(_FLAT))
        if do == "call":
            return pp.miller_flat(*args)
        return pp._miller_flat.lower(*args, interpret=False)

    def wpow(n_bits, do="lower"):
        def go():
            from ..crypto import pallas_pairing as pp

            if do == "call":
                return pp.f12_wpow_flat(_gt(_FLAT), _scalar(_FLAT),
                                        n_bits=n_bits, cyc=True)
            return pp._f12_wpow_flat.lower(
                _gt(_FLAT), _scalar(_FLAT), n_bits=n_bits, wbits=3,
                cyc=True, interpret=False)
        return go

    def mulreduce8(do="lower"):
        from ..crypto import pallas_pairing as pp

        g = _z((_FLAT, 8, 6, 2, NL))
        if do == "call":
            return pp.f12_mulreduce8_flat(g)
        return pp._f12_mulreduce8_flat.lower(g, interpret=False)

    return [
        ProgramSpec(f"pallas:miller_flat@{_FLAT}", "miller_flat", "pallas",
                    "Pairing", miller, _pallas_on,
                    lambda: miller("call")),
        ProgramSpec(f"pallas:f12_wpow_flat@{_FLAT}/63c", "f12_wpow_flat",
                    "pallas", "RangeProofVerify", wpow(63), _pallas_on,
                    wpow(63, "call")),
        ProgramSpec(f"pallas:f12_wpow_flat@{_FLAT}/128c", "f12_wpow_flat",
                    "pallas", "GTOrderGate", wpow(128), _pallas_on,
                    wpow(128, "call")),
        ProgramSpec(f"pallas:f12_wpow_flat@{_FLAT}/256c", "f12_wpow_flat",
                    "pallas", "RangeProofCreate", wpow(256), _pallas_on,
                    wpow(256, "call")),
        ProgramSpec(f"pallas:f12_mulreduce8_flat@{_FLAT}",
                    "f12_mulreduce8_flat", "pallas", "RangeProofCreate",
                    mulreduce8, _pallas_on, lambda: mulreduce8("call")),
    ]


def _fused_specs(p: Profile) -> list:
    """The fused exec pipeline (service.py module-level jits), at the exact
    survey shapes run_survey dispatches."""
    V, nd, T = p.n_values, p.n_dps, 2 * p.dlog_limit

    def enc_at(w):
        def go(do="lower"):
            import jax.numpy as jnp
            import numpy as np

            from ..service import service as svc

            args = (_fb_table(),
                    jnp.asarray(np.zeros((nd, w), dtype=np.int64)),
                    _z((nd, w, NL)))
            return (svc._fused_enc(*args) if do == "call"
                    else svc._fused_enc.lower(*args))
        return go

    enc = enc_at(V)

    def agg(do="lower"):
        from ..service import service as svc

        a = (_z((nd, V, 2, 3, NL)),)
        return (svc._fused_agg(*a) if do == "call"
                else svc._fused_agg.lower(*a))

    # the key switch is one pass a computing node and a finish, both at
    # width V: no program of it knows the roster's size
    def ks(do="lower"):
        from ..parallel import keyswitch as kswitch

        args = (_fb_table(), _z((V, 3, NL)), _z((NL,)), _z((V, NL)),
                _z((V, 3, NL)), _z((V, 3, NL)))
        return (kswitch._ks_pass(*args) if do == "call"
                else kswitch._ks_pass.lower(*args))

    def ks_finish(do="lower"):
        import jax.numpy as jnp

        from ..parallel import keyswitch as kswitch

        args = (_z((V, 2, 3, NL)), _z((V, 3, NL)), _z((V, 3, NL)),
                jnp.asarray(0, dtype=jnp.int64))
        return (kswitch._ks_finish(*args) if do == "call"
                else kswitch._ks_finish.lower(*args))

    def dec(do="lower"):
        from ..service import service as svc

        args = (_z((V, 2, 3, NL)), _z((NL,)), _z((T,)), _z((T, NL)),
                _z((T,)), _z((T,), "int32"))
        return (svc._fused_dec(*args) if do == "call"
                else svc._fused_dec.lower(*args))

    mk = lambda nm, th, ph: ProgramSpec(f"fused:{nm}", nm, "fused", ph, th,
                                        lambda: True,
                                        lambda th=th: th("call"))
    specs = [mk("enc", enc, "DataCollection"),
             mk("agg", agg, "Aggregation"),
             mk("ks", ks, "KeySwitching"),
             mk("ks_finish", ks_finish, "KeySwitching"),
             mk("dec", dec, "Decryption")]
    if p.n_buckets > 0:
        # chunked encrypt of a grid survey: service.execute_survey slabs
        # the (nd, n_buckets) stats through _fused_enc at plan_tiles
        # widths (balanced tiling => at most 2 distinct widths)
        from ..encoding import tiles as _tiles

        t = _tiles.auto_tile(p.n_buckets)
        if t:
            widths = sorted({b - a for a, b
                             in _tiles.plan_tiles(p.n_buckets, t).tiles})
            for w in widths:
                th = enc_at(w)
                specs.append(ProgramSpec(
                    f"fused:enc@{w}", "enc", "fused",
                    "DataCollectionTile", th, lambda: True,
                    lambda th=th: th("call")))
    return specs


def _pool_specs(p: Profile) -> list:
    """The DRO slab program set of a diffp survey (Profile.n_noise): the
    three stored programs `parallel.dro` runs the phase's G1 work in
    (`dro.PROGRAMS`: noise encryption, zero encryption for a fresh pass or
    a pool refill, gather + add), certified at the exact slab widths
    `dro.slab_widths` chunks n_noise into; the gather reads the whole
    n_noise list. Empty when n_noise <= 0, so non-diffp registries are a
    subset of pooled ones (tests/test_precompile.py enforces both
    directions)."""
    if p.n_noise <= 0:
        return []
    from ..parallel import dro as _dro

    def idx(w):
        import jax
        import numpy as np

        # a slab of jax.random.permutation(key, n_noise): jax's default int
        return _z((w,), jax.dtypes.canonicalize_dtype(np.int64))

    args_of = {
        "_dro_noise_enc": lambda w: (_fb_table(), _fb_table(), _i64(w),
                                     _scalar(w)),
        "_dro_zero_enc": lambda w: (_fb_table(), _fb_table(), _scalar(w)),
        "_dro_permute_add": lambda w: (_ct(p.n_noise), idx(w), _ct(w)),
    }

    def at(nm, w):
        def go(do="lower"):
            prog, args = getattr(_dro, nm), args_of[nm](w)
            return prog(*args) if do == "call" else prog.lower(*args)
        return go

    specs = []
    for w in _dro.slab_widths(p.n_noise):
        for nm in _dro.PROGRAMS:
            th = at(nm, w)
            specs.append(ProgramSpec(
                f"pool:{nm[1:]}@{w}", nm[1:], "pool", "DROPool", th,
                lambda: True, lambda th=th: th("call"),
                family="device"))
    return specs


def _pane_specs(p: Profile) -> list:
    """The pane-delta program set of a streaming survey
    (service/streaming.StreamEngine.advance): every steady-state window
    slide dispatches the RAW ciphertext jits ``eg.ct_add`` / ``eg.ct_sub``
    at the standing (V, 2, 3, NL) window-aggregate shape — one call per
    added / expired pane. Raw, not bucketed: the delta chain runs
    elementwise on the window tensor, so the jits trace at exactly that
    shape (the bucketed ct_add family only covers the batch-flattened
    widths). Empty when n_pane <= 1, so one-shot registries stay a
    subset of streaming ones (tests/test_precompile.py enforces both
    directions)."""
    if p.n_pane <= 1:
        return []
    V = p.n_values

    def at(nm):
        def go(do="lower"):
            from ..crypto import elgamal as eg

            fn = getattr(eg, nm)
            args = (_ct(V), _ct(V))
            return fn(*args) if do == "call" else fn.lower(*args)
        return go

    specs = []
    for nm in ("ct_add", "ct_sub"):
        th = at(nm)
        specs.append(ProgramSpec(
            f"pane:{nm}@{V}", nm, "pane", "PaneDelta", th,
            lambda: True, lambda th=th: th("call"), family="device"))
    return specs


# canonical flat width the wire widen programs lower at: the program is
# elementwise so any width certifies the pipeline; 4096 matches the pool
# slab width (the largest steady-state wire tensor)
_WIRE_WIDEN_FLAT = 4096


def _wire_specs(p: Profile) -> list:
    """The device-direct decode's on-device widen programs: one jitted
    astype per (narrow, wide) integer dtype pair the v2 wire can ship
    (transport.widen_pairs). Profile-independent — every survey decodes
    frames — so they appear in every registry and never perturb the
    subset/identity contracts of the optional axes."""
    from ..service import transport as T

    specs = []
    for narrow, wide in T.widen_pairs():
        def th(do="lower", narrow=narrow, wide=wide):
            from ..service import transport as T

            prog = T.widen_program(narrow, wide)
            arg = _z((_WIRE_WIDEN_FLAT,), narrow)
            return prog(arg) if do == "call" else prog.lower(arg)

        specs.append(ProgramSpec(
            f"wire:widen@{narrow}->{wide}", "widen", "wire",
            "WireDecode", th, lambda: True,
            lambda th=th: th("call"), family="device"))
    return specs


def build_registry(profile: Profile = BENCH) -> list[ProgramSpec]:
    """Enumerate the proofs-on program set for `profile`.

    Entries landing on the same (op, bucket) dedupe; the returned order is
    cheap-first (fn family before pairings) so an interrupted precompile
    still banks the most programs per second."""
    from ..crypto import batching as B
    from ..proofs import range_proof as rp

    # force-build the lazy bucketed wrappers so BUCKETED_OPS is complete
    # (the gtB table build is host work — TPU path only)
    rp.aot_register_bucketed(build_gtb_table=_pallas_on())

    specs: dict[str, ProgramSpec] = {}
    for op, args_fn, batches, phase, gate in (
            _B_SCHEMAS + _shard_schemas(profile)
            + _queue_schemas(profile) + _bucket_schemas(profile)
            + _fold_schemas(profile) + _pane_schemas(profile)):
        w = B.BUCKETED_OPS.get(op)
        for bexpr in batches:
            batch = int(bexpr(profile))
            if w is not None:
                bucket = w.bucket_of(batch)
            else:
                # lazy Pallas-only op not built on this backend: name by
                # its known (min=32, max=2048) bucket config
                bucket = min(max(32, 1 << (batch - 1).bit_length()), 2048)
            name = f"bucketed:{op}@{bucket}"
            if name in specs:
                continue

            def lower(op=op, args_fn=args_fn, bucket=bucket):
                from ..crypto.batching import BUCKETED_OPS

                return BUCKETED_OPS[op].lower(*args_fn(profile, bucket))

            def call(op=op, args_fn=args_fn, bucket=bucket):
                from ..crypto.batching import BUCKETED_OPS

                return BUCKETED_OPS[op](*args_fn(profile, bucket))

            specs[name] = ProgramSpec(name, op, "bucketed", phase, lower,
                                      _GATES[gate], call, family=gate)
    for s in (_pallas_specs(profile) + _fused_specs(profile)
              + _pool_specs(profile) + _pane_specs(profile)
              + _wire_specs(profile)):
        specs[s.name] = s
    return list(specs.values())


# The program set a verify WORKER thread dispatches as real jits on CPU:
# the mod-p/mod-n scalar family used by payload deserialization
# (to_mont_p in _g1/_g2/_gt _from_bytes), the RLC weights (int_to_scalar,
# fn_*), and the wire encoders. The g1/pairing families host-detour on
# CPU and everything else dispatches from the drain thread. The registry
# owns this set so the server's compile lane (which executes exactly
# these during a lower-mode pass) and the warm-coverage test stay in
# lockstep with the schemas above — a worker POOL of any width shares
# the process-wide dispatch caches, so warming the set once covers every
# worker (tests/test_precompile.py asserts the coverage).
WORKER_OPS = frozenset({
    "fn_add", "fn_sub", "fn_neg", "fn_mul_plain", "fn_mont_mul",
    "int_to_scalar", "to_mont_p", "from_mont_p",
})


def worker_specs(profile: Profile) -> list:
    """The registry subset a verify worker may dispatch (device-family
    programs over WORKER_OPS) — the server's execute filter during a CPU
    lower-mode compile pass."""
    return [s for s in build_registry(profile)
            if s.family == "device" and s.op in WORKER_OPS]


# ---------------------------------------------------------------------------
# Serial driver
# ---------------------------------------------------------------------------

def precompile(profile: Profile = BENCH, mode: str = "compile",
               stats: CompileStats | None = None,
               log: Callable[[str], None] | None = None,
               only: Callable[[ProgramSpec], bool] | None = None
               ) -> CompileStats:
    """Drive every dispatched program, SERIALLY.

    ``only`` filters the registry before driving it (e.g. the standing
    server's CPU compile lane lower-passes everything, then EXECUTES just
    the ``family == "device"`` programs — the single family the verify
    worker would otherwise first-trace off the main thread).

    mode:
      "lower"   — trace + lower only (--dry-run; CPU-safe, no executable)
      "compile" — AOT .lower().compile(): feeds the persistent XLA cache
                  without executing (the CLI default). NOTE this does NOT
                  warm the jits' own dispatch caches — runtime calls still
                  trace once (cheap) and then hit the persistent cache.
      "execute" — dispatch each program exactly like runtime does, with
                  zero-valued canonical-shape inputs. The only mode that
                  leaves the dispatch caches warm, so later survey calls
                  at these shapes perform ZERO tracing — LocalCluster's
                  main-thread warmup uses it.

    Serial is load-bearing: XLA's CPU compiler has segfaulted under
    concurrent compiles (service._async_proof docstring), and the
    persistent-cache write path assumes one writer per key."""
    assert mode in ("lower", "compile", "execute"), mode
    import jax

    stats = stats or STATS
    install_cache_listener()
    if log is None:
        log = lambda m: print(f"[precompile] {m}", file=sys.stderr,
                              flush=True)
    specs = build_registry(profile)
    if only is not None:
        specs = [s for s in specs if only(s)]
    log(f"{len(specs)} programs registered (mode={mode})")
    errors = 0
    for spec in specs:
        if not spec.dispatched():
            stats.record(spec.name, "skipped",
                         detail="not dispatched on this backend")
            continue
        t0 = time.perf_counter()
        try:
            h0 = stats.listener_hits
            if mode == "execute":
                jax.block_until_ready(spec.call())
                t1 = time.perf_counter()
                cache = "hit" if stats.listener_hits > h0 else "miss"
                stats.record(spec.name, "executed", lower_s=t1 - t0,
                             cache=cache)
                continue
            lowered = spec.lower()
            t1 = time.perf_counter()
            if mode == "lower":
                stats.record(spec.name, "lowered", lower_s=t1 - t0)
                continue
            lowered.compile()
            t2 = time.perf_counter()
            cache = "hit" if stats.listener_hits > h0 else "miss"
            stats.record(spec.name, "compiled", lower_s=t1 - t0,
                         compile_s=t2 - t1, cache=cache)
        except Exception as e:  # record + keep going; CLI exits nonzero
            errors += 1
            stats.record(spec.name, "error",
                         lower_s=time.perf_counter() - t0,
                         detail=f"{type(e).__name__}: {e}")
    t = stats.totals()
    log(f"done: {t['compiled']} compiled / {t['executed']} executed / "
        f"{t['lowered']} lowered / {t['skipped']} skipped / "
        f"{errors} errors; lower {t['lower_seconds']:.1f}s compile "
        f"{t['compile_seconds']:.1f}s")
    return stats


# ---------------------------------------------------------------------------
# Trace-safety guard (the r05 segfault class)
# ---------------------------------------------------------------------------

_GUARDED = False


def trace_guard(min_recursion: int = 20000,
                stack_bytes: int = 64 * 1024 * 1024) -> None:
    """Make first-touch tracing survivable anywhere it happens.

    partial_eval recurses ~1 Python frame per traced equation; the pairing
    kernels reach >10k frames. Two failure modes guarded here:
      * RecursionError on the MAIN thread (recursion limit too low),
      * a C-STACK overflow (segfault, not an exception) on WORKER threads,
        whose default 8 MB stacks are half the main thread's — the r05
        crash tracing pair_flat from a dp_lists proof thread.
    threading.stack_size applies to threads created AFTER this call, so
    LocalCluster runs it in __init__, before any _async_proof thread."""
    global _GUARDED
    if _GUARDED:
        return
    if sys.getrecursionlimit() < min_recursion:
        sys.setrecursionlimit(min_recursion)
    try:
        import threading

        threading.stack_size(stack_bytes)
    except (ValueError, RuntimeError, OverflowError):
        pass  # platform cap; recursion limit still protects the main thread
    _GUARDED = True

"""Compile the main path's Pallas kernels for a described TPU v5e, no chip.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is described, not attached (on-chip-measurement guide, section 2,
rehearsal 3). Each case lowers one kernel entry point at the bucket the
registry dispatches (compilecache/registry.py: 2048 for the flat pairing
family) with interpret=False and checks that a Mosaic kernel
(`tpu_custom_call`) is in the compiled program. Nothing runs, so this says
nothing about results or times on the device; it catches block shapes,
VMEM budgets and dtypes that Mosaic refuses before any chip time is spent.

This is the only test file that describes a topology. Only one process may
load libtpu at a time, so the call lives in a module-scoped fixture and
never runs at import; see the guide for why.

Each case prints one `TPU_COMPILE {...}` line with its lower and compile
seconds (sandbox seconds, not device metrics; run with -s to see them).
A case stays in tier-1 only while it takes under 30 s alone on the 8-core
sandbox and the file under 120 s in all; the others are marked slow with
the lower+compile seconds measured there in PR 21 (seven cases at a time,
so some 1.5x what each takes alone; the variable-base ladder's two on limb
tiles alone, PR 35).
The reduce kernel's rows are a loop, so `point_reduce_flat/R3` and `/R10`
trace one addition each (8-10 s each on an 8-core CPU host, where R10
unrolled took 129 s); R10 stays out of tier-1 because
`test_the_aggregate_compiles_for_v5e` lowers the same 10-row kernel at the
grid cells' width.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from drynx_tpu.crypto import pallas_ops as po
from drynx_tpu.crypto import pallas_pairing as pp

NL = po.NL
B = 2048        # registry._FLAT: the pairing family's max_bucket


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip: keep it off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def s(one_chip):
    """`s(shape)`: a ShapeDtypeStruct (uint32 unless told) placed on the
    described chip."""
    def spec(shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return spec


def _slow(seconds, pr=21):
    return pytest.mark.slow(
        reason=f"{seconds} s lower+compile on the 8-core sandbox (PR {pr})")


# name -> builder(s) returning the jax.stages.Lowered; `s` is the fixture
# above.
def _g1(s, n=B):
    return s((n, 3, NL))


def _gt(s, n=B):
    return s((n, 6, 2, NL))


def _k(s, n=B):
    return s((n, NL))


def _pair_args(s, n=B):
    return s((n, NL)), s((n, NL)), s((n, 2, NL)), s((n, 2, NL))


CASES = [
    ("point_add_flat",
     lambda s: po._point_add_flat.lower(_g1(s), _g1(s), interpret=False)),
    ("point_reduce_flat/R3",
     lambda s: po._point_reduce_flat.lower(s((3, B, 3, NL)),
                                           interpret=False)),
    ("point_reduce_flat/R10",
     lambda s: po._point_reduce_flat.lower(s((10, B, 3, NL)),
                                           interpret=False)),
    # the limb-tile kernel since PR 33: 2 048 lanes are two of its tiles;
    # 12.5-15 s a case, 11 of them the lowering of 31 000 operations a
    # window, so the file's 120 s hold no case at 1 024 or 4 096 besides
    ("fixed_base_mul_flat/64w",
     lambda s: po._fixed_base_mul_flat.lower(
         s((64, 16, 3, NL)), _k(s), n_windows=64, interpret=False)),
    ("fixed_base_mul_flat/16w",
     lambda s: po._fixed_base_mul_flat.lower(
         s((64, 16, 3, NL)), _k(s), n_windows=16, interpret=False)),
    ("f12_csqr_flat",
     lambda s: pp._f12_csqr_flat.lower(_gt(s), interpret=False)),
    ("f12_mul_flat",
     lambda s: pp._f12_mul_flat.lower(_gt(s), _gt(s), interpret=False)),
    ("f12_slotmul_flat/frob1",
     lambda s: pp._f12_slotmul_flat.lower(_gt(s), which="frob1",
                                          interpret=False)),
    ("f12_slotmul_flat/frob2",
     lambda s: pp._f12_slotmul_flat.lower(_gt(s), which="frob2",
                                          interpret=False)),
    ("fp_inv_flat",
     lambda s: pp._fp_inv_flat.lower(_k(s), interpret=False)),
    ("f2_inv_flat",
     lambda s: pp._f2_inv_flat.lower(s((B, 2, NL)), interpret=False)),
    ("f12_inv_flat",
     lambda s: pp._f12_inv_flat.lower(_gt(s), interpret=False)),
    # on limb tiles since PR 35: 2 048 lanes are two tiles; 54 s of the 62
    # the lowering of 140 000 operations (30 s from a script)
    ("scalar_mul_flat/16w",
     lambda s: po._scalar_mul_flat.lower(_g1(s), _k(s), n_windows=16,
                                         interpret=False)),
    ("scalar_mul_flat/64w",
     lambda s: po._scalar_mul_flat.lower(_g1(s), _k(s), n_windows=64,
                                         interpret=False)),
    ("f12_mulreduce8_flat",
     lambda s: pp._f12_mulreduce8_flat.lower(s((B, 8, 6, 2, NL)),
                                             interpret=False)),
    ("f12_wpow_flat/63c",
     lambda s: pp._f12_wpow_flat.lower(_gt(s), _k(s), n_bits=63, wbits=3,
                                       cyc=True, interpret=False)),
    ("f12_wpow_flat/128c",
     lambda s: pp._f12_wpow_flat.lower(_gt(s), _k(s), n_bits=128, wbits=3,
                                       cyc=True, interpret=False)),
    ("f12_wpow_flat/256c",
     lambda s: pp._f12_wpow_flat.lower(_gt(s), _k(s), n_bits=256, wbits=3,
                                       cyc=True, interpret=False)),
    ("g2_scalar_mul_flat",
     lambda s: pp._g2_scalar_mul_flat.lower(s((B, 3, 2, NL)), _k(s),
                                            interpret=False)),
    ("miller_flat",
     lambda s: pp._miller_flat.lower(*_pair_args(s), interpret=False)),
    # the three below are compositions of the kernels above under one jit,
    # as batching.bucketed dispatches them (final_exp@8, pair@2048,
    # gt_pow_fixed_multi@2048 with the 3 CN x u=16 window tables)
    ("final_exp_flat@8",
     lambda s: jax.jit(pp.final_exp_flat).lower(_gt(s, 8))),
    ("pair_flat",
     lambda s: jax.jit(pp.pair_flat).lower(*_pair_args(s))),
    ("gt_pow_fixed_multi",
     lambda s: jax.jit(pp.gt_pow_fixed_multi).lower(
         s((48, 64, 16, 6, 2, NL)), s((B,), jnp.int32), _k(s))),
]

_MARKS = {
    "f12_mul_flat": _slow(43),
    "f12_inv_flat": _slow(108),
    "point_reduce_flat/R10": pytest.mark.slow(
        reason="8 s lower+compile on an 8-core CPU host, rolled; the same "
               "10-row kernel as test_the_aggregate_compiles_for_v5e"),
    "scalar_mul_flat/16w": _slow(62, pr=35),
    "scalar_mul_flat/64w": _slow(63, pr=35),
    "miller_flat": _slow(385),
    "f12_wpow_flat/128c": _slow(462),
    "f12_wpow_flat/63c": _slow(478),
    "f12_mulreduce8_flat": _slow(482),
    "f12_wpow_flat/256c": _slow(495),
    "g2_scalar_mul_flat": _slow(700),
    "final_exp_flat@8": _slow(745),
    "pair_flat": _slow(852),
    "gt_pow_fixed_multi": _slow(941),
}


@pytest.mark.parametrize(
    "name,build",
    [pytest.param(n, b, id=n, marks=_MARKS.get(n, ())) for n, b in CASES])
def test_kernel_compiles_for_v5e(name, build, s, no_persistent_cache,
                                 monkeypatch):
    # the composed entry points read the module flag at trace time
    monkeypatch.setattr(po, "INTERPRET", False)
    monkeypatch.setattr(pp, "INTERPRET", False)
    _compile_and_report(name, lambda: build(s))


def _compile_and_report(name, lower):
    t0 = time.perf_counter()
    lowered = lower()
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    mem = compiled.memory_analysis()
    print("TPU_COMPILE " + json.dumps({
        "kernel": name, "lower_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
        "code_bytes": mem.generated_code_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes}))
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_the_noise_phases_add_compiles_for_v5e(s, no_persistent_cache,
                                               monkeypatch):
    """`parallel/dro._dro_permute_add` as a TPU traces it, at the diffp
    cell's shapes (a slab of 4 096 of a list of 262 144): the gather, the
    complete-add kernel under the name the benchmark's patterns read, and
    no scratch to speak of (9 s lower + compile on the 8-core sandbox,
    PR 31)."""
    from drynx_tpu.parallel import dro

    monkeypatch.setattr(po, "INTERPRET", False)
    monkeypatch.setattr(po, "available", lambda: True)
    size, slab = 262144, dro.CHUNK
    compiled = _compile_and_report(
        f"dro_permute_add@{size}/{slab}",
        lambda: dro._dro_permute_add.lower(
            s((size, 2, 3, NL)), s((slab,), jnp.int32),
            s((slab, 2, 3, NL))))
    text = compiled.as_text()
    assert "%_point_add_flat" in text and "gather" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_the_aggregate_compiles_for_v5e(s, no_persistent_cache, monkeypatch):
    """`service._fused_agg` as a TPU traces it, at the grid cells' shape
    (10 DPs x 12 288 buckets): ONE call of the reduce kernel over the
    DPs, each component of each bucket a lane, and a scratch under a GB,
    where the jnp tree's levels took 12.4 GB (8 s lower + compile on an
    8-core CPU host, temp size 377 519 616 B)."""
    from drynx_tpu.service import service as svc

    monkeypatch.setattr(po, "INTERPRET", False)
    monkeypatch.setattr(po, "available", lambda: True)
    n_dps, v = 10, 12288
    compiled = _compile_and_report(
        f"fused_agg@{n_dps}x{v}",
        lambda: svc._fused_agg.lower(s((n_dps, v, 2, 3, NL))))
    text = compiled.as_text()
    assert "%_point_reduce_flat" in text
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"u32[{n_dps},3,16,{2 * v}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.slow(reason="44 s lower+compile on the 8-core sandbox (PR 35; "
                  "152 s before the ladder moved to limb tiles): the "
                  "64-window variable-base ladder's lowering")
def test_the_obfuscation_pass_compiles_for_v5e(s, no_persistent_cache,
                                               monkeypatch):
    """`parallel/obfuscation._obf_scalar_mul` as a TPU traces it, at the
    obfuscated grid cell's width (12 288 ciphertexts, 12 288 lanes a
    component, no padding to a power of two): the variable-base ladder
    under the name the benchmark's patterns read, and no scratch (36 s
    lower + 7 s compile on the 8-core sandbox, temp size 0 B, PR 35)."""
    from drynx_tpu.parallel import obfuscation as obf

    monkeypatch.setattr(po, "INTERPRET", False)
    monkeypatch.setattr(po, "available", lambda: True)
    v = 12288
    compiled = _compile_and_report(
        f"obf_scalar_mul@{v}",
        lambda: obf._obf_scalar_mul.lower(s((v, 2, 3, NL)), s((v, NL))))
    text = compiled.as_text()
    # a component a call, V lanes each (the decryption's shape), as limb
    # tiles: 12 whole tiles of 1 024 lanes, nothing is padded to a power
    # of two
    assert "%_scalar_mul_flat" in text
    assert f"u32[3,16,{v // po.LANES},{po.LANES}]" in text
    assert "16384" not in text and "u32[3,16,128,128]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20

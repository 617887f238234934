"""Collective-layer tests on a virtual 8-device CPU mesh (shard_map).

The multi-"node" analogue of the reference's in-process LocalTest protocol
tests (reference protocols/*_test.go, services/service_test.go:70): 8 mesh
devices play 8 servers; aggregation + key-switch + obfuscation run as real
sharded collectives and results are checked against clear-text twins.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from drynx_tpu.crypto import elgamal as eg
from drynx_tpu.parallel import collective as col

pytestmark = pytest.mark.slow  # heavy compiles; fast tier = -m 'not slow'

RNG = np.random.default_rng(21)
NS = 8


@pytest.fixture(scope="module")
def setup():
    secrets, pubs = zip(*[eg.keygen(RNG) for _ in range(NS)])
    coll_pub = col.collective_key(pubs)
    qx, qpub = eg.keygen(RNG)
    return {
        "secrets": secrets,
        "coll_tab": eg.pub_table(coll_pub),
        "qx": qx,
        "q_tab": eg.pub_table(qpub),
        "table": eg.DecryptionTable(limit=200),
        "mesh": col.make_mesh(NS),
    }


def test_aggregate_then_keyswitch(setup):
    s = setup
    values = np.arange(1, NS + 1, dtype=np.int64)  # one value per DP/server
    cts, _ = eg.encrypt_ints(jax.random.PRNGKey(0), s["coll_tab"], values)
    xs = jnp.asarray(np.stack([eg.secret_to_limbs(x) for x in s["secrets"]]))
    rs = eg.random_scalars(jax.random.PRNGKey(1), (NS,))

    qtab = s["q_tab"].table

    def prog(ct, x, r):
        agg = col.allreduce_group_add(ct, "srv", NS)
        return col.keyswitch_collective(agg, x, r, qtab, "srv", NS)

    f = shard_map(prog, mesh=s["mesh"],
                  in_specs=(P("srv"), P("srv"), P("srv")),
                  out_specs=P("srv"), check_vma=False)
    out = f(cts, xs, rs)  # (NS, 2, 3, 16) — identical switched ct per device

    dec, found = eg.decrypt_ints(out[0], s["qx"], s["table"])
    assert bool(found) and int(dec) == int(values.sum())
    dec2, _ = eg.decrypt_ints(out[3], s["qx"], s["table"])
    assert int(dec2) == int(values.sum())


def test_obfuscation_preserves_zero_semantics(setup):
    s = setup
    values = np.asarray([0, 5], dtype=np.int64)
    cts, _ = eg.encrypt_ints(jax.random.PRNGKey(2), s["coll_tab"], values)
    cts = jnp.broadcast_to(cts, (NS,) + cts.shape)  # replicated input
    scalars = eg.random_scalars(jax.random.PRNGKey(3), (NS, 2))

    def prog(ct, sc):
        return col.obfuscate_collective(ct[0], sc[0], "srv", NS)

    f = shard_map(prog, mesh=s["mesh"], in_specs=(P("srv"), P("srv")),
                  out_specs=P("srv"), check_vma=False)
    out = f(cts, scalars)

    xsum = sum(s["secrets"])  # decrypt under collective secret
    # out_specs=P("srv") concatenates each device's (2, ...) ct block along
    # axis 0; device 0's block is out[:2].
    z = eg.decrypt_check_zero(
        out[:2], jnp.asarray(eg.secret_to_limbs(xsum)))
    assert np.asarray(z).tolist() == [True, False]


def test_allreduce_scalar_product_matches_host(setup):
    from drynx_tpu.crypto import field as F
    from drynx_tpu.crypto import params
    s = setup
    sc = eg.random_scalars(jax.random.PRNGKey(4), (NS,))

    def prog(x):
        return col.allreduce_scalar_mul(x, "srv", NS)

    f = shard_map(prog, mesh=s["mesh"], in_specs=(P("srv"),),
                  out_specs=P("srv"), check_vma=False)
    out = f(sc)
    ints = F.to_int(np.asarray(sc))
    want = 1
    for i in ints:
        want = want * int(i) % params.N
    got = F.to_int(np.asarray(out[0]))
    assert int(got) == want

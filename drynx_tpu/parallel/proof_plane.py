"""The mesh proof plane: device-sharding policy for proof creation and
joint-range verification.

Whenever >= 2 devices are visible, the proof pipeline's two flat-batch
hot paths run SHARDED by default:

  * creation — the `dp` axis: each shard of the all-DP digit batch builds
    its `a_ij` GT-table exponentiations locally (proofs/range_proof.py
    `_commit_kernel_sharded`), gathered once per batch before the
    Fiat-Shamir hash;
  * verification — the `vn` axis: each VN-role shard verifies a slice of
    the joint RLC digit batch (parallel/proof_mesh.py `rlc_total_shards`),
    partial GT products combined with one log-tree GT multiplication.

Execution strategy (why this is NOT shard_map): the per-shard work is the
SAME single-device bucketed program set (crypto/batching.py) dispatched
once per shard, so the plane reuses executables the compilecache registry
already covers (at the smaller per-shard buckets — registry._shard_schemas)
instead of minting one giant SPMD program. The monolithic shard_map path
exceeded 90 minutes of XLA CPU compile (tests/test_proof_mesh.py history)
because a shard_map body must stay traceable and therefore cannot take the
host-oracle detour; per-shard dispatch keeps every backend's normal
routing. On an accelerator mesh each shard's inputs are device_put onto
its own device and JAX's async dispatch overlaps the shards; on CPU the
shards execute through the host-native backend sequentially (placement is
skipped — host detours ignore placement, and committed-device mixing
would break the small XLA fn_* programs), so the fake 8-device mesh
exercises sharding SEMANTICS, not speedup. Per-value independence of the
range-proof transcripts makes every sharded result bit-identical to the
single-device path (exact mod-p arithmetic is associative), so the
accept/reject decision cannot drift — tests/test_proof_mesh.py asserts
byte equality.

Policy env DRYNX_PROOF_PLANE: "auto" (default — shard over all visible
devices when >= 2), "off" (single-device everywhere), or an integer shard
count override.
"""
from __future__ import annotations

import os
import time

from ..utils.timers import PhaseTimers

ENV_FLAG = "DRYNX_PROOF_PLANE"

# Async shard pipeline kill-switch: "serial"/"off" restores the
# block-per-shard dispatch loop (the reference side of
# tests/test_device_path.py::test_async_dispatch_matches_serial).
ASYNC_ENV = "DRYNX_ASYNC_DISPATCH"

# Batches smaller than this never shard: the per-shard dispatch overhead
# (host_dispatch flatten + jit cache lookup per shard) would exceed the
# per-element work of a handful of digit proofs.
MIN_ITEMS_PER_SHARD = 1

# Per-shard phase spans ("<Phase>.shard<i>"), folded into the bench
# supervisor record (bench.py) — the observability analogue of the
# per-program CompileStats rows.
SHARD_TIMERS = PhaseTimers()


def _policy() -> str:
    return os.environ.get(ENV_FLAG, "auto").strip().lower()


def device_count() -> int:
    """Visible devices. A backend that fails to initialise raises here: a
    chip that cannot be reached must not read as a one-device host."""
    import jax

    return len(jax.devices())


def n_shards() -> int:
    """Shard count the plane runs at: visible devices under "auto", a
    forced count under an integer policy, 1 under "off"."""
    pol = _policy()
    if pol in ("off", "0", "none", "single"):
        return 1
    if pol not in ("", "auto", "on"):
        try:
            return max(1, int(pol))
        except ValueError:
            pass
    return max(1, device_count())


def enabled() -> bool:
    """True iff sharded creation/verification is the default path."""
    return n_shards() >= 2


def placement_on() -> bool:
    """True iff shards are device_put onto distinct mesh devices: only on
    the Pallas (accelerator) backend with a real multi-device mesh. On CPU
    the heavy per-shard families detour to the host backend (placement is
    meaningless) while the small XLA helpers would error on mixed
    committed devices."""
    from ..crypto import pallas_ops as po

    return po.available() and device_count() >= 2


def shard_device(i: int):
    import jax

    devs = jax.devices()
    return devs[i % len(devs)]


def async_on() -> bool:
    """True iff dispatch_shards pipelines: never blocks between enqueues,
    one block_until_ready barrier at the end. DRYNX_ASYNC_DISPATCH=serial
    (or off/0/no) restores the per-shard blocking loop."""
    return os.environ.get(ASYNC_ENV,
                          "").strip().lower() not in ("serial", "off",
                                                      "0", "no")


def _put_leaf(x, dev, donate: bool):
    import jax

    # identity fast-path: already committed to the target device — a
    # device_put here would be a redundant copy on every shard hop
    if getattr(x, "device", None) == dev:
        return x
    return jax.device_put(x, dev, donate=donate)


def put_shard(tree, i: int, donate: bool = False):
    """Place one shard's arrays on mesh device i (identity off-mesh and
    on single-device hosts). ``donate`` hands the source buffers to the
    transfer — safe only for arrays the caller never reads again (the
    per-shard input slices); backends that cannot alias simply copy."""
    if not placement_on():
        return tree
    import jax

    dev = shard_device(i)
    return jax.tree_util.tree_map(
        lambda x: _put_leaf(x, dev, donate), tree)


def gather(tree):
    """Bring per-shard results back to the lead device for the combine /
    concat ("results gathered once per batch"). Leaves already on the
    lead device pass through untouched — the consumer and producer share
    a device, so there is nothing to move."""
    if not placement_on():
        return tree
    import jax

    dev = shard_device(0)
    return jax.tree_util.tree_map(
        lambda x: x if getattr(x, "device", None) == dev
        else jax.device_put(x, dev), tree)


def shard_slices(n: int, k: int,
                 min_items: int = MIN_ITEMS_PER_SHARD) -> list:
    """Balanced contiguous [start, stop) slices of range(n) over <= k
    shards; never emits an empty shard, never splits below min_items."""
    n, k = int(n), int(k)
    if n <= 0:
        return []
    k = max(1, min(k, n // max(1, min_items)) or 1)
    base, extra = divmod(n, k)
    out, start = [], 0
    for i in range(k):
        stop = start + base + (1 if i < extra else 0)
        out.append((start, stop))
        start = stop
    return out


def record_shard(phase: str, i: int, seconds: float) -> None:
    SHARD_TIMERS.add(f"{phase}.shard{i}", seconds)


def timers_snapshot() -> dict:
    """{"<Phase>.shard<i>": seconds} accumulated this process, plus the
    "<Phase>.<stage>#<host_glue|device_compute>" attribution keys."""
    return {k: round(v, 6) for k, v in SHARD_TIMERS.items()}


def dispatch_shards(phase: str, fn, shard_args: list,
                    prefetch=None) -> list:
    """Dispatch fn(i, *args_i) for every shard as a pipeline.

    Async mode (default): the dispatch thread never blocks between
    enqueues — shard i+1's inputs are ``prefetch``-uploaded right after
    shard i is enqueued (so the upload overlaps shard i's compute on an
    async backend) and one ``block_until_ready`` barrier at the end waits
    for the whole batch. ``DRYNX_ASYNC_DISPATCH=serial`` restores the
    block-per-shard loop (bench comparison / debugging).

    ``prefetch(i, *args_i) -> new_args_i`` is the input-staging stage
    (put_shard uploads, slicing); when given, ``fn`` receives its return
    value instead of the raw args. Prefetch time is attributed as
    host_glue; the barrier as device_compute (on a synchronous backend
    the fn() span itself is the device compute and is attributed so).

    Results are gathered to the lead device. The per-shard span keys
    ("<Phase>.shard<i>" dispatch-start -> outputs-ready and
    "<Phase>.dispatch.shard<i>" for the fn() call) are unchanged."""
    import jax

    serial = not async_on()
    n = len(shard_args)
    # fn spans are pure enqueue cost only when placement puts shards on
    # an async accelerator mesh; on the synchronous host backend the
    # fn() call runs the shard's kernels to completion
    fn_kind = "host_glue" if placement_on() else "device_compute"
    outs, t0s = [], []
    nxt = prefetch(0, *shard_args[0]) if (prefetch and n) else None
    for i, args in enumerate(shard_args):
        cur = nxt if prefetch else args
        t0 = time.perf_counter()
        t0s.append(t0)
        out = fn(i, *cur)
        dt = time.perf_counter() - t0
        record_shard(f"{phase}.dispatch", i, dt)
        SHARD_TIMERS.add_split(f"{phase}.enqueue", fn_kind, dt)
        outs.append(out)
        if prefetch and i + 1 < n:
            tp = time.perf_counter()
            nxt = prefetch(i + 1, *shard_args[i + 1])
            SHARD_TIMERS.add_split(f"{phase}.upload", "host_glue",
                                   time.perf_counter() - tp)
        if serial:
            jax.block_until_ready(out)
            record_shard(phase, i, time.perf_counter() - t0s[i])
    if not serial:
        tb = time.perf_counter()
        jax.block_until_ready(outs)
        tend = time.perf_counter()
        SHARD_TIMERS.add_split(f"{phase}.block", "device_compute",
                               tend - tb)
        for i in range(n):
            record_shard(phase, i, tend - t0s[i])
    return [gather(o) for o in outs]


__all__ = ["enabled", "n_shards", "device_count", "placement_on",
           "shard_slices", "put_shard", "gather", "dispatch_shards",
           "async_on", "record_shard", "timers_snapshot", "SHARD_TIMERS",
           "ENV_FLAG", "ASYNC_ENV"]

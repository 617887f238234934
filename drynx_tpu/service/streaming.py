"""Streaming surveys: pane-delta aggregation with proof reuse (PR 18).

A production querier re-asks: sliding-window statistics over arriving DP
rows (ROADMAP item 4). The one-shot pipeline charges the FULL survey —
encode, encrypt, range-prove, verify — on every query even when 99% of
the window's rows are unchanged. This engine makes a window advance cost
O(delta) instead of O(window):

  * Arriving rows land in immutable fixed-width **panes** (the row-axis
    analogue of the PR-8 bucket tiles). Each pane is encoded
    (``stats.encode_clear`` — the tiled grid path above the tile
    threshold), encrypted (``_fused_enc`` slabs) and range-proven ONCE.
    Pane randomness is derived by ``jax.random.fold_in`` from the stream
    seed and the pane id, so a restarted engine fed the same rows
    re-derives byte-identical ciphertexts and proof blobs.
  * A pane never mutates, so its range-proof blob (with its Fiat-Shamir
    transcripts) is cached — in memory and, when a ``ProofDB`` is
    attached, durably under the ``pane:`` key prefix (store.pane_key) —
    and **reused byte-identically by every window slide containing it**.
    A reopened engine finds the stored blob and skips proof creation
    entirely.
  * A window advance ships only the ciphertext **delta**: newly sealed
    panes are added, expired panes subtracted via the additive
    homomorphism (``eg.ct_add`` / ``eg.ct_sub``), then canonicalized
    with ``topology.canon_points``. Canonicalization maps a group
    element to ONE byte representation, so delta-advance bytes equal a
    from-scratch ``fold_cts`` over the same window — the mod-p
    fold-associativity argument of tests/test_topology.py extended to
    add/subtract (exactness is the abelian-group cancellation; the
    tests assert byte identity at 1/2/4-pane slides).
  * VNs verify only the NEW panes' proofs plus one per-advance
    aggregation proof — structurally, not just via caching. A pane's
    range proofs are signed and delivered ONCE, at seal time, under a
    stream-stable per-pane survey id (``{stream_id}-p{pid}``) whose
    audit block is committed when the pane seals; the per-advance
    survey id carries only the CN aggregation proofs binding the
    window fold. An old pane therefore costs an advance ZERO envelope
    crypto (the host Schnorr sign + verify per request is ~0.25 s of
    pure-Python field inversions — re-shipping W panes per slide was
    the O(window) term the delta path exists to remove). The stable
    pane sid also makes the VN VerifyCache's (type, sid, digest) key
    effective across engine restarts; the engine's own digest-keyed
    verdict memo (``verify_pane_blob``, routed through the CN's range
    verifier via ``Survey.stream``) additionally dedups identical-
    content panes. Pane transcripts are byte-identical between a
    delta engine and a from-scratch engine on the same stream id —
    same storage keys, payload digests, and codes under the same
    pane sids (the tests assert this digest-for-digest).
  * Privacy soundness for repeated queries: an optional
    ``pool.EpsilonLedger`` charges every responding DP's per-cohort
    budget BEFORE the advance runs (``EpsilonExhausted`` otherwise),
    and a DiffP-enabled stream consumes DRO precompute from the
    cluster's persistent pool — never fresh randomness outside the
    refill lane (the bench gates on ``dro.PRECOMPUTE_CALLS``).

Restricted to additive encodings (``ADDITIVE_OPS``): pane subtraction is
exact only when the window statistic is the plain sum of per-pane
encodings. The frequency grid makes that cover quantiles / medians /
top-k too — they are pure decode modes over the count histogram
(``decode_mode=``, encoding/stats.py).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import secrets
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto import elgamal as eg
from ..encoding import stats as st
from ..encoding import tiles as enc_tiles
from ..parallel import dro
from ..proofs import aggregation as agg_proof
from ..proofs import range_proof as rproof
from ..proofs import requests as rq
from ..resilience import policy as rp
from ..utils.timers import PhaseTimers
from . import topology as topo
from .service import Survey, _once, _pickle
from .store import pane_key

# DRYNX_PROTO_TRACE: report pane seal / proof-commit lifecycle events
# to the runtime protocol recorder (analysis/prototrace.py) — the
# dynamic half of the seal-commit-once typestate rule.
_PROTO_TRACE = os.environ.get("DRYNX_PROTO_TRACE", "0") == "1"

# Encodings whose window statistic is the exact sum of per-pane
# encodings — the precondition for expired-pane subtraction. The grid
# decode modes (quantile/median/top_k/union-style presence) all read a
# frequency_count window.
ADDITIVE_OPS = ("frequency_count", "sum")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name, "").strip()
    return int(v) if v else default


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name, "").strip()
    return float(v) if v else default


@dataclasses.dataclass
class Pane:
    """One sealed, immutable pane: its canonical ciphertext fold and the
    per-DP range-proof blobs. The raw (n_dps, V) ciphertexts are NOT
    retained — the delta path and the aggregation proof only ever need
    the fold."""

    pane_id: int
    fold: np.ndarray               # (V, 2, 3, 16) canonical (canon_points)
    blobs: dict                    # dp name -> RangeProofList bytes
    proofs_reused: bool = False    # blobs came from the pane: store
    block: object = None           # per-pane VN audit block (proofs-on)


@dataclasses.dataclass
class StreamAdvance:
    """Result of one window advance (the streaming SurveyResult)."""

    survey_id: str
    result: object
    decrypted: st.DecryptedVector
    window: tuple                  # (first_pane_id, last_pane_id) inclusive
    panes_new: int                 # sealed for this advance
    panes_expired: int             # subtracted out of the window
    block: object = None           # VN audit block (proofs-on)


class StreamEngine:
    """Pane-based streaming survey over a LocalCluster.

    Contract: every DP is fed the same number of rows (panes seal in
    lockstep across DPs — the aligned pane axis is what makes per-pane
    folds element-wise addable), and a restarted engine re-fed the same
    rows re-derives byte-identical panes (determinism is seeded; see
    module docstring).
    """

    def __init__(self, cluster, op_name: str = "frequency_count",
                 query_min: int = 0, query_max: int = 0, *,
                 stream_id: Optional[str] = None,
                 pane_width: Optional[int] = None,
                 window_panes: Optional[int] = None,
                 ranges=None, proofs: int = 1, diffp=None,
                 decode_mode: Optional[str] = None,
                 pane_db=None, epsilon_ledger=None,
                 epsilon_per_advance: Optional[float] = None,
                 seed: int = 0):
        if op_name not in ADDITIVE_OPS:
            raise ValueError(
                f"streaming requires an additive encoding, got {op_name!r} "
                f"(supported: {ADDITIVE_OPS})")
        self.cluster = cluster
        self.op_name = op_name
        self.query_min = int(query_min)
        self.query_max = int(query_max)
        self.decode_mode = decode_mode
        self.stream_id = stream_id or f"stream-{secrets.token_hex(4)}"
        self.pane_width = (int(pane_width) if pane_width
                           else _env_int("DRYNX_PANE_WIDTH", rp.PANE_WIDTH))
        self.window_panes = (int(window_panes) if window_panes
                             else _env_int("DRYNX_STREAM_WINDOW",
                                           rp.STREAM_WINDOW_PANES))
        if self.pane_width <= 0 or self.window_panes <= 0:
            raise ValueError("pane_width and window_panes must be positive")
        self.proofs_on = proofs == 1 and cluster.vns is not None
        # prototype query: carries the validated ranges / thresholds /
        # diffp every per-advance SurveyQuery re-derives from
        self.sq_proto = cluster.generate_survey_query(
            op_name, query_min, query_max, proofs=proofs, ranges=ranges,
            diffp=diffp, survey_id=f"{self.stream_id}-proto")
        self.ranges = (list(self.sq_proto.query.ranges)
                       if self.sq_proto.query.ranges is not None else None)
        # proofs-off queries carry no ranges (check_parameters forbids
        # them); the per-value specs only feed proof create/verify
        self._ranges_v = (cluster._ranges_per_value(self.sq_proto.query)
                          if self.ranges is not None else [])
        self.V = int(st.output_size(op_name, self.query_min, self.query_max))
        self.pane_db = pane_db
        self.epsilon_ledger = epsilon_ledger
        self.epsilon_per_advance = (
            float(epsilon_per_advance) if epsilon_per_advance is not None
            else _env_float("DRYNX_EPSILON_PER_ADVANCE",
                            rp.EPSILON_PER_ADVANCE))
        # cohort digest: the accountant's key is the (roster, query)
        # population a budget protects — stable across engine restarts
        self.cohort = hashlib.sha256(json.dumps(
            {"op": op_name, "min": self.query_min, "max": self.query_max,
             "dps": sorted(d.name for d in cluster.dp_idents)},
            sort_keys=True).encode()).hexdigest()[:16]
        self._base_key = jax.random.PRNGKey(int(seed))
        self._buffers: dict[str, list] = {d.name: []
                                          for d in cluster.dp_idents}
        self._buffered: dict[str, int] = {d.name: 0
                                          for d in cluster.dp_idents}
        self._panes: list[Pane] = []
        self._win_first = 0
        self._win_last = -1            # empty window
        self._window_ct: Optional[np.ndarray] = None  # noise-free aggregate
        self._last_sid: Optional[str] = None
        self._verify_lock = rp.named_lock("stream_verify_memo_lock")
        self._verify_memo: dict[bytes, bool] = {}
        self.timers = PhaseTimers()
        self.counters = {"panes_sealed": 0, "proofs_created": 0,
                         "proofs_reused": 0, "pane_verifies": 0,
                         "pane_verify_hits": 0, "advances": 0,
                         "epsilon_charges": 0}
        if self.proofs_on:
            for u, _l in rproof.group_ranges(self._ranges_v):
                cluster.ensure_range_sigs(u)
            cluster._warm_kernels(self.timers, self.sq_proto.query)

    # -- feeding + pane sealing --------------------------------------------

    def feed(self, rows_by_dp: dict) -> None:
        """Buffer arriving rows per DP (row values in
        [query_min, query_max] for grid ops). Panes seal at the next
        ``advance()`` — feeding never does device work."""
        for name, rows in rows_by_dp.items():
            if name not in self._buffers:
                raise KeyError(f"unknown DP {name!r}")
            a = np.asarray(rows, dtype=np.int64).reshape(-1)
            self._buffers[name].append(a)
            self._buffered[name] += int(a.shape[0])

    def sealable_panes(self) -> int:
        """Complete panes currently buffered across EVERY DP."""
        if not self._buffered:
            return 0
        return min(self._buffered.values()) // self.pane_width

    def _take_pane_rows(self, name: str) -> np.ndarray:
        buf = np.concatenate(self._buffers[name]) if self._buffers[name] \
            else np.zeros((0,), dtype=np.int64)
        rows, rest = buf[:self.pane_width], buf[self.pane_width:]
        self._buffers[name] = [rest] if rest.size else []
        self._buffered[name] = int(rest.shape[0])
        return rows

    def _pane_key(self, kind: int, pane_id: int):
        return jax.random.fold_in(
            jax.random.fold_in(self._base_key, kind), pane_id)

    def _seal_next_pane(self) -> Pane:
        pid = len(self._panes)
        dp_idents = self.cluster.dp_idents
        tm = self.timers
        tm.start("PaneSeal")
        stats = np.stack([
            np.asarray(st.encode_clear(self.op_name,
                                       self._take_pane_rows(d.name),
                                       self.query_min, self.query_max))
            for d in dp_idents]).astype(np.int64)
        enc_rs = eg.random_scalars(self._pane_key(1, pid), stats.shape)
        f_enc, _f_agg, _f_dec = self.cluster._fused()
        with self.cluster._proof_device_lock:
            tile = enc_tiles.auto_tile(self.V)
            if tile:
                stats_dev = jnp.asarray(stats)
                parts = [np.asarray(f_enc(stats_dev[:, a:b],
                                          enc_rs[:, a:b]))
                         for a, b in enc_tiles.plan_tiles(self.V,
                                                          tile).tiles]
                cts = jnp.asarray(np.concatenate(parts, axis=1))
            else:
                cts = f_enc(jnp.asarray(stats), enc_rs)
            fold = np.asarray(topo.fold_cts(cts))
        blobs: dict = {}
        reused = False
        if self.proofs_on:
            if self.pane_db is not None:
                stored = {d.name: self.pane_db.get(
                    pane_key(self.stream_id, pid, d.name))
                    for d in dp_idents}
                if all(v is not None for v in stored.values()):
                    blobs, reused = stored, True
                    self.counters["proofs_reused"] += len(dp_idents)
            if not blobs:
                sigs_by_u = {u: self.cluster.ensure_range_sigs(u)
                             for u, _l in rproof.group_ranges(
                                 self._ranges_v)}
                with self.cluster._proof_device_lock:
                    lists = rproof.create_range_proof_lists_batched(
                        self._pane_key(2, pid), stats, enc_rs, cts,
                        self._ranges_v, sigs_by_u,
                        self.cluster.coll_tbl.table)
                blobs = {d.name: lists[i].to_bytes()
                         for i, d in enumerate(dp_idents)}
                self.counters["proofs_created"] += len(dp_idents)
                if self.pane_db is not None:
                    for d in dp_idents:
                        self.pane_db.put(
                            pane_key(self.stream_id, pid, d.name),
                            blobs[d.name])
                    self.pane_db.sync()
        pane = Pane(pane_id=pid, fold=fold, blobs=blobs,
                    proofs_reused=reused)
        if self.proofs_on:
            pane.block = self._deliver_pane_proofs(pane)
        self._panes.append(pane)
        self.counters["panes_sealed"] += 1
        if _PROTO_TRACE:
            from ..analysis import prototrace
            prototrace.record(prototrace.new_instance("seal"), "seal")
        tm.end("PaneSeal")
        return pane

    def pane_sid(self, pane_id: int) -> str:
        """Stream-stable survey id a pane's proofs live under at the VNs.
        Stable across advances AND engine restarts — the whole point: the
        envelope is signed once per pane lifetime, and a restarted engine
        re-delivering the byte-identical blob hits the VN VerifyCache's
        (type, sid, digest) key instead of re-verifying."""
        return f"{self.stream_id}-p{pane_id}"

    def _deliver_pane_proofs(self, pane: Pane):
        """Ship one sealed pane's range proofs to the VNs and commit its
        audit block. This is the ONLY time the pane's proofs ride an
        envelope: advances reference the pane by its committed block, so
        sliding a W-pane window re-signs and re-verifies nothing for the
        W-1 carried panes."""
        cluster = self.cluster
        psid = self.pane_sid(pane.pane_id)
        survey = Survey(self.sq_proto)
        survey.stream = self
        cluster.surveys[psid] = survey
        cluster.vns.register_survey(
            psid, len(cluster.dp_idents),
            {"range": self.sq_proto.range_proof_threshold},
            expected_range=0)
        with cluster._proof_device_lock:
            for d in cluster.dp_idents:
                req = rq.new_proof_request(
                    "range", psid, d.name,
                    f"range-{d.name}-p{pane.pane_id}", 0,
                    pane.blobs[d.name], d.secret)
                cluster.vns.deliver(req)
        block = cluster.vns.end_verification(
            psid, timeout=rp.VN_GROUP_WAIT_S,
            quorum=self.sq_proto.vn_quorum)
        if _PROTO_TRACE:
            from ..analysis import prototrace
            prototrace.record(prototrace.new_instance("seal"), "commit")
        return block

    # -- epsilon accounting ------------------------------------------------

    def charge_epsilon(self) -> None:
        """Charge one advance's epsilon against every responding DP's
        (dp, cohort) budget — raises ``pool.EpsilonExhausted`` before any
        device work when a budget cannot cover it. Charges already
        journaled for other DPs in the same advance stay spent (the
        conservative direction; see pool/epsilon.py)."""
        if self.epsilon_ledger is None:
            return
        for d in self.cluster.dp_idents:
            self.epsilon_ledger.charge(d.name, self.cohort,
                                       self.epsilon_per_advance)
            self.counters["epsilon_charges"] += 1

    # -- VN-side pane verdict memo ------------------------------------------

    def verify_pane_blob(self, data: bytes) -> bool:
        """Range-verify one pane blob with a stream-lifetime digest memo.

        Called from the CN's installed ``vrange`` (service._verify_fns)
        when the survey id belongs to this stream. Pane sids are stream-
        stable, so the VN VerifyCache's (type, sid, digest) key already
        dedups re-deliveries (engine restarts on the same stream id);
        this memo adds digest-only dedup on top — identical-content
        panes (and deliveries under distinct sids within one engine)
        verify once per stream lifetime. Sound because a pane blob is
        immutable and self-contained: its Fiat-Shamir transcripts bind
        the ciphertexts inside the blob."""
        dg = hashlib.sha256(data).digest()
        with self._verify_lock:
            if dg in self._verify_memo:
                self.counters["pane_verify_hits"] += 1
                return self._verify_memo[dg]
        lst = rproof.RangeProofList.from_bytes(data)
        sigs_pub_by_u = {u: [s.public for s in sigs]
                         for u, sigs in self.cluster.range_sigs.items()}
        ok = bool(rproof.verify_range_proof_list(
            lst, self._ranges_v, sigs_pub_by_u,
            self.cluster.coll_tbl.table))
        with self._verify_lock:
            self._verify_memo[dg] = ok
            self.counters["pane_verifies"] += 1
        return ok

    # -- the window advance --------------------------------------------------

    def advance(self, precharged: bool = False) -> StreamAdvance:
        """Seal buffered panes, slide the window over them, and run the
        survey tail (delta fold -> [DRO noise] -> key switch -> decrypt
        -> decode), delivering only new panes' proofs for verification.

        ``precharged=True`` skips the engine's own epsilon charge (the
        scheduler's admission lane already charged at submit)."""
        n_new = self.sealable_panes()
        for _ in range(n_new):
            self._seal_next_pane()
        if not self._panes:
            raise ValueError(
                f"stream {self.stream_id}: no sealed panes "
                f"(feed at least pane_width={self.pane_width} rows per DP)")
        new_last = len(self._panes) - 1
        new_first = max(0, len(self._panes) - self.window_panes)
        if self.epsilon_ledger is not None and not precharged:
            self.charge_epsilon()
        tm = self.timers
        cluster = self.cluster

        # --- delta fold (exact mod-p cancellation; canon erases the
        # representation so bytes match a from-scratch fold) -------------
        tm.start("DeltaFold")
        expired = list(range(self._win_first, min(new_first,
                                                  self._win_last + 1)))
        added = list(range(max(self._win_last + 1, new_first),
                           new_last + 1))
        with cluster._proof_device_lock:
            if self._window_ct is None:
                stack = jnp.asarray(np.stack(
                    [self._panes[i].fold
                     for i in range(new_first, new_last + 1)]))
                agg = topo.fold_cts(stack)
            else:
                cur = jnp.asarray(self._window_ct)
                for pid in expired:
                    cur = eg.ct_sub(cur, jnp.asarray(self._panes[pid].fold))
                for pid in added:
                    cur = eg.ct_add(cur, jnp.asarray(self._panes[pid].fold))
                agg = topo.canon_points(cur)
            agg = np.asarray(agg)
        self._window_ct = agg
        tm.end("DeltaFold")

        # --- per-advance survey registration + proof delivery ------------
        sid = f"{self.stream_id}-w{new_first}-{new_last}"
        sq = cluster.generate_survey_query(
            self.op_name, self.query_min, self.query_max,
            proofs=1 if self.proofs_on else 0, ranges=self.ranges,
            diffp=self.sq_proto.query.diffp, survey_id=sid)
        survey = Survey(sq)
        survey.stream = self
        cluster.surveys[sid] = survey
        window = [self._panes[i] for i in range(new_first, new_last + 1)]
        if self.proofs_on:
            tm.start("ProofDeliver")
            # the advance's own survey carries ONLY the CN aggregation
            # proofs binding the window fold — every window pane's range
            # proofs were delivered (and their audit blocks committed)
            # once at seal time under the stream-stable pane sids, so a
            # slide ships zero envelopes for the W-1 carried panes
            cluster.vns.register_survey(
                sid, len(cluster.cns),
                {"aggregation": sq.aggregation_proof_threshold},
                expected_range=0)
            agg_dev = jnp.asarray(agg)
            stack = jnp.asarray(np.stack([p.fold for p in window]))
            agg_bytes = _once(lambda: _pickle(
                agg_proof.create_aggregation_proof(stack, agg_dev)))
            with cluster._proof_device_lock:
                for cn in cluster.cns:
                    req = rq.new_proof_request(
                        "aggregation", sid, cn.name,
                        f"aggregation-{cn.name}", 0, agg_bytes(),
                        cn.secret)
                    cluster.vns.deliver(req)
            tm.end("ProofDeliver")

        # --- DRO noise (DiffP streams): pool-first, fresh only as the
        # last resort (the bench gates PRECOMPUTE_CALLS flat) -------------
        agg_n = jnp.asarray(agg)
        q = sq.query
        if q.diffp.enabled():
            tm.start("DROPhase")
            d = q.diffp
            noise = dro.generate_noise_values(
                d.noise_list_size, d.lap_mean, d.lap_scale, d.quanta,
                d.scale, d.limit)
            k_adv = jax.random.fold_in(
                self._pane_key(4, new_first), new_last)
            n_cts = dro.encrypt_noise(k_adv, cluster.coll_tbl, noise, tm=tm)
            with cluster._proof_device_lock:
                for ci in range(len(cluster.cns)):
                    n_cts, _perm, _rs = dro.node_pass(
                        jax.random.fold_in(k_adv, ci + 1), n_cts,
                        cluster.coll_tbl.table, pool=cluster.pool,
                        digest=cluster._pool_digest, tm=tm)
                agg_n = dro.pick_add(agg_n, n_cts, tm=tm)
            tm.end("DROPhase")

        # --- key switch + decrypt + decode (execute_survey tail) ---------
        tm.start("KeySwitchingPhase")
        _f_enc, _f_agg, f_dec = cluster._fused()
        with cluster._proof_device_lock:
            switched, _ = cluster.key_switch(
                jax.random.fold_in(self._pane_key(3, new_first), new_last),
                agg_n, tm=tm)
            xq = jnp.asarray(eg.secret_to_limbs(cluster.client.secret))
            dl = cluster.dlog
            vals, found, zeros = f_dec(switched, xq, dl.keys, dl.xs,
                                       dl.ysign, dl.vals)
            zeros.block_until_ready()
        tm.end("KeySwitchingPhase")
        dec = st.DecryptedVector(values=np.asarray(vals),
                                 found=np.asarray(found),
                                 is_zero=np.asarray(zeros))
        result = st.decode(self.decode_mode or self.op_name, dec,
                           self.query_min, self.query_max)

        block = None
        if self.proofs_on:
            block = cluster.vns.end_verification(
                sid, timeout=rp.VN_GROUP_WAIT_S, quorum=sq.vn_quorum)
        # bound the survey map: only the latest advance's record stays,
        # plus the live window's pane records (an expired pane's proofs
        # are committed — nothing routes its sid through vrange again)
        if self._last_sid is not None:
            cluster.surveys.pop(self._last_sid, None)
        for pid in expired:
            cluster.surveys.pop(self.pane_sid(pid), None)
        self._last_sid = sid
        self._win_first, self._win_last = new_first, new_last
        self.counters["advances"] += 1
        return StreamAdvance(survey_id=sid, result=result, decrypted=dec,
                             window=(new_first, new_last),
                             panes_new=len(added),
                             panes_expired=len(expired), block=block)


__all__ = ["StreamEngine", "StreamAdvance", "Pane", "ADDITIVE_OPS"]

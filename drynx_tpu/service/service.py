"""Survey orchestration: CN / DP / VN roles + the in-process cluster harness.

This is the TPU-native counterpart of the reference's service layer
(services/service.go HandleSurveyQuery :263 / StartService :711,
service_data_provider.go HandleSurveyQueryToDP :15) plus the onet LocalTest
in-process multi-node harness the reference uses for every integration test
(services/service_test.go:29-66).

Phase pipeline per survey (reference StartService order, service.go:711-747):

  DP encode+encrypt  ->  collective aggregation  ->  [obfuscation]
  -> [DRO noise]     ->  key switch to querier   ->  decrypt + decode

All ciphertext math runs as batched device kernels (drynx_tpu.crypto,
drynx_tpu.parallel); proofs fire on worker threads to the VNs (the
reference's async goroutine pipeline, data_collection_protocol.go:279-347)
while the main phase path continues.
"""
from __future__ import annotations

import dataclasses
import os
import secrets
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto import batching as B
from ..crypto import curve as C
from ..crypto import elgamal as eg
from ..crypto import refimpl
from ..encoding import stats as st
from ..encoding import tiles as enc_tiles
from ..models import logreg as lr
from ..parallel import collective as col
from ..parallel import dro
from ..parallel import keyswitch as kswitch
from ..parallel import obfuscation as obf
from ..proofs import aggregation as agg_proof
from ..proofs import keyswitch as ks_proof
from ..proofs import obfuscation as obf_proof
from ..proofs import range_proof as rproof
from ..proofs import requests as rq
from ..proofs import shuffle as shuffle_proof
from ..resilience import faults
from ..resilience import policy as rp
from ..utils import log
from ..utils.exec_store import stored as _stored
# `_trace_reads`: what the stored programs' keys hold of the environment,
# under the name tests and readers of this module know
from ..utils.exec_store import trace_reads as _trace_reads  # noqa: F401
from ..utils.timers import PROCESS, PhaseTimers, install_listener, step_of
from . import topology as topo
from .proof_collection import VerifyCache, VerifyingNode, VNGroup
from .store import ProofDB, SurveyCheckpoint
from .query import (DiffPParams, Operation, Query, SurveyQuery,
                    check_parameters, choose_operation, query_to_proofs_nbrs)


@dataclasses.dataclass
class NodeIdentity:
    name: str
    secret: int
    public: tuple  # affine int pair


def _new_identity(name: str, rng: np.random.Generator) -> NodeIdentity:
    x, pub = eg.keygen(rng)
    return NodeIdentity(name=name, secret=x, public=pub)


class DataProvider:
    """DP role: local data -> sufficient statistics -> ciphertexts + proofs
    (reference GenerateData, data_collection_protocol.go:178-374)."""

    def __init__(self, ident: NodeIdentity, data=None, groups=None):
        self.ident = ident
        self.data = data  # op-dependent host array (or (X, y) for log_reg)
        self.groups = groups  # int64 (rows, n_attrs) group labels, or None

    def local_stats(self, op: Operation, rng, group_by=None) -> np.ndarray:
        """(V,) ungrouped, or (n_groups, V) when the query groups
        (reference GenerateData encodes per group,
        data_collection_protocol.go:254-267)."""
        if op.name == "log_reg":
            X, y = self.data
            return np.asarray(lr.encode_clear(X, y, op.lr_params))
        data = self.data
        if data is None:  # dummy data like createFakeDataForOperation
            data = rng.integers(op.query_min, max(op.query_max, 1),
                                size=(32,)).astype(np.int64)
        if group_by:
            groups = self.groups
            if groups is None:  # dummy group labels (fake-data path)
                groups = np.stack(
                    [rng.choice(np.asarray(vals), size=len(data))
                     for vals in group_by], axis=-1).astype(np.int64)
            grid = st.group_grid(group_by)
            return np.asarray(st.encode_clear_grouped(
                op.name, data, groups, grid, op.query_min, op.query_max))
        return np.asarray(st.encode_clear(
            op.name, data, op.query_min, op.query_max))


class Survey:
    """Mutable per-survey state on the root CN (reference ServiceDrynx
    survey map, service.go:82-108)."""

    def __init__(self, sq: SurveyQuery):
        self.sq = sq
        self.timers = PhaseTimers(sq.survey_id)
        self.proof_threads: list[threading.Thread] = []
        # streaming surveys (PR 18): a per-advance survey registered by a
        # StreamEngine carries its engine here so the VN-side range
        # verifier routes pane blobs through the engine's cross-advance
        # digest memo (service/streaming.py) instead of re-verifying a
        # cached pane every slide. None for ordinary one-shot surveys.
        self.stream = None


class LocalCluster:
    """In-process roster: CNs, DPs (mapped to CNs), VNs + querier.

    The onet LocalTest equivalent — full multi-node semantics, one process
    (reference services/service_test.go:29-66 generateNodes/repartitionDPs).
    """

    @PROCESS.step("setup/cluster")
    def __init__(self, n_cns: int = 3, n_dps: int = 5, n_vns: int = 3,
                 seed: int = 1, dlog_limit: int = 10000,
                 link=None, share_verify_cache: bool = True,
                 precompile: str = "auto", pool=None):
        # precompile: "auto" warms the proofs-on kernel set on the MAIN
        # thread before the first proofs-on survey WHEN the Pallas backend
        # is up (where _async_proof uses real threads — first-touch tracing
        # on a worker thread is the r05 segfault class); "on" forces the
        # warmup on any backend; "off" disables it (compilecache/registry).
        assert precompile in ("auto", "on", "off"), precompile
        install_listener()      # jax's trace/lower/compile events -> PROCESS
        # link: an optional transport.LinkModel; when active, the in-process
        # cluster sleeps at every boundary where the reference pays a real
        # network message (DP ciphertext upload, proof delivery to each VN),
        # so simulation rows reproduce the reference's delay/bandwidth
        # sensitivity (simul/runfiles/drynx.toml:6-7) with real wall-clock
        from .transport import LinkModel

        self.link = link if link is not None else LinkModel()
        # persistent crypto pool (drynx_tpu.pool): activated BEFORE any
        # fixed-base table build so the fb tenant serves the cluster's
        # own key tables; the DRO digest is derived once coll_tbl exists
        self.pool = pool
        self._pool_digest: Optional[str] = None
        if pool is not None:
            from .. import pool as pool_mod

            pool_mod.activate(pool)
        rng = np.random.default_rng(seed)
        self.rng = rng
        with PROCESS.step("keys"):
            self.cns = [_new_identity(f"cn{i}", rng) for i in range(n_cns)]
            self.dp_idents = [_new_identity(f"dp{i}", rng)
                              for i in range(n_dps)]
            self.vn_idents = [_new_identity(f"vn{i}", rng)
                              for i in range(n_vns)]
            self.client = _new_identity("client", rng)
            # collective key over the CN roster
            self.coll_pub = col.collective_key([c.public for c in self.cns])
        with PROCESS.step("tables"):
            self.coll_tbl = eg.pub_table(self.coll_pub)
            self.client_tbl = eg.pub_table(self.client.public)
            self.client_pt = jnp.asarray(C.from_ref(self.client.public))
        with PROCESS.step("dlog_table"):
            self.dlog = eg.DecryptionTable(limit=dlog_limit)

        # DP -> CN mapping (reference repartitionDPs round robin)
        self.server_to_dp = {}
        for i, dp in enumerate(self.dp_idents):
            cn = self.cns[i % n_cns].name
            self.server_to_dp.setdefault(cn, []).append(dp.name)

        self.dps: dict[str, DataProvider] = {
            d.name: DataProvider(d) for d in self.dp_idents}

        pubs = {n.name: n.public
                for n in self.cns + self.dp_idents + [self.client]}
        self.vns: Optional[VNGroup] = None
        if n_vns > 0:
            import tempfile

            self._vn_dir = tempfile.mkdtemp(prefix="drynx_vn_")
            # co-located VNs share ONE verification cache: identical proof
            # payloads (e.g. the keyswitch batch every CN relays, or the
            # joint range flush) verify once per process — real VNs on
            # separate machines do this same work in parallel, so the
            # single-chip wall time stays comparable (see VerifyCache,
            # including its soundness caveat: shared cache = one RLC weight
            # draw per process). share_verify_cache=False DISABLES caching
            # entirely (maxsize=0: every delivery recomputes, so the 9
            # keyswitch deliveries cost 9 verifies, not 1 or 3) — the
            # undeduped control configuration bench.py --no-verify-cache
            # records next to the headline.
            shared_cache = VerifyCache()
            with PROCESS.step("vns"):
                self.vns = VNGroup([
                    VerifyingNode(v.name, f"{self._vn_dir}/{v.name}.db", pubs,
                                  verify_fns=self._verify_fns(), seed=i,
                                  verify_cache=(shared_cache
                                                if share_verify_cache
                                                else VerifyCache(maxsize=0)))
                    for i, v in enumerate(self.vn_idents)])

        # DRO slab tenant: the noise phase below consumes slabs under the
        # collective-key digest (all tenants are content-addressed —
        # collective-key / A-table / affine-point digests — so a shared
        # pool can never serve an artifact to the wrong key)
        if pool is not None:
            from .. import pool as pool_mod

            self._pool_digest = pool_mod.key_digest(self.coll_tbl.table)

        self.range_sigs: dict[int, list[rproof.RangeSig]] = {}
        self.surveys: dict[str, Survey] = {}
        # Per-survey phase checkpoints (PR 17): execute_survey records
        # phase entries here; the scheduler's resume lane reads them to
        # re-enter a failed survey instead of restarting it, and the
        # soak harness asserts resume-not-restart on the counters.
        # attach_checkpoint_store() makes them durable via store.ProofDB.
        self.checkpoints: dict[str, SurveyCheckpoint] = {}
        self.checkpoint_db = None
        self._probe_cache: Optional[tuple] = None
        # serializes proof threads' device work (see _async_proof)
        self._proof_device_lock = rp.named_lock("proof_device_lock")
        self._aot_mode = precompile
        self._aot_warmed = False
        # recursion-limit + thread-stack-size guard BEFORE any proof
        # thread exists (threading.stack_size only affects later threads)
        from .. import compilecache as cc

        cc.trace_guard()

    # ------------------------------------------------------------------
    # Proof payload verifiers installed at the VNs
    # ------------------------------------------------------------------
    def _verify_fns(self):
        def vrange(data: bytes, survey_id: str) -> bool:
            survey = self.surveys.get(survey_id)
            if survey is None:
                return False
            if survey.stream is not None:
                # streaming advance: pane blobs are immutable and recur
                # across window slides under fresh per-advance survey ids,
                # which the VerifyCache's sid-scoped key cannot exploit —
                # the engine's digest-keyed memo verifies each pane ONCE
                # for the stream's whole lifetime (service/streaming.py)
                return survey.stream.verify_pane_blob(data)
            lst = rproof.RangeProofList.from_bytes(data)
            expected = self._ranges_per_value(survey.sq.query)
            sigs_pub_by_u = {
                u: [s.public for s in sigs]
                for u, sigs in self.range_sigs.items()}
            return rproof.verify_range_proof_list(
                lst, expected, sigs_pub_by_u, self.coll_tbl.table)

        def vrange_joint(datas: list, survey_id: str) -> list:
            survey = self.surveys.get(survey_id)
            if survey is None:
                return [False] * len(datas)
            expected = self._ranges_per_value(survey.sq.query)
            sigs_pub_by_u = {
                u: [s.public for s in sigs]
                for u, sigs in self.range_sigs.items()}
            return rproof.verify_range_proof_payloads_joint(
                datas, expected, sigs_pub_by_u, self.coll_tbl.table)

        def vrange_cross(payloads_by_sid: dict) -> dict:
            # cross-survey joint RLC (server/ scheduler): amortizes the RLC
            # + shared final exponentiation across every QUEUED survey at
            # equal bucket shapes, not just within one survey. A survey the
            # CN no longer knows verifies False (same containment as
            # vrange_joint's unknown-survey arm).
            expected_by_sid = {}
            for sid in payloads_by_sid:
                survey = self.surveys.get(sid)
                expected_by_sid[sid] = (
                    None if survey is None
                    else self._ranges_per_value(survey.sq.query))
            sigs_pub_by_u = {
                u: [s.public for s in sigs]
                for u, sigs in self.range_sigs.items()}
            return rproof.verify_cross_survey_payloads_joint(
                payloads_by_sid, expected_by_sid, sigs_pub_by_u,
                self.coll_tbl.table)

        def vagg(data: bytes, _sid: str) -> bool:
            from ..proofs.safe_pickle import safe_loads

            proof = safe_loads(data)
            return bool(np.all(agg_proof.verify_aggregation_proof(proof)))

        def vobf(data: bytes, _sid: str) -> bool:
            from ..proofs.safe_pickle import safe_loads

            proof = safe_loads(data)
            return bool(np.all(obf_proof.verify_obfuscation_proofs(proof)))

        def vks(data: bytes, _sid: str) -> bool:
            from ..proofs.safe_pickle import safe_loads

            proof = safe_loads(data)
            return bool(np.all(ks_proof.verify_keyswitch_proofs(
                proof, self.client_tbl.table)))

        def vshuffle(data: bytes, _sid: str) -> bool:
            from ..proofs.safe_pickle import safe_loads

            proof, in_cts, out_cts = safe_loads(data)
            return shuffle_proof.verify_shuffle(
                proof, jnp.asarray(in_cts), jnp.asarray(out_cts),
                jnp.asarray(C.from_ref(self.coll_pub)))

        # Phase attribution (reference CSV taxonomy, parse_time_data_test.go
        # flags): each payload verification lands in its Verify<Type> column
        # AND in AllProofs (with creation time, added by _async_proof), so
        # proof cost no longer hides inside JustExecution (round-4 VERDICT
        # missing #4). Cache HITS add nothing — only computed verifications
        # count, matching "time the process spent verifying".
        def _timed(name, fn):
            def wrapped(data, sid, _fn=fn, _name=name):
                t0 = time.perf_counter()
                try:
                    return _fn(data, sid)
                finally:
                    sv = self.surveys.get(sid)
                    if sv is not None:
                        dt = time.perf_counter() - t0
                        sv.timers.add(_name, dt)
                        sv.timers.add("AllProofs", dt)
            return wrapped

        def _timed_cross(fn):
            # the cross fn's cost is split evenly across the batched
            # surveys' timers (one dispatch serves them all)
            def wrapped(payloads_by_sid, _fn=fn):
                t0 = time.perf_counter()
                try:
                    return _fn(payloads_by_sid)
                finally:
                    dt = time.perf_counter() - t0
                    share = dt / max(1, len(payloads_by_sid))
                    for sid in payloads_by_sid:
                        sv = self.surveys.get(sid)
                        if sv is not None:
                            sv.timers.add("VerifyRange", share)
                            sv.timers.add("AllProofs", share)
            return wrapped

        return {"range": _timed("VerifyRange", vrange),
                "range_joint": _timed("VerifyRange", vrange_joint),
                "range_cross": _timed_cross(vrange_cross),
                "aggregation": _timed("VerifyAggregation", vagg),
                "obfuscation": _timed("VerifyObfuscation", vobf),
                "keyswitch": _timed("VerifyKeySwitch", vks),
                "shuffle": _timed("VerifyShuffle", vshuffle)}

    # ------------------------------------------------------------------
    # Survey query construction (reference API.GenerateSurveyQuery, api.go:58)
    # ------------------------------------------------------------------
    def generate_survey_query(self, op_name: str, query_min: int = 0,
                              query_max: int = 0, dims: int = 1,
                              proofs: int = 0, obfuscation: bool = False,
                              ranges=None, diffp: Optional[DiffPParams] = None,
                              lr_params=None, thresholds: float = 1.0,
                              cutting_factor: int = 0,
                              group_by=None, min_dp_quorum: int = 0,
                              vn_quorum: float = 1.0,
                              survey_id: Optional[str] = None) -> SurveyQuery:
        # survey_id: callers needing reproducible ids (the serial-vs-batched
        # bit-identity comparison in scripts/serve_surveys.py re-runs the
        # SAME surveys through two schedulers) pass one explicitly; the
        # default stays collision-resistant random.
        op = choose_operation(op_name, query_min, query_max, dims,
                              cutting_factor, lr_params)
        if group_by and op_name == "log_reg":
            raise ValueError("group_by is not supported for log_reg")
        if group_by and cutting_factor > 1 and proofs:
            # the replica-major dp_stats tiling and the group-major ranges
            # tiling would interleave differently; nothing in the reference
            # combines these either (CuttingFactor is a scale-test knob)
            raise ValueError(
                "cutting_factor > 1 with group_by and proofs is unsupported")
        if (op_name == "log_reg" and proofs and ranges
                and len(set(map(tuple, ranges))) > 1):
            # the signed-encoding shift (run_survey) derives ONE offset from
            # the spec; per-index specs would shift values out of range
            raise ValueError(
                "log_reg range proofs require a uniform (u, l) spec")
        if proofs and ranges is None:
            # default range: values fit in [0, 16^4)
            ranges = [(16, 4)] * op.nbr_output
        q = Query(operation=op, ranges=ranges, proofs=proofs,
                  obfuscation=obfuscation,
                  diffp=diffp or DiffPParams(),
                  cutting_factor=cutting_factor,
                  dp_data_min=query_min, dp_data_max=query_max,
                  sigs_present=proofs == 1 and ranges is not None
                  and not all(u == 0 and l == 0 for (u, l) in ranges),
                  group_by=group_by)
        sq = SurveyQuery(
            survey_id=survey_id or f"survey-{secrets.token_hex(4)}",
            query=q,
            server_ids=[c.name for c in self.cns],
            server_to_dp=self.server_to_dp,
            vn_ids=[v.name for v in self.vn_idents] if proofs else [],
            client_pub=self.client.public,
            id_to_public={n.name: n.public for n in
                          self.cns + self.dp_idents + self.vn_idents},
            threshold=thresholds if proofs else 0.0,
            aggregation_proof_threshold=thresholds if proofs else 0.0,
            obfuscation_proof_threshold=(thresholds if proofs and obfuscation
                                         else 0.0),
            range_proof_threshold=thresholds if proofs else 0.0,
            key_switching_proof_threshold=thresholds if proofs else 0.0,
            min_dp_quorum=min_dp_quorum, vn_quorum=vn_quorum)
        ok, msg = check_parameters(sq, q.diffp.enabled())
        if not ok:
            raise ValueError(f"invalid survey parameters: {msg}")
        return sq

    # ------------------------------------------------------------------
    # Range-proof signature setup (reference InitRangeProofSignature — done
    # once per (server, base u) at query setup, api.go / simul)
    # ------------------------------------------------------------------
    def ensure_range_sigs(self, u: int) -> list[rproof.RangeSig]:
        if u not in self.range_sigs:
            self.range_sigs[u] = [rproof.init_range_sig(u, self.rng)
                                  for _ in self.cns]
            # one-time GT tables (sig_gt_table; + the ~10 s host build of
            # sig_gt_pow_tables on the Pallas path) built HERE, at
            # signature setup, instead of lazily inside the first timed
            # proof creation — both are LRU-cached by A-table digest, so
            # in-survey lookups become pure cache hits
            rproof.prewarm_sig_tables(self.range_sigs[u])
        return self.range_sigs[u]

    def prewarm_dro(self, noise_size: int, n_surveys: int = 1,
                    seed: int = 0, cache_dir: Optional[str] = None) -> None:
        """Pre-fill the shuffle-precomputation pool: one fresh entry per
        (CN, survey). The reference does this at survey setup and persists
        it (service.go:316-317 PrecomputationWritingForShuffling /
        pre_compute_multiplications.gob) so the timed DRO phase only
        permutes + adds. With cache_dir set, each entry is ALSO written to
        disk so a restarted process re-loads it (load_shuffle_precomp)
        instead of re-paying the fixed-base mults; entries are consume-once
        — the backing file is deleted when an entry is used."""
        pool = getattr(self, "_shuffle_precomp", None)
        if pool is None:
            pool = self._shuffle_precomp = {}
        key = jax.random.PRNGKey(secrets.randbits(63) ^ seed)
        for ci in range(len(self.cns)):
            for _ in range(n_surveys):
                key, k_pc = jax.random.split(key)
                pc = dro.precompute_rerandomization(
                    k_pc, self.coll_tbl.table, noise_size)
                path = None
                if cache_dir is not None:
                    import os

                    os.makedirs(cache_dir, exist_ok=True)
                    path = os.path.join(
                        cache_dir, f"precomp_{ci}_{noise_size}_"
                        f"{secrets.token_hex(6)}.npz")
                    dro.save_precompute(path, pc)
                pool.setdefault((ci, noise_size), []).append((pc, path))

    def load_shuffle_precomp(self, cache_dir: str) -> int:
        """Re-load persisted precomputation entries after a restart (the
        reference reads its gob cache at service init, service.go:316-317).
        Returns the number of entries loaded."""
        import glob
        import os

        pool = getattr(self, "_shuffle_precomp", None)
        if pool is None:
            pool = self._shuffle_precomp = {}
        n = 0
        for path in sorted(glob.glob(os.path.join(cache_dir,
                                                  "precomp_*.npz"))):
            stem = os.path.basename(path)[len("precomp_"):-len(".npz")]
            ci_s, size_s, _ = stem.split("_", 2)
            pc = dro.load_precompute(path)
            pool.setdefault((int(ci_s), int(size_s)), []).append((pc, path))
            n += 1
        return n

    def _take_shuffle_precomp(self, ci: int, size: int):
        """CN `ci`'s prewarm_dro entry for a list of `size`, consumed
        (popped, its persisted copy deleted), or None."""
        entries = getattr(self, "_shuffle_precomp", {}).get((ci, size))
        if not entries:
            return None
        pc, path = entries.pop()
        if path is not None:
            try:  # consume-once: drop the persisted copy
                os.unlink(path)
            except OSError:
                pass
        return pc

    # ------------------------------------------------------------------
    # Fused exec-path programs: the modular bucketed primitives cost one
    # trace+lower each (~25-30 medium programs, ~12 min of host lowering
    # per fresh process on this 1-core box — the round-2 bench timeouts).
    # Fusing each phase into ONE jitted program mirrors flagship
    # build_pipeline, which lowers+compiles in ~25 s.
    #
    # MODULE-LEVEL jits with the key tables as ARGUMENTS: per-instance jits
    # (closures over each cluster's tables) re-compiled identical programs
    # for every LocalCluster — a test suite churning clusters accumulated
    # dozens of duplicate compiles until XLA's CPU compiler segfaulted
    # (deterministically, at the same test). One jit per SHAPE per process.
    # ------------------------------------------------------------------
    def _fused(self):
        coll_tbl = self.coll_tbl.table

        def enc(stats, enc_rs):
            return _fused_enc(coll_tbl, stats, enc_rs)

        return enc, _aggregate, _fused_dec

    # the stored programs of a survey, for the set-up report: the fused
    # phases with the key switch's node pass and finish among them, the
    # noise phase's three slab programs and the obfuscation phase's pass
    FUSED = ("_fused_enc", "_fused_agg") + kswitch.PROGRAMS \
        + ("_fused_dec",) + dro.PROGRAMS + obf.PROGRAMS

    def key_switch(self, key, agg, offset_total: int = 0, tm=None,
                   keep: bool = False):
        """The aggregate (V, 2, 3, 16) switched to the querier's key: one
        pass a computing node, each with its own secret and V fresh scalars
        of its own (parallel/keyswitch.py, the guarantee), the
        contributions summed in roster order, then the finish. Every
        roster runs the same passes, and nothing here is as wide as the
        roster. Returns (switched, kept): with `keep` (proofs on) every
        node's (U_i, W_i, r_i), what its proof is made of; else empty."""
        with step_of(tm, "secrets"):
            xs = [jnp.asarray(eg.secret_to_limbs(c.secret))
                  for c in self.cns]
            PROCESS.count("h2d_bytes", sum(x.nbytes for x in xs))
        K0, q_tbl = agg[:, 0], self.client_tbl.table
        acc, kept = None, []
        for i, x in enumerate(xs):
            acc, made = kswitch.node_pass(jax.random.fold_in(key, i), K0, x,
                                          q_tbl, acc, tm=tm)
            if keep:
                kept.append(made)
        return kswitch.finish(agg, acc, offset_total, tm=tm), kept

    # bucket-grid Profile axis: st.grid_buckets(q) — shared with admission

    @staticmethod
    def _ranges_per_value(q) -> list:
        """Per-OUTPUT-INDEX (u, l) specs: the query's per-V ranges, tiled
        across group-by groups (every group's value i shares spec i —
        reference validates per-index ranges, lib/structs.go:446-533).
        NOTE: q.ranges already spans the CuttingFactor replicas — the
        query model multiplies nbr_output by cf (query.py choose_operation,
        mirroring lib/structs.go:637-639) and check_parameters enforces
        len(ranges) == nbr_output."""
        return list(q.ranges) * (q.n_groups() if q.group_by else 1)

    # ------------------------------------------------------------------
    def _warm_kernels(self, tm: PhaseTimers, q) -> None:
        """Main-thread warmup of the proofs-on program set (compilecache).

        Dispatches every registered program once, serially, under
        _proof_device_lock, BEFORE _async_proof / dp_lists threads start —
        so proof worker threads only ever re-execute cached traces. This
        eliminated the r05 segfault class: partial_eval tracing pair_flat
        from a DP proof thread overflowed the thread's C stack
        (service.py:500 dp_lists). Runs once per cluster; "auto" mode
        limits it to the Pallas backend, where _async_proof actually uses
        threads (on CPU the proof work runs inline on the main thread, so
        lazy first-touch tracing is already main-thread-only)."""
        from ..crypto import pallas_ops as po
        from .. import compilecache as cc

        if self._aot_warmed or self._aot_mode == "off":
            return
        if self._aot_mode == "auto" and not po.available():
            return
        from ..parallel import proof_plane as plane

        ranges = self._ranges_per_value(q)
        u0, l0 = ranges[0] if ranges else (16, 5)
        profile = cc.Profile(
            n_cns=len(self.cns), n_dps=len(self.dp_idents),
            n_values=max(len(ranges), 1), u=int(u0) or 16,
            l=int(l0) or 5, dlog_limit=self.dlog.limit,
            n_shards=plane.n_shards(),
            n_buckets=st.grid_buckets(q),
            n_noise=(int(q.diffp.noise_list_size)
                     if q.diffp.enabled() else 0))
        with self._proof_device_lock:
            cc.trace_guard()
            before = cc.STATS.totals()
            cc.precompile(profile, mode="execute",
                          log=lambda m: log.lvl2(f"precompile: {m}"))
            after = cc.STATS.totals()
            tm.add("PrecompileTraceExec",
                   after["lower_seconds"] - before["lower_seconds"])
            self._aot_warmed = True

    # ------------------------------------------------------------------
    # The full survey (reference SendSurveyQuery path, SURVEY.md §3.1)
    # ------------------------------------------------------------------
    def run_survey(self, sq: SurveyQuery, seed: int = 0):
        return self.finalize_survey(self.execute_survey(sq, seed))

    def attach_checkpoint_store(self, path: str) -> None:
        """Make survey checkpoints durable: phase records persist to a
        store.ProofDB at ``path`` so a restarted root process resumes
        accounting (and in-flight surveys) instead of restarting them."""
        self.checkpoint_db = ProofDB(path)

    def checkpoint_for(self, survey_id: str) -> Optional[SurveyCheckpoint]:
        ck = self.checkpoints.get(survey_id)
        if ck is None:
            ck = SurveyCheckpoint.load(self.checkpoint_db, survey_id)
            if ck is not None:
                self.checkpoints[survey_id] = ck
        return ck

    def probe_liveness(self) -> dict:
        """Concurrent DP liveness probe — the survey-resume re-triage hook
        (ROADMAP item 6): one ping per DP over the fan_out pool through
        transport.local_call, so an active FaultPlan's connect/node hooks
        decide reachability exactly as a TCP probe would. Without a plan
        every in-process DP is trivially alive.

        Verdicts carry a TTL (rp.PROBE_TTL_S / DRYNX_PROBE_TTL): calls
        within it reuse the cached map, past it the probe re-runs — so a
        resume never dispatches on a verdict drawn before a healing
        fault window moved. The cache is keyed to the active plan
        object; swapping plans invalidates it immediately."""
        from . import node as nd
        from . import transport as tr

        # DP names are public routing metadata (same declassification as
        # the execute_survey probe loop)
        names = [d.name for d in self.dp_idents]  # drynx: declassify[secret]
        plan = faults.fault_plan()
        if plan is None:
            return {n: True for n in names}
        import os

        env = os.environ.get("DRYNX_PROBE_TTL", "").strip()
        ttl = float(env) if env else rp.PROBE_TTL_S
        now = time.monotonic()
        if (self._probe_cache is not None
                and self._probe_cache[0] is plan
                and now - self._probe_cache[1] < ttl):
            return dict(self._probe_cache[2])
        outs = nd.fan_out(
            names, lambda n: None,
            call=lambda n, m: tr.local_call(n, "ping", lambda: True))
        alive = {n: err is None for n, (_, err) in zip(names, outs)}
        self._probe_cache = (plan, time.monotonic(), alive)
        return alive

    def execute_survey(self, sq: SurveyQuery, seed: int = 0,
                       hold_range: bool = False, tenant: str = "default",
                       responders: Optional[list] = None):
        """Phases through decrypt+decode; returns a PendingSurvey whose
        proof verification has not been finalized. run_survey composes this
        with finalize_survey; the standing scheduler (drynx_tpu.server)
        splits them so survey N+1's encode overlaps survey N's verify, and
        passes hold_range=True so queued surveys' range payloads buffer at
        the VNs for ONE cross-survey joint flush.

        ``responders`` restricts the DP candidate set to the named nodes
        (survey resume carries the live set from a probe_liveness pass);
        DPs outside it are recorded absent and the quorum check applies
        to the restriction. ``tenant`` tags the PendingSurvey/SurveyResult
        for the server's fair-queueing bookkeeping."""
        survey = Survey(sq)
        self.surveys[sq.survey_id] = survey
        q = sq.query
        op = q.operation
        tm = survey.timers
        key = jax.random.PRNGKey(seed)
        proofs_on = q.proofs == 1 and self.vns is not None

        # phase checkpoint (PR 17): first entry creates the record; a
        # re-entry (scheduler resume lane after a mid-phase fault) finds
        # it — in memory or the durable store — and bumps ``resumes``.
        # Every phase entry below lands in ck.phase_entries, the
        # resume-not-restart evidence the soak harness asserts on.
        ck = self.checkpoint_for(sq.survey_id)
        if ck is None:
            ck = SurveyCheckpoint(survey_id=sq.survey_id)
            self.checkpoints[sq.survey_id] = ck
        elif not ck.done:
            ck.resumes += 1

        def mark(phase: str) -> None:
            with tm.step("checkpoint"):
                ck.enter(phase)
                ck.save(self.checkpoint_db)

        mark("probe")

        with tm.step("probe"):
            # --- Quorum-degraded membership: with an active FaultPlan every
            # DP dispatch rides transport.local_call, so the in-process path
            # sees the same connect/request/node hooks as a TCP dispatch
            # (service/node.py _h_survey_query): a killed, refusing, or
            # dropped DP is simply absent. The survey proceeds over the
            # responders iff they meet min_dp_quorum, and the VN
            # expected-proof counters are sized to the responder set.
            plan = faults.fault_plan()
            allowed = None if responders is None else {str(n)
                                                      for n in responders}
            dp_idents: list = []
            absent: list[str] = []
            for d in self.dp_idents:
                # DP names are public routing metadata even though the
                # identity objects also carry the node's secret scalar
                name = d.name  # drynx: declassify[secret]
                if allowed is not None and name not in allowed:
                    # resume carried a responder set that excludes this DP:
                    # it is absent by restriction, no probe needed
                    absent.append(name)
                    continue
                if plan is not None:
                    from . import transport as tr

                    try:
                        tr.local_call(name, "survey_query", lambda: None)
                        dp_idents.append(d)
                    except tr.TransportError:
                        absent.append(name)
                else:
                    dp_idents.append(d)
            responders = [d.name for d in dp_idents]
            need = (sq.min_dp_quorum if sq.min_dp_quorum > 0
                    else len(self.dp_idents))
            if len(responders) < need:
                raise RuntimeError(
                    f"survey {sq.survey_id}: only {len(responders)}/"
                    f"{len(self.dp_idents)} DPs responded (quorum {need}); "
                    f"absent: {sorted(absent)}")
            ck.responders = list(responders)
            ck.absent = sorted(absent)
        log.lvl1(f"survey {sq.survey_id}: op={op.name} "
                 f"dps={len(responders)}/{len(self.dp_idents)} "
                 f"cns={len(self.cns)} "
                 f"proofs={int(proofs_on)} groups={q.n_groups()} "
                 f"resumes={ck.resumes}")

        if proofs_on:
            nbrs = query_to_proofs_nbrs(sq)
            # absent DPs owe one range proof each; everything else is CN-side
            expected = sum(nbrs) - len(absent)
            self.vns.register_survey(
                sq.survey_id, expected,
                {"range": sq.range_proof_threshold,
                 "shuffle": sq.threshold,
                 "aggregation": sq.aggregation_proof_threshold,
                 "obfuscation": sq.obfuscation_proof_threshold,
                 "keyswitch": sq.key_switching_proof_threshold},
                expected_range=nbrs[0] - len(absent),
                hold_range=hold_range)
            # first-touch tracing of the proofs-on kernel set happens HERE,
            # on the main thread, before any proof worker thread exists
            self._warm_kernels(tm, q)

        # --- DP phase: encode + encrypt (+ range proofs) ----------------
        mark("collect")
        tm.start("DataCollectionProtocol")
        with tm.step("local_stats"):
            dp_stats = np.stack([
                self.dps[d.name].local_stats(op, self.rng, q.group_by)
                for d in dp_idents])               # (n_dps, V) or (n_dps,G,Vg)
            if q.group_by:
                # group-major flatten: the aligned group axis makes
                # element-wise homomorphic addition the per-group aggregation
                # (no same-group matching; reference
                # data_collection_protocol.go:157-168)
                dp_stats = dp_stats.reshape(dp_stats.shape[0], -1)
            cf = max(int(q.cutting_factor), 1)
            if cf > 1:
                # CuttingFactor scale testing: replicate the output vector (and
                # therefore every downstream ciphertext + proof) cf times
                # (reference lib/structs.go:637-639)
                dp_stats = np.tile(dp_stats, (1, cf))
            V = dp_stats.shape[1]

            # Sound range proofs for signed encodings: logreg fixed-point
            # coefficients can be negative, which a [0, u^l) digit proof cannot
            # express (the reference's ToBase silently emits NO digits for
            # negative secrets, range_proof.go:584 — its LR range proofs are
            # vacuous). We instead SHIFT each plaintext by u^l/2 so the proved
            # statement is real, and homomorphically subtract the public
            # n_dps*offset from the key-switched result before decryption.
            range_offset = 0
            if proofs_on and op.name == "log_reg" and q.ranges:
                u0, l0 = q.ranges[0]
                if u0:
                    range_offset = (int(u0) ** int(l0)) // 2
                    assert int(np.abs(dp_stats).max()) < range_offset, \
                        "logreg encoding exceeds range proof bound u^l/2"
                    dp_stats = dp_stats + range_offset
        with tm.step("randomness"):
            key, k_enc = jax.random.split(key)
            enc_rs = eg.random_scalars(k_enc, dp_stats.shape)
        f_enc, f_agg, f_dec = self._fused()
        enc_tile = enc_tiles.auto_tile(V)
        PROCESS.count("h2d_bytes", dp_stats.nbytes)
        with tm.step("enc"):
            if enc_tile:
                # bucket-tiled encryption (grid-op scale axis): the fused enc
                # program runs per value-axis slab so no single dispatch
                # materializes the full (n_dps, V, 2, 3, 16) ciphertext array
                # (384 MB at 1M buckets). enc_rs is drawn full-size above and
                # sliced, and the program is element-wise per (dp, value), so
                # the concatenation is bit-identical to one dispatch. Balanced
                # tiles -> at most two slab shapes compile.
                with tm.step("upload"):
                    stats_dev = jnp.asarray(dp_stats)
                parts = []
                tiles = enc_tiles.plan_tiles(V, enc_tile).tiles
                for i, (a, b) in enumerate(tiles):
                    with tm.step(f"tile{i}"):
                        parts.append(np.asarray(
                            f_enc(stats_dev[:, a:b], enc_rs[:, a:b])))
                with tm.step("regroup"):
                    # the tiles came down; in one piece they go up again
                    cts = jnp.asarray(np.concatenate(parts, axis=1))
                    cts.block_until_ready()
                PROCESS.count("d2h_bytes", cts.nbytes)
                PROCESS.count("h2d_bytes", cts.nbytes)
            else:
                cts = f_enc(jnp.asarray(dp_stats), enc_rs)  # (n_dps,V,2,3,16)
                cts.block_until_ready()
        if self.link.active:
            # DP->CN uploads ride INDEPENDENT links in parallel (the
            # reference's per-link model): wall time = max over links =
            # one delay + one payload serialization (V cts x 128 B)
            self.link.charge(V * 128)
        tm.end("DataCollectionProtocol")

        if proofs_on:
            ranges_v = self._ranges_per_value(q)
            sigs_by_u = {u: self.ensure_range_sigs(u)
                         for (u, _l) in rproof.group_ranges(ranges_v)}
            key, k_rp = jax.random.split(key)
            # ONE device-batched creation for all DPs (their per-value
            # transcripts are independent, so batching changes no proof);
            # each DP's payload still ships + verifies separately
            lists_box: dict = {}
            lock = threading.Lock()

            def dp_lists():
                with lock:
                    if "v" not in lists_box:
                        lists_box["v"] = \
                            rproof.create_range_proof_lists_batched(
                                k_rp, dp_stats, enc_rs, cts, ranges_v,
                                sigs_by_u, self.coll_tbl.table)
                    return lists_box["v"]

            for i, dp in enumerate(dp_idents):
                self._async_proof(
                    survey, "range", dp,
                    lambda i=i: dp_lists()[i].to_bytes())

        # --- Aggregation phase (reference AggregationPhase :775) --------
        mark("aggregate")
        tm.start("AggregationPhase")
        # canonical aggregate (topology.canon_points): the in-process
        # plane lands on the same aggregate BYTES as the remote tree/star
        # dispatch paths, which all fold through topology.fold_cts
        with tm.step("reduce"):
            red = f_agg(cts)
        with tm.step("canon"):
            agg = topo.canon_points(red)
            jax.block_until_ready(agg)
        tm.end("AggregationPhase")
        if proofs_on:
            # each CN signs its own request but the (transparent) proof body
            # is identical — build + serialize it ONCE, not per CN
            agg_bytes = _once(lambda: _pickle(
                agg_proof.create_aggregation_proof(cts, agg)))
            for cn in self.cns:
                self._async_proof(survey, "aggregation", cn, agg_bytes)

        # --- Obfuscation phase (zero/nonzero ops only) ------------------
        if q.obfuscation:
            mark("obfuscate")
            key, *node_keys = jax.random.split(key, 1 + len(self.cns))
            tm.start("ObfuscationPhase")
            # one pass a computing node, each by V fresh scalars of its own
            # on the previous node's output: never one pass by a product of
            # the nodes' scalars (parallel/obfuscation.py, the guarantee)
            for cn, k_node in zip(self.cns, node_keys):
                prove = None
                if proofs_on:
                    def prove(k_w, cts, s, cn=cn):
                        pr = obf_proof.create_obfuscation_proofs(k_w, cts, s)
                        self._async_proof(survey, "obfuscation", cn,
                                          lambda: _pickle(pr))
                        return pr.obf
                agg, _ = obf.node_pass(k_node, agg, tm=tm, prove=prove)
            tm.end("ObfuscationPhase")

        # --- DRO / differential privacy noise phase ---------------------
        if q.diffp.enabled():
            mark("dro")
            tm.start("DROPhase")
            d = q.diffp
            with tm.step("noise_values"):
                noise = dro.generate_noise_values(
                    d.noise_list_size, d.lap_mean, d.lap_scale, d.quanta,
                    d.scale, d.limit)
            key, k_n = jax.random.split(key)
            n_cts = dro.encrypt_noise(k_n, self.coll_tbl, noise, tm=tm)
            for ci, cn in enumerate(self.cns):
                key, k_sh = jax.random.split(key)
                # zero encryptions, the pass's hot cost: this cluster's own
                # prewarm_dro entry, else the persistent pool's slabs
                # (drynx_tpu.pool; claimed strictly once), else made fresh
                # for this pass. Never reused: re-using a re-randomization
                # mask across surveys would let a proof observer cancel the
                # masks and recover both permutations.
                out_cts, perm, rs = dro.node_pass(
                    k_sh, n_cts, self.coll_tbl.table,
                    precomp=self._take_shuffle_precomp(ci, len(noise)),
                    pool=self.pool, digest=self._pool_digest, tm=tm)
                if proofs_on:
                    betas = [_limbs_to_int(r) for r in np.asarray(rs)]
                    pr = shuffle_proof.prove_shuffle(
                        n_cts, out_cts, np.asarray(perm), betas,
                        jnp.asarray(C.from_ref(self.coll_pub)),
                        np.random.default_rng(secrets.randbits(128)))
                    self._async_proof(
                        survey, "shuffle", cn,
                        lambda pr=pr, a=np.asarray(n_cts),
                        b=np.asarray(out_cts): _pickle((pr, a, b)))
                n_cts = out_cts
            # one noise ct added per result (service.go:600-604)
            agg = dro.pick_add(agg, n_cts, tm=tm)
            tm.end("DROPhase")

        # --- Key switch to the querier's key ----------------------------
        mark("keyswitch")
        tm.start("KeySwitchingPhase")
        key, k_ks = jax.random.split(key)
        # one pass a computing node, U = r·B, W = r·Q − x·K (commuting; the
        # sum replaces the CN chain); the finish also subtracts the public
        # aggregate shift (n_dps * u^l/2)·B so decrypted values are true
        # signed statistics: one offset per RESPONDER
        switched, kept = self.key_switch(
            k_ks, agg, range_offset * len(dp_idents), tm=tm, keep=proofs_on)
        tm.end("KeySwitchingPhase")
        if proofs_on:
            key, k_kp = jax.random.split(key)
            # the proof batch is as wide as the roster: every node's own
            # contribution and scalars, stacked in roster order
            u_pts, w_pts, ks_rs = (jnp.stack(c) for c in zip(*kept))
            srv_x = jnp.asarray(np.stack([eg.secret_to_limbs(c.secret)
                                          for c in self.cns]))
            pr = ks_proof.create_keyswitch_proofs(
                k_kp, agg[:, 0], srv_x, ks_rs, self.client_pt,
                self.client_tbl.table, u_pts, w_pts)
            ks_bytes = _once(lambda: _pickle(pr))
            for cn in self.cns:
                self._async_proof(survey, "keyswitch", cn, ks_bytes)

        # --- Querier decrypt + decode -----------------------------------
        mark("decrypt")
        tm.start("Decryption")
        with tm.step("dec"):
            xq = jnp.asarray(eg.secret_to_limbs(self.client.secret))
            PROCESS.count("h2d_bytes", xq.nbytes)
            dl = self.dlog
            vals, found, zeros = f_dec(switched, xq, dl.keys, dl.xs,
                                       dl.ysign, dl.vals)
            zeros.block_until_ready()
        tm.end("Decryption")

        with tm.step("fetch"):
            dec = st.DecryptedVector(values=np.asarray(vals),
                                     found=np.asarray(found),
                                     is_zero=np.asarray(zeros))
            PROCESS.count("d2h_bytes", dec.values.nbytes + dec.found.nbytes
                          + dec.is_zero.nbytes)
        if cf > 1:
            # decode only the first replica (the rest are the scale-test
            # padding; they decrypt to identical values)
            v0 = V // cf
            dec = st.DecryptedVector(values=dec.values[:v0],
                                     found=dec.found[:v0],
                                     is_zero=dec.is_zero[:v0])
        with tm.step("decode"):
            if op.name == "log_reg":
                tm.start("GradientDescent")
                Ts = lr.unpack(jnp.asarray(dec.values), op.lr_params)
                w = np.asarray(lr.train(Ts, op.lr_params))
                tm.end("GradientDescent")
                PROCESS.count("h2d_bytes", dec.values.nbytes)
                PROCESS.count("d2h_bytes", w.nbytes)
                result = w
            elif q.group_by:
                # per-group decode at the querier (reference api.go:124-128)
                result = st.decode_grouped(
                    op.name, dec, st.group_grid(q.group_by),
                    op.query_min, op.query_max,
                    dims=(op.nbr_input - 1) if op.name == "lin_reg" else 1)
            else:
                result = st.decode(op.name, dec, op.query_min, op.query_max,
                                   dims=(op.nbr_input - 1)
                                   if op.name == "lin_reg" else 1)
        PROCESS.count("surveys")

        ck.responders = list(responders)
        ck.absent = sorted(absent)
        ck.done = True
        mark("done")

        return PendingSurvey(survey=survey, sq=sq, result=result,
                             decrypted=dec, responders=responders,
                             absent=sorted(absent), proofs_on=proofs_on,
                             hold_range=hold_range, tenant=tenant,
                             checkpoint=ck)

    def finalize_survey(self, pending: "PendingSurvey"):
        """Join the survey's proof threads, end VN verification, and
        commit the audit block (the back half of run_survey)."""
        # a PendingSurvey aggregates the decode output (secret-derived
        # result/decrypted fields) with public bookkeeping; the Survey
        # record and its SurveyQuery are caller-visible metadata, not key
        # material — their object-level taint is an artifact of riding in
        # the same dataclass as the decode output
        survey, sq = pending.survey, pending.sq  # drynx: declassify[secret]
        sid = sq.survey_id
        tm = survey.timers
        block = None
        with tm.step("finalize"):
            if pending.proofs_on:
                # generous: on a cold CPU process the proof threads' FIRST run
                # includes all pairing-kernel compiles (tens of minutes at
                # opt-level 0 on one core; seconds on TPU)
                for t in survey.proof_threads:
                    t.join(timeout=rp.COLD_COMPILE_WAIT_S)
                if pending.hold_range:
                    # safety release: a held survey reaching finalization
                    # without the scheduler's cross-survey flush (e.g. its
                    # batch partners all faulted away) flushes solo here —
                    # otherwise end_verification would stall out its timeout
                    self.vns.flush_cross_survey([sid])
                block = self.vns.end_verification(
                    sid, timeout=rp.COLD_COMPILE_WAIT_S,
                    quorum=sq.vn_quorum)
                log.lvl2(f"survey {sid}: audit block "
                         f"#{block.index} committed, "
                         f"{len(block.data.bitmap)} bitmap entries")
        if PROCESS.seal_setup() and log.debug_visible():
            log.lvl1("set-up report\n" + PROCESS.setup_report(
                tm.records(), programs=self.FUSED))
        log.lvl1(f"survey {sid}: done; phases: " + ", ".join(
            f"{k}={v:.3f}s" for k, v in tm.items()))
        ck = pending.checkpoint
        return SurveyResult(result=pending.result,
                            decrypted=pending.decrypted, block=block,
                            timers=tm, survey_id=sid,
                            responders=pending.responders,
                            absent=pending.absent,
                            tenant=pending.tenant,
                            resumes=ck.resumes if ck else 0,
                            phases=dict(ck.phase_entries) if ck else {})

    # ------------------------------------------------------------------
    def _async_proof(self, survey: Survey, ptype: str, ident: NodeIdentity,
                     build) -> None:
        """Fire-and-track: build proof bytes + deliver to VNs on a thread
        (the reference's async goroutine pipeline).

        Device work inside the threads is SERIALIZED by one lock, so at
        most one proof thread enqueues programs at a time; the threads
        still overlap with the main phase path's host work. The lock was
        written for an accelerator arrangement that is gone, and whether a
        local chip needs it is unverified: removing it is a perf_opt
        issue's, with a cell to judge it.

        On CPU (no Pallas) the proof work runs INLINE instead: overlap buys
        nothing on one core, and XLA's CPU compiler has segfaulted under
        CONCURRENT compiles (a proof thread compiling the keyswitch verify
        kernel while the main phase path compiles — observed killing a
        pytest worker; same crash class as pytest.ini's isolation note).
        """
        from ..crypto import pallas_ops as po

        lock = self._proof_device_lock

        def work():
            try:
                with lock:
                    t0 = time.perf_counter()
                    data = build()
                    # creation cost -> AllProofs (the reference's creation
                    # runs inside its phase timers; ours runs here)
                    survey.timers.add("AllProofs",
                                      time.perf_counter() - t0)
                req = rq.new_proof_request(
                    ptype, survey.sq.survey_id, ident.name,
                    f"{ptype}-{ident.name}", 0, data, ident.secret)
                if self.link.active:
                    # star fan-out to the VNs on parallel links: wall time
                    # = one per-link delay + one payload serialization
                    self.link.charge(len(data))
                with lock:
                    self.vns.deliver(req)
            except BaseException:
                # surface thread deaths LOUDLY — a dead proof thread means
                # the VN counter never drains and the survey stalls at
                # end_verification with zero evidence otherwise
                import traceback

                log.warn(f"proof thread {ptype}/{ident.name} DIED: "
                         f"{traceback.format_exc()}")
                raise

        if not po.available():
            work()   # synchronous on CPU; build errors surface immediately
            return
        t = threading.Thread(target=work, daemon=True)
        t.start()
        survey.proof_threads.append(t)


@_stored
@jax.jit
def _fused_enc(coll_tbl, stats, enc_rs):
    m = eg.int_to_scalar(stats)
    return eg.encrypt_with_tables(eg.BASE_TABLE.table, coll_tbl, m, enc_rs)


@_stored
@jax.jit
def _fused_agg(cts):
    """The DPs' ciphertexts (n_dps, V, 2, 3, 16) summed over the DPs: on a
    TPU one call of the Pallas reduce kernel, each component of each
    bucket a lane (`curve.sum_points`)."""
    return C.sum_points(cts)


def _aggregate(cts):
    """`_fused_agg(cts)`, and on the kernel path the point additions the
    kernel made, (n_dps - 1) x 2V, under the counter
    `agg_kernel_additions`; the jnp tree counts none."""
    from ..crypto import pallas_ops as po

    out = _fused_agg(cts)
    if po.available():
        n_dps, V, n_comps = cts.shape[:3]
        PROCESS.count("agg_kernel_additions", (n_dps - 1) * V * n_comps)
    return out


@_stored
@jax.jit
def _fused_dec(switched, qx, keys, xs, ysign, vals):
    pts = eg.decrypt_point(switched, qx)
    dvals, found = eg._table_lookup(keys, xs, ysign, vals, pts)
    zeros = C.is_infinity(pts)
    return dvals, found, zeros


@dataclasses.dataclass
class PendingSurvey:
    """A survey that ran through decrypt+decode but whose proof
    verification is not yet finalized (execute_survey/finalize_survey)."""
    survey: Survey
    sq: SurveyQuery
    result: object
    decrypted: st.DecryptedVector
    responders: list
    absent: list
    proofs_on: bool
    hold_range: bool = False
    tenant: str = "default"    # fair-queueing lane key (server DRR/quota)
    checkpoint: Optional[SurveyCheckpoint] = None  # phase ledger (PR 17)


@dataclasses.dataclass
class SurveyResult:
    result: object
    decrypted: st.DecryptedVector
    block: object
    timers: PhaseTimers
    survey_id: str
    # quorum bookkeeping: which DPs actually contributed (ROBUSTNESS.md)
    responders: list = dataclasses.field(default_factory=list)
    absent: list = dataclasses.field(default_factory=list)
    tenant: str = "default"
    # resume accounting (PR 17): how many scheduler re-entries this survey
    # took, and the checkpoint's per-phase entry counters (a clean run is
    # resumes=0 with every counter at 1)
    resumes: int = 0
    phases: dict = dataclasses.field(default_factory=dict)


def _pickle(obj) -> bytes:
    import pickle

    return pickle.dumps(obj)


def _once(build):
    """Memoize a zero-arg builder across the per-CN async proof threads."""
    lock = threading.Lock()
    box: dict = {}

    def get():
        with lock:
            if "v" not in box:
                box["v"] = build()
            return box["v"]

    return get


def _limbs_to_int(limbs: np.ndarray) -> int:
    from ..crypto import params

    return params.from_limbs(limbs)


__all__ = ["NodeIdentity", "DataProvider", "LocalCluster", "SurveyResult",
           "PendingSurvey"]

"""Multi-process node roles over the TCP control plane.

One process = one node; the role (CN / DP / VN) is decided by roster
position, exactly like the reference's single binary (cmd/README.md:13-18).
The message flow mirrors SURVEY.md §3.1 — with proofs on, the FULL proof
pipeline runs from each node's own process (reference
services/service_data_provider.go:48 generateRangePI fires range proofs from
the DP; services/service.go:533-558 hooks aggregation/obfuscation/keyswitch
proofs at the CNs):

  client ──vn_register──▶ each VN        (expected counts + verify context)
  client ──survey_query──▶ root CN
     root CN ──range_sig──▶ each CN      (BB digit-signature setup per base u)
     root CN ──survey_dp──▶ each DP      (encode + encrypt locally;
                                          DP ──proof_request──▶ VNs  [range])
     root CN aggregates ciphertexts      (root ──proof──▶ VNs  [aggregation])
     root CN ──obf_contrib──▶ each CN    (obf ops: scalar-mult chain;
                                          CN ──proof──▶ VNs  [obfuscation])
     root CN ──shuffle_contrib──▶ each CN (diffP: DRO noise shuffle;
                                          CN ──proof──▶ VNs  [shuffle])
     root CN ──ks_contrib──▶ each CN     (partial decrypt + re-encrypt;
                                          CN ──proof──▶ VNs  [keyswitch])
     root CN ◀─ contributions, assembles switched ciphertext
  client ◀── switched ciphertext, decrypts with its own key
  client ──end_verification──▶ root VN   (counter-gated bitmap merge + block)
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import os
import pickle
import secrets
import threading
import time
from concurrent.futures import ThreadPoolExecutor, as_completed
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto import batching as B
from ..crypto import curve as C
from ..crypto import elgamal as eg
from ..crypto import refimpl
from ..analysis import Secret
from ..encoding import stats as st
from ..parallel import dro
from ..parallel import keyswitch as kswitch
from ..parallel import obfuscation as obf
from ..proofs import aggregation as agg_proof
from ..proofs import keyswitch as ks_proof
from ..proofs import obfuscation as obf_proof
from ..proofs import range_proof as rproof
from ..proofs import requests as rq
from ..proofs import schnorr
from ..proofs import shuffle as shuffle_proof
from ..pool import store as pool_store
from .. import pool as pool_mod
from ..proofs.safe_pickle import safe_loads
from ..resilience import policy as rp
from ..utils import log
from . import topology as topo
from .proof_collection import VerifyingNode
from .skipchain import DataBlock
from .store import ProofDB, SurveyCheckpoint
from .transport import (ConnectError, Conn, NodeServer, RemoteError,
                        TransportError, conn_pool, current_node,
                        link_model, pack_array, set_current_node,
                        unpack_array, unpack_array_device)


def _net_delta(before: dict, after: dict) -> dict:
    """LinkModel stats delta over one survey (process-global counters)."""
    peers = {k: v - before["by_peer"].get(k, 0)
             for k, v in after["by_peer"].items()}
    rx = {k: v - before.get("rx_by_node", {}).get(k, 0)
          for k, v in after.get("rx_by_node", {}).items()}
    return {"bytes_total": after["bytes_total"] - before["bytes_total"],
            "msgs_total": after["msgs_total"] - before["msgs_total"],
            "by_peer": {k: v for k, v in peers.items() if v},
            "rx_by_node": {k: v for k, v in rx.items() if v}}


def _probe_ttl() -> float:
    """probe_liveness verdict lifetime: within it, resume paths reuse
    the cached alive/dead map; past it they re-probe automatically (a
    healing fault window can flip a verdict at any moment).
    DRYNX_PROBE_TTL overrides rp.PROBE_TTL_S per process."""
    env = os.environ.get("DRYNX_PROBE_TTL", "").strip()
    return float(env) if env else rp.PROBE_TTL_S


def _pack_bytes(b: bytes) -> dict:
    return pack_array(np.frombuffer(b, dtype=np.uint8))


def _unpack_bytes(d: dict) -> bytes:
    return unpack_array(d).tobytes()


def call_entry(entry, msg: dict, retries: Optional[int] = None,
               timeout: Optional[float] = None,
               policy: Optional[rp.RetryPolicy] = None) -> dict:
    """One request/response to a roster entry under a RetryPolicy
    (the reference leans on onet's connect retry; errors here raise instead
    of log.Fatal-ing the process).

    Idempotency-aware: connect failures and failed IDEMPOTENT calls
    (policy.is_idempotent — ping, roster, bitmap reads...) retry with
    exponential backoff + jitter on a FRESH connection; once any bytes of
    a non-idempotent request (survey_query, the contribution handlers)
    have been written, the failure surfaces immediately — a re-send could
    re-execute the handler. A RemoteError always surfaces: the handler
    ran, so the transport did its job. ``retries``/``timeout`` override
    the corresponding policy fields for this one call.

    Connections come from the process ConnPool when one is active
    (DRYNX_CONN_POOL=off disables): checked out per call, returned on
    success — RemoteError included, the handler ran so the framing is
    intact — and discarded after any transport failure, so a broken or
    half-read socket can never serve a later call."""
    pol = policy or rp.DEFAULT_POLICY
    if retries is not None:
        pol = dataclasses.replace(pol, connect_retries=int(retries))
    if timeout is not None:
        pol = dataclasses.replace(pol, call_timeout_s=float(timeout))
    mtype = msg.get("type", "")
    pool = conn_pool()
    attempt = 0
    while True:
        conn = None
        try:
            if pool is not None:
                conn = pool.get(entry.host, entry.port,
                                timeout=pol.call_timeout_s, peer=entry.name)
            else:
                conn = Conn(entry.host, entry.port,
                            timeout=pol.call_timeout_s, peer=entry.name)
            reply = conn.call(msg)
        except RemoteError:
            if pool is not None:
                pool.put(conn)
            elif conn is not None:
                conn.close()
            raise
        except (TransportError, OSError) as e:
            sent = conn.sent if conn is not None else False
            if pool is not None:
                pool.discard(conn)
            elif conn is not None:
                conn.close()
            attempt += 1
            if attempt >= pol.attempts_for(mtype, sent):
                if sent:
                    raise
                raise ConnectError(
                    f"node {entry.name} at {entry.host}:{entry.port} "
                    f"unreachable after {attempt} attempts: {e!r}") from e
            time.sleep(pol.backoff(attempt - 1))
        else:
            if pool is not None:
                pool.put(conn)
            else:
                conn.close()
            return reply


def _fan_out_workers() -> int:
    """DRYNX_FANOUT=serial forces one-at-a-time dispatch;
    DRYNX_FANOUT_WORKERS overrides the pool width (rp.FAN_OUT_WORKERS)."""
    if os.environ.get("DRYNX_FANOUT", "").strip().lower() == "serial":
        return 1
    w = os.environ.get("DRYNX_FANOUT_WORKERS", "").strip()
    if w:
        return int(w)
    return rp.FAN_OUT_WORKERS


def fan_out(entries, make_msg: Callable, call: Callable = None,
            policy: Optional[rp.RetryPolicy] = None,
            workers: Optional[int] = None) -> list:
    """One RPC per roster entry on a bounded worker pool.

    The shared dispatch primitive for every star-topology round (range-sig
    collection, DP dispatch, VN broadcasts, key-switch contributions,
    liveness probes): remote wall-clock becomes max-over-nodes instead of
    sum-over-nodes, while each call keeps its own RetryPolicy semantics
    via ``call_entry``.

    Messages are built upfront on the CALLER's thread (``make_msg(entry)``
    may touch non-thread-safe state), and the return value is
    ``[(reply, None) | (None, exc)]`` aligned with roster order — callers
    iterate ``zip(entries, results)`` and re-raise/aggregate in roster
    order, which keeps transcripts and sums byte-identical to the old
    serial loops whatever the completion interleaving. ``call`` defaults
    to ``call_entry`` under ``policy``; pass a custom callable to reuse
    the pool for loopback or raw-socket dispatch.
    """
    entries = list(entries)
    if call is None:
        def call(e, m):
            return call_entry(e, m, policy=policy)
    msgs = [make_msg(e) for e in entries]
    n = _fan_out_workers() if workers is None else int(workers)
    n = max(1, min(n, len(entries)))
    results: list = [None] * len(entries)
    if n <= 1:
        for i, (e, m) in enumerate(zip(entries, msgs)):
            try:
                results[i] = (call(e, m), None)
            except Exception as err:
                results[i] = (None, err)
        return results
    # carry the caller's node identity onto the pool threads: replies read
    # on a worker must be charged to the DIALING node's rx ledger, and a
    # tree relay fans out from a server handler thread that set it
    amb = current_node()

    def run(e, m):
        set_current_node(amb)
        return call(e, m)

    with ThreadPoolExecutor(max_workers=n) as ex:
        futs = {ex.submit(run, e, m): i
                for i, (e, m) in enumerate(zip(entries, msgs))}
        for f in as_completed(futs):
            i = futs[f]
            try:
                results[i] = (f.result(), None)
            except Exception as err:
                results[i] = (None, err)
    return results


@dataclasses.dataclass
class RosterEntry:
    name: str
    role: str          # "cn" | "dp" | "vn"
    host: str
    port: int
    public: tuple      # affine ints


@dataclasses.dataclass
class Roster:
    entries: list

    def of_role(self, role: str) -> list:
        return [e for e in self.entries if e.role == role]

    def collective_pub(self) -> tuple:
        acc = None
        for e in self.of_role("cn"):
            acc = refimpl.g1_add(acc, e.public)
        return acc

    def to_dict(self) -> dict:
        return {"entries": [dataclasses.asdict(e) for e in self.entries]}

    @classmethod
    def from_dict(cls, d: dict) -> "Roster":
        return cls([RosterEntry(**{**e, "public": tuple(e["public"])})
                    for e in d["entries"]])


class DrynxNode:
    """A node process serving its role's handlers."""

    def __init__(self, name: str, secret: Secret[int], public: tuple,
                 host: str = "127.0.0.1", port: int = 0,
                 data: Optional[np.ndarray] = None,
                 db_path: Optional[str] = None,
                 policy: Optional[rp.RetryPolicy] = None,
                 pool: Optional[pool_store.CryptoPool] = None):
        self.name = name
        self.secret = secret
        self.public = public
        self.data = data
        # Activate the crypto pool BEFORE any table build so the sig/fb
        # tenants warm-start this process and shuffle contributions can
        # consume DRO slabs (ROADMAP item 5's remaining gap: remote CNs
        # used to precompute locally). $DRYNX_POOL_DIR covers processes
        # that don't pass one explicitly (pool_mod.active_pool()).
        if pool is not None:
            pool_mod.activate(pool)
        # all of this node's OUTBOUND calls (DP dispatch, proof delivery,
        # VN polling) run under one RetryPolicy; tests inject short
        # timeouts here instead of monkeypatching call sites
        self.policy = policy or rp.DEFAULT_POLICY
        self.server = NodeServer(host, port, node_name=name)
        self.roster: Optional[Roster] = None
        self.vn: Optional[VerifyingNode] = None
        self._db_path = db_path or f"/tmp/drynx_node_{name}.db"
        self._range_sigs: dict[int, rproof.RangeSig] = {}  # CN role, per u
        self._survey_ctx: dict[str, dict] = {}             # VN role
        self._proof_threads: dict[str, list] = {}          # prover roles
        # DP role: per-survey cached contribution (insertion-ordered;
        # pruned to rp.DP_REPLY_CACHE_MAX finished surveys). A tree
        # re-dispatch after a relay timeout replays the SAME ciphertext
        # bytes instead of re-encrypting, so a contribution can never be
        # double-counted and its range proof never double-fires.
        self._dp_replies: dict[str, dict] = {}
        # Root CN role: per-survey phase checkpoints (PR 17). In-memory
        # always; durable through store.ProofDB when DRYNX_CKPT_PERSIST
        # is set (the soak harness and cmd/server deployments turn it
        # on), so a restarted root resumes accounting instead of
        # restarting it. Probe verdicts are cached per DP for
        # _probe_ttl() seconds so a healing-window re-entry never
        # dispatches on a stale liveness map.
        self._ckpts: dict[str, SurveyCheckpoint] = {}
        self._ckpt_db: Optional[ProofDB] = None
        self._probe_cache: dict[str, tuple[float, bool]] = {}
        self._state_lock = rp.named_lock("node_state_lock")  # handlers run on server threads

        s = self.server
        s.register("set_roster", self._h_set_roster)
        s.register("survey_query", self._h_survey_query)
        s.register("survey_dp", self._h_survey_dp)
        s.register("range_sig", self._h_range_sig)
        s.register("obf_contrib", self._h_obf_contrib)
        s.register("shuffle_contrib", self._h_shuffle_contrib)
        s.register("ks_contrib", self._h_ks_contrib)
        s.register("proof_request", self._h_proof_request)
        s.register("proof_batch", self._h_proof_batch)
        s.register("vn_register", self._h_vn_register)
        s.register("vn_adjust", self._h_vn_adjust)
        s.register("vn_bitmap", self._h_vn_bitmap)
        s.register("end_verification", self._h_end_verification)
        # skipchain retrieval RPCs (reference serves genesis/latest/specific
        # block + stored proofs + close-DB to REMOTE clients,
        # services/service_skipchain.go:173-342)
        s.register("get_genesis", self._h_get_block)
        s.register("get_latest", self._h_get_block)
        s.register("get_block", self._h_get_block)
        s.register("get_proofs", self._h_get_proofs)
        s.register("close_db", self._h_close_db)
        s.register("ping", lambda m: {"ok": True, "name": self.name})

    # ------------------------------------------------------------------
    @property
    def address(self):
        return self.server.host, self.server.port

    def start(self):
        self.server.start()

    def stop(self):
        self.server.stop()

    # ------------------------------------------------------------------
    def _h_set_roster(self, msg: dict) -> dict:
        self.roster = Roster.from_dict(msg["roster"])
        me = [e for e in self.roster.entries if e.name == self.name]
        if me and me[0].role == "vn" and self.vn is None:
            pubs = {e.name: e.public for e in self.roster.entries}
            self.vn = VerifyingNode(self.name, self._db_path, pubs,
                                    verify_fns=self._vn_verify_fns(), seed=0)
        return {"ok": True}

    # ------------------------------------------------------------------
    # VN payload verifiers: real verification in the VN's own process
    # (round-1 gap: distributed VNs had verify_fns={} so every payload was
    # BM_RECVD at best; reference VNs verify, structs_proofs.go:135-492)
    # ------------------------------------------------------------------
    def _vn_verify_fns(self):
        def ctx_of(sid: str) -> Optional[dict]:
            return self._survey_ctx.get(sid)

        def vrange(data: bytes, sid: str) -> bool:
            ctx = ctx_of(sid)
            if ctx is None:
                return False
            lst = rproof.RangeProofList.from_bytes(data)
            return rproof.verify_range_proof_list(
                lst, ctx["ranges_v"], ctx["sigs_pub_by_u"],
                self._pub_table(ctx["coll_pub"]).table)

        def vrange_joint(datas: list, sid: str) -> list:
            ctx = ctx_of(sid)
            if ctx is None:
                return [False] * len(datas)
            return rproof.verify_range_proof_payloads_joint(
                datas, ctx["ranges_v"], ctx["sigs_pub_by_u"],
                self._pub_table(ctx["coll_pub"]).table)

        def vagg(data: bytes, _sid: str) -> bool:
            return bool(np.all(agg_proof.verify_aggregation_proof(
                safe_loads(data))))

        def vobf(data: bytes, _sid: str) -> bool:
            return bool(np.all(obf_proof.verify_obfuscation_proofs(
                safe_loads(data))))

        def vks(data: bytes, sid: str) -> bool:
            ctx = ctx_of(sid)
            if ctx is None:
                return False
            return bool(np.all(ks_proof.verify_keyswitch_proofs(
                safe_loads(data),
                self._pub_table(ctx["client_pub"]).table)))

        def vshuffle(data: bytes, sid: str) -> bool:
            ctx = ctx_of(sid)
            if ctx is None:
                return False
            proof, in_cts, out_cts = safe_loads(data)
            return shuffle_proof.verify_shuffle(
                proof, jnp.asarray(in_cts), jnp.asarray(out_cts),
                jnp.asarray(C.from_ref(ctx["coll_pub"])))

        return {"range": vrange, "range_joint": vrange_joint,
                "aggregation": vagg, "obfuscation": vobf,
                "keyswitch": vks, "shuffle": vshuffle}

    # ------------------------------------------------------------------
    # Async proof delivery to every VN (the reference's goroutine pipeline,
    # data_collection_protocol.go:279-347)
    # ------------------------------------------------------------------
    @staticmethod
    def _proof_fields(req) -> dict:
        """Wire form of one signed ProofRequest (minus the mtype): the unit
        a relay hop batches and a VN unbatches."""
        return {"proof_type": req.proof_type, "survey_id": req.survey_id,
                "sender_id": req.sender_id, "differ_info": req.differ_info,
                "round_id": req.round_id, "data": _pack_bytes(req.data),
                "signature": _pack_bytes(req.signature.to_bytes())}

    def _track_proof_thread(self, survey_id: str,
                            t: threading.Thread) -> threading.Thread:
        t.start()
        # prune finished surveys' threads so long-lived DP/CN processes don't
        # accumulate Thread objects across surveys (handlers run on server
        # threads — guard the shared dict)
        with self._state_lock:
            for sid in list(self._proof_threads):
                alive = [x for x in self._proof_threads.get(sid, [])
                         if x.is_alive()]
                if alive or sid == survey_id:
                    self._proof_threads[sid] = alive
                else:
                    self._proof_threads.pop(sid, None)
            self._proof_threads.setdefault(survey_id, []).append(t)
        return t

    def _send_proof_async(self, ptype: str, survey_id: str, differ: str,
                          data: bytes) -> threading.Thread:
        req = rq.new_proof_request(ptype, survey_id, self.name, differ, 0,
                                   data, self.secret)
        return self._fire_proof_request_async(req)

    def _fire_proof_request_async(self, req) -> threading.Thread:
        vns = self.roster.of_role("vn")

        def work():
            set_current_node(self.name)  # fresh thread: re-pin the identity
            frame = {"type": "proof_request", **self._proof_fields(req)}
            outs = fan_out(vns, lambda e: dict(frame), policy=self.policy)
            for e, (_r, err) in zip(vns, outs):
                if err is not None:
                    # an unreachable/erroring VN simply never counts this
                    # proof; the end_verification counter gate reports the
                    # shortfall. The REMAINING VNs were still delivered to.
                    log.warn(f"{self.name}: {req.proof_type} proof "
                             f"undeliverable to VN {e.name}: {err}")

        return self._track_proof_thread(
            req.survey_id, threading.Thread(target=work, daemon=True))

    def _send_proof_batch_async(self, survey_id: str,
                                blobs: list) -> threading.Thread:
        """Tree mode: the root delivers every range-proof blob the tree
        collected as ONE proof_batch frame per VN (one RPC per VN instead
        of one per DP per VN). Blobs are sorted by differ_info so the
        frame — and every VN's receive order — is identical whatever
        subtree interleaving produced the batch."""
        vns = self.roster.of_role("vn")
        blobs = sorted(blobs, key=lambda b: (b["proof_type"],
                                             b["differ_info"]))
        frame = {"type": "proof_batch", "survey_id": survey_id,
                 "proofs": blobs}

        def work():
            set_current_node(self.name)
            outs = fan_out(vns, lambda e: dict(frame), policy=self.policy)
            for e, (_r, err) in zip(vns, outs):
                if err is not None:
                    log.warn(f"{self.name}: proof batch undeliverable to "
                             f"VN {e.name}: {err}")

        return self._track_proof_thread(
            survey_id, threading.Thread(target=work, daemon=True))

    def _pub_table(self, pub: tuple) -> eg.FixedBase:
        """Fixed-base tables are key-lifetime objects: cache per affine point
        (building one costs ~1k host-side bigint point adds)."""
        cache = getattr(self, "_tbl_cache", None)
        if cache is None:
            cache = self._tbl_cache = {}
        if pub not in cache:
            cache[pub] = eg.pub_table(pub)
        return cache[pub]

    # -- CN side: own BB digit-signature set for base u (reference
    # InitRangeProofSignature, range_proof.go:270-288 — per-server secret)
    def _h_range_sig(self, msg: dict) -> dict:
        u = int(msg["u"])
        with self._state_lock:
            if u not in self._range_sigs:
                rng = np.random.default_rng(secrets.randbits(63))
                self._range_sigs[u] = rproof.init_range_sig(u, rng)
            sg = self._range_sigs[u]
        return {"pub": [int(sg.public[0]), int(sg.public[1])],
                "A": pack_array(sg.A)}

    @staticmethod
    def _sigs_from_msg(range_sigs_msg: dict) -> dict:
        """{u: [RangeSig(pub-only)]} from the wire form sent by the root CN
        (A tables stacked (ns, u, 3, 2, 16), publics per CN)."""
        out = {}
        for u_str, blob in range_sigs_msg.items():
            A_all = unpack_array(blob["A"])
            pubs = [tuple(int(t) for t in p) for p in blob["pubs"]]
            out[int(u_str)] = [
                rproof.RangeSig(secret=0, public=pubs[i], A=A_all[i])
                for i in range(A_all.shape[0])]
        return out

    # -- DP side: encode + encrypt local data (survey_dp); with proofs on,
    # fire the range-proof list at the VNs from THIS process (reference
    # service_data_provider.go:48 generateRangePI). Carries the FULL
    # encoder surface over the wire like the reference GenerateData
    # (data_collection_protocol.go:206-267): log_reg ((X, y) DP data +
    # LRParams + the signed-offset shift) and group-by (per-group encoding
    # over the AllPossibleGroups grid).
    #
    # Re-entry is IDEMPOTENT per (survey_id, this DP): the contribution is
    # computed once and cached (_dp_reply_entry), so a tree re-dispatch
    # after a relay failure replays the same ciphertext bytes — never a
    # re-encryption that would double-count under aggregation, never a
    # second range-proof firing. Frames carrying "dp_order" take the tree
    # relay path: same mtype on purpose, so fault plans and the
    # idempotency table apply identically at every hop.
    def _h_survey_dp(self, msg: dict) -> dict:
        if msg.get("dp_order") is not None:
            return self._h_survey_dp_relay(msg)
        ent = self._dp_reply_entry(msg)
        fire = None
        with self._state_lock:
            if ent["req"] is not None and not ent["fired"]:
                ent["fired"] = True
                fire = ent["req"]
        if fire is not None:
            self._fire_proof_request_async(fire)
        return {"cts": pack_array(ent["cts"])}

    def _dp_reply_entry(self, msg: dict) -> dict:
        """The cached (computed-at-most-once) contribution for a survey.
        Concurrent re-entries block on the per-entry lock and read the
        first computation's result; finished foreign surveys are pruned
        past rp.DP_REPLY_CACHE_MAX in insertion order."""
        sid = msg["survey_id"]
        with self._state_lock:
            ent = self._dp_replies.get(sid)
            if ent is None:
                for k in list(self._dp_replies):
                    if len(self._dp_replies) < rp.DP_REPLY_CACHE_MAX:
                        break
                    if self._dp_replies[k]["done"]:
                        del self._dp_replies[k]
                ent = {"lock": threading.Lock(), "done": False,
                       "cts": None, "req": None, "fired": False}
                self._dp_replies[sid] = ent
        with ent["lock"]:
            if not ent["done"]:
                ent["cts"], ent["req"] = self._dp_contribution(msg)
                ent["done"] = True
        return ent

    def _dp_contribution(self, msg: dict):
        """Encode + encrypt this node's data for one survey. Returns
        (cts ndarray, signed range-proof request | None) — the caller
        decides whether the proof goes to the VNs directly (star) or rides
        a relay hop's batch (tree)."""
        op = msg["op"]
        qmin, qmax = msg["query_min"], msg["query_max"]
        group_by = msg.get("group_by") or None
        # dummy-data seed derived from sha256(name): `hash()` is salted per
        # process (PYTHONHASHSEED), which made multi-process runs draw
        # different dummy data for the same node name — irreproducible
        rng = np.random.default_rng(int.from_bytes(
            hashlib.sha256(self.name.encode()).digest()[:4], "big"))
        if op == "log_reg":
            from ..models import logreg as lr

            lrp = lr.LRParams(**{
                k: (tuple(v) if isinstance(v, list) else v)
                for k, v in msg["lr_params"].items()})
            if not (isinstance(self.data, tuple) and len(self.data) == 2):
                raise RuntimeError(
                    f"DP {self.name}: log_reg survey but node data is not "
                    "an (X, y) tuple")
            X, y = self.data
            stats = np.asarray(lr.encode_clear(X, y, lrp)).reshape(-1)
        elif group_by:
            # node data for grouped queries: (values, group_labels); dummy
            # labels when absent (reference createFakeDataForOperation)
            if isinstance(self.data, tuple):
                data, groups = self.data
            else:
                data, groups = self.data, None
            if data is None:
                data = rng.integers(qmin, max(qmax, 1),
                                    size=(32,)).astype(np.int64)
            if groups is None:
                groups = np.stack(
                    [rng.choice(np.asarray(vals), size=len(data))
                     for vals in group_by], axis=-1).astype(np.int64)
            grid = st.group_grid(group_by)
            # group-major flatten — aligned group axis makes element-wise
            # homomorphic addition the per-group aggregation
            stats = np.asarray(st.encode_clear_grouped(
                op, data, groups, grid, qmin, qmax)).reshape(-1)
        else:
            data = self.data
            if data is None:
                data = rng.integers(qmin, max(qmax, 1),
                                    size=(32,)).astype(np.int64)
            stats = np.asarray(st.encode_clear(op, data, qmin, qmax))
        # signed-encoding shift (sound range proofs for negative logreg
        # fixed-point coefficients; the root CN subtracts n_dps*offset
        # after key switch — mirrors service.py run_survey)
        range_offset = int(msg.get("range_offset", 0))
        if range_offset:
            if int(np.abs(stats).max()) >= range_offset:
                raise RuntimeError(
                    f"DP {self.name}: encoding exceeds range-proof bound "
                    f"u^l/2 = {range_offset}")
            stats = stats + range_offset
        tbl = self._pub_table(self.roster.collective_pub())
        # fresh OS entropy: blinding scalars must never be derivable from
        # survey metadata, and must differ across runs of the same survey
        key = jax.random.PRNGKey(secrets.randbits(63))
        cts, rs = eg.encrypt_ints(key, tbl, jnp.asarray(stats))

        req = None
        if msg.get("proofs"):
            ranges_v = [tuple(r) for r in msg["ranges"]]
            sigs_by_u = self._sigs_from_msg(msg["range_sigs"])
            key2 = jax.random.PRNGKey(secrets.randbits(63))
            lst = rproof.create_range_proof_list(
                key2, stats, rs, cts, ranges_v, sigs_by_u, tbl.table)
            req = rq.new_proof_request("range", msg["survey_id"], self.name,
                                       f"range-{self.name}", 0,
                                       lst.to_bytes(), self.secret)
        return np.asarray(cts), req

    # -- tree overlay relay (frames carrying dp_order): contribute locally,
    # collect the child subtrees, homomorphically fold everything into ONE
    # canonical partial, and pass the hop's range-proof blobs (plus a
    # per-hop aggregation proof the parent verifies) upward. O(log n)
    # depth replaces the root's O(n) fan-in; the fold is exact mod-p point
    # addition, so the root's final aggregate is the same group element —
    # and after canon_points the same BYTES — as the star sum.
    def _h_survey_dp_relay(self, msg: dict) -> dict:
        order = list(msg["dp_order"])
        n, b = len(order), int(msg["fanout"])
        idx = int(msg["index"])
        proofs = bool(msg.get("proofs"))
        ent = self._dp_reply_entry(msg)
        partials = [np.asarray(ent["cts"])]
        responders = [self.name]
        absent: list[str] = []
        blobs: list[dict] = []
        if proofs and ent["req"] is not None:
            blobs.append(self._proof_fields(ent["req"]))
        kids = topo.children(idx, n, b)
        if kids:
            by_name = {e.name: e for e in self.roster.entries}
            idx_of = {order[c]: c for c in kids}
            entries = [by_name[order[c]] for c in kids]

            def mk(e):
                m = dict(msg)
                m["index"] = idx_of[e.name]
                return m

            outs = fan_out(entries, mk, policy=self.policy)
            for e, (r, err) in zip(entries, outs):
                if err is None:
                    part = np.asarray(unpack_array(r["cts"]))
                    self._check_hop_proof(r, part, proofs, e.name)
                    partials.append(part)
                    responders.extend(r["responders"])
                    absent.extend(r["absent"])
                    blobs.extend(r.get("proof_blobs") or [])
                elif isinstance(err, RemoteError):
                    raise err   # the child's handler ran and errored: a
                                # real bug, not an availability fault
                elif isinstance(err, (TransportError, OSError)):
                    # the whole child subtree is unreached from HERE; the
                    # root re-dispatches the failed relay's children as
                    # subtree roots, so only the dead node itself is lost
                    log.warn(f"{self.name}: subtree {e.name} unreachable "
                             f"for survey {msg['survey_id']}: {err}")
                    absent.extend(order[j] for j in
                                  topo.subtree(idx_of[e.name], n, b))
                else:
                    raise err
        if len(partials) == 1:
            reply = {"cts": pack_array(partials[0])}
        else:
            stack = np.stack(partials)
            folded = np.asarray(topo.fold_cts(stack))
            reply = {"cts": pack_array(folded)}
            if proofs:
                reply["hop_proof"] = _pack_bytes(pickle.dumps(
                    agg_proof.create_aggregation_proof(stack, folded)))
        reply["responders"] = responders
        reply["absent"] = absent
        if proofs:
            reply["proof_blobs"] = blobs
        return reply

    def _check_hop_proof(self, r: dict, part: np.ndarray, proofs: bool,
                         child: str) -> None:
        """Parent-side check of a relay hop's aggregation proof: the fold
        must verify AND the proven aggregate must be the very bytes the
        reply carries — otherwise a relay could attach a valid proof of
        some OTHER fold."""
        if not proofs or r.get("hop_proof") is None:
            return
        batch = safe_loads(_unpack_bytes(r["hop_proof"]))
        ok = bool(np.all(agg_proof.verify_aggregation_proof(batch)))
        if not ok or not np.array_equal(np.asarray(batch.aggregate), part):
            raise RuntimeError(
                f"{self.name}: relay {child} hop aggregation proof rejected")

    # -- CN side: obfuscation contribution — multiply every ciphertext by a
    # fresh secret scalar (reference obfuscation_protocol.go:241-243) and
    # prove it (lib/obfuscation/obfuscation_proof.go:47)
    def _h_obf_contrib(self, msg: dict) -> dict:
        cts = unpack_array_device(msg["cts"])
        prove = None
        if msg.get("proofs"):
            def prove(k_w, cts, s):
                pr = obf_proof.create_obfuscation_proofs(k_w, cts, s)
                self._send_proof_async("obfuscation", msg["survey_id"],
                                       f"obf-{self.name}", pickle.dumps(pr))
                return pr.obf
        out, _ = obf.node_pass(jax.random.PRNGKey(secrets.randbits(63)),
                               cts, prove=prove)
        return {"cts": pack_array(np.asarray(out))}

    # -- CN side: DRO shuffle contribution (reference unlynx shuffling
    # protocol with proof, SURVEY.md §2.2; Neff-style argument)
    def _h_shuffle_contrib(self, msg: dict) -> dict:
        cts = unpack_array_device(msg["cts"])
        coll_pub = self.roster.collective_pub()
        tbl = self._pub_table(coll_pub)
        key = jax.random.PRNGKey(secrets.randbits(63))
        # Pooled DRO precompute when the active pool covers this collective
        # key: the fixed-base pass (the dominant cost) is skipped and the
        # slab's single-consumption claim guarantees the randomness is
        # never served twice, even across CN processes sharing one pool
        # directory. Cold path: the pass pays the fixed-base mults itself,
        # through the COUNTED builder (dro.PRECOMPUTE_CALLS) so
        # pooled-vs-fresh serving is observable per process — the bench and
        # tests assert the counter stays flat when slabs covered the need
        out_cts, perm, rs = dro.node_pass(key, cts, tbl.table,
                                          pool=pool_mod.active_pool())
        if msg.get("proofs"):
            from ..crypto.params import from_limbs

            betas = [from_limbs(r) for r in np.asarray(rs)]
            pr = shuffle_proof.prove_shuffle(
                cts, out_cts, np.asarray(perm), betas,
                jnp.asarray(C.from_ref(coll_pub)),
                np.random.default_rng(secrets.randbits(128)))
            self._send_proof_async(
                "shuffle", msg["survey_id"], f"shuffle-{self.name}",
                pickle.dumps((pr, np.asarray(cts), np.asarray(out_cts))))
        return {"cts": pack_array(np.asarray(out_cts))}

    # -- CN side: key-switch contribution for an aggregate; with proofs on,
    # a per-CN keyswitch proof (ns=1 batch) goes to the VNs (reference
    # service.go:566-616 proof hook)
    def _h_ks_contrib(self, msg: dict) -> dict:
        K0 = unpack_array_device(msg["k_component"])   # (V, 3, 16)
        client_pub = tuple(msg["client_pub"])
        q_tbl = self._pub_table(client_pub)
        x = jnp.asarray(eg.secret_to_limbs(self.secret))
        # the node's own pass, as LocalCluster.key_switch makes it; the
        # switched component w = rQ - xK is ciphertext — a public protocol
        # output even though the secret key went into it
        _, (u_pts, w_pts, rs) = kswitch.node_pass(  # drynx: declassify[secret]
            jax.random.PRNGKey(secrets.randbits(63)), K0, x, q_tbl.table)
        if msg.get("proofs"):
            key2 = jax.random.PRNGKey(secrets.randbits(63))
            # a ZK proof transcript (commitments + responses) is public
            # by construction; x is an input, never serialized
            pr = ks_proof.create_keyswitch_proofs(  # drynx: declassify[secret]
                key2, K0, x[None], rs[None],
                jnp.asarray(C.from_ref(client_pub)), q_tbl.table,
                u_pts[None], w_pts[None])
            self._send_proof_async("keyswitch", msg["survey_id"],
                                   f"ks-{self.name}", pickle.dumps(pr))
        return {"u": pack_array(np.asarray(u_pts)),
                "w": pack_array(np.asarray(w_pts))}

    def _call_cn(self, entry, msg: dict) -> dict:
        """Dispatch to a CN — loopback for self, TCP otherwise."""
        if entry.name == self.name:
            return self.server.handlers[msg["type"]](msg)
        return call_entry(entry, msg, policy=self.policy)

    # -- root CN: durable phase checkpoints + healing-window re-entry ----
    def _ckpt_store(self) -> Optional[ProofDB]:
        if (self._ckpt_db is None
                and os.environ.get("DRYNX_CKPT_PERSIST", "").strip()):
            self._ckpt_db = ProofDB(self._db_path + ".ckpt")
        return self._ckpt_db

    def _checkpoint(self, sid: str) -> SurveyCheckpoint:
        """This survey's checkpoint record: fresh on first entry, the
        surviving record (memory first, then the durable store — a
        restarted root finds it there) on re-entry, with ``resumes``
        bumped so phase counters distinguish a resume from a restart."""
        with self._state_lock:
            ck = self._ckpts.get(sid)
            if ck is None:
                ck = SurveyCheckpoint.load(self._ckpt_store(), sid)
            if ck is None:
                ck = SurveyCheckpoint(survey_id=sid)
            elif not ck.done:
                ck.resumes += 1
            # bound like the DP reply cache: prune finished foreign
            # surveys in insertion order
            for k in list(self._ckpts):
                if len(self._ckpts) < rp.DP_REPLY_CACHE_MAX:
                    break
                if self._ckpts[k].done and k != sid:
                    del self._ckpts[k]
            self._ckpts[sid] = ck
            return ck

    def _ckpt_enter(self, ck: SurveyCheckpoint, phase: str) -> None:
        ck.enter(phase)
        ck.save(self._ckpt_store())

    def _probe_dp(self, entry) -> bool:
        """TTL-cached liveness probe for one roster entry (resume path):
        an ALIVE verdict older than _probe_ttl() re-probes automatically,
        so a re-entry never dispatches on a map drawn before a fault
        window moved. DEAD verdicts are never cached — the healing loop's
        passes are spaced tighter than the TTL, and a pinned negative
        would hide a node that revived between passes (the only cost of
        not caching is one PING_TIMEOUT_S per pass, on an already
        degraded survey)."""
        now = time.monotonic()
        with self._state_lock:
            hit = self._probe_cache.get(entry.name)
            if hit is not None and now - hit[0] < _probe_ttl():
                return True
        pol = dataclasses.replace(self.policy,
                                  call_timeout_s=rp.PING_TIMEOUT_S,
                                  connect_retries=0)
        try:
            alive = bool(call_entry(entry, {"type": "ping"},
                                    policy=pol).get("ok"))
        except (TransportError, OSError):
            alive = False
        with self._state_lock:
            if alive:
                self._probe_cache[entry.name] = (time.monotonic(), True)
            else:
                self._probe_cache.pop(entry.name, None)
        return alive

    def _dispatch_star(self, dps, dp_frame: dict):
        """Flat DP fan-out; same result shape as _dispatch_tree so the
        re-entry pass composes over either topology."""
        outs = fan_out(dps, lambda e: dict(dp_frame), policy=self.policy)
        partials, responders, failed = [], [], []
        for e, (r, err) in zip(dps, outs):
            if err is None:
                responders.append(e.name)
                partials.append(unpack_array(r["cts"]))
            elif isinstance(err, RemoteError):
                raise err   # the DP's handler ran and errored: a real
                            # bug, not an availability fault
            elif isinstance(err, (TransportError, OSError)):
                log.warn(f"{self.name}: DP {e.name} unavailable for "
                         f"survey {dp_frame['survey_id']}: {err}")
                failed.append(e.name)
            else:
                raise err
        return partials, responders, sorted(failed), []

    def _redispatch_missing(self, dps, dp_frame: dict, proofs: bool,
                            mode: str, partials, responders, failed,
                            blobs, ck: SurveyCheckpoint):
        """Mid-survey healing re-entry: while contributions are missing,
        checkpoint, wait out part of the fault window, re-probe ONLY the
        missing DPs (TTL-cached verdicts), and re-dispatch only those
        that answer — over a survivor-layout tree when more than one
        heals (a dead interior relay's subtree re-parents onto the new
        layout), a flat fan-out otherwise. Partials stay disjoint by
        construction (a DP is re-dialed only while absent), and the DP
        reply cache replays byte-identical bytes for any DP that
        contributed before dying, so re-entry can never double-count.
        Bounded by rp.CHECKPOINT_MAX_RESUMES passes."""
        by_name = {e.name: e for e in dps}
        order = [e.name for e in dps]
        attempt = 0
        failed = set(failed)
        while failed and attempt < rp.CHECKPOINT_MAX_RESUMES:
            attempt += 1
            time.sleep(rp.RESUME_BACKOFF_S)
            healed = [nm for nm in sorted(failed)
                      if self._probe_dp(by_name[nm])]
            if not healed:
                continue
            log.lvl1(f"{self.name}: survey {dp_frame['survey_id']} "
                     f"re-entering collect for healed DPs {healed} "
                     f"(pass {attempt})")
            self._ckpt_enter(ck, "collect")
            retry = [by_name[nm]
                     for nm in topo.survivor_layout(order, healed)]
            if mode == "tree" and len(retry) > 1:
                p2, r2, _f2, b2 = self._dispatch_tree(retry, dp_frame,
                                                      proofs)
            else:
                p2, r2, _f2, b2 = self._dispatch_star(retry, dp_frame)
            partials += p2
            blobs += b2
            got = set(responders) | set(r2)
            responders = [nm for nm in order if nm in got]
            failed -= set(r2)
        return partials, responders, sorted(failed), blobs

    def _dispatch_tree(self, dps, dp_frame: dict, proofs: bool):
        """Tree-overlay DP dispatch from the root: contact the forest
        roots, let relays fold their subtrees, and recover from a dead
        relay by re-dispatching its CHILDREN as new subtree roots — never
        the failed node itself, so a node that failed transport is not
        re-sent its contribution request (only its own contribution is
        lost, not its subtree's). Partials from distinct dispatches cover
        disjoint index sets, so summing them never double-counts; the DP
        reply cache makes the re-dispatched subtrees replay identical
        bytes even when a torn reply hid work that already ran. Returns
        (partials, responders roster-ordered, failed sorted, proof blobs).
        """
        order = [e.name for e in dps]
        idx_of = {nm: i for i, nm in enumerate(order)}
        n, b = len(order), topo.tree_fanout(len(order))
        frame = {**dp_frame, "dp_order": order, "fanout": b}
        partials: list[np.ndarray] = []
        blobs: list[dict] = []
        got: set[str] = set()
        failed: set[str] = set()
        expanded: set[int] = set()
        wave = topo.roots(n, b)
        while wave:
            nxt: list[int] = []

            def expand(i):
                # at most once per index: its children become independent
                # subtree roots in the next dispatch wave
                if i not in expanded:
                    expanded.add(i)
                    nxt.extend(topo.children(i, n, b))

            entries = [dps[i] for i in wave]
            widx = {order[i]: i for i in wave}

            def mk(e):
                m = dict(frame)
                m["index"] = widx[e.name]
                return m

            outs = fan_out(entries, mk, policy=self.policy)
            for i, e, (r, err) in zip(wave, entries, outs):
                if err is None:
                    part = np.asarray(unpack_array(r["cts"]))
                    self._check_hop_proof(r, part, proofs, e.name)
                    partials.append(part)
                    got.update(r["responders"])
                    blobs.extend(r.get("proof_blobs") or [])
                    # a relay reports a failed child's WHOLE subtree
                    # absent; expand only the topmost node of each absent
                    # subtree — its children's re-dispatch covers the
                    # descendants, and expanding those too would dial the
                    # same indices twice and double-count their partials
                    abs_set = set(r["absent"])
                    failed |= abs_set
                    for nm in abs_set:
                        j = idx_of[nm]
                        p = topo.parent(j, b)
                        if p is None or order[p] not in abs_set:
                            expand(j)
                elif isinstance(err, RemoteError):
                    raise err   # the handler ran and errored: a real bug,
                                # not an availability fault — don't degrade
                elif isinstance(err, (TransportError, OSError)):
                    log.warn(f"{self.name}: DP subtree {e.name} unavailable "
                             f"for survey {dp_frame['survey_id']}: {err}")
                    failed.add(e.name)
                    expand(i)
                else:
                    raise err
            wave = nxt
        # a subtree member that answered a re-dispatch is not absent
        failed -= got
        responders = [nm for nm in order if nm in got]
        return partials, responders, sorted(failed), blobs

    # -- root CN: the whole survey (reference HandleSurveyQuery +
    # StartService phase order, service.go:263-747)
    def _h_survey_query(self, msg: dict) -> dict:
        if self.roster is None:
            raise RuntimeError("roster not set (send set_roster first)")
        op = msg["op"]
        survey_id = msg["survey_id"]
        proofs = bool(msg.get("proofs"))
        ranges_v = [tuple(r) for r in msg.get("ranges") or []]
        excluded = set(msg.get("dp_exclude") or ())
        dps = [e for e in self.roster.of_role("dp")
               if e.name not in excluded]
        cns = self.roster.of_role("cn")
        # quorum-degraded execution: min_dp_quorum DPs must contribute for
        # the survey to complete; 0 (the default) = all of them, the strict
        # pre-resilience semantics
        min_q = int(msg.get("min_dp_quorum") or 0)
        need = min_q if min_q > 0 else len(dps)
        mode = topo.topology_mode()
        ck = self._checkpoint(survey_id)
        log.lvl1(f"{self.name}: survey {survey_id} op={op} "
                 f"dps={len(dps)} cns={len(cns)} proofs={int(proofs)} "
                 f"quorum={need} topology={mode} resumes={ck.resumes}")

        # range-signature setup: every CN publishes its BB digit signatures
        # for each distinct base u in the query's ranges
        self._ckpt_enter(ck, "setup")
        range_sigs_msg: dict = {}
        if proofs and ranges_v:
            for (u, _l) in rproof.group_ranges(ranges_v):
                outs = fan_out(cns,
                               lambda e, u=u: {"type": "range_sig", "u": u},
                               call=self._call_cn)
                pubs, As = [], []
                for e, (r, err) in zip(cns, outs):
                    if err is not None:
                        raise err
                    pubs.append([int(t) for t in r["pub"]])
                    As.append(unpack_array(r["A"]))
                range_sigs_msg[str(u)] = {"pubs": pubs,
                                          "A": pack_array(np.stack(As))}

        # collect encrypted DP responses — tree overlay by default (relays
        # fold their subtrees, range proofs ride the hops as batched
        # blobs); DRYNX_TOPOLOGY=star restores the flat fan-out where DPs
        # fire range proofs at the VNs from their own processes
        range_offset = int(msg.get("range_offset", 0))
        dp_frame = {"type": "survey_dp", "op": op,
                    "survey_id": survey_id,
                    "query_min": msg["query_min"],
                    "query_max": msg["query_max"],
                    "lr_params": msg.get("lr_params"),
                    "group_by": msg.get("group_by"),
                    "range_offset": range_offset,
                    "proofs": proofs, "ranges": ranges_v,
                    "range_sigs": range_sigs_msg}
        self._ckpt_enter(ck, "collect")
        if mode == "tree" and len(dps) > 1:
            (partials, responders,
             failed, blobs) = self._dispatch_tree(dps, dp_frame, proofs)
        else:
            (partials, responders,
             failed, blobs) = self._dispatch_star(dps, dp_frame)
        if failed:
            # mid-survey healing re-entry: checkpointed, probe-gated,
            # bounded — only the missing sub-work is re-dispatched
            (partials, responders,
             failed, blobs) = self._redispatch_missing(
                dps, dp_frame, proofs, mode, partials, responders,
                failed, blobs, ck)
        ck.responders = list(responders)
        if len(responders) < need:
            ck.save(self._ckpt_store())
            raise RuntimeError(
                f"survey {survey_id}: only {len(responders)}/{len(dps)} DPs "
                f"responded (quorum {need}); failed: {sorted(failed)}")
        absent = sorted(excluded | set(failed))
        if proofs and failed:
            # the VNs were registered expecting a range proof per dialed
            # DP; shrink their counters to the responder set or the
            # expected-proof gate never drains (and the joint range flush
            # never triggers)
            adj = {"type": "vn_adjust", "survey_id": survey_id,
                   "expected_drop": len(failed),
                   "expected_range": len(responders),
                   "absent": sorted(failed)}
            vns_all = self.roster.of_role("vn")
            for v, (_r, err) in zip(vns_all,
                                    fan_out(vns_all, lambda e: dict(adj),
                                            policy=self.policy)):
                if isinstance(err, (TransportError, OSError)):
                    log.warn(f"{self.name}: vn_adjust undeliverable to "
                             f"{v.name}: {err}")
                elif err is not None:
                    raise err
        # canonical fold (topology.fold_cts) in BOTH modes: tree partials
        # and star payloads land on identical aggregate bytes, which is
        # what makes the final transcripts byte-comparable across
        # topologies (ISSUE 11 acceptance gate)
        ck.absent = list(absent)
        self._ckpt_enter(ck, "aggregate")
        cts = jnp.asarray(np.stack(partials))  # (n_partials, V, 2, 3, 16)
        agg = topo.fold_cts(cts)
        if proofs:
            self._send_proof_async(
                "aggregation", survey_id, f"agg-{self.name}",
                pickle.dumps(agg_proof.create_aggregation_proof(cts, agg)))
            if blobs:
                # tree mode: the DPs' range proofs were carried up the
                # relay hops instead of fired at the VNs per-DP — deliver
                # the whole survey's worth as one batch per VN
                self._send_proof_batch_async(survey_id, blobs)

        # obfuscation chain over the CNs (zero/nonzero-semantics ops).
        # This round (and the DRO shuffle below) is a CHAIN, not a star:
        # each CN consumes the previous CN's output ciphertexts, so the
        # crypto forces sequential dispatch — fan_out does not apply.
        if msg.get("obfuscation"):
            self._ckpt_enter(ck, "obfuscate")
            for e in cns:
                r = self._call_cn(e, {"type": "obf_contrib",
                                      "survey_id": survey_id,
                                      "proofs": proofs,
                                      "cts": pack_array(np.asarray(agg))})
                agg = unpack_array_device(r["cts"])

        # DRO / differential-privacy noise: root builds the encrypted noise
        # list, every CN shuffles + re-randomizes it in turn, one noise ct
        # lands on each result (reference service.go:600-665,809-851)
        diffp = msg.get("diffp") or {}
        if diffp.get("noise_list_size", 0) > 0:
            self._ckpt_enter(ck, "dro")
            noise = dro.generate_noise_values(
                int(diffp["noise_list_size"]), float(diffp["lap_mean"]),
                float(diffp["lap_scale"]), float(diffp["quanta"]),
                float(diffp["scale"]), float(diffp["limit"]))
            tbl = self._pub_table(self.roster.collective_pub())
            n_cts = dro.encrypt_noise(
                jax.random.PRNGKey(secrets.randbits(63)), tbl, noise)
            for e in cns:
                r = self._call_cn(e, {"type": "shuffle_contrib",
                                      "survey_id": survey_id,
                                      "proofs": proofs,
                                      "cts": pack_array(np.asarray(n_cts))})
                n_cts = unpack_array_device(r["cts"])
            agg = dro.pick_add(agg, n_cts)

        # key switch: gather contributions from every CN (including self).
        # A star round — every CN switches the SAME K0 component — so it
        # fans out; the point sums accumulate in roster order below.
        self._ckpt_enter(ck, "keyswitch")
        K0 = np.asarray(agg[:, 0])
        ks_frame = {"type": "ks_contrib", "k_component": pack_array(K0),
                    "client_pub": list(msg["client_pub"]),
                    "survey_id": survey_id, "proofs": proofs}
        outs = fan_out(cns, lambda e: dict(ks_frame), call=self._call_cn)
        k_sum = c_sum = None
        for e, (r, err) in zip(cns, outs):
            if err is not None:
                raise err
            u = unpack_array_device(r["u"])
            w = unpack_array_device(r["w"])
            k_sum = u if k_sum is None else B.g1_add(k_sum, u)
            c_sum = w if c_sum is None else B.g1_add(c_sum, w)

        # C + the summed contributions, less the public aggregate shift
        # (n_responders * u^l/2)·B so the decrypted values are the true
        # signed statistics — each RESPONDING DP added one offset; absent
        # DPs added none
        switched = kswitch.finish(jnp.asarray(agg), (k_sum, c_sum),
                                  range_offset * len(responders))
        # let this node's own proof threads drain before replying so the
        # querier's end_verification doesn't race local stragglers
        with self._state_lock:
            drained = self._proof_threads.pop(survey_id, [])
        for t in drained:
            t.join(timeout=rp.PROOF_DRAIN_S)
        ck.done = True
        self._ckpt_enter(ck, "done")
        return {"switched": pack_array(np.asarray(switched)),
                "responders": responders, "absent": absent,
                "resumes": ck.resumes,
                "phases": dict(ck.phase_entries)}

    # -- VN handlers
    def _h_vn_register(self, msg: dict) -> dict:
        if self.vn is None:
            raise RuntimeError(f"node {self.name} is not a VN (no roster, or "
                               "not in the vn role)")
        sid = msg["survey_id"]
        self.vn.register_survey(sid, msg["expected"],
                                msg.get("thresholds", {}),
                                expected_range=int(
                                    msg.get("expected_range", 0)))
        if msg.get("proofs"):
            sigs_pub_by_u = {
                int(u): [tuple(int(t) for t in p) for p in pubs]
                for u, pubs in (msg.get("range_sig_pubs") or {}).items()}
            self._survey_ctx[sid] = {
                "coll_pub": self.roster.collective_pub(),
                "client_pub": tuple(int(t) for t in msg["client_pub"]),
                "ranges_v": [tuple(r) for r in msg.get("ranges") or []],
                "sigs_pub_by_u": sigs_pub_by_u,
            }
        return {"ok": True}

    def _h_vn_adjust(self, msg: dict) -> dict:
        """Root CN tells this VN that DPs went absent mid-survey: shrink
        the expected-proof counter (and the joint-range flush threshold)
        to the responder set. Idempotent per absentee set — the adjustment
        is expressed as absolute expected_range, not a delta on retry."""
        if self.vn is None:
            raise RuntimeError(f"node {self.name} is not a VN")
        self.vn.adjust_expected(
            msg["survey_id"], int(msg.get("expected_drop", 0)),
            expected_range=int(msg["expected_range"])
            if msg.get("expected_range") is not None else None)
        log.lvl2(f"VN {self.name}: survey {msg['survey_id']} adjusted for "
                 f"absent DPs {msg.get('absent')}")
        return {"ok": True}

    @staticmethod
    def _req_of_blob(p: dict) -> rq.ProofRequest:
        return rq.ProofRequest(
            proof_type=p["proof_type"], survey_id=p["survey_id"],
            sender_id=p["sender_id"], differ_info=p["differ_info"],
            round_id=p["round_id"], data=unpack_array(p["data"]).tobytes(),
            signature=schnorr.Signature.from_bytes(
                unpack_array(p["signature"]).tobytes()))

    def _h_proof_request(self, msg: dict) -> dict:
        if self.vn is None:
            raise RuntimeError(f"node {self.name} is not a VN")
        code = self.vn.receive_proof(self._req_of_blob(msg))
        return {"code": code}

    def _h_proof_batch(self, msg: dict) -> dict:
        """A whole survey's worth of relayed proof blobs in ONE frame —
        tree mode's replacement for per-DP proof_request fan-in. Each blob
        is received exactly as _h_proof_request would, in the frame's
        deterministic (differ-sorted) order, so the VN's bitmap keys,
        verdict codes and proofdb contents are identical to star's."""
        if self.vn is None:
            raise RuntimeError(f"node {self.name} is not a VN")
        codes = {}
        for p in msg["proofs"]:
            codes[p["differ_info"]] = self.vn.receive_proof(
                self._req_of_blob(p))
        return {"codes": codes}

    def _h_vn_bitmap(self, msg: dict) -> dict:
        if self.vn is None:
            raise RuntimeError(f"node {self.name} is not a VN")
        sid = msg["survey_id"]
        state = self.vn.surveys.get(sid)
        if state is None:
            raise RuntimeError(f"unknown survey {sid!r} at VN {self.name}")
        if msg.get("vn_order") is not None:
            return self._h_vn_bitmap_relay(msg, state)
        if msg.get("wait"):
            # block until this VN's expected-proof counter drains
            if not state.done.wait(float(msg.get("timeout",
                                                 rp.VERIFY_WAIT_S))):
                raise TimeoutError(
                    f"VN {self.name}: {len(state.bitmap)}/{state.expected} "
                    f"proofs received for {sid!r}")
        return {"bitmap": self.vn.bitmap_for(sid),
                "expected": state.expected}

    def _h_vn_bitmap_relay(self, msg: dict, state) -> dict:
        """Tree-overlay bitmap collection (frames carrying vn_order): wait
        out this VN's own counter CONCURRENTLY with the child subtrees'
        waits, then merge upward. Reports carry only COMPLETE bitmaps;
        anything short lands in failures, so the root applies its quorum
        to exactly the same evidence the star poll would gather."""
        sid = msg["survey_id"]
        timeout = float(msg.get("timeout", rp.VERIFY_WAIT_S))
        order = list(msg["vn_order"])
        n, b = len(order), int(msg["fanout"])
        kids = topo.children(int(msg["index"]), n, b)
        reports: dict[str, dict] = {}
        failures: dict[str, str] = {}

        def poll_children():
            set_current_node(self.name)
            by_name = {e.name: e for e in self.roster.entries}
            idx_of = {order[c]: c for c in kids}
            entries = [by_name[order[c]] for c in kids]

            def mk(e):
                m = dict(msg)
                m["index"] = idx_of[e.name]
                return m

            # socket budget must outlive the child's own blocking wait
            outs = fan_out(entries, mk,
                           call=lambda e, m: call_entry(
                               e, m,
                               timeout=timeout + rp.STRAGGLER_GRACE_S,
                               policy=self.policy))
            for e, (r, err) in zip(entries, outs):
                if err is None:
                    reports.update(r["reports"])
                    failures.update(r["failures"])
                else:
                    for j in topo.subtree(idx_of[e.name], n, b):
                        failures[order[j]] = repr(err)

        t = None
        if kids:
            t = threading.Thread(target=poll_children, daemon=True)
            t.start()
        own_err = None
        try:
            if not state.done.wait(timeout):
                raise TimeoutError(
                    f"VN {self.name}: {len(state.bitmap)}/{state.expected} "
                    f"proofs received for {sid!r}")
            bm = self.vn.bitmap_for(sid)
            if len(bm) < state.expected:
                raise RuntimeError(
                    f"VN {self.name} reports {len(bm)}/{state.expected} "
                    f"proofs for {sid!r}; refusing to commit it")
        except Exception as e:
            own_err = repr(e)
        if t is not None:
            t.join()
        if own_err is None:
            reports[self.name] = {"bitmap": bm, "expected": state.expected}
        else:
            failures[self.name] = own_err
        return {"reports": reports, "failures": failures}

    def _h_end_verification(self, msg: dict) -> dict:
        """Root VN: counter-gated bitmap merge + audit-block commit.

        Round-1 weakness fixed: a survey with missing proofs can no longer
        commit a clean-looking block — a reporting VN must have received
        its full expected count (reference: the bitmap-aggregation
        goroutine only fires after the proof counter reaches zero,
        proof_collection_protocol.go:362-398).

        VN quorum: ``vn_quorum`` in (0, 1] is the fraction of VNs that
        must report a COMPLETE bitmap before the block commits (default
        1.0 = every VN, the strict behavior). All VNs — including this
        node's own counter wait — are polled CONCURRENTLY, so the commit
        fires as soon as the quorum is met instead of serializing a full
        timeout behind each straggler; the reply records which VNs made
        the block (vn_reported) and which straggled (vn_absent)."""
        if self.vn is None:
            raise RuntimeError(f"node {self.name} is not a VN")
        survey_id = msg["survey_id"]
        timeout = float(msg.get("timeout", rp.VERIFY_WAIT_S))
        quorum = float(msg.get("vn_quorum") or 1.0)
        vns = self.roster.of_role("vn")
        state = self.vn.surveys.get(survey_id)
        if state is None:
            raise RuntimeError(f"unknown survey {survey_id!r}")
        # epsilon guards float fractions: 2/3 * 3 == 2.0000000000000004,
        # which a bare ceil would round to "all 3 VNs"
        need = max(1, math.ceil(quorum * len(vns) - 1e-9))

        b = topo.tree_fanout(len(vns))
        if (topo.topology_mode() == "tree" and quorum >= 1.0
                and len(vns) > b):
            # full-quorum collection rides the VN tree: every bitmap is
            # needed anyway, so there is no early-settle semantics to
            # preserve, and relay hops merge sub-polls instead of this
            # root holding one blocked socket per VN. Sub-1.0 quorums
            # keep the concurrent star poll — its commit-as-soon-as-met
            # early exit is the point of a quorum.
            snap, fails = self._collect_bitmaps_tree(survey_id, vns,
                                                     timeout, state, b)
        else:
            lock = threading.Lock()
            reports: dict[str, dict] = {}
            failures: dict[str, str] = {}
            settled = threading.Event()

            def note(name: str, bitmap=None, err=None):
                with lock:
                    if err is None:
                        reports[name] = bitmap
                    else:
                        failures[name] = err
                    if (len(reports) >= need
                            or len(reports) + len(failures) >= len(vns)):
                        settled.set()

            def poll(e):
                set_current_node(self.name)
                try:
                    if e.name == self.name:
                        if not state.done.wait(timeout):
                            raise TimeoutError(
                                f"VN {self.name}: {len(state.bitmap)}/"
                                f"{state.expected} proofs received for "
                                f"{survey_id!r}")
                        bm, expected = (self.vn.bitmap_for(survey_id),
                                        state.expected)
                    else:
                        # socket timeout must outlive the peer's wait
                        r = call_entry(e, {"type": "vn_bitmap",
                                           "survey_id": survey_id,
                                           "wait": True,
                                           "timeout": timeout},
                                       timeout=timeout
                                       + rp.STRAGGLER_GRACE_S,
                                       policy=self.policy)
                        bm, expected = r["bitmap"], r["expected"]
                    if len(bm) < expected:
                        raise RuntimeError(
                            f"VN {e.name} reports {len(bm)}/{expected} "
                            f"proofs for {survey_id!r}; refusing to "
                            f"commit it")
                    note(e.name, bitmap=bm)
                except Exception as err:
                    note(e.name, err=repr(err))

            threads = [threading.Thread(target=poll, args=(e,),
                                        daemon=True)
                       for e in vns]
            for t in threads:
                t.start()
            settled.wait(timeout + 2 * rp.STRAGGLER_GRACE_S)
            with lock:
                snap = dict(reports)
                fails = dict(failures)
        if len(snap) < need:
            raise TimeoutError(
                f"root VN {self.name}: {len(snap)}/{len(vns)} VNs report "
                f"complete bitmaps for {survey_id!r} (quorum {need}); "
                f"failures: {fails}")
        reported = [e.name for e in vns if e.name in snap]
        absent = [e.name for e in vns if e.name not in snap]
        merged = {}
        for name in reported:
            for k, v in snap[name].items():
                merged[f"{name}:{k}"] = v

        self.vn.local_bitmaps[survey_id] = merged
        block = self.vn.chain.append(
            # drynx: deterministic[sample_time is excluded from transcripts]
            DataBlock(survey_id=survey_id, sample_time=time.time(),
                      bitmap=merged))
        return {"block_index": block.index, "block_hash": block.hash(),
                "bitmap": merged, "vn_reported": reported,
                "vn_absent": absent}

    def _collect_bitmaps_tree(self, sid: str, vns, timeout: float,
                              state, b: int):
        """Tree-overlay VN bitmap collection (full-quorum mode): this root
        VN walks its own subtree inline while the OTHER forest roots are
        polled concurrently; each relay hop merges complete bitmaps and
        failures upward. Returns (snap {name: bitmap}, fails)."""
        order = [e.name for e in vns]
        n = len(order)
        base = {"type": "vn_bitmap", "survey_id": sid, "wait": True,
                "timeout": timeout, "vn_order": order, "fanout": b}
        tops = topo.roots(n, b)
        i0 = order.index(self.name) if self.name in order else -1
        remote = [i for i in tops if i != i0]
        reports: dict[str, dict] = {}
        failures: dict[str, str] = {}
        r_out: list = []

        def run_remote():
            set_current_node(self.name)
            entries = [vns[i] for i in remote]
            iix = {order[i]: i for i in remote}

            def mk(e):
                m = dict(base)
                m["index"] = iix[e.name]
                return m

            # two grace units: the remote relay's own sockets already
            # carry one on top of the blocking wait they wrap
            outs = fan_out(entries, mk,
                           call=lambda e, m: call_entry(
                               e, m,
                               timeout=timeout
                               + 2 * rp.STRAGGLER_GRACE_S,
                               policy=self.policy))
            r_out.append((entries, iix, outs))

        t = None
        if remote:
            t = threading.Thread(target=run_remote, daemon=True)
            t.start()
        if i0 in tops:
            # walk our own subtree inline; a non-root self is instead
            # polled over TCP by its tree parent like any other VN
            own = self._h_vn_bitmap_relay(dict(base, index=i0), state)
            reports.update(own["reports"])
            failures.update(own["failures"])
        if t is not None:
            t.join()
        for entries, iix, outs in r_out:
            for e, (r, err) in zip(entries, outs):
                if err is None:
                    reports.update(r["reports"])
                    failures.update(r["failures"])
                else:
                    for j in topo.subtree(iix[e.name], n, b):
                        failures[order[j]] = repr(err)
        snap = {nm: rep["bitmap"] for nm, rep in reports.items()}
        return snap, failures

    # -- VN skipchain retrieval handlers (reference
    # services/service_skipchain.go:173-342: HandleGetGenesisBlock :173,
    # HandleGetLatestBlock :204, HandleGetBlock :226, HandleGetProofs :240,
    # HandleCloseDB :324) — a REMOTE querier can audit the chain.
    def _require_vn(self) -> VerifyingNode:
        if self.vn is None:
            raise RuntimeError(f"node {self.name} is not a VN")
        return self.vn

    def _h_get_block(self, msg: dict) -> dict:
        vn = self._require_vn()
        t = msg["type"]
        if t == "get_genesis":
            blk = vn.chain.genesis()
        elif t == "get_latest":
            blk = vn.chain.latest()
        elif "survey_id" in msg:
            blk = vn.chain.block_for_survey(msg["survey_id"])
        else:
            blk = vn.chain.block(int(msg["index"]))
        if blk is None:
            return {"found": False}
        return {"found": True, "block": _pack_bytes(blk.to_bytes()),
                "hash": blk.hash(), "chain_length": len(vn.chain)}

    def _h_get_proofs(self, msg: dict) -> dict:
        vn = self._require_vn()
        stored = vn.stored_proofs(msg["survey_id"])
        return {"proofs": {k: _pack_bytes(v) for k, v in stored.items()}}

    def _h_close_db(self, msg: dict) -> dict:
        vn = self._require_vn()
        vn.db.sync()
        vn.db.close()
        return {"ok": True}


class RemoteClient:
    """Querier for a multi-process deployment."""

    def __init__(self, roster: Roster,
                 rng: Optional[np.random.Generator] = None,
                 policy: Optional[rp.RetryPolicy] = None):
        self.roster = roster
        rng = rng or np.random.default_rng()
        self.secret, self.public = eg.keygen(rng)
        self.policy = policy or rp.DEFAULT_POLICY
        # Populated by run_survey when proofs/quorum bookkeeping runs.
        self.last_responders: list[str] = []
        self.last_absent: list[str] = []
        # Root-side resume accounting from the last survey reply: how
        # many checkpointed re-entries the root took, and its per-phase
        # entry counters (soak harnesses assert "resumed, not
        # restarted" on these).
        self.last_resumes: int = 0
        self.last_phases: dict = {}
        self._probe_cache: Optional[tuple[float, dict]] = None
        # Per-survey LinkModel byte accounting (delta over run_survey):
        # {"bytes_total", "msgs_total", "by_peer"} — zeros with no link
        # model configured beyond the counters themselves.
        self.last_net: dict = {}

    def broadcast_roster(self) -> dict:
        """Push the roster to every entry. Unreachable nodes are recorded
        as False instead of aborting the whole broadcast — a dead node
        picks the roster up via set_roster when it rejoins, and the
        probe/quorum survey path tolerates its absence meanwhile.
        Deliberately unpooled fresh connections (a one-shot bootstrap
        broadcast, not survey traffic), fanned out concurrently."""
        def send_one(e, m):
            c = Conn(e.host, e.port, peer=e.name)
            try:
                return c.call(m)
            finally:
                c.close()

        msg = {"type": "set_roster", "roster": self.roster.to_dict()}
        outs = fan_out(self.roster.entries, lambda e: msg, call=send_one)
        ok = {}
        for e, (_r, err) in zip(self.roster.entries, outs):
            if err is None:
                ok[e.name] = True
            elif isinstance(err, (TransportError, OSError)):
                log.warn(f"roster undeliverable to {e.name}: {err!r}")
                ok[e.name] = False
            else:
                raise err
        return ok

    def ping(self, entry: RosterEntry) -> bool:
        """Liveness probe: one quick round-trip on a fresh connection. The
        handler answers straight from the accept loop (no device work), so
        an unanswered ping within PING_TIMEOUT_S means the node is down or
        wedged — either way, unfit for survey dispatch."""
        pol = dataclasses.replace(self.policy,
                                  call_timeout_s=rp.PING_TIMEOUT_S,
                                  connect_retries=0)
        try:
            r = call_entry(entry, {"type": "ping"}, policy=pol)
            return bool(r.get("ok"))
        except (TransportError, OSError):
            return False

    def probe_liveness(self) -> dict[str, bool]:
        """Ping every roster entry CONCURRENTLY; map node name -> alive.
        Dead nodes each burn a connect timeout — fanned out, a roster
        full of corpses costs one timeout, not one per corpse. This is
        the re-probe hook survey resume builds on (ROADMAP item 6).

        Verdicts carry a TTL (_probe_ttl): resume paths calling back
        within it reuse the map; past it the probe re-runs automatically,
        so no dispatch ever rides a verdict drawn before a healing fault
        window moved."""
        now = time.monotonic()
        if (self._probe_cache is not None
                and now - self._probe_cache[0] < _probe_ttl()):
            return dict(self._probe_cache[1])
        outs = fan_out(self.roster.entries, lambda e: {"type": "ping"},
                       call=lambda e, m: self.ping(e))
        alive = {e.name: bool(r) for e, (r, _err)
                 in zip(self.roster.entries, outs)}
        self._probe_cache = (time.monotonic(), alive)
        return alive

    def expected_proofs(self, n_dps: int, n_cns: int, obfuscation: bool,
                        diffp: bool) -> int:
        """Proof count every VN must receive for one survey over the TCP
        path: range per DP, ONE aggregation (whatever the dispatch
        topology, exactly one VN-visible aggregation proof comes from the
        root — tree relays' per-hop proofs are verified by their PARENT,
        never delivered to VNs), keyswitch per CN, obfuscation/shuffle per
        CN when enabled."""
        return (n_dps + 1 + n_cns + (n_cns if obfuscation else 0)
                + (n_cns if diffp else 0))

    @staticmethod
    def _diffp_on(diffp: Optional[dict]) -> bool:
        """Mirror the root CN's gate exactly: the shuffle chain (and its
        proofs) only run when noise_list_size > 0."""
        return bool(diffp and int(diffp.get("noise_list_size", 0)) > 0)

    def run_survey(self, op: str, query_min: int = 0, query_max: int = 0,
                   survey_id: str = "sv-remote",
                   dlog: Optional[eg.DecryptionTable] = None,
                   proofs: bool = False, ranges=None,
                   obfuscation: bool = False, diffp: Optional[dict] = None,
                   lr_params=None, group_by=None,
                   thresholds: float = 1.0,
                   timeout: float = rp.VERIFY_WAIT_S,
                   min_dp_quorum: int = 0, vn_quorum: float = 1.0,
                   probe: bool = False):
        """Full remote survey. With proofs on: collect range-sig publics from
        the CNs, register the survey (+ verify context) at every VN, run the
        query, then block on the root VN's counter-gated audit block
        (reference SendSurveyQueryToVNs + SendEndVerification,
        services/api_skipchain.go:16-46). Returns (result, block_info).

        op == "log_reg" requires lr_params (an LRParams) and each DP process
        holding (X, y) data; group_by runs grouped encoding at every DP over
        the AllPossibleGroups grid (reference GenerateData handles both over
        the real network, data_collection_protocol.go:206-267)."""
        from ..encoding import output_size

        net0 = link_model().stats()
        cns = self.roster.of_role("cn")
        dps = self.roster.of_role("dp")
        vns = self.roster.of_role("vn")
        root = cns[0]
        root_vn = vns[0] if vns else None

        dp_exclude: list[str] = []
        if probe:
            # Exclude dead roster entries before dispatch instead of paying
            # a connect-timeout per dead node inside the survey itself.
            alive = self.probe_liveness()
            dp_exclude = [e.name for e in dps if not alive.get(e.name)]
            dps = [e for e in dps if alive.get(e.name)]
            live_cns = [e for e in cns if alive.get(e.name)]
            if not live_cns:
                raise ConnectError("no CN answered the liveness probe")
            root = live_cns[0]
            if vns:
                live_vns = [e for e in vns if alive.get(e.name)]
                if not live_vns:
                    raise ConnectError("no VN answered the liveness probe")
                # register/collect only at live VNs; dead ones still count
                # against the end_verification quorum (it walks the roster)
                vns = live_vns
                root_vn = live_vns[0]

        if op == "log_reg" and lr_params is None:
            raise ValueError("log_reg survey requires lr_params")
        if op == "log_reg" and group_by:
            raise ValueError("group_by is not supported for log_reg")
        n_groups = 1
        if group_by:
            n_groups = int(np.prod([len(v) for v in group_by]))
        if op == "log_reg":
            n_out = lr_params.num_coeffs()
        else:
            n_out = output_size(op, query_min, query_max) * n_groups

        range_offset = 0
        if proofs:
            if ranges is None:
                ranges = [(16, 4)] * n_out
            elif group_by and len(ranges) == n_out // n_groups:
                ranges = list(ranges) * n_groups  # tile per-group specs
            if len(ranges) != n_out:
                raise ValueError(
                    f"{len(ranges)} range specs for {n_out} outputs")
            if op == "log_reg":
                if len(set(map(tuple, ranges))) > 1:
                    raise ValueError(
                        "log_reg range proofs require a uniform (u, l) spec")
                u0, l0 = ranges[0]
                if u0:
                    range_offset = (int(u0) ** int(l0)) // 2
            if not vns:
                raise ValueError("proofs on but the roster has no VNs")
            from ..proofs.range_proof import group_ranges

            sig_pubs = {}
            for (u, _l) in group_ranges(ranges):
                outs = fan_out(cns,
                               lambda e, u=u: {"type": "range_sig", "u": u},
                               policy=self.policy)
                pubs = []
                for e, (r, err) in zip(cns, outs):
                    if err is not None:
                        raise err
                    pubs.append([int(t) for t in r["pub"]])
                sig_pubs[str(u)] = pubs
            expected = self.expected_proofs(
                len(dps), len(cns), obfuscation, self._diffp_on(diffp))
            reg = {"type": "vn_register", "survey_id": survey_id,
                   "expected": expected, "proofs": True,
                   "expected_range": len(dps),
                   "thresholds": {t: thresholds for t in rq.PROOF_TYPES},
                   "client_pub": list(self.public),
                   "ranges": [list(r) for r in ranges],
                   "range_sig_pubs": sig_pubs}
            for e, (_r, err) in zip(vns, fan_out(vns, lambda e: dict(reg),
                                                 policy=self.policy)):
                if err is not None:
                    raise err

        lrp_msg = None
        if lr_params is not None:
            lrp_msg = {k: (list(v) if isinstance(v, tuple) else v)
                       for k, v in dataclasses.asdict(lr_params).items()}
        r = call_entry(root, {"type": "survey_query", "op": op,
                              "survey_id": survey_id,
                              "query_min": query_min,
                              "query_max": query_max,
                              "proofs": proofs,
                              "ranges": [list(t) for t in ranges or []],
                              "obfuscation": obfuscation,
                              "diffp": diffp,
                              "lr_params": lrp_msg,
                              "group_by": [list(v) for v in group_by]
                              if group_by else None,
                              "range_offset": range_offset,
                              "min_dp_quorum": int(min_dp_quorum),
                              "dp_exclude": dp_exclude,
                              "client_pub": list(self.public)},
                       timeout=max(timeout, rp.CALL_TIMEOUT_S))
        self.last_responders = list(r.get("responders") or [])
        self.last_absent = list(r.get("absent") or [])
        self.last_resumes = int(r.get("resumes") or 0)
        self.last_phases = dict(r.get("phases") or {})
        switched = unpack_array_device(r["switched"])
        dl = dlog or eg.DecryptionTable(limit=10000)
        xq = jnp.asarray(eg.secret_to_limbs(self.secret))
        pts = B.decrypt_point(switched, xq)
        vals, found = B.table_lookup(dl.keys, dl.xs, dl.ysign, dl.vals, pts)
        zeros = B.is_infinity(pts)
        dec = st.DecryptedVector(values=np.asarray(vals),
                                 found=np.asarray(found),
                                 is_zero=np.asarray(zeros))
        if op == "log_reg":
            from ..models import logreg as lr

            Ts = lr.unpack(jnp.asarray(dec.values), lr_params)
            result = np.asarray(lr.train(Ts, lr_params))
        elif group_by:
            result = st.decode_grouped(op, dec, st.group_grid(group_by),
                                       query_min, query_max)
        else:
            result = st.decode(op, dec, query_min, query_max)
        self.last_net = _net_delta(net0, link_model().stats())
        if not proofs:
            return result

        # the handler may block ~timeout on its own counter plus the
        # straggler grace on concurrent VN polls; budget the socket so the
        # transport timeout outlives the application wait it wraps
        block = call_entry(root_vn, {"type": "end_verification",
                                     "survey_id": survey_id,
                                     "timeout": timeout,
                                     "vn_quorum": float(vn_quorum)},
                           timeout=2 * timeout + 3 * rp.STRAGGLER_GRACE_S,
                           policy=self.policy)
        self.last_net = _net_delta(net0, link_model().stats())
        return result, block

    # -- remote skipchain audit (reference api_skipchain.go:48-106:
    # SendGetGenesis/SendGetBlock/SendGetLatestBlock/SendGetProofs + close)
    def _root_vn(self):
        vns = self.roster.of_role("vn")
        if not vns:
            raise ValueError("roster has no VNs")
        return vns[0]

    @staticmethod
    def _block_of(r: dict):
        from .skipchain import Block

        return Block.from_bytes(_unpack_bytes(r["block"])) \
            if r.get("found") else None

    def get_genesis(self):
        return self._block_of(call_entry(self._root_vn(),
                                         {"type": "get_genesis"}))

    def get_latest(self):
        return self._block_of(call_entry(self._root_vn(),
                                         {"type": "get_latest"}))

    def get_block(self, index: int = None, survey_id: str = None):
        msg = {"type": "get_block"}
        if survey_id is not None:
            msg["survey_id"] = survey_id
        else:
            msg["index"] = int(index)
        return self._block_of(call_entry(self._root_vn(), msg))

    def get_proofs(self, survey_id: str) -> dict[str, bytes]:
        """Stored proof bytes for a survey, keyed like the VN's proofdb."""
        r = call_entry(self._root_vn(), {"type": "get_proofs",
                                         "survey_id": survey_id})
        return {k: _unpack_bytes(v) for k, v in r["proofs"].items()}

    def close_db(self) -> None:
        for e in self.roster.of_role("vn"):
            call_entry(e, {"type": "close_db"})


__all__ = ["RosterEntry", "Roster", "DrynxNode", "RemoteClient",
           "call_entry", "fan_out"]

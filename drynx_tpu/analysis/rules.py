"""The repo-specific lint rules (see ANALYSIS.md for the full rationale).

Every rule is a static approximation: it must be cheap, zero-dependency
(no jax import) and err toward flagging — suppressions (`# drynx:
noqa[rule]`) and the committed baseline absorb deliberate exceptions.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, FrozenSet, Iterator, List, Optional, Set, Tuple

from .core import (Finding, ModuleInfo, Rule, _contains_env_read, _dotted,
                   _local_bindings, register)
from .graph import FuncNode, _own_calls, _own_returns
from .project import ProjectInfo, ProjectRule, chain_hop

_SECRET_RE = re.compile(
    r"(^|_)(sk|secret|secrets|priv|privkey|private(_?key)?)(_|$)|secret",
    re.IGNORECASE)

_LOG_METHODS = {"debug", "info", "warning", "warn", "error", "exception",
                "critical", "log", "lvl", "lvl1", "lvl2", "lvl3"}
_LOGGER_NAMES = {"log", "logging", "logger", "_logger", "LOG", "LOGGER"}


def _in_scope(mod: ModuleInfo, *parts: str) -> bool:
    return any(f"/{p}/" in f"/{mod.relpath}" for p in parts)


def _is_drynx_pkg(mod: ModuleInfo) -> bool:
    # lintpkg is the test fixture package: it opts into the scoped rules so
    # the project-level pass can be exercised end-to-end from the CLI.
    return (mod.relpath.startswith("drynx_tpu/")
            or "/drynx_tpu/" in mod.relpath
            or "lintpkg" in mod.relpath)


# ---------------------------------------------------------------------------
@register
class JitGlobalCapture(Rule):
    """A @jax.jit function (or a pallas_call builder — its body runs at
    trace time) reading a *mutable* module global bakes the value into the
    trace cache, keyed only on shapes/static args. Flipping the flag later
    (monkeypatch, kill-switch) silently reuses stale traces — the
    INTERPRET trace-cache leak of round 5. Pass such values as static
    arguments, or accept the capture explicitly via the baseline + a
    cache-clearing teardown. This rule covers flags defined in the SAME
    module; imported ones are handled by cross-module-flag-capture, which
    propagates real mutability through the import graph instead of the
    old KNOWN_MUTABLE_FLAGS allowlist."""

    id = "jit-global-capture"
    summary = ("jit-traced code reads a mutable module-level flag; the value "
               "is frozen into the trace cache at first call")

    def run(self, mod: ModuleInfo) -> Iterator[Finding]:
        mutable = set(mod.env_derived) | mod.rebound
        if not mutable:
            return
        for fn in mod.traced_functions:
            local = _local_bindings(fn)
            for sub in ast.walk(fn):
                if (isinstance(sub, ast.Name)
                        and isinstance(sub.ctx, ast.Load)
                        and sub.id in mutable and sub.id not in local):
                    yield self.finding(
                        mod, sub,
                        f"trace-time capture of mutable module global "
                        f"'{sub.id}' in '{fn.name}' — value is frozen into "
                        f"the jit/pallas trace cache")


# ---------------------------------------------------------------------------
@register
class UnsafePickle(Rule):
    """VNs deserialize proof bodies sent by the very parties they exist to
    distrust; `pickle.loads` on those bytes is remote code execution via a
    crafted __reduce__. All deserialization must go through the restricted
    unpickler in proofs/safe_pickle.py (the only file allowed here)."""

    id = "unsafe-pickle"
    summary = ("raw pickle.load(s)/Unpickler outside proofs/safe_pickle.py "
               "— RCE on attacker-controlled bytes")

    _ALLOWED_SUFFIX = "proofs/safe_pickle.py"

    def run(self, mod: ModuleInfo) -> Iterator[Finding]:
        if mod.relpath.endswith(self._ALLOWED_SUFFIX):
            return
        # track `from pickle import loads [as x]`
        from_pickle: Set[str] = set()
        for node in mod.tree.body:
            if isinstance(node, ast.ImportFrom) and node.module == "pickle":
                for a in node.names:
                    if a.name in ("loads", "load", "Unpickler"):
                        from_pickle.add(a.asname or a.name)
        for sub in ast.walk(mod.tree):
            if not isinstance(sub, ast.Call):
                continue
            d = _dotted(sub.func)
            bad = (d in ("pickle.loads", "pickle.load", "pickle.Unpickler")
                   or (isinstance(sub.func, ast.Name)
                       and sub.func.id in from_pickle))
            if bad:
                yield self.finding(
                    mod, sub,
                    f"'{d or sub.func.id}' on untrusted bytes is arbitrary "
                    f"code execution; use proofs.safe_pickle.safe_loads")


# ---------------------------------------------------------------------------
@register
class ImplicitDtype(Rule):
    """The crypto/proof layers are exact uint32 limb arithmetic with
    jax_enable_x64 on: a dtype-inferred array (weak int64/float64) silently
    corrupts Montgomery carries or changes a hash transcript. Array
    constructors inside crypto/ and proofs/ must pin their dtype."""

    id = "implicit-dtype"
    summary = ("jnp array constructor without an explicit dtype inside "
               "crypto/ or proofs/ — inferred dtypes corrupt limb math")

    # positional index at which dtype may appear
    _CTORS = {"jnp.array": 1, "jnp.asarray": 1, "jnp.zeros": 1,
              "jnp.ones": 1, "jnp.empty": 1, "jnp.full": 2}

    def run(self, mod: ModuleInfo) -> Iterator[Finding]:
        if not (_is_drynx_pkg(mod) and _in_scope(mod, "crypto", "proofs")):
            return
        for sub in ast.walk(mod.tree):
            if not isinstance(sub, ast.Call):
                continue
            d = _dotted(sub.func)
            if d not in self._CTORS:
                continue
            if any(k.arg == "dtype" for k in sub.keywords):
                continue
            if len(sub.args) > self._CTORS[d]:
                continue  # dtype passed positionally
            yield self.finding(
                mod, sub,
                f"'{d}' without explicit dtype — pin it (uint32 limb "
                f"tensors / exact-int statistics must not rely on "
                f"inference)")


# ---------------------------------------------------------------------------
@register
class HostRoundtripInDecode(Rule):
    """A value materialized on the host with ``np.asarray(...)`` and
    immediately re-uploaded via ``jnp.asarray`` / ``jax.device_put`` is
    the host round-trip the device-direct data path removed: the wire /
    staging layers (service/, parallel/) should hand device consumers a
    device array directly (transport.unpack_array_device, proof_plane
    put_shard) instead of copying through host memory. Flags the nested
    form ``jnp.asarray(np.asarray(x))`` and the two-statement form
    ``v = np.asarray(...)`` followed by ``jnp.asarray(v)``."""

    id = "host-roundtrip-in-decode"
    summary = ("np.asarray(...) immediately re-uploaded with jnp.asarray/"
               "device_put inside service/ or parallel/ — use the "
               "device-direct decode path instead of a host round-trip")

    _HOST = {"np.asarray", "numpy.asarray"}
    _DEVICE = {"jnp.asarray", "jax.numpy.asarray", "jax.device_put",
               "device_put"}

    def _is_host_call(self, node) -> bool:
        return (isinstance(node, ast.Call)
                and _dotted(node.func) in self._HOST)

    def run(self, mod: ModuleInfo) -> Iterator[Finding]:
        if not (_is_drynx_pkg(mod)
                and _in_scope(mod, "service", "parallel")):
            return
        for sub in ast.walk(mod.tree):
            # nested form: device sink taking a host materialization as
            # its first argument
            if isinstance(sub, ast.Call) \
                    and _dotted(sub.func) in self._DEVICE \
                    and sub.args and self._is_host_call(sub.args[0]):
                yield self.finding(
                    mod, sub,
                    f"'{_dotted(sub.func)}(np.asarray(...))' round-trips "
                    f"through host memory — decode/stage straight to "
                    f"device (unpack_array_device / put_shard)")
            # two-statement form: v = np.asarray(...); <device sink>(v)
            for field in ("body", "orelse", "finalbody"):
                stmts = getattr(sub, field, None)
                if not isinstance(stmts, list):
                    continue
                for prev, nxt in zip(stmts, stmts[1:]):
                    if not (isinstance(prev, ast.Assign)
                            and len(prev.targets) == 1
                            and isinstance(prev.targets[0], ast.Name)
                            and self._is_host_call(prev.value)):
                        continue
                    name = prev.targets[0].id
                    for call in ast.walk(nxt):
                        if isinstance(call, ast.Call) \
                                and _dotted(call.func) in self._DEVICE \
                                and call.args \
                                and isinstance(call.args[0], ast.Name) \
                                and call.args[0].id == name:
                            yield self.finding(
                                mod, call,
                                f"'{name} = np.asarray(...)' is "
                                f"immediately re-uploaded by "
                                f"'{_dotted(call.func)}({name})' — a "
                                f"host round-trip the device-direct "
                                f"path avoids")
                            break


# ---------------------------------------------------------------------------
@register
class HostSyncInHotPath(ProjectRule):
    """Inside jit-traced crypto/parallel code, float()/int()/bool()/
    np.asarray() on a traced value either crashes at trace time or forces a
    device->host sync that serializes the pipeline; .block_until_ready()
    inside a trace is always a mistake. Heuristic taint: function
    parameters (minus static_argnames) and locals derived from them.

    The per-module pass (``run``) checks jit/pallas bodies lexically. The
    project pass (``run_project``) follows the callgraph: a sync inside a
    plain helper *transitively reachable* from a jit entry fires too, with
    the call chain rendered and the finding suppressible at the sync site
    OR the entry. Reads of ``.shape/.ndim/.dtype/.size`` are host metadata
    and never taint."""

    id = "host-sync-in-hot-path"
    summary = ("host-synchronizing call on a traced value inside (or "
               "transitively reachable from) jitted crypto/ or parallel/ "
               "code")

    _HOST_CASTS = {"float", "int", "bool"}
    _HOST_FUNCS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
    _SYNC_METHODS = {"block_until_ready", "item", "tolist"}

    _SHAPE_ATTRS = {"shape", "ndim", "dtype", "size"}
    _MAX_DEPTH = 5

    def run(self, mod: ModuleInfo) -> Iterator[Finding]:
        if not (_is_drynx_pkg(mod) and _in_scope(mod, "crypto", "parallel")):
            return
        for fn in mod.traced_functions:
            tainted = self._tainted_names(fn)
            for sub, what in self._body_syncs(fn, tainted):
                yield self.finding(
                    mod, sub,
                    f"'{what}' on a traced value inside jit-traced "
                    f"'{fn.name}' — crashes at trace time or forces a "
                    f"device->host sync")

    def _body_syncs(self, fn: ast.AST, tainted: Set[str],
                    ) -> Iterator[Tuple[ast.Call, str]]:
        """(call node, rendered sink) for every host sync on a tainted
        value lexically in fn (nested defs included: they close over the
        same traced values)."""
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            d = _dotted(sub.func)
            if (isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in self._SYNC_METHODS):
                if sub.func.attr == "block_until_ready" or \
                        self._refs_tainted(sub.func.value, tainted):
                    yield sub, f".{sub.func.attr}()"
                continue
            name = d if d in self._HOST_FUNCS else (
                sub.func.id if isinstance(sub.func, ast.Name)
                and sub.func.id in self._HOST_CASTS else None)
            if name and any(self._refs_tainted(a, tainted)
                            for a in sub.args):
                yield sub, f"{name}()"

    # -- project pass: follow the callgraph out of jit entries ------------

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        reported: Set[Tuple[str, int]] = set()
        for fid in sorted(project.calls.traced_entries):
            entry = project.calls.functions.get(fid)
            if entry is None:
                continue
            mg = project.graphs[entry.module]
            if not (_is_drynx_pkg(mg.info)
                    and _in_scope(mg.info, "crypto", "parallel")):
                continue
            # decorator-marked entries' own bodies are covered lexically by
            # run(); wrapper-marked ones (g = jax.jit(f), bucketed(f)) are
            # not, so include their bodies here.
            decorated = any(entry.node is f for f in mg.info.traced_functions)
            chain = [chain_hop(mg.info.relpath, entry.node.lineno,
                               entry.qual)]
            anchors = ((mg.info.relpath, entry.node.lineno),)
            yield from self._walk_entry(
                project, entry, frozenset(self._tainted_names(entry.node)),
                chain, anchors, include_body=not decorated,
                reported=reported, visited=set(), depth=0)

    def _walk_entry(self, project: ProjectInfo, fn: FuncNode,
                    tainted_params: FrozenSet[str], chain: List[str],
                    anchors: Tuple[Tuple[str, int], ...], include_body: bool,
                    reported: Set[Tuple[str, int]],
                    visited: Set[Tuple[str, FrozenSet[str]]], depth: int,
                    ) -> Iterator[Finding]:
        key = (fn.fid, tainted_params)
        if key in visited or depth > self._MAX_DEPTH:
            return
        visited.add(key)
        mg = project.graphs[fn.module]
        tainted = self._propagate(fn.node, set(tainted_params))
        if include_body:
            for sub, what in self._body_syncs(fn.node, tainted):
                site = (mg.info.relpath, sub.lineno)
                if site in reported:
                    continue
                reported.add(site)
                full = chain + [chain_hop(mg.info.relpath, sub.lineno, what)]
                yield self.finding(
                    mg.info, sub,
                    f"'{what}' on a traced value in '{fn.qual}', reachable "
                    f"from jit entry '{chain[0].rsplit(':', 1)[-1]}' — "
                    f"forces a device->host sync inside the trace",
                    call_chain=full, anchors=anchors)
        for site in project.calls.callees(fn.fid):
            callee = project.calls.functions.get(site.callee)
            if callee is None or callee.fid in project.calls.traced_entries:
                continue  # traced callees are analyzed as their own entries
            passed = self._callee_taint(site.node, callee.node, tainted)
            if not passed:
                continue
            hop = chain_hop(mg.info.relpath, site.lineno, callee.qual)
            yield from self._walk_entry(
                project, callee, frozenset(passed), chain + [hop], anchors,
                include_body=True, reported=reported, visited=visited,
                depth=depth + 1)

    def _callee_taint(self, call: ast.Call, callee: ast.AST,
                      tainted: Set[str]) -> Set[str]:
        """Callee parameter names that receive tainted arguments."""
        args = callee.args
        params = [a.arg for a in (args.posonlyargs + args.args)
                  if a.arg != "self"]
        static = self._static_args(callee)
        out: Set[str] = set()
        splat = False
        for i, a in enumerate(call.args):
            if isinstance(a, ast.Starred):
                splat = splat or self._refs_tainted(a, tainted)
                continue
            if self._refs_tainted(a, tainted) and i < len(params):
                out.add(params[i])
        for kw in call.keywords:
            if kw.arg is None:
                splat = splat or self._refs_tainted(kw.value, tainted)
            elif self._refs_tainted(kw.value, tainted):
                out.add(kw.arg)
        if splat:
            out.update(params)
        return out - static

    @staticmethod
    def _static_args(fn: ast.AST) -> Set[str]:
        out: Set[str] = set()
        for dec in fn.decorator_list:
            if not isinstance(dec, ast.Call):
                continue
            for kw in dec.keywords:
                if kw.arg in ("static_argnames", "static_argnums"):
                    for n in ast.walk(kw.value):
                        if isinstance(n, ast.Constant) \
                                and isinstance(n.value, str):
                            out.add(n.value)
        return out

    def _tainted_names(self, fn: ast.AST) -> Set[str]:
        static = self._static_args(fn)
        args = fn.args
        start = {a.arg for a in
                 (args.posonlyargs + args.args + args.kwonlyargs)
                 if a.arg not in static and a.arg != "self"}
        return self._propagate(fn, start)

    def _propagate(self, fn: ast.AST, tainted: Set[str]) -> Set[str]:
        # one forward pass of simple propagation through assignments
        for stmt in ast.walk(fn):
            if isinstance(stmt, ast.Assign) \
                    and self._refs_tainted(stmt.value, tainted):
                for t in stmt.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            tainted.add(n.id)
        return tainted

    @classmethod
    def _refs_tainted(cls, node: ast.AST, tainted: Set[str]) -> bool:
        # x.shape / x.ndim / x.dtype / x.size are host-side metadata: code
        # like `int(np.prod(x.shape[:3]))` never syncs the device buffer.
        def walk(n: ast.AST) -> bool:
            if isinstance(n, ast.Attribute) and n.attr in cls._SHAPE_ATTRS:
                return False
            if isinstance(n, ast.Name) and n.id in tainted:
                return True
            return any(walk(c) for c in ast.iter_child_nodes(n))
        return walk(node)


# ---------------------------------------------------------------------------
@register
class EnvReadIntoTrace(Rule):
    """`X = os.environ[...]` at import time, with X read inside jit-traced
    code, wires process environment into compiled artifacts: two processes
    with different env silently compute different programs from the same
    call site, and tests that mutate the env (or monkeypatch X) leave stale
    traces behind. Thread such config through as explicit (static)
    arguments instead. Fires at the assignment; the use sites are covered
    by jit-global-capture."""

    id = "env-read-into-trace"
    summary = ("import-time os.environ read whose value flows into "
               "jit-traced code")

    def run(self, mod: ModuleInfo) -> Iterator[Finding]:
        used_in_trace: Dict[str, List[str]] = {}
        for fn in mod.traced_functions:
            local = _local_bindings(fn)
            for sub in ast.walk(fn):
                if (isinstance(sub, ast.Name)
                        and isinstance(sub.ctx, ast.Load)
                        and sub.id in mod.env_derived
                        and sub.id not in local):
                    used_in_trace.setdefault(sub.id, []).append(fn.name)
        for name, fns in sorted(used_in_trace.items()):
            node = mod.env_derived[name]
            yield self.finding(
                mod, node,
                f"import-time environment read bound to '{name}' is "
                f"captured by jit-traced code ({', '.join(sorted(set(fns)))})"
            )
        # direct env reads lexically inside traced functions
        for fn in mod.traced_functions:
            for sub in ast.walk(fn):
                if isinstance(sub, (ast.Attribute, ast.Call)):
                    d = _dotted(sub if isinstance(sub, ast.Attribute)
                                else sub.func)
                    if d and (d.startswith("os.environ") or d == "os.getenv"):
                        yield self.finding(
                            mod, sub,
                            f"os.environ read inside jit-traced "
                            f"'{fn.name}' is evaluated once at trace time")
                        break


# ---------------------------------------------------------------------------
@register
class SecretLogging(Rule):
    """Secret-key material (ElGamal secrets, Schnorr nonces) must never hit
    a log stream or stdout: logs cross trust boundaries (CI artifacts,
    shared hosts) that the ciphertexts are specifically protecting the data
    from. Flags print()/log.*/logging calls whose arguments reference a
    secret-shaped identifier.

    Kept as the *seed list* for the dataflow successor
    ``secret-flow-to-sink`` (which tracks actual values from keygen/nonce
    definition sites instead of matching names): where both fire on the
    same line, the dataflow finding wins and this one is absorbed."""

    id = "secret-logging"
    seed_only = True
    summary = "print/log call referencing secret-key material"

    def run(self, mod: ModuleInfo) -> Iterator[Finding]:
        for sub in ast.walk(mod.tree):
            if not isinstance(sub, ast.Call):
                continue
            if not self._is_log_sink(sub):
                continue
            ident = self._secret_ident(sub)
            if ident:
                yield self.finding(
                    mod, sub,
                    f"'{ident}' looks like secret-key material flowing "
                    f"into a log/print sink")

    @staticmethod
    def _is_log_sink(call: ast.Call) -> bool:
        if isinstance(call.func, ast.Name) and call.func.id == "print":
            return True
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in _LOG_METHODS:
            root = call.func.value
            while isinstance(root, ast.Attribute):
                root = root.value
            return isinstance(root, ast.Name) and root.id in _LOGGER_NAMES
        return False

    @classmethod
    def _secret_ident(cls, call: ast.Call) -> str:
        for arg in list(call.args) + [k.value for k in call.keywords]:
            for n in ast.walk(arg):
                name = None
                if isinstance(n, ast.Name):
                    name = n.id
                elif isinstance(n, ast.Attribute):
                    name = n.attr
                if name and _SECRET_RE.search(name):
                    return name
        return ""


# ---------------------------------------------------------------------------
@register
class HardcodedTimeout(Rule):
    """Retry/timeout numbers scattered as bare literals made failure
    behavior unauditable: nobody could say how long a dead DP stalls a
    survey without reading every call site (the pre-resilience state of
    node.py/api.py/service.py). Every such number must be a named constant
    in drynx_tpu/resilience/policy.py — that module is the single place
    the rule exempts. Fires on: timeout=/retries= keyword literals,
    timeout-ish parameter defaults, sleep/wait calls with literal
    durations, and `.get("...timeout...", <literal>)` fallbacks.

    The network plane (PR 10) added a second family of tuning knobs with
    the same auditability problem: fan-out worker counts and connection-
    pool bounds (workers=/max_workers=/max_idle=/pool_size=). A bare
    ``max_workers=8`` decides how hard a survey hammers a roster exactly
    like a bare ``timeout=900`` decides how long it stalls — both live as
    named constants in resilience/policy.py (FAN_OUT_WORKERS,
    CONN_POOL_MAX_IDLE).

    The tree overlay (PR 11) added a third family: tree fanout and pool
    caps (fanout=/tree_fanout=/pool_max=), surfaced as the
    DRYNX_TREE_FANOUT / DRYNX_TOPOLOGY / DRYNX_CONN_POOL_MAX env knobs.
    A literal ``fanout=8`` shapes dispatch depth — and a numeric literal
    fallback in ``.get("DRYNX_CONN_POOL_MAX", 1024)`` silently forks the
    default away from policy — so both route through TREE_FANOUT_MIN/MAX
    and CONN_POOL_MAX instead (env fallbacks stay string-typed, which
    this rule ignores by design).

    Saturation serving (PR 12) added the admission-control family:
    verify-worker pool width, per-tenant quotas, shed thresholds, and
    retry-after hint bounds (workers=/quota=/shed_fraction=/
    retry_after_*=), surfaced as the DRYNX_VERIFY_WORKERS /
    DRYNX_TENANT_QUOTA / DRYNX_SHED_FRACTION env knobs. A literal
    ``tenant_quota=8`` decides when a tenant starts seeing typed
    rejections exactly like a bare timeout decides when a caller gives
    up — the defaults live in policy.py (VERIFY_WORKERS, TENANT_QUOTA,
    SHED_FRACTION, SHED_RETRY_MIN_S/MAX_S).

    Streaming surveys (PR 18) added the window/pane/epsilon family:
    pane width, window span, per-advance privacy spend and slide pacing
    (pane_width=/window_panes=/epsilon_budget=/epsilon_per_advance=/
    slide_pacing=), surfaced as the DRYNX_PANE_WIDTH /
    DRYNX_STREAM_WINDOW / DRYNX_EPSILON_BUDGET /
    DRYNX_EPSILON_PER_ADVANCE / DRYNX_SLIDE_PACING env knobs. A literal
    ``epsilon_budget=1.0`` is a PRIVACY bound — a fork of that default
    away from policy is strictly worse than an unauditable timeout —
    and a literal ``pane_width=4096`` silently re-shapes every proof
    blob a stream caches; the defaults live in policy.py (PANE_WIDTH,
    STREAM_WINDOW_PANES, EPSILON_BUDGET, EPSILON_PER_ADVANCE,
    SLIDE_PACING_S)."""

    id = "hardcoded-timeout"
    summary = ("bare numeric timeout/retry/worker-pool literal outside "
               "drynx_tpu/resilience/ — name it in resilience/policy.py")

    _SLEEPY = {"sleep", "wait", "join"}

    @staticmethod
    def _timeoutish(name: str) -> bool:
        n = name.lower()
        return ("timeout" in n or n == "retries" or n.endswith("_retries")
                or n.endswith("deadline")
                or n == "workers" or n.endswith("_workers")
                or n == "max_idle" or n.endswith("_idle")
                or n == "pool_size" or n.endswith("_pool_size")
                or n == "fanout" or n.endswith("_fanout")
                or n == "pool_max" or n.endswith("_pool_max")
                or n == "quota" or n.endswith("_quota")
                # NB: substring "shed" would also match "finished"
                or n == "shed" or n.startswith("shed_")
                or n.endswith("_shed") or "shed_fraction" in n
                or "retry_after" in n
                # streaming knobs: substring matches so the env-var forms
                # (DRYNX_PANE_WIDTH, DRYNX_STREAM_WINDOW, ...) fire in
                # .get() fallbacks too; bare "epsilon" stays unmatched —
                # it is a common math variable name
                or "pane_width" in n
                or "window_panes" in n or "stream_window" in n
                or "epsilon_budget" in n or "epsilon_per_advance" in n
                or n.endswith("_epsilon")
                or "slide_pacing" in n)

    @staticmethod
    def _nonzero_num(node: ast.AST) -> bool:
        return (isinstance(node, ast.Constant)
                and isinstance(node.value, (int, float))
                and not isinstance(node.value, bool)
                and node.value != 0)

    def run(self, mod: ModuleInfo) -> Iterator[Finding]:
        if not _is_drynx_pkg(mod) or _in_scope(mod, "resilience"):
            return
        for sub in ast.walk(mod.tree):
            if isinstance(sub, ast.Call):
                yield from self._check_call(mod, sub)
            elif isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_defaults(mod, sub)

    def _check_call(self, mod: ModuleInfo, call: ast.Call):
        for kw in call.keywords:
            if kw.arg and self._timeoutish(kw.arg) \
                    and self._nonzero_num(kw.value):
                yield self.finding(
                    mod, call,
                    f"literal {kw.arg}={kw.value.value!r} — use a named "
                    f"constant from drynx_tpu/resilience/policy.py")
                return
        if isinstance(call.func, ast.Attribute):
            if call.func.attr in self._SLEEPY and call.args \
                    and self._nonzero_num(call.args[0]):
                yield self.finding(
                    mod, call,
                    f"literal duration in '.{call.func.attr}"
                    f"({call.args[0].value!r})' — use a named constant "
                    f"from drynx_tpu/resilience/policy.py")
                return
            if (call.func.attr == "get" and len(call.args) >= 2
                    and isinstance(call.args[0], ast.Constant)
                    and isinstance(call.args[0].value, str)
                    and self._timeoutish(call.args[0].value)
                    and self._nonzero_num(call.args[1])):
                yield self.finding(
                    mod, call,
                    f"literal fallback in .get({call.args[0].value!r}, "
                    f"{call.args[1].value!r}) — use a named constant from "
                    f"drynx_tpu/resilience/policy.py")

    def _check_defaults(self, mod: ModuleInfo, fn):
        args = fn.args
        pos = args.posonlyargs + args.args
        for a, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
            if self._timeoutish(a.arg) and self._nonzero_num(d):
                yield self.finding(
                    mod, d,
                    f"literal default {a.arg}={d.value!r} in '{fn.name}' — "
                    f"use a named constant from "
                    f"drynx_tpu/resilience/policy.py")
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if d is not None and self._timeoutish(a.arg) \
                    and self._nonzero_num(d):
                yield self.finding(
                    mod, d,
                    f"literal default {a.arg}={d.value!r} in '{fn.name}' — "
                    f"use a named constant from "
                    f"drynx_tpu/resilience/policy.py")


# ---------------------------------------------------------------------------
@register
class ThreadTrace(Rule):
    """First-touch jit tracing from a worker thread is the r05 segfault
    class: partial_eval recurses roughly one C frame per traced equation,
    the pairing kernels trace >10k equations, and non-main threads get half
    the main thread's C stack — the process dies in the interpreter with no
    Python traceback. All first-touch tracing must happen on the main
    thread (the compilecache warmup) or under the shared compile lock.
    Flags `threading.Thread(target=f)` where `f` is a function defined in
    this module whose body calls a trace entry — a jit/pallas-decorated
    function, a `bucketed(...)`/`jax.jit(...)`-bound name, or a bucketed-op
    attribute — outside a `with <...lock...>:` block."""

    id = "thread-trace"
    summary = ("threading.Thread target reaches a jit/trace entry point "
               "outside a compile lock — first-touch tracing off the main "
               "thread can overflow the worker's C stack")

    _ENTRY_FACTORIES = {"bucketed", "jit", "pjit"}

    def run(self, mod: ModuleInfo) -> Iterator[Finding]:
        entries = self._trace_entry_names(mod)
        if not entries:
            return
        defs = {f.name: f for f in mod.functions}
        for sub in ast.walk(mod.tree):
            if not isinstance(sub, ast.Call):
                continue
            d = _dotted(sub.func)
            if d not in ("threading.Thread", "Thread"):
                continue
            target = next((kw.value for kw in sub.keywords
                           if kw.arg == "target"), None)
            if target is None:
                continue
            if isinstance(target, ast.Lambda):
                hit = self._unlocked_entry_call(target.body, entries,
                                                under_lock=False)
                if hit:
                    yield self.finding(
                        mod, sub,
                        f"Thread target lambda calls trace entry "
                        f"'{hit}' — first-touch tracing off the main "
                        f"thread (warm it via drynx_tpu.compilecache or "
                        f"wrap in the compile lock)")
                continue
            if not isinstance(target, ast.Name) or target.id not in defs:
                continue  # dynamic/imported target: out of static reach
            fn = defs[target.id]
            hit = None
            for stmt in fn.body:
                hit = self._unlocked_entry_call(stmt, entries,
                                                under_lock=False)
                if hit:
                    break
            if hit:
                yield self.finding(
                    mod, sub,
                    f"Thread target '{fn.name}' calls trace entry "
                    f"'{hit}' outside a compile lock — first-touch "
                    f"tracing off the main thread (warm it via "
                    f"drynx_tpu.compilecache or wrap in the compile lock)")

    def _trace_entry_names(self, mod: ModuleInfo) -> Set[str]:
        names = {f.name for f in mod.traced_functions}
        # names bound to bucketed(...)/jax.jit(...) factory calls anywhere
        for sub in ast.walk(mod.tree):
            if not isinstance(sub, ast.Assign) \
                    or not isinstance(sub.value, ast.Call):
                continue
            d = _dotted(sub.value.func) or ""
            if d.split(".")[-1] in self._ENTRY_FACTORIES:
                for t in sub.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
        return names

    @classmethod
    def _unlocked_entry_call(cls, node: ast.AST, entries: Set[str],
                             under_lock: bool) -> Optional[str]:
        """Name of the first trace-entry call NOT under a lock-ish `with`,
        else None. Recursion tracks `with ...lock...:` ancestry — ast.walk
        can't, it loses parents."""
        if isinstance(node, ast.With):
            locked = under_lock or any(
                "lock" in (_dotted(item.context_expr) or "").lower()
                for item in node.items)
            for child in node.body:
                hit = cls._unlocked_entry_call(child, entries, locked)
                if hit:
                    return hit
            return None
        if isinstance(node, ast.Call) and not under_lock:
            d = _dotted(node.func)
            leaf = (d or "").split(".")[-1]
            if leaf in entries:
                return leaf
        for child in ast.iter_child_nodes(node):
            hit = cls._unlocked_entry_call(child, entries, under_lock)
            if hit:
                return hit
        return None


# ---------------------------------------------------------------------------
@register
class CrossModuleFlagCapture(ProjectRule):
    """The import-graph version of jit-global-capture: a flag assigned
    from os.environ or rebound at runtime *anywhere* in the project, then
    imported (through any number of re-export hops) or read via a module
    alias, taints every jit/pallas body that reads it — the read is
    evaluated once at trace time and frozen into the cache. This replaces
    the old KNOWN_MUTABLE_FLAGS allowlist with real propagation: only
    flags that are actually mutable at their definition fire."""

    id = "cross-module-flag-capture"
    summary = ("jit/pallas-traced code reads a mutable flag defined in "
               "another module (env-derived or rebound) — frozen into the "
               "trace cache")

    _REASONS = {"env": "assigned from os.environ",
                "rebound": "rebound at runtime",
                "rebound-externally": "attribute-rebound from another module"}

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        for dotted in sorted(project.graphs):
            mg = project.graphs[dotted]
            info = mg.info
            if not info.traced_functions or not project.in_focus(
                    info.relpath):
                continue
            for fn in info.traced_functions:
                local = _local_bindings(fn)
                seen: Set[str] = set()
                for sub in ast.walk(fn):
                    hit = self._mutable_read(project, dotted, local, sub)
                    if hit is None:
                        continue
                    token, origin = hit
                    if token in seen or origin.module == dotted:
                        continue
                    seen.add(token)
                    chain = [chain_hop(info.relpath, sub.lineno, token)]
                    chain += [chain_hop(rel, ln, "import")
                              for rel, ln in origin.hops]
                    chain.append(chain_hop(
                        origin.relpath, origin.lineno,
                        f"{origin.name} ({self._REASONS[origin.reason]})"))
                    yield self.finding(
                        info, sub,
                        f"trace-time capture of mutable flag '{token}' in "
                        f"'{fn.name}' — defined in {origin.module} and "
                        f"{self._REASONS[origin.reason]}; the value is "
                        f"frozen into the jit/pallas trace cache",
                        call_chain=chain,
                        anchors=((origin.relpath, origin.lineno),))

    @staticmethod
    def _mutable_read(project, dotted, local, sub):
        """(rendered token, FlagOrigin) when `sub` is a Load of a mutable
        cross-module flag, else None."""
        mg = project.graphs[dotted]
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load) \
                and sub.id not in local and sub.id in mg.froms:
            origin = project.flag_origin(dotted, sub.id)
            if origin is not None:
                return sub.id, origin
        elif isinstance(sub, ast.Attribute) and isinstance(sub.ctx, ast.Load):
            d = _dotted(sub)
            if d and d.count(".") == 1:
                alias, attr = d.split(".")
                if alias not in local:
                    target = project.imports.module_for_alias(dotted, alias)
                    if target is not None and target != dotted \
                            and target in project.graphs:
                        origin = project.flag_origin(target, attr)
                        if origin is not None:
                            return d, origin
        return None


# ---------------------------------------------------------------------------
_UINT32_DTYPES = {"jnp.uint32", "np.uint32", "numpy.uint32",
                  "jax.numpy.uint32"}


def _is_uint32_dtype(expr: ast.AST) -> bool:
    if isinstance(expr, ast.Constant) and expr.value == "uint32":
        return True
    return _dotted(expr) in _UINT32_DTYPES


@register
class PallasOperandDtype(ProjectRule):
    """Mosaic kernels in this repo are exact uint32 limb arithmetic with
    x64 disabled: a pallas_call operand that arrives as weak int32/float32
    (or x64-demoted int64) silently truncates limbs inside the kernel.
    Every ``pl.pallas_call(...)(operands...)`` operand must be *provably*
    uint32: a literal dtype at the constructor, a dtype-preserving chain
    (reshape/transpose/indexing/uint32 arithmetic) rooted at one, a
    callgraph hop through a helper whose returns pin uint32 (e.g.
    ``_pad_lanes``), or — for operands that are function parameters — a
    reverse hop proving every project call site passes uint32."""

    id = "pallas-operand-dtype"
    summary = ("pl.pallas_call operand not provably uint32 — weak/implicit "
               "dtypes miscompile the Mosaic limb kernels")

    _CTOR_DTYPE_POS = {"array": 1, "asarray": 1, "zeros": 1, "ones": 1,
                       "empty": 1, "full": 2}
    _ARRAY_NS = {"jnp", "np", "numpy", "jax.numpy"}
    # dtype(out) == dtype(arg0)
    _PRESERVING_FUNCS = {"transpose", "reshape", "concatenate", "stack",
                         "broadcast_to", "tile", "repeat", "flip", "roll",
                         "moveaxis", "swapaxes", "expand_dims", "squeeze",
                         "ravel", "pad", "zeros_like", "ones_like",
                         "empty_like", "full_like", "flipud", "rot90"}
    _PRESERVING_METHODS = {"reshape", "transpose", "ravel", "squeeze",
                           "swapaxes", "copy", "flatten"}
    _MAX_DEPTH = 8

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        self._pins_memo: Dict[Tuple[str, Optional[int]], bool] = {}
        self._ctx_memo: Dict[Tuple[str, int], tuple] = {}
        for dotted in sorted(project.graphs):
            mg = project.graphs[dotted]
            info = mg.info
            if not (_is_drynx_pkg(info)
                    and _in_scope(info, "crypto", "parallel")
                    and project.in_focus(info.relpath)):
                continue
            for qual in sorted(mg.functions):
                fn = mg.functions[qual]
                for call in _own_calls(fn.node):
                    if not (isinstance(call.func, ast.Call)
                            and (_dotted(call.func.func) or ""
                                 ).split(".")[-1] == "pallas_call"):
                        continue
                    for i, op in enumerate(call.args):
                        trail = [chain_hop(info.relpath, call.lineno,
                                           f"pallas_call operand {i}")]
                        if self._prove(project, fn, op, trail, 0, set()):
                            continue
                        try:
                            src = ast.unparse(op)
                        except Exception:
                            src = "<operand>"
                        if len(src) > 48:
                            src = src[:45] + "..."
                        yield self.finding(
                            info, op,
                            f"pallas_call operand {i} ('{src}') in "
                            f"'{fn.qual}' is not provably uint32 — coerce "
                            f"with jnp.asarray(..., jnp.uint32) or pin the "
                            f"dtype in the producing helper",
                            call_chain=trail[:6],
                            anchors=((info.relpath, call.lineno),))

    # -- the prover -------------------------------------------------------

    def _ctx(self, project: ProjectInfo, fn: FuncNode):
        """(assigns, params, sites) for a function: last simple assignment
        per name, parameter names, and call-node -> callee-fid map."""
        key = (fn.fid, id(fn.node))
        cached = self._ctx_memo.get(key)
        if cached is not None:
            return cached
        assigns: Dict[str, tuple] = {}
        for stmt in ast.walk(fn.node):
            if not isinstance(stmt, ast.Assign):
                continue
            t = stmt.targets[0] if len(stmt.targets) == 1 else None
            if isinstance(t, ast.Name):
                assigns[t.id] = ("expr", stmt.value, stmt.lineno)
            elif isinstance(t, (ast.Tuple, ast.List)):
                for idx, el in enumerate(t.elts):
                    if isinstance(el, ast.Name):
                        assigns[el.id] = ("unpack", idx, stmt.value,
                                          stmt.lineno)
        a = fn.node.args
        params = [x.arg for x in (a.posonlyargs + a.args) if x.arg != "self"]
        sites = {id(s.node): s.callee
                 for s in project.calls.callees(fn.fid)}
        self._ctx_memo[key] = (assigns, params, sites)
        return assigns, params, sites

    def _prove(self, project: ProjectInfo, fn: FuncNode, expr: ast.AST,
               trail: List[str], depth: int, visiting: Set[tuple]) -> bool:
        if depth > self._MAX_DEPTH:
            return False
        mg = project.graphs[fn.module]
        rel = mg.info.relpath
        assigns, params, sites = self._ctx(project, fn)

        if isinstance(expr, ast.Starred):
            return self._prove(project, fn, expr.value, trail, depth,
                               visiting)
        if isinstance(expr, (ast.Tuple, ast.List)):
            return all(self._prove(project, fn, e, trail, depth + 1,
                                   visiting) for e in expr.elts)
        if isinstance(expr, ast.Subscript):
            return self._prove(project, fn, expr.value, trail, depth + 1,
                               visiting)
        if isinstance(expr, ast.IfExp):
            return (self._prove(project, fn, expr.body, trail, depth + 1,
                                visiting)
                    and self._prove(project, fn, expr.orelse, trail,
                                    depth + 1, visiting))
        if isinstance(expr, ast.BinOp):
            # uint32 op uint32 stays uint32; weak python int literals do
            # not promote it under x64-off
            ops = [expr.left, expr.right]
            arr = [o for o in ops if not (isinstance(o, ast.Constant)
                                          and isinstance(o.value, int))]
            return bool(arr) and all(
                self._prove(project, fn, o, trail, depth + 1, visiting)
                for o in arr)
        if isinstance(expr, ast.Attribute):
            if expr.attr == "T":
                return self._prove(project, fn, expr.value, trail,
                                   depth + 1, visiting)
            return False
        if isinstance(expr, ast.Name):
            got = assigns.get(expr.id)
            if got is not None:
                if got[0] == "expr":
                    trail.append(chain_hop(rel, got[2],
                                           f"{expr.id} = ..."))
                    return self._prove(project, fn, got[1], trail,
                                       depth + 1, visiting)
                _, idx, value, lineno = got
                trail.append(chain_hop(rel, lineno,
                                       f"{expr.id} = ...[{idx}]"))
                return self._prove_unpack(project, fn, value, idx, trail,
                                          depth + 1, visiting)
            if expr.id in params:
                return self._param_proven(project, fn, expr.id, trail,
                                          depth + 1, visiting)
            return self._module_const_proven(project, fn.module, expr.id,
                                             trail, depth + 1, visiting)
        if isinstance(expr, ast.Call):
            return self._prove_call(project, fn, expr, trail, depth,
                                    visiting, sites)
        return False

    def _prove_call(self, project, fn, call, trail, depth, visiting, sites):
        mg = project.graphs[fn.module]
        rel = mg.info.relpath
        d = _dotted(call.func) or ""
        leaf = d.split(".")[-1]
        root = d.rsplit(".", 1)[0] if "." in d else ""
        if isinstance(call.func, ast.Attribute) and root not in self._ARRAY_NS:
            # method call on an expression
            if call.func.attr == "astype":
                if call.args and _is_uint32_dtype(call.args[0]):
                    trail.append(chain_hop(rel, call.lineno,
                                           ".astype(uint32)"))
                    return True
                return False
            if call.func.attr in self._PRESERVING_METHODS:
                return self._prove(project, fn, call.func.value, trail,
                                   depth + 1, visiting)
        if root in self._ARRAY_NS:
            dtype = next((kw.value for kw in call.keywords
                          if kw.arg == "dtype"), None)
            pos = self._CTOR_DTYPE_POS.get(leaf)
            if dtype is None and pos is not None and len(call.args) > pos:
                dtype = call.args[pos]
            if dtype is not None:
                if _is_uint32_dtype(dtype):
                    trail.append(chain_hop(rel, call.lineno,
                                           f"{d}(dtype=uint32)"))
                    return True
                return False
            if leaf in ("array", "asarray") and call.args:
                # no dtype: preserves the input's dtype
                return self._prove(project, fn, call.args[0], trail,
                                   depth + 1, visiting)
            if leaf in self._PRESERVING_FUNCS and call.args:
                return self._prove(project, fn, call.args[0], trail,
                                   depth + 1, visiting)
            return False
        # callgraph hop: a project function whose returns pin uint32
        callee_fid = sites.get(id(call))
        if callee_fid is not None:
            callee = project.calls.functions[callee_fid]
            if self._fn_pins(project, callee, None, depth + 1, visiting):
                trail.append(chain_hop(
                    project.graphs[callee.module].info.relpath,
                    callee.node.lineno, f"{callee.qual}() pins uint32"))
                return True
        return False

    def _prove_unpack(self, project, fn, value, idx, trail, depth, visiting):
        """`a, b = <value>` — prove element idx of the rhs."""
        if isinstance(value, (ast.Tuple, ast.List)):
            if idx < len(value.elts):
                return self._prove(project, fn, value.elts[idx], trail,
                                   depth, visiting)
            return False
        if isinstance(value, ast.Call):
            _, _, sites = self._ctx(project, fn)
            callee_fid = sites.get(id(value))
            if callee_fid is not None:
                callee = project.calls.functions[callee_fid]
                if self._fn_pins(project, callee, idx, depth, visiting):
                    trail.append(chain_hop(
                        project.graphs[callee.module].info.relpath,
                        callee.node.lineno,
                        f"{callee.qual}()[{idx}] pins uint32"))
                    return True
        return False

    def _fn_pins(self, project, fn: FuncNode, idx, depth, visiting) -> bool:
        """True when every return of fn is provably uint32 (element idx of
        tuple returns when idx is not None), regardless of its inputs."""
        key = (fn.fid, idx)
        if key in self._pins_memo:
            return self._pins_memo[key]
        vkey = ("pins", fn.fid, idx)
        if vkey in visiting or depth > self._MAX_DEPTH:
            return False
        visiting.add(vkey)
        returns = [r.value for r in _own_returns(fn.node)
                   if r.value is not None]
        ok = bool(returns)
        for r in returns:
            if idx is not None:
                if isinstance(r, (ast.Tuple, ast.List)) and idx < len(r.elts):
                    ok = ok and self._prove(project, fn, r.elts[idx],
                                            [], depth + 1, visiting)
                else:
                    ok = ok and self._prove_unpack(project, fn, r, idx,
                                                   [], depth + 1, visiting)
            else:
                ok = ok and self._prove(project, fn, r, [], depth + 1,
                                        visiting)
            if not ok:
                break
        visiting.discard(vkey)
        self._pins_memo[key] = ok
        return ok

    def _param_proven(self, project, fn: FuncNode, pname, trail, depth,
                      visiting) -> bool:
        """Reverse hop: every project call site of fn passes a provably
        uint32 value for parameter pname."""
        vkey = ("param", fn.fid, pname)
        if vkey in visiting or depth > self._MAX_DEPTH:
            return False
        visiting.add(vkey)
        try:
            a = fn.node.args
            pos_params = [x.arg for x in (a.posonlyargs + a.args)]
            pidx = pos_params.index(pname) if pname in pos_params else None
            callers = [(cfid, s) for cfid, ss in project.calls.calls.items()
                       for s in ss if s.callee == fn.fid]
            if not callers:
                return False
            for cfid, site in callers:
                caller = project.calls.functions[cfid]
                arg = next((kw.value for kw in site.node.keywords
                            if kw.arg == pname), None)
                if arg is None and pidx is not None \
                        and pidx < len(site.node.args):
                    arg = site.node.args[pidx]
                if arg is None:
                    # default value used
                    ndef = len(a.defaults)
                    di = pidx - (len(pos_params) - ndef) \
                        if pidx is not None else -1
                    if not (0 <= di < ndef and self._prove(
                            project, fn, a.defaults[di], [], depth + 1,
                            visiting)):
                        return False
                    continue
                if isinstance(arg, ast.Starred) or not self._prove(
                        project, caller, arg, [], depth + 1, visiting):
                    return False
            trail.append(chain_hop(
                project.graphs[fn.module].info.relpath, fn.node.lineno,
                f"{fn.qual}({pname}) uint32 at all "
                f"{len(callers)} call site(s)"))
            return True
        finally:
            visiting.discard(vkey)

    def _module_const_proven(self, project, module, name, trail, depth,
                             visiting) -> bool:
        """Module-level constant (possibly imported) provably uint32."""
        vkey = ("mod", module, name)
        if vkey in visiting or depth > self._MAX_DEPTH:
            return False
        visiting.add(vkey)
        try:
            def_mod, def_name, _hops = project.imports.resolve(module, name)
            mg = project.graphs.get(def_mod)
            if mg is None or not def_name:
                return False
            node = mg.info.env_derived.get(def_name)
            if node is not None:
                return False  # env-derived is never a provable dtype
            assigns = mg.info.module_assigns.get(def_name)
            if not assigns or len(assigns) != 1:
                return False
            ok = self._prove_module_expr(project, mg, assigns[0].value,
                                         trail, depth + 1, visiting)
            if ok:
                trail.append(chain_hop(mg.info.relpath, assigns[0].lineno,
                                       f"{def_name} pins uint32"))
            return ok
        finally:
            visiting.discard(vkey)

    def _prove_module_expr(self, project, mg, expr, trail, depth,
                           visiting) -> bool:
        """Prove a module-level expression: no params, no local assigns —
        reuse the ctor/preserving logic via a synthetic module-scope
        FuncNode whose body is empty."""
        shim = FuncNode(mg.dotted, "<module>",
                        ast.parse("def _m():\n    pass").body[0])
        return self._prove(project, shim, expr, trail, depth, visiting)


# ---------------------------------------------------------------------------
# Value-level dataflow rules (drynx_tpu/analysis/dataflow.py): both are
# thin wrappers over one shared engine run — dataflow_for() memoizes on a
# content-hash fingerprint of the whole project, so the abstract
# interpreter executes once per tree version no matter how many rules (or
# repeated analyze_project calls) consume it.

def _raw_to_finding(rule_id: str, project: ProjectInfo, raw) -> Finding:
    mod = project.modules.get(raw.file)
    return Finding(rule=rule_id, file=raw.file, line=raw.line,
                   message=raw.message,
                   line_text=mod.line_text(raw.line) if mod else "",
                   call_chain=raw.chain, anchors=raw.anchors)


@register
class CiphertextDtypeLaunder(ProjectRule):
    """A ciphertext limb array that was provably uint32 loses the dtype
    (``astype(float32)``, float-constant arithmetic, true division —
    often hidden inside a pytree flatten/transform/unflatten round trip)
    and then reaches a pallas/jit kernel or a serialization point. The
    kernels compute exact Montgomery limb arithmetic: one weak promotion
    silently corrupts carries and changes the proof transcript. The
    finding renders the whole value-flow chain (pin site, laundering hop,
    sink) and is suppressible at any hop; re-pinning with
    ``jnp.asarray(..., jnp.uint32)`` at the boundary clears the taint,
    and ``# drynx: declassify[dtype]`` marks deliberate byte-packing."""

    id = "ciphertext-dtype-launder"
    engine = "dataflow"
    summary = ("uint32 limb value reaches a pallas/jit kernel or "
               "serialization after a dtype-laundering hop (value "
               "dataflow)")

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        from .dataflow import dataflow_for
        df = dataflow_for(project, getattr(project, "focus", None))
        for raw in df.dtype_raw:
            if project.in_focus(raw.file):
                yield _raw_to_finding(self.id, project, raw)


@register
class SecretFlowToSink(ProjectRule):
    """The dataflow successor to the regex ``secret-logging`` rule:
    secrecy is seeded at *definition sites* — ``keygen()`` (ElGamal
    secret), ``secrets.randbelow()`` (Schnorr nonce), DP cleartext loads —
    and propagated per value through assignments, tuples, dataclass
    fields, f-strings and interprocedural summaries. It fires when a
    secret value reaches ``print``/``log.*``/TOML-or-serialized
    output/exception messages/transport ``send`` calls, with the full
    value-flow chain rendered. Where the regex rule flags the same line,
    this finding absorbs it (one leak, one report). Deliberate key-store
    writes are ``noqa``'d with a reason; protocol outputs that are public
    by construction are marked ``# drynx: declassify[secret]`` at the
    defining assignment."""

    id = "secret-flow-to-sink"
    engine = "dataflow"
    summary = ("secret value (keygen/nonce/DP cleartext) reaches a "
               "log/print/serialization/exception/send sink (value "
               "dataflow)")
    absorbs = ("secret-logging",)

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        from .dataflow import dataflow_for
        df = dataflow_for(project, getattr(project, "focus", None))
        for raw in df.secret_raw:
            if project.in_focus(raw.file):
                yield _raw_to_finding(self.id, project, raw)


# ---------------------------------------------------------------------------
# Concurrency rules (drynx_tpu/analysis/concurrency.py): three thin
# wrappers over one shared engine run — concurrency_for() memoizes on the
# same content-hash fingerprint as the dataflow engine, so thread-entry
# discovery, the interprocedural lock-set walk and the lock-order graph
# are computed once per tree version for all three rules (and for the
# DRYNX_LOCK_TRACE runtime cross-check).

@register
class UnguardedSharedMutation(ProjectRule):
    """A module global, class attribute or shared container is mutated
    from two concurrent contexts (thread targets, executor submissions,
    ``fan_out`` worker callables, timers — or one multi-instance entry
    racing with itself) and the lock sets provably held at the mutation
    sites share no common lock. That is the textbook data race: lost
    counter increments, torn dict updates, iteration-during-mutation.
    The finding names every mutating context and the locks each holds;
    it is suppressible at the mutation site *or* at the thread entry
    (dual anchors). Fix by guarding all mutating paths with one named
    lock (see ``resilience.policy.named_lock``)."""

    id = "unguarded-shared-mutation"
    engine = "concurrency"
    summary = ("shared state mutated from multiple thread contexts with "
               "no common lock held (interprocedural lock-set analysis)")

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        from .concurrency import concurrency_for
        cc = concurrency_for(project)
        for raw in cc.unguarded_raw:
            if project.in_focus(raw.file):
                yield _raw_to_finding(self.id, project, raw)


@register
class LockOrderInversion(ProjectRule):
    """Two locks are acquired in opposite nesting orders on different
    code paths — the classic ABBA deadlock: each thread holds one lock
    and blocks forever waiting for the other. The engine records every
    nested acquisition (``with`` or bare ``acquire()``) per thread entry,
    unions the edges into a lock-order graph over the stable diagnostic
    lock names, and reports each cycle once with the full acquisition
    chain rendered as a SARIF codeFlow (one threadFlow location per
    hop). Re-entering an ``RLock`` already held is not an edge. Fix by
    picking one global order (document it next to the named_lock defs)
    or collapsing to a single lock."""

    id = "lock-order-inversion"
    engine = "concurrency"
    summary = ("named locks acquired in conflicting order on different "
               "paths — ABBA deadlock cycle in the lock-order graph")

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        from .concurrency import concurrency_for
        cc = concurrency_for(project)
        for raw in cc.cycle_raw:
            if project.in_focus(raw.file):
                yield _raw_to_finding(self.id, project, raw)


@register
class BlockingCallUnderLock(ProjectRule):
    """A blocking operation — socket/frame I/O (``recv_msg``,
    ``send_frame``, ``sendall``...), ``time.sleep``, subprocess spawns,
    a bare ``join()`` — is reachable while a lock is held. Under load
    every thread contending on that lock serializes behind the wait:
    with the proof-device lock or a ConnPool lock this invisibly
    flattens the serving tier to one in-flight operation. The finding
    carries the interprocedural path from the thread entry to the call.
    Fix by moving the wait outside the critical section (snapshot under
    the lock, operate after release); where the serialization *is* the
    design — e.g. a per-connection lock serializing one socket
    conversation — suppress at the site with a reason."""

    id = "blocking-call-under-lock"
    engine = "concurrency"
    summary = ("socket/sleep/subprocess/join reachable while holding a "
               "lock — serializes every contending thread")

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        from .concurrency import concurrency_for
        cc = concurrency_for(project)
        for raw in cc.blocking_raw:
            if project.in_focus(raw.file):
                yield _raw_to_finding(self.id, project, raw)


# ---------------------------------------------------------------------------
# Determinism rules (drynx_tpu/analysis/determinism.py): two thin
# wrappers over one shared nondeterminism-taint run — determinism_for()
# memoizes on the same content-hash fingerprint, so the interprocedural
# source->sink walk is computed once per tree version for both rules
# (and for the DRYNX_DET_TRACE runtime cross-check).

@register
class NondetFlowToTranscript(ProjectRule):
    """A nondeterministic *value* — a wall-clock read, unseeded RNG
    draw, or object identity (``id()``/``hash()`` under hash
    randomization) — flows into a byte-identity sink: transcript
    serialization, a digest, a ProofDB/``pane:``/``ckpt:`` write, a
    skipchain append, a wire v2 frame encode, or an fsync'd journal
    line. Those surfaces back the repo's byte-identical-transcript
    equivalence claims, so any such flow makes two same-seed runs
    diverge. The finding carries the full source->sink chain as a
    SARIF codeFlow with dual anchors (suppressible at the source or
    the sink). Fix by deriving the value from survey inputs (seeded
    ``fold_in``); a *deliberate* nondeterministic surface — e.g. a
    block's wall-clock ``sample_time``, excluded from the transcript
    by design — is declared with ``# drynx: deterministic[reason]``
    at the source line."""

    id = "nondet-flow-to-transcript"
    engine = "determinism"
    summary = ("wall-clock/RNG/identity value flows into a "
               "byte-identity sink (transcript, digest, ProofDB, "
               "skipchain, wire encode, journal)")

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        from .determinism import determinism_for
        det = determinism_for(project, getattr(project, "focus", None))
        for raw in det.nondet_raw:
            if project.in_focus(raw.file):
                yield _raw_to_finding(self.id, project, raw)


@register
class UnorderedIterationAtSink(ProjectRule):
    """Bytes reach a byte-identity sink in a nondeterministic *order*:
    a value derived from an unsorted directory listing, a ``set``'s
    iteration order, or thread-completion order (``as_completed``) is
    written to a sink — or the sink call itself sits inside a loop
    over such an iterate, so the write sequence varies run to run even
    though each individual write is deterministic. Fix by sorting the
    iterate (``sorted(...)`` with a total key), canonicalizing
    (``canon_points``/``fold_cts``), or gathering into an
    index-addressed structure (the roster-order ``fan_out`` result
    list) before serializing."""

    id = "unordered-iteration-at-sink"
    engine = "determinism"
    summary = ("listing/set/thread-completion order reaches a "
               "byte-identity sink — write order varies run to run")

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        from .determinism import determinism_for
        det = determinism_for(project, getattr(project, "focus", None))
        for raw in det.unordered_raw:
            if project.in_focus(raw.file):
                yield _raw_to_finding(self.id, project, raw)

# ---------------------------------------------------------------------------
# Typestate rules (drynx_tpu/analysis/typestate.py): four thin wrappers
# over one shared resource-lifecycle run — typestate_for() memoizes on
# the same content-hash fingerprint, so the interprocedural automaton
# walk (instance tracking through parameters, returns, aliases, branch
# joins and try/finally edges) is computed once per tree version for
# all four protocols (and for the DRYNX_PROTO_TRACE runtime cross-check).

@register
class AtomicDurableWrite(ProjectRule):
    """A durable artifact (ledger/journal/checkpoint/bench/slab/.npz
    path) is written without the crash-consistent tmp-write -> fsync ->
    rename protocol: an in-place ``open(final, "w")``, a rename before
    the data hit the disk (no ``os.fsync`` between the last write and
    the publish), a write after the file was already published, or a
    tmp file that is flushed but never renamed into place. Any of these
    can leave a torn or missing artifact after a crash — the pool
    store's replay and the proof transcript both assume publishes are
    all-or-nothing. Append-mode opens of durable paths are only legal
    in modules that declare a replay routine (the journal idiom).
    Fix with the ``_atomic_write_npz`` shape; a deliberately relaxed
    write (scratch diagnostics) is declared with
    ``# drynx: protocol[reason]`` at the open or the violation site."""

    id = "atomic-durable-write"
    engine = "typestate"
    summary = ("durable-path write skips the tmp-write -> fsync -> "
               "rename crash-consistency protocol (typestate)")

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        from .typestate import typestate_for
        ts = typestate_for(project, getattr(project, "focus", None))
        for raw in ts.atomic_raw:
            if project.in_focus(raw.file):
                yield _raw_to_finding(self.id, project, raw)


@register
class SlabConsumptionOrder(ProjectRule):
    """A claimed pool slab (the ``os.rename`` claim-move that fences
    out concurrent consumers) is consumed out of order: read before its
    consumption was journaled in the fsync'd ledger, unlinked before it
    was read, or claimed and then leaked without the final unlink. The
    ledger append IS the commit point — a crash between claim and
    append must leave evidence for replay, so reading or deleting
    first reintroduces the double-spend/lost-slab windows the pool
    store's recovery protocol exists to close. The required order is
    claim-rename -> ledger append -> read -> unlink, machine-checked
    per instance across calls and exception edges."""

    id = "slab-consumption-order"
    engine = "typestate"
    summary = ("claimed slab read/unlinked before the fsync'd ledger "
               "append, or never unlinked (typestate)")

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        from .typestate import typestate_for
        ts = typestate_for(project, getattr(project, "focus", None))
        for raw in ts.slab_raw:
            if project.in_focus(raw.file):
                yield _raw_to_finding(self.id, project, raw)


@register
class ConnCheckoutDiscipline(ProjectRule):
    """A connection checked out of a ``ConnPool`` (or constructed
    directly) fails to reach exactly one terminal — ``put``/``discard``
    back to the pool or ``close`` — on some path, including exception
    edges: a return/raise that abandons the socket, or a conn that a
    transport failure (``CallTimeout``/``TransportError``/``OSError``
    handler) marked suspect being reused or returned to the pool as if
    healthy. Leaks starve the pool under load; returning a suspect
    conn poisons a later checkout with a dead socket. The walker
    tracks each instance through helper calls, aliases and
    try/finally, so release-in-a-helper and retry-loop idioms are
    recognized; the finding's codeFlow shows the path that leaks."""

    id = "conn-checkout-discipline"
    engine = "typestate"
    summary = ("pool conn misses put/discard/close on some path, or is "
               "reused after a transport failure (typestate)")

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        from .typestate import typestate_for
        ts = typestate_for(project, getattr(project, "focus", None))
        for raw in ts.conn_raw:
            if project.in_focus(raw.file):
                yield _raw_to_finding(self.id, project, raw)


@register
class SealCommitOnce(ProjectRule):
    """A streaming pane is sealed twice under one pane key, a pane's
    proof blob is committed twice, or a checkpoint loaded for resume is
    saved again without re-entering a phase (a blind save would
    overwrite the resume evidence — the ``phase_entries`` counters —
    with stale state). Seal and commit are at-most-once per instance
    per path: the VN verify cache and the epsilon ledger both key on
    the pane identity, so a double seal double-charges and a double
    commit forks the audit trail. The checkpoint clause enforces
    load -> enter -> save ordering per ``SurveyCheckpoint`` instance."""

    id = "seal-commit-once"
    engine = "typestate"
    summary = ("pane sealed/committed twice under one key, or a "
               "resumed checkpoint saved without re-entering a phase "
               "(typestate)")

    def run_project(self, project: ProjectInfo) -> Iterator[Finding]:
        from .typestate import typestate_for
        ts = typestate_for(project, getattr(project, "focus", None))
        for raw in ts.seal_raw:
            if project.in_focus(raw.file):
                yield _raw_to_finding(self.id, project, raw)

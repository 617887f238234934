"""The executable store (drynx_tpu/utils/exec_store.py), driven directly with
a temporary directory: on the CPU the four fused programs bypass it."""
import logging
import os
import pickle
import shutil
import sys
import threading
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from drynx_tpu.crypto import field, pallas_ops, pallas_pairing
from drynx_tpu.encoding import tiles as enc_tiles
from drynx_tpu.parallel import keyswitch as kswitch
from drynx_tpu.service import service as svc
from drynx_tpu.utils import exec_store as es
from drynx_tpu.utils.timers import PROCESS, ProcessTracer

# the fused survey programs and where each lives
FUSED = {"_fused_enc": svc, "_fused_agg": svc, "_ks_pass": kswitch,
         "_ks_finish": kswitch, "_fused_dec": svc}


@jax.jit
def _small(x, y):
    return x * y + 1, x.sum()


def _small_args():
    return (jnp.arange(6, dtype=jnp.uint32).reshape(2, 3),
            jnp.asarray(3, dtype=jnp.uint32))


def _agg_args():
    rng = np.random.default_rng(7)
    return (jnp.asarray(rng.integers(0, 1 << 16, (2, 2, 2, 3, 16),
                                     dtype=np.uint32)),)


CASES = {"small": (_small, _small_args),
         "fused_agg": (svc._fused_agg.jit, _agg_args)}


def _key(fn, args, **facts):
    return es.key_of(fn.__name__, es.avals_of(args),
                     dict(es.process_facts(), **facts))


def _stored(fn):
    return es.StoredProgram(fn, dict)


@pytest.fixture
def engage(monkeypatch, tmp_path):
    """`engage()`: a new process's store under `tmp_path`, engaged (on the
    CPU nothing engages it: `active` is stood in for)."""
    def fresh():
        store = es.ExecStore(str(tmp_path))
        monkeypatch.setattr(es, "active", lambda: store)
        return store
    return fresh


def _same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


@pytest.fixture(autouse=True, scope="module")
def no_persistent_cache():
    """On the CPU backend an executable that jax's persistent cache handed
    back does not survive being serialised AGAIN (its kernels are lost:
    "Function ... not found" when it runs), and the test tier keeps that
    cache on (conftest.py): off around these tests, whose subject is the
    store. What the TPU does there is PERF.md's to say (PR 27)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def tracer(monkeypatch):
    """A tracer of the test's own in `PROCESS`'s place."""
    fresh = ProcessTracer()
    monkeypatch.setattr(es, "PROCESS", fresh)
    return fresh


@pytest.mark.parametrize("case", sorted(CASES))
def test_round_trip_is_bit_identical(case, tmp_path, tracer, engage):
    fn, make = CASES[case]
    args = make()
    want = fn(*args)
    prog = _stored(fn)
    key = prog.key(args)
    cold = engage()
    assert _same(prog(*args), want) and _same(prog(*args), want)
    assert os.listdir(tmp_path) == [f"{fn.__name__}-{key}.exe"]
    warm = engage()
    assert warm.ready(key) is None
    assert _same(prog(*args), want) and _same(prog(*args), want)
    assert warm.ready(key) is not None is not cold.ready(key)
    assert warm.ready(key) is not cold.ready(key)
    assert tracer.counters() == {"exec_store_lookups": 2,
                                 "exec_store_hits": 1}
    # a loaded executable refuses arguments it was not built for: a wrong
    # key raises, it never computes something else
    with pytest.raises(TypeError):
        warm.ready(key)(*[jnp.concatenate([a, a]) if a.ndim else a
                          for a in args])


@pytest.mark.parametrize("what", [
    "program", "shape", "dtype", "weak_type", "tree", "source", "jax",
    "jaxlib", "platform_version", "device_kind", "x64", "XLA_FLAGS",
    "LIBTPU_INIT_ARGS", "devices"])
def test_the_key_changes_with(what):
    facts = dict(es.process_facts(), devices=[0])
    args = (jnp.zeros((2, 3), jnp.uint32), jnp.asarray(3, dtype=jnp.int32))
    base = es.key_of("p", es.avals_of(args), facts)
    assert base == es.key_of("p", es.avals_of(args), dict(facts))
    other_args = {
        "shape": (jnp.zeros((2, 4), jnp.uint32), args[1]),
        "dtype": (jnp.zeros((2, 3), jnp.int32), args[1]),
        "weak_type": (args[0], 3),
        "tree": ((args[0],), args[1])}
    if what == "program":
        other = es.key_of("q", es.avals_of(args), facts)
    elif what in other_args:
        assert jax.typeof(3).weak_type and not jax.typeof(args[1]).weak_type
        other = es.key_of("p", es.avals_of(other_args[what]), facts)
    else:
        assert what in facts
        changed = {"x64": not facts["x64"], "devices": [1]}.get(
            what, str(facts[what]) + "+")
        other = es.key_of("p", es.avals_of(args), dict(facts,
                                                       **{what: changed}))
    assert other != base


@pytest.mark.parametrize("name", [
    "DRYNX_NO_PALLAS", enc_tiles.ENV_TILE, "field.UNROLL",
    "pallas_ops.INTERPRET", "pallas_pairing.INTERPRET"])
def test_the_key_changes_with_what_the_trace_reads(name, monkeypatch):
    """Every variable and module global that `service._trace_reads` lists
    moves the key of the four programs."""
    args = _agg_args()
    before = svc._trace_reads()
    assert name in before and len(before) == 5
    if "." in name:
        module = {"field": field, "pallas_ops": pallas_ops,
                  "pallas_pairing": pallas_pairing}[name.split(".")[0]]
        monkeypatch.setattr(module, name.split(".")[1], not before[name])
    else:
        monkeypatch.setenv(name, "1" if before[name] != "1" else "0")
    after = svc._trace_reads()
    assert {k for k in before if before[k] != after[k]} == {name}
    assert _key(_small, args, **before) != _key(_small, args, **after)


def _tree(root, files):
    for rel, text in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    return root


@pytest.mark.parametrize("change,moves", [
    ("another checkout path", False), ("a file that is not .py", False),
    ("one line edited", True), ("a file renamed", True),
    ("a file added", True)])
def test_the_source_digest(change, moves, tmp_path):
    files = {"__init__.py": "", "a.py": "x = 1\n", "sub/b.py": "y = 2\n"}
    base = es.source_digest(_tree(str(tmp_path / "one" / "pkg"), files))
    other = dict(files)
    if change == "a file that is not .py":
        other["sub/notes.txt"] = "z"
    elif change == "one line edited":
        other["sub/b.py"] = "y = 3\n"
    elif change == "a file renamed":
        other["sub/c.py"] = other.pop("sub/b.py")
    elif change == "a file added":
        other["sub/c.py"] = ""
    got = es.source_digest(_tree(str(tmp_path / "two" / "deeper" / "pkg"),
                                 other))
    assert (got != base) is moves
    # and the package's own digest is what the process's facts hold
    assert es.process_facts()["source"] == es.source_digest()


@pytest.mark.parametrize("damage", ["truncated", "garbage", "empty"])
def test_a_bad_entry_is_counted_deleted_and_recompiled(damage, tmp_path,
                                                       tracer, caplog,
                                                       engage):
    args = _small_args()
    prog = _stored(_small)
    key = prog.key(args)
    path = engage().path("_small", key)
    prog(*args)
    with open(path, "rb") as f:
        good = f.read()
    with open(path, "wb") as f:
        f.write({"truncated": good[:len(good) // 2], "empty": b"",
                 "garbage": os.urandom(len(good))}[damage])
    seen = []
    real_unlink = os.unlink

    def unlink(p):
        seen.append(p)
        real_unlink(p)

    logger = logging.getLogger("drynx_tpu")
    logger.addHandler(caplog.handler)
    engage()
    try:
        os.unlink = unlink
        again = prog(*args)
    finally:
        os.unlink = real_unlink
        logger.removeHandler(caplog.handler)
    assert _same(again, _small(*args))
    assert seen == [path]
    assert tracer.counter("exec_store_load_failures") == 1
    assert tracer.counter("exec_store_lookups") == 2
    assert tracer.counter("exec_store_hits") == 0
    assert any(r.levelno == logging.WARNING and key in r.getMessage()
               for r in caplog.records)
    # written anew and whole: the next process loads it
    engage()
    assert _same(prog(*args), _small(*args))
    assert tracer.counter("exec_store_hits") == 1


@pytest.mark.parametrize("fault", ["none", "write fails", "fsync fails"])
def test_no_partial_file_under_the_final_name(fault, tmp_path, monkeypatch):
    path = str(tmp_path / "p-k.exe")
    blob = os.urandom(1 << 16)
    seen = []
    real_replace, real_fsync = os.replace, os.fsync

    def replace(src, dst):
        # at the rename the temporary file is whole and synced, and the
        # final name holds nothing yet
        with open(src, "rb") as f:
            seen.append((f.read() == blob, os.path.exists(dst), synced[:]))
        real_replace(src, dst)

    synced = []

    def fsync(fd):
        if fault == "fsync fails":
            raise OSError("disk full")
        synced.append(fd)
        real_fsync(fd)

    monkeypatch.setattr(es.os, "replace", replace)
    monkeypatch.setattr(es.os, "fsync", fsync)
    if fault == "write fails":
        with pytest.raises(TypeError):
            es._write_atomic(path, None)
    elif fault == "fsync fails":
        with pytest.raises(OSError):
            es._write_atomic(path, blob)
    else:
        es._write_atomic(path, blob)
    if fault == "none":
        assert seen == [(True, False, synced)] and len(synced) == 1
        with open(path, "rb") as f:
            assert f.read() == blob
    else:
        assert not seen and not os.path.exists(path)


class _Counting:
    """`_small`, counting its plain calls and its lowerings."""
    __name__ = "_small"

    def __init__(self, fail=False):
        self.calls, self.lowerings, self.fail = [], [], fail

    def __call__(self, *a):
        self.calls.append([f.name for f in traceback.extract_stack()])
        threading.Event().wait(0.05)    # the others arrive meanwhile
        if self.fail:
            raise ValueError("refused by the compiler")
        return _small(*a)

    def lower(self, *a):
        self.lowerings.append(threading.get_ident())
        return _small.lower(*a)


def test_eight_threads_asking_for_one_key_compile_once(tmp_path, tracer,
                                                       engage):
    args = _small_args()
    fn = _Counting()
    prog = _stored(fn)
    store = engage()
    got, errors = [], []
    gate = threading.Barrier(8)

    def ask():
        try:
            gate.wait(timeout=30)
            got.append(prog(*args))
        except BaseException as e:      # noqa: BLE001 - reported below
            errors.append(e)
            raise

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=ask) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    assert len(fn.calls) == 1 and len(fn.lowerings) == 1
    assert len(got) == 8 and all(_same(g, _small(*args)) for g in got)
    assert tracer.counters() == {"exec_store_lookups": 1}
    assert len(os.listdir(tmp_path)) == 1
    assert store.ready(prog.key(args)) is not None


def test_a_miss_is_a_plain_call_from_the_wrappers_own_frame(tmp_path, tracer,
                                                            engage):
    """The trace of a miss has one frame of the store above it, and what
    the compiler raises reaches the caller with nothing kept."""
    args = _small_args()
    fn = _Counting(fail=True)
    prog = _stored(fn)
    store = engage()
    with pytest.raises(ValueError, match="refused by the compiler"):
        prog(*args)
    assert os.listdir(tmp_path) == [] and store.ready(prog.key(args)) is None
    fn.fail = False
    assert _same(prog(*args), _small(*args))
    assert len(fn.calls) == 2 and tracer.counter("exec_store_lookups") == 2
    here = "test_a_miss_is_a_plain_call_from_the_wrappers_own_frame"
    for stack in fn.calls:
        assert stack[-3:] == [here, "__call__", "__call__"]
    # jit's own caches hand the call's executable back: one trace, and the
    # second look at the program neither lowers nor compiles anew
    counted = []

    def listen(event, seconds, **kw):
        if "_small" in str(kw.get("fun_name")):
            counted.append(event.rsplit("/", 1)[-1])

    engage()
    os.unlink(store.path("_small", prog.key(args)))
    wide = (jnp.ones((5, 3), jnp.uint32), args[1])
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        got = _stored(_small)(*wide)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert _same(got, _small(*wide))
    assert counted.count("jaxpr_trace_duration") <= 2   # the call; .lower
    assert counted.count("jaxpr_to_mlir_module_duration") == 1
    assert counted.count("backend_compile_duration") == 1


@pytest.mark.parametrize("codec", ["zstandard", "zlib"])
def test_entries_are_compressed(codec, tmp_path, monkeypatch, tracer,
                                engage):
    if codec == "zlib":
        monkeypatch.setattr(es, "zstandard", None)
    elif es.zstandard is None:
        pytest.skip("no zstandard here: the store uses zlib")
    blob = bytes(1 << 20) + os.urandom(1 << 10)
    packed = es._compress(blob)
    assert len(packed) < len(blob) // 50 and es._decompress(packed) == blob
    args = _small_args()
    prog = _stored(_small)
    path = engage().path("_small", prog.key(args))
    prog(*args)
    engage()
    assert _same(prog(*args), _small(*args))
    assert tracer.counter("exec_store_hits") == 1
    with open(path, "rb") as f:
        payload, in_tree, out_tree = pickle.loads(es._decompress(f.read()))
    assert isinstance(payload, bytes) and in_tree.num_leaves == 2


def test_counters_and_spans_land_on_the_process_tracer(tmp_path, engage,
                                                       monkeypatch):
    """The store writes to `timers.PROCESS`; here a tracer of the same class
    stands in for it, so that no other test finds counters it did not
    make."""
    assert es.PROCESS is PROCESS and isinstance(PROCESS, ProcessTracer)
    tr = ProcessTracer()
    monkeypatch.setattr(es, "PROCESS", tr)
    args = _small_args()
    prog = _stored(_small)
    engage()
    prog(*args)
    engage()
    prog(*args)
    assert tr.counters() == {"exec_store_lookups": 2, "exec_store_hits": 1}
    new = tr.records("setup/exec_store/")
    assert [r.name for r in new] == [
        "setup/exec_store/compile:_small", "setup/exec_store/save:_small",
        "setup/exec_store/load:_small"]
    assert all(r.cpu is not None and r.t1 >= r.t0 for r in new)
    line = next(ln for ln in tr.setup_report().splitlines()
                if ln.startswith("executable store:"))
    assert "1 of 2 look-ups hit, 0 bad entries" in line
    assert "load" in line and "compile" in line and "save" in line
    assert "executable store:" not in ProcessTracer().setup_report()


@pytest.mark.parametrize("name", FUSED)
def test_on_the_cpu_the_fused_names_are_the_plain_jits(name, tmp_path):
    """The test tier configures a persistent cache directory
    (tests/conftest.py), and still the store does not engage: no TPU."""
    prog = getattr(FUSED[name], name)
    assert isinstance(prog, es.StoredProgram) and es.active() is None
    assert prog.program == prog.__name__ == name
    assert prog.lower == prog.jit.lower
    assert hasattr(prog.jit, "lower") and prog.reads is svc._trace_reads
    if name == "_fused_agg":
        args = _agg_args()
        before = PROCESS.counters()
        assert _same(prog(*args), prog.jit(*args))
        assert prog.lower(*args).as_text()
        assert PROCESS.counters() == before
    root = jax.config.jax_compilation_cache_dir
    assert not root or not os.path.exists(os.path.join(root, es.SUBDIR))


@pytest.mark.parametrize("backend,cache_dir,engages", [
    ("tpu", True, True), ("tpu", False, False), ("cpu", True, False),
    ("gpu", True, False)])
def test_the_store_engages_by_what_it_observes(backend, cache_dir, engages,
                                               tmp_path, monkeypatch):
    """A persistent cache directory and a TPU: no option, variable or
    argument. With both (the backend's name faked), a stored program's
    call goes through the store under `<cache dir>/exec_store`."""
    old = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(es, "PROCESS", ProcessTracer())
    prog = es.StoredProgram(_small, lambda: {"SOME_VARIABLE": "0"})
    args = _small_args()
    try:
        jax.config.update("jax_compilation_cache_dir",
                          str(tmp_path) if cache_dir else None)
        store = es.active()
        assert (store is not None) is engages
        assert _same(prog(*args), _small(*args))
        if engages:
            assert store is es.active()
            assert store.directory == str(tmp_path / es.SUBDIR)
            assert _same(prog(*args), _small(*args))
            wide = (jnp.ones((4, 3), jnp.uint32), args[1])
            assert _same(prog(*wide), _small(*wide))
            names = sorted(os.listdir(store.directory))
            assert len(names) == 2 and all(
                n.startswith("_small-") and n.endswith(".exe") for n in names)
            assert es.PROCESS.counters() == {"exec_store_lookups": 2}
            # under a trace the arguments are not concrete: the plain jit
            outer = jax.jit(lambda x, y: prog(x, y))
            assert _same(outer(*args), _small(*args))
            assert es.PROCESS.counters() == {"exec_store_lookups": 2}
        else:
            assert os.listdir(tmp_path) == []
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
        shutil.rmtree(tmp_path / es.SUBDIR, ignore_errors=True)

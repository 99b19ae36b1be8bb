"""Multi-process runtime (DESIGN.md §13): wire-format roundtrips, remote
agent parity with the in-process agents across every registered alias,
worker-side quarantine propagation, and the dead-worker -> comm-repair ->
replay ladder.

Worker processes pay a full jax import (~5-10 s): the suite spawns three in
total — one module-scoped worker shared by the parity/quarantine tests and
one private worker for each destructive test."""
import socket
import struct
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # hypothesis is an optional extra
    def given(*a, **k):
        def deco(fn):
            return pytest.mark.skip(reason="hypothesis not installed")(fn)
        return deco

    def settings(*a, **k):
        return lambda fn: fn

    class _NoStrategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _NoStrategies()

from repro.core import RuntimeAgent, default_manifest
from repro.core.agents import (AgentState, HaloFuture, HealthConfig,
                               HealthMonitor)
from repro.core.registry import KernelRegistry
from repro.core.scheduler import _record_key
from repro.distributed.remote import (RemoteWorkerError, _WireCache,
                                      decode_payload, encode_payload,
                                      recv_frame, send_frame, spawn_worker)
from repro.kernels import register_all
from repro.kernels.spmm.ref import dense_to_bell, random_block_sparse

SETTINGS = dict(max_examples=15, deadline=None)


# ---------------------------------------------------------------------------
# Wire format
# ---------------------------------------------------------------------------
DTYPES = ["float32", "float64", "int32", "int8", "bool", "bfloat16"]


def _mk_array(dtype: str, shape):
    rng = np.random.RandomState(hash((dtype, tuple(shape))) % (2 ** 31))
    data = rng.uniform(-4, 4, size=shape)
    if dtype == "bfloat16":
        return jnp.asarray(data, dtype=jnp.bfloat16)
    return np.asarray(data).astype(dtype)


def _roundtrip(obj):
    header, bufs = encode_payload(obj)
    import json
    json.dumps(header)                    # header must be pure JSON
    return decode_payload(header, bufs)


def _assert_tree_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb, f"tree structure changed: {ta} vs {tb}"
    for x, y in zip(la, lb):
        if hasattr(x, "dtype") or hasattr(y, "dtype"):
            xa, ya = np.asarray(x), np.asarray(y)
            assert xa.dtype == ya.dtype, (xa.dtype, ya.dtype)
            assert xa.shape == ya.shape, (xa.shape, ya.shape)
            assert xa.tobytes() == ya.tobytes()   # bit-exact
        else:
            assert x == y


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(), (1,), (3, 5), (2, 3, 4)])
def test_payload_roundtrip_shapes_dtypes(dtype, shape):
    arr = _mk_array(dtype, shape)
    out = _roundtrip(arr)
    assert np.asarray(out).shape == tuple(shape)
    assert str(np.asarray(out).dtype) == dtype
    np.testing.assert_array_equal(np.asarray(out, dtype=np.float64),
                                  np.asarray(arr, dtype=np.float64))


def test_payload_roundtrip_nested_pytree():
    tree = {"a": (np.float32(1.5), None, "tag"),
            "b": [_mk_array("bfloat16", (2, 2)), {"k": 7, "f": 2.25}],
            "c": (), "d": {}, "flag": True}
    _assert_tree_equal(_roundtrip(tree), tree)


def test_payload_rejects_callables():
    with pytest.raises(TypeError, match="cannot serialize"):
        encode_payload({"fn": lambda: 1})


def test_payload_exception_marker():
    out = _roundtrip({"exc": ValueError("boom")})
    assert isinstance(out["exc"], Exception)
    assert "ValueError" in str(out["exc"]) and "boom" in str(out["exc"])


def _tree_strategy():
    # built lazily: the no-hypothesis stub cannot chain .flatmap/.map
    leaf = st.one_of(
        st.none(), st.booleans(), st.integers(-2**31, 2**31),
        st.text(max_size=8),
        st.floats(allow_nan=False, allow_infinity=False, width=32),
        st.sampled_from(DTYPES).flatmap(lambda d: st.sampled_from(
            [(), (1,), (4,), (2, 3)]).map(lambda s: _mk_array(d, s))))
    return st.recursive(
        leaf, lambda c: st.one_of(
            st.lists(c, max_size=3), st.tuples(c, c),
            st.dictionaries(st.text(max_size=4), c, max_size=3)),
        max_leaves=8)


@given(tree=st.deferred(_tree_strategy))
@settings(**SETTINGS)
def test_payload_roundtrip_property(tree):
    _assert_tree_equal(_roundtrip(tree), tree)


def test_frame_roundtrip_over_socket():
    a, b = socket.socketpair()
    try:
        msg = {"op": "exec", "uid": 3,
               "args": [_mk_array("float32", (4, 4)),
                        _mk_array("bfloat16", (2,))]}
        send_frame(a, msg)
        out = recv_frame(b.makefile("rb"))
        assert out["op"] == "exec" and out["uid"] == 3
        _assert_tree_equal(out["args"], msg["args"])
    finally:
        a.close()
        b.close()


def test_frame_eof_raises():
    a, b = socket.socketpair()
    rf = b.makefile("rb")
    a.close()
    with pytest.raises(EOFError):
        recv_frame(rf)
    b.close()


def test_frame_corrupt_length_rejected():
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack(">QI", 1 << 40, 4))
        with pytest.raises(Exception):
            recv_frame(b.makefile("rb"))
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# Content-addressed wire buffer cache
# ---------------------------------------------------------------------------
def _cached_roundtrip(cache, store, msg):
    hdr, bufs = encode_payload(msg, cache)
    cache.commit()
    return hdr, decode_payload(hdr, bufs, store)


def test_wire_cache_pins_once_then_refs():
    cache, store = _WireCache(), {}
    a = jnp.arange(64 * 64, dtype=jnp.float32).reshape(64, 64)  # 16 KiB
    h1, d1 = _cached_roundtrip(cache, store, {"args": (a,)})
    h2, d2 = _cached_roundtrip(cache, store, {"args": (a,)})
    m1, m2 = h1["__d__"][0][1]["__t__"][0], h2["__d__"][0][1]["__t__"][0]
    assert "put" in m1 and "__a__" in m1        # first send ships raw + pins
    assert "__aref__" in m2 and "__a__" not in m2   # later sends elide bytes
    assert d2["args"][0] is d1["args"][0]       # one shared pinned buffer
    assert not d1["args"][0].flags.writeable
    assert np.asarray(d1["args"][0]).tobytes() == np.asarray(a).tobytes()
    assert cache.stats()["bytes_saved"] == a.nbytes


def test_wire_cache_skips_mutable_and_small_arrays():
    cache, store = _WireCache(), {}
    big_np = np.ones((64, 64), np.float32)      # mutable: digest memo would
    small = jnp.ones(4, jnp.float32)            # not see in-place writes
    for _ in range(2):
        h, _ = _cached_roundtrip(cache, store, {"args": (big_np, small)})
        for mark in h["__d__"][0][1]["__t__"]:
            assert "__a__" in mark and "put" not in mark
    assert not store and cache.stats()["pinned_buffers"] == 0


def test_wire_cache_cap_ships_raw_instead_of_promising():
    cache, store = _WireCache(), {}
    cache.cap_bytes = 100                       # below any eligible array
    a = jnp.ones((64, 64), jnp.float32)
    for _ in range(2):
        h, d = _cached_roundtrip(cache, store, {"a": a})
        mark = h["__d__"][0][1]
        assert "__a__" in mark and "put" not in mark
        np.testing.assert_array_equal(d["a"], np.asarray(a))
    assert cache.stats()["pinned_bytes"] == 0


def test_wire_cache_unpinned_ref_rejected():
    with pytest.raises(RemoteWorkerError, match="unpinned"):
        decode_payload({"__aref__": "deadbeef", "s": [2], "d": "float32"},
                       [], {})


def test_spawned_worker_always_runs_jax_on_cpu(monkeypatch):
    """A worker never inherits an accelerator platform: the parent that
    touched JAX holds the chip, so a child asking for it fails or hangs."""
    from repro.distributed import remote
    seen = {}

    def popen(cmd, env):
        seen.update(env)
        raise OSError("not started")

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(remote.subprocess, "Popen", popen)
    with pytest.raises(OSError):
        spawn_worker("tw-env", devices=1, env={"JAX_PLATFORMS": "tpu"})
    assert seen["JAX_PLATFORMS"] == "cpu"


# ---------------------------------------------------------------------------
# Live worker fixtures
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def sess():
    registry = KernelRegistry()
    register_all(registry)
    s = RuntimeAgent(registry=registry, manifest=default_manifest())
    yield s
    s.finalize()


@pytest.fixture(scope="module")
def worker():
    w = spawn_worker("tw0", devices=2)
    yield w
    w.shutdown()


@pytest.fixture(scope="module")
def ragent(sess, worker):
    return worker.agent("xla").attach(sess)


def _pinned(sess, alias, platform):
    return sess.claim(alias, overrides={"allowed_platforms": [platform],
                                        "platform_preference": [platform]})


def _exec_on(sess, alias, platform, args, kwargs):
    cr = _pinned(sess, alias, platform)
    return sess.isend(tuple(args), cr, mailbox=False, **kwargs)


def _alias_payloads():
    """One representative (args, kwargs) per registered alias — shapes small
    enough for CI, large enough to exercise the real code paths."""
    k = jax.random.PRNGKey(11)
    ks = jax.random.split(k, 24)

    def a(i, shape, dtype=jnp.float32):
        return jax.random.normal(ks[i], shape, dtype=jnp.float32).astype(dtype)

    n = 16
    diag_dom = a(0, (n, n)) + n * jnp.eye(n)
    sparse = random_block_sparse(ks[1], 16, 16, 4, 4)
    values, indices = dense_to_bell(sparse, 4, 4)
    q, kk, v = a(2, (1, 2, 64, 16)), a(3, (1, 2, 64, 16)), a(4, (1, 2, 64, 16))
    B, S, H, P, G, N = 1, 128, 2, 4, 1, 8
    return {
        "MMM": ((a(5, (16, 12)), a(6, (12, 8))), {}),
        "EWMM": ((a(7, (8, 8)), a(8, (8, 8))), {}),
        "EWMD": ((a(9, (8, 8)), jnp.abs(a(10, (8, 8))) + 1.0), {}),
        "EWADD": ((a(11, (8, 8)), a(12, (8, 8))), {}),
        "EWSUB": ((a(13, (8, 8)), a(14, (8, 8))), {}),
        "MVM": ((a(15, (8, 8)), a(16, (8,))), {}),
        "VDP": ((a(17, (16,)), a(18, (16,))), {}),
        "JS": ((diag_dom, a(19, (n,)), a(20, (n,))), {}),
        "1DCONV": ((a(21, (32,)), a(22, (5,))), {}),
        "RMSNORM": ((a(23, (4, 16)), jnp.ones((16,))), {}),
        "FLASH_ATTN": ((q, kk, v), {}),
        "SMMM": ((values, indices, a(5, (16, 8))), {}),
        "SSD": ((a(6, (B, S, H, P)),
                 jax.nn.softplus(a(7, (B, S, H))) * 0.1,
                 -jnp.exp(a(8, (H,))), a(9, (B, S, G, N)) * 0.5,
                 a(10, (B, S, G, N)) * 0.5, a(11, (H,)) * 0.1), {}),
        "SSD_DECODE": ((jnp.zeros((B, H, P, N), jnp.float32),
                        a(12, (B, H, P)),
                        jax.nn.softplus(a(13, (B, H))) * 0.1,
                        -jnp.exp(a(14, (H,))), a(15, (B, G, N)) * 0.5,
                        a(16, (B, G, N)) * 0.5, a(17, (H,)) * 0.1), {}),
        "MOE_FFN": ((a(18, (2, 4, 8)), a(19, (2, 8, 16)),
                     a(20, (2, 8, 16)), a(21, (2, 16, 8))), {}),
        "GQA_DECODE": ((a(2, (1, 2, 4, 16)), kk, v), {}),
        "COPY": ((a(22, (8, 8)),), {}),
        "CONCAT": ((a(23, (4, 4)), a(5, (4, 4))), {}),
        "FFT": ((a(6, (4, 32)),), {}),
        "SORT": ((a(7, (33,)),), {}),
        "HIST": ((jax.nn.sigmoid(a(8, (200,))),), {}),
        "LM_GRAD": (_lm_grad_payload(), _STEP_KW),
        "ADAMW_STEP": (_adamw_payload(), dict(_STEP_KW, n_micro=2)),
    }


_STEP_KW = dict(arch="h2o-danube-1.8b", reduced=True)


def _lm_grad_payload():
    from repro.train.step_kernels import param_size, resolve_arch
    p = param_size(**_STEP_KW)
    v = resolve_arch(**_STEP_KW).vocab_size
    kp, kt = jax.random.split(jax.random.PRNGKey(12))
    toks = jax.random.randint(kt, (2, 16), 0, v)
    return (jax.random.normal(kp, (p,)) * 0.02, toks,
            jnp.roll(toks, -1, 1), jnp.ones((2, 16), jnp.float32))


def _adamw_payload():
    from repro.train.step_kernels import param_size
    p = param_size(**_STEP_KW)
    kg, kp = jax.random.split(jax.random.PRNGKey(13))
    return (jax.random.normal(kg, (p + 1,)) * 0.01,
            jax.random.normal(kp, (p,)) * 0.02,
            jnp.zeros(p, jnp.float32), jnp.zeros(p, jnp.float32),
            jnp.asarray(0, jnp.int32))


@pytest.mark.slow
def test_attach_clones_every_alias(sess, ragent):
    aliases = set(sess.registry.aliases())
    cloned = {r.alias for r in ragent._clones}
    # every alias with an xla record is republished under the remote id
    expected = {al for al in aliases
                if any(r.platform == "xla" for r in sess.registry.records(al))}
    assert cloned == expected
    for al in cloned:
        assert any(r.platform == ragent.platform
                   for r in sess.registry.records(al))
        # clones must never become the fail-safe
        fs = sess.registry.failsafe(al)
        assert fs is None or fs.platform == "jnp"


@pytest.mark.slow
def test_remote_parity_all_aliases(sess, worker, ragent):
    """Async parity: every registered alias dispatched to the remote member
    and the in-process xla agent concurrently returns bit-identical pytrees
    (the remote worker runs the same record fn on the same substrate)."""
    payloads = _alias_payloads()
    missing = set(sess.registry.aliases()) - set(payloads)
    assert not missing, f"add sample payloads for {sorted(missing)}"
    futures = []
    for alias, (args, kwargs) in payloads.items():
        f_local = _exec_on(sess, alias, "xla", args, kwargs)
        f_remote = _exec_on(sess, alias, ragent.platform, args, kwargs)
        futures.append((alias, f_local, f_remote))
    for alias, f_local, f_remote in futures:
        local = f_local.result(timeout=120)
        remote = f_remote.result(timeout=120)
        _assert_tree_equal(remote, local)
    # nothing got quarantined along the way (i.e. parity came from the
    # remote substrate, not from a silent fail-safe fallback)
    assert not sess.scheduler.failed_record_keys()


@pytest.mark.slow
def test_wire_cache_elides_repeated_operands(sess, worker, ragent):
    """Dispatching the same immutable matrix twice ships its bytes once:
    the second exec travels as a digest ref, end to end through a live
    worker, and still returns the bit-identical result."""
    a = jnp.arange(48 * 48, dtype=jnp.float32).reshape(48, 48)  # 9 KiB
    x = jnp.ones((48,), jnp.float32)
    first = _exec_on(sess, "MVM", ragent.platform, (a, x), {}).result(
        timeout=120)
    saved0 = worker.client.wire_stats()["bytes_saved"]
    second = _exec_on(sess, "MVM", ragent.platform, (a, x), {}).result(
        timeout=120)
    _assert_tree_equal(second, first)
    stats = worker.client.wire_stats()
    assert stats["bytes_saved"] - saved0 >= a.nbytes
    assert stats["pinned_bytes"] >= a.nbytes
    assert worker.heartbeat()["pins"] == stats["pinned_buffers"]


@pytest.mark.slow
def test_worker_heartbeat_op(worker):
    hb = worker.heartbeat()
    assert hb["name"] == worker.name
    assert hb["devices"] == 2
    assert "xla" in hb["platforms"]


@pytest.mark.slow
def test_worker_quarantine_propagates_to_host(sess, worker, ragent):
    """A record that only fails *inside* the worker: the worker's ladder
    falls back (result still correct) and the host mirrors the quarantine
    under the remote member's record key."""
    worker.chaos(platform="xla", mode="raise", aliases=["EWADD"], times=1)
    try:
        args, kwargs = _alias_payloads()["EWADD"]
        remote = _exec_on(sess, "EWADD", ragent.platform,
                          args, kwargs).result(timeout=120)
        local = _exec_on(sess, "EWADD", "xla", args, kwargs).result(timeout=120)
        _assert_tree_equal(remote, local)
        failed = sess.scheduler.failed_record_keys()
        clone = next(r for r in sess.registry.records("EWADD")
                     if r.platform == ragent.platform)
        assert _record_key(clone) in failed
        # the *local* xla record is untouched: quarantine is per-member
        local_rec = next(r for r in sess.registry.records("EWADD")
                         if r.platform == "xla")
        assert _record_key(local_rec) not in failed
    finally:
        worker.release()
        sess.scheduler.clear_failures()
        ragent._applied_quarantine.clear()


# ---------------------------------------------------------------------------
# Failure semantics (destructive: private workers)
# ---------------------------------------------------------------------------
def _jacobi_reference(sess, a, b, d, iters):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "examples"))
    from collective_jacobi import collective_jacobi
    comm = sess.comm_split(["xla", "jnp"])
    try:
        return collective_jacobi(comm, a, b, d, iters=iters)
    finally:
        comm.free()


@pytest.mark.slow
def test_dead_worker_mid_jacobi_replays_bit_identical():
    """FaultPlan wedges the worker's substrate mid-collective; killing the
    process then drives transport EOF -> handle_dead_agent -> mark_dead
    (clones deregistered, queue collected) -> comm re-bind -> replay on the
    survivors — and the result stays bit-identical to the fault-free run."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "examples"))
    from collective_jacobi import _problem, collective_jacobi

    registry = KernelRegistry()
    register_all(registry)
    sess = RuntimeAgent(registry=registry, manifest=default_manifest())
    w = spawn_worker("tw-kill", devices=2)
    try:
        a, b, d = _problem(48)
        x_ref, _ = _jacobi_reference(sess, a, b, d, iters=3)

        agent = w.agent("xla").attach(sess)
        # wedge the worker's 2nd MVM (the per-iteration sweep kernel): the
        # collective cannot finish until the killer fires, so the death
        # path is exercised deterministically, not raced
        w.chaos(platform="xla", mode="die", aliases=["MVM"], nth=2)
        fired = threading.Event()

        def killer():
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if agent.heartbeat()[1] and w.client.pending_count() > 0:
                    time.sleep(0.3)       # let the request wedge in flight
                    break
                time.sleep(0.01)
            w.kill()
            fired.set()

        comm = sess.comm_split(["xla", agent.platform])
        t = threading.Thread(target=killer, daemon=True)
        t.start()
        x_mix, _ = collective_jacobi(comm, a, b, d, iters=3)
        t.join(timeout=30)
        comm.free()
        assert fired.is_set()
        assert agent.dead and w.dead
        assert agent._clones == []        # clones left the registry
        assert not any(r.platform == agent.platform
                       for r in sess.registry.records("JS"))
        np.testing.assert_array_equal(np.asarray(x_ref), np.asarray(x_mix))
    finally:
        w.kill()
        sess.finalize()


@pytest.mark.slow
def test_dead_worker_heartbeat_classifies_dead():
    """The monitor path (DESIGN.md §11): a busy remote agent whose process
    died reports an infinitely stale heartbeat, so a single sweep marks it
    DEAD regardless of the configured timeout."""
    w = spawn_worker("tw-hb", devices=1, platforms=("jnp",))
    agent = w.agent("jnp")                # deliberately unattached
    gate = threading.Event()
    fut = HaloFuture()
    agent.submit(lambda: gate.wait(60), future=fut)
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not agent.heartbeat()[1]:
            time.sleep(0.01)
        assert agent.heartbeat()[1]       # busy
        w.kill()
        w.proc.wait(timeout=30)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not w.dead:
            time.sleep(0.01)
        beats, busy, last = agent.heartbeat()
        assert busy and last == float("-inf")
        mon = HealthMonitor(HealthConfig(heartbeat_timeout=30.0))
        mon.register(agent)
        mon.check(now=time.monotonic())
        assert mon.state(agent) == AgentState.DEAD
    finally:
        gate.set()
        agent.shutdown(cancel_pending=True, wait=True)
        w.kill()


def test_request_to_dead_worker_raises():
    """Transport-level: a client whose process is gone refuses new
    requests with RemoteWorkerError (no silent hangs)."""
    a, b = socket.socketpair()
    from repro.distributed.remote import WorkerClient
    client = WorkerClient(a, name="dead")
    b.close()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and not client.dead:
        time.sleep(0.01)
    assert client.dead
    with pytest.raises(RemoteWorkerError):
        client.request("ping")

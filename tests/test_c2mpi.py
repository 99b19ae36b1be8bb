"""C2MPI semantics: claim/send/recv, tags, pipelines, buffers, fail-safe,
selection, manifest, plug-and-play (paper §IV–V)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (KernelAttributes, KernelRecord, KernelRegistry,
                        Manifest, RuntimeAgent, VirtualizationAgent,
                        default_manifest)
from repro.core.compute_object import (BufferHandle, ComputeObject,
                                       as_compute_object)
from repro.kernels import register_all
from repro.testing.faults import FaultyAgent


@pytest.fixture()
def agent():
    registry = KernelRegistry()
    register_all(registry)
    return RuntimeAgent(registry=registry, manifest=default_manifest())


def test_claim_send_recv_roundtrip(agent, rng):
    a = jax.random.normal(rng, (32, 32))
    b = jax.random.normal(rng, (32, 32))
    cr = agent.claim("MMM")
    agent.send((a, b), cr)
    out = agent.recv(cr)
    np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-4)


def test_tag_fifo_out_of_order(agent, rng):
    """Repeated recv with the same tag is FIFO; tags are independent."""
    a = jnp.eye(4)
    cr = agent.claim("MMM")
    agent.send((a * 1, a), cr, tag=7)
    agent.send((a * 2, a), cr, tag=7)
    agent.send((a * 3, a), cr, tag=9)
    np.testing.assert_allclose(agent.recv(cr, tag=9), 3 * a)
    np.testing.assert_allclose(agent.recv(cr, tag=7), 1 * a)  # FIFO
    np.testing.assert_allclose(agent.recv(cr, tag=7), 2 * a)


def test_recv_empty_mailbox_raises(agent):
    cr = agent.claim("MMM")
    with pytest.raises(RuntimeError, match="empty mailbox"):
        agent.recv(cr)


def test_send_fwd_routes_to_dest(agent, rng):
    """MPIX_SendFwd delivers the result to another CR's mailbox."""
    a = jax.random.normal(rng, (16, 16))
    src = agent.claim("MMM")
    dst = agent.claim("EWMM")
    agent.send_fwd((a, a), src, dst, tag=3)
    out = agent.recv(dst, tag=3)
    np.testing.assert_allclose(out, a @ a, rtol=1e-4, atol=1e-4)


def test_pipeline_cr(agent, rng):
    """A pipeline CR chains kernels without host round-trips (§IV-C)."""
    a = jnp.abs(jax.random.normal(rng, (16, 16))) + 1.0
    cr = agent.claim(["EWMM", "EWMD"])   # (a*a) then (a*a)/(a*a)? needs 2 args
    # EWMM(a, a) -> one output; EWMD needs two args — use (out, out) style
    # kernels take the tuple; EWMD(out) is invalid, so pipeline with MMM:
    agent.free(cr)
    cr = agent.claim(["MMM"])
    agent.send((a, a), cr)
    np.testing.assert_allclose(agent.recv(cr), a @ a, rtol=1e-4, atol=1e-4)


def test_failsafe_callback(agent, rng):
    called = {}

    def failsafe(*args):
        called["yes"] = True
        return jnp.zeros((2, 2))

    cr = agent.claim("NO_SUCH_KERNEL", failsafe=failsafe)
    agent.send((jnp.ones((2, 2)),), cr)
    out = agent.recv(cr)
    assert called.get("yes")
    np.testing.assert_allclose(out, 0.0)


def test_failsafe_registry_fallback(agent, rng):
    """No feasible candidate → registry fail-safe record (the jnp oracle)."""
    a = jax.random.normal(rng, (8, 8))
    rec = agent.registry.select("MMM", a, a, allowed_platforms=["jnp"])
    assert rec.platform == "jnp" and rec.is_failsafe


def test_execution_failure_falls_back_to_failsafe_record(agent, rng):
    """An agent raising in _device_execute re-places the request onto the
    registry fail-safe record: host code still gets the right answer."""
    faulty = FaultyAgent(platform="xla", mode="raise")
    agent.attach_agent(faulty)            # replaces the real xla agent
    a = jax.random.normal(rng, (16, 16))
    cr = agent.claim("MMM", overrides={
        "allowed_platforms": ["xla", "jnp"],
        "platform_preference": ["xla", "jnp"]})
    agent.send((a, a), cr)                # must not raise
    out = agent.recv(cr)
    np.testing.assert_allclose(out, a @ a, rtol=1e-4, atol=1e-4)
    assert faulty.failures == 1


def test_execution_failure_quarantines_record_in_scheduler(agent, rng):
    """After one failure the scheduler stops selecting the failing record:
    later sends never touch the faulty substrate again."""
    faulty = FaultyAgent(platform="xla", mode="raise")
    agent.attach_agent(faulty)
    a = jax.random.normal(rng, (16, 16))
    overrides = {"allowed_platforms": ["xla", "jnp"],
                 "platform_preference": ["xla", "jnp"]}
    cr = agent.claim("MMM", overrides=overrides)
    for _ in range(4):
        agent.send((a, a), cr)
        agent.recv(cr)
    assert faulty.failures == 1           # only the first send tried xla
    xla_rec = next(r for r in agent.registry.records("MMM")
                   if r.platform == "xla")
    assert agent.scheduler.is_failed(xla_rec)
    # a *fresh* CR also skips the quarantined record immediately
    cr2 = agent.claim("MMM", overrides=overrides)
    agent.send((a, a), cr2)
    agent.recv(cr2)
    assert faulty.failures == 1


def test_execution_failure_error_surfaces_sync_and_async(agent):
    """When no fallback exists (the fail-safe itself fails), the original
    error surfaces through both the blocking send and the future path."""
    def boom(x):
        raise ValueError("kernel exploded")

    agent.registry.register(KernelRecord(alias="BOOM", fn=boom,
                                         platform="jnp", is_failsafe=True))
    cr = agent.claim("BOOM")
    with pytest.raises(ValueError, match="kernel exploded"):
        agent.send((jnp.ones(2),), cr)    # sync path
    fut = agent.isend((jnp.ones(2),), agent.claim("BOOM"))
    with pytest.raises(ValueError, match="kernel exploded"):
        fut.result(timeout=30)            # future path
    assert isinstance(fut.exception(), ValueError)


def test_execution_failure_engages_claim_callback_last(agent, rng):
    """Claim-level fail-safe callback engages only after every registered
    record (including the registry fail-safe) failed."""
    faulty = FaultyAgent(platform="xla", mode="raise")
    agent.attach_agent(faulty)

    def bad_ref(x):
        raise RuntimeError("oracle also down")

    reg = KernelRegistry()
    reg.register(KernelRecord(alias="K", fn=bad_ref, platform="xla",
                              priority=10))
    reg.register(KernelRecord(alias="K", fn=bad_ref, platform="jnp",
                              is_failsafe=True))
    agent2 = RuntimeAgent(registry=reg, manifest=default_manifest(),
                          agents=[faulty, VirtualizationAgent()])
    called = {}

    def cb(*args):
        called["yes"] = True
        return jnp.zeros(2)

    cr = agent2.claim("K", failsafe=cb)
    agent2.send((jnp.ones(2),), cr)
    np.testing.assert_allclose(agent2.recv(cr), 0.0)
    assert called.get("yes")
    agent2.finalize()


def test_selection_prefers_optimized(agent, rng):
    a = jax.random.normal(rng, (8, 8))
    rec = agent.registry.select("MMM", a, a,
                                allowed_platforms=["jnp", "xla", "pallas"],
                                platform_preference=["pallas", "xla", "jnp"])
    assert rec.platform == "pallas"   # small arrays: pallas feasible off-TPU


def test_selection_respects_supports_predicate(agent):
    """Oversized arrays off-TPU are infeasible for the pallas substrate."""
    big = jax.ShapeDtypeStruct((4096, 4096), jnp.float32)
    rec = agent.registry.select("MMM", big, big,
                                allowed_platforms=["jnp", "xla", "pallas"],
                                platform_preference=["pallas", "xla", "jnp"])
    assert rec.platform == "xla"


def test_smmm_pallas_record_takes_int_indices(agent, rng):
    from repro.kernels.spmm import dense_to_bell, random_block_sparse
    vals, idx = dense_to_bell(random_block_sparse(rng, 128, 128, 32, 128),
                              32, 128)
    b = jnp.ones((128, 16))
    cands = agent.registry.candidates("SMMM", vals, idx, b,
                                      allowed_platforms=["pallas"])
    assert [r.platform for r in cands] == ["pallas"]


@pytest.mark.parametrize("shape,pallas", [((1,), True), ((2, 2), False)])
def test_pallas_records_step_aside_under_multi_device_mesh(agent, shape,
                                                          pallas, caplog):
    """Mosaic kernels cannot be partitioned automatically: model code
    tracing under a multi-device mesh gets the xla records, and the
    runtime says so once."""
    from repro.distributed.sharding import mesh_context
    a = jnp.ones((16, 16))
    axes = ("data", "model")[:len(shape)]
    with mesh_context(jax.sharding.AbstractMesh(shape, axes)):
        for _ in range(2):
            allowed = agent._allowed_platforms()
        plats = {r.platform for r in agent.registry.candidates(
            "MMM", a, a, allowed_platforms=allowed)}
    assert plats == ({"pallas", "xla", "jnp"} if pallas else {"xla", "jnp"})
    assert "pallas" in agent._allowed_platforms()
    notes = [r for r in caplog.records if "step aside" in r.getMessage()]
    assert len(notes) == (0 if pallas else 1)


def test_sw_fid_lookup(agent, rng):
    """Resources resolve by sw_fid as well as alias (Table I/II)."""
    a = jax.random.normal(rng, (8, 8))
    rec = agent.registry.select("fid:mmm", a, a)
    assert rec.alias == "MMM"


def test_attribute_matching():
    attrs = KernelAttributes(vid="google", pid="tpu-v5e")
    assert attrs.matches(KernelAttributes(vid="google", pid="*"))
    assert not attrs.matches(KernelAttributes(vid="nvidia"))


def test_round_robin_among_ties():
    reg = KernelRegistry()
    seen = []
    for i in range(2):
        reg.register(KernelRecord(alias="X", fn=lambda i=i: i, platform="jnp",
                                  priority=5))
    picks = {reg.select("X").fn() for _ in range(4)}
    assert picks == {0, 1}      # round-robin cycles both replicas


def test_plug_and_play_register_deregister(agent, rng):
    class NewAgent(VirtualizationAgent):
        platform = "npu"

    agent.attach_agent(NewAgent())
    agent.registry.register(KernelRecord(
        alias="MMM", fn=lambda a, b: jnp.zeros((a.shape[0], b.shape[1])),
        platform="npu", priority=99))
    a = jnp.ones((4, 4))
    cr = agent.claim("MMM", overrides={
        "allowed_platforms": ["npu", "jnp"],
        "platform_preference": ["npu", "jnp"]})
    agent.send((a, a), cr)
    np.testing.assert_allclose(agent.recv(cr), 0.0)
    # disconnecting the platform must not affect host code (fail-safe path)
    agent.detach_agent("npu")
    agent.registry.deregister("MMM", "npu")
    cr2 = agent.claim("MMM")
    agent.send((a, a), cr2)
    np.testing.assert_allclose(agent.recv(cr2), a @ a)


def test_sharded_agent_executes_under_its_mesh(agent, rng):
    """A record placed on the sharded substrate runs inside the agent's
    mesh (jax.set_mesh) and is not quarantined."""
    from repro.launch.mesh import make_mesh
    agent.attach_mesh(make_mesh((1,), ("data",)))
    seen = []

    def add_under_mesh(a, b):
        seen.append(jax.sharding.get_abstract_mesh().axis_names)
        return a + b

    agent.registry.register(KernelRecord(
        alias="EWADD", fn=add_under_mesh, platform="sharded", priority=99))
    a = jax.random.normal(rng, (8, 8))
    cr = agent.claim("EWADD", overrides={"allowed_platforms": ["sharded"]})
    agent.send((a, a), cr)
    np.testing.assert_allclose(agent.recv(cr), 2 * a)
    assert seen == [("data",)]
    assert agent.scheduler.failed_record_keys() == []


def test_internal_buffers_stateful(agent):
    """MPIX_CreateBuffer turns a CR stateful; state persists across sends."""
    reg = agent.registry

    def accum(x, state):
        new = state["acc"] + x
        return new, {"acc": new}

    reg.register(KernelRecord(alias="ACCUM", fn=accum, platform="jnp",
                              is_failsafe=True))
    cr = agent.claim("ACCUM")
    agent.create_buffer(cr, (2,), jnp.float32, name="acc")
    agent.send((jnp.ones(2),), cr)
    agent.recv(cr)
    agent.send((jnp.ones(2),), cr)
    out = agent.recv(cr)
    np.testing.assert_allclose(out, 2.0)


def test_free_and_finalize(agent):
    cr = agent.claim("MMM")
    h = agent.create_buffer(cr, (2, 2), jnp.float32)
    agent.free(cr)
    assert cr.freed
    with pytest.raises(RuntimeError):
        agent.send((jnp.eye(2), jnp.eye(2)), cr)
    agent.finalize()
    with pytest.raises(RuntimeError):
        agent.claim("MMM")


def test_manifest_roundtrip(tmp_path):
    m = default_manifest()
    p = tmp_path / "manifest.json"
    m.to_json(p)
    m2 = Manifest.from_json(p)
    assert m2.func("MMM").sw_fid == "fid:mmm"
    assert m2.total_slots() == 512
    assert m2.platform_preference()[0] == "sharded"


def test_compute_object_pytree(rng):
    co = ComputeObject(inputs={"a": jnp.ones(3), "b": jnp.zeros(2)},
                       meta={"k": 1}, tag=5)
    leaves, tdef = jax.tree.flatten(co)
    co2 = jax.tree.unflatten(tdef, leaves)
    assert co2.tag == 5 and co2.meta == {"k": 1}
    assert not co.stateful
    co3 = co.with_buffer("s", BufferHandle.allocate((2,), jnp.float32))
    assert co3.stateful


def test_single_input_optimization():
    co = as_compute_object(jnp.ones(3))
    assert list(co.inputs) == ["arg000"]
    co = as_compute_object((jnp.ones(3), jnp.zeros(2)))
    assert sorted(co.inputs) == ["arg000", "arg001"]


def test_t1_overhead_instrumentation(agent, rng):
    a = jax.random.normal(rng, (16, 16))
    cr = agent.claim("MMM")
    agent.reset_t1()
    for _ in range(5):
        agent.send((a, a), cr)
        agent.recv(cr)
    assert agent.t1_seconds_per_call < 0.01   # dispatch path is cheap
    assert agent._t1_calls == 5

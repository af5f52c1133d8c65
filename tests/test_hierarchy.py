"""The two-tier hierarchy: sub-masters over slave groups.

``HierarchicalCluster`` puts a batch-axis root over whole groups, each
group a full ``HeteroCluster`` behind a sub-master that speaks the
ordinary slave wire upward.  These tests pin the composition end to
end: group-aggregate Eq. 1 capacity math (rates sum, bandwidth
bottleneck folds), topology parsing, the SharedNIC master-ingress
emulation, two-tier numerics against the single-device VJP on inproc
AND tcp roots, degenerate topologies (one group, one-device groups,
zero-row groups) planning without division hazards, elasticity at both
tiers (``admit_group``/``evict`` at the root, ``admit``/``evict``
inside a group with ``refresh_capacity`` re-pricing), and the composed
failure domains — a SIGKILLed LEAF recovered entirely inside its group
(invisible to the root), a SIGKILLed SUB-MASTER recovered at the root
as one dead batch member, both VJP-exact for the survivors.
"""
import time

import numpy as np
import pytest

from repro.core.cluster import plans
from repro.core.cluster.hierarchy import (
    GroupSpec,
    HierarchicalCluster,
    group_hello_meta,
    parse_groups,
)
from repro.core.cluster.transport import SharedNIC


def _data(batch, seed=7):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(batch, 8, 8, 3)).astype(np.float32)
    w1 = rng.normal(size=(3, 3, 3, 6)).astype(np.float32)
    w2 = rng.normal(size=(3, 3, 6, 9)).astype(np.float32)
    g = rng.normal(size=(batch, 8, 8, 9)).astype(np.float32)
    return x, w1, w2, g


def _single_device_grads(x, w1, w2, g):
    import jax
    import jax.numpy as jnp

    def f(x_, w1_, w2_):
        y = jax.nn.relu(jax.lax.conv_general_dilated(
            x_, w1_, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ))
        y2 = jax.lax.conv_general_dilated(
            y, w2_, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )
        return jnp.sum(y2 * g)

    return tuple(
        np.asarray(a)
        for a in jax.grad(f, argnums=(0, 1, 2))(
            jnp.asarray(x), jnp.asarray(w1), jnp.asarray(w2)
        )
    )


def _train_chain(c, x, w1, w2, g):
    def between(y):
        mask = (y > 0).astype(np.float32)
        return np.maximum(y, 0.0), lambda gz: gz * mask

    slices = c.microbatch_slices(x.shape[0])

    def head(z, i):
        return None, g[slices[i]]

    return c.conv_train_chain(x, [w1, w2], [between, None], head)


def _assert_grads(res, want, atol=1e-3):
    dx_want, dw1_want, dw2_want = want
    np.testing.assert_allclose(res.dx, dx_want, rtol=1e-4, atol=atol)
    np.testing.assert_allclose(res.dw[0], dw1_want, rtol=1e-4, atol=atol)
    np.testing.assert_allclose(res.dw[1], dw2_want, rtol=1e-4, atol=atol)


# ---------------------------------------------------------------- units


def test_group_aggregate_time_harmonic():
    # rates SUM: two devices at 2s each == one device at 1s
    assert plans.group_aggregate_time([2.0, 2.0]) == pytest.approx(1.0)
    # a fast member dominates but never hurts
    agg = plans.group_aggregate_time([1.0, 10.0])
    assert agg < 1.0
    assert agg == pytest.approx(1.0 / (1.0 + 0.1))
    # singleton: aggregate is the member
    assert plans.group_aggregate_time([3.0]) == pytest.approx(3.0)


def test_group_aggregate_time_rejects_bad_input():
    with pytest.raises(ValueError):
        plans.group_aggregate_time([])
    with pytest.raises(ValueError):
        plans.group_aggregate_time([1.0, 0.0])
    with pytest.raises(ValueError):
        plans.group_aggregate_time([-1.0])


def test_group_capacity_bandwidth_bottleneck():
    t, bw = plans.group_capacity([2.0, 2.0], [100.0, 50.0, None])
    assert t == pytest.approx(1.0)
    assert bw == 50.0
    _, bw_none = plans.group_capacity([1.0], [None, None])
    assert bw_none is None


def test_parse_groups():
    specs = parse_groups("2x3")
    assert [s.size for s in specs] == [3, 3]
    assert all(s.slowdowns == [1.0, 1.0, 1.0] for s in specs)
    # explicit per-device values chunk M per group, in order
    specs = parse_groups("2x2", slowdowns=[1.0, 2.0, 3.0, 4.0],
                         backends=["numpy", "sim", "numpy", "sim"])
    assert specs[0].slowdowns == [1.0, 2.0]
    assert specs[1].slowdowns == [3.0, 4.0]
    assert specs[1].backends == ["numpy", "sim"]
    for bad in ("2", "0x3", "2x0", "axb", "2x3x4"):
        with pytest.raises(ValueError):
            parse_groups(bad)
    with pytest.raises(ValueError):
        parse_groups("2x3", slowdowns=[1.0])  # needs 6


def test_shared_nic_serializes_per_direction():
    nic = SharedNIC(bandwidth_mbps=8.0)  # 1e6 bytes/s
    t0 = time.perf_counter()
    a = nic.reserve("down", 100_000)  # 0.1s transit
    b = nic.reserve("down", 100_000)  # queued behind a
    # same direction serializes: b's window starts where a's ends
    assert b >= a + 0.099
    # directions are independent ports: up is not queued behind down
    c = nic.reserve("up", 100_000)
    assert c < b
    assert a >= t0  # windows are in the future, not the past
    with pytest.raises(ValueError):
        SharedNIC(0.0)


# ----------------------------------------------------- two-tier numerics


def test_hierarchy_inproc_matches_single_device():
    """ISSUE acceptance: a 2x3 two-tier cluster trains with gradients
    matching single-device jax — the root's sum of per-group full dW
    over disjoint rows is the exact all-reduce, one tier up from PR 9.
    Second step rides the WeightRef token path at BOTH tiers."""
    x, w1, w2, g = _data(batch=12)
    want = _single_device_grads(x, w1, w2, g)
    c = HierarchicalCluster("2x3", microbatches=3)
    try:
        assert c.n_slaves == 2  # two sub-masters
        assert [g_.n_slaves for g_ in c.group_clusters] == [2, 2]
        c.probe(image_size=8, in_channels=3, kernel_size=3,
                num_kernels=4, batch=4, repeats=1)
        # every root member is a group: hello meta says so
        for dev in c.slave_ids:
            assert c.hello_meta[dev]["group"]["size"] == 3
        _assert_grads(_train_chain(c, x, w1, w2, g), want)
        _assert_grads(_train_chain(c, x, w1, w2, g), want)
    finally:
        c.shutdown()


def test_hierarchy_tcp_matches_single_device():
    """Same acceptance over the real wire: each sub-master is an OS
    subprocess (spawned with ``--group-*`` flags) mastering its own
    in-proc group, and the grammar round-trips through real sockets."""
    x, w1, w2, g = _data(batch=8)
    want = _single_device_grads(x, w1, w2, g)
    c = HierarchicalCluster("2x2", transport="tcp", microbatches=2)
    try:
        assert c.n_slaves == 2
        assert c.group_clusters == []  # groups live in the subprocesses
        for dev in c.slave_ids:
            assert c.hello_meta[dev]["group"]["size"] == 2
        c.probe(image_size=8, in_channels=3, kernel_size=3,
                num_kernels=4, batch=4, repeats=1)
        _assert_grads(_train_chain(c, x, w1, w2, g), want)
        _assert_grads(_train_chain(c, x, w1, w2, g), want)
    finally:
        c.shutdown()


# ------------------------------------------- degenerate topologies plan


def test_single_group_plans_and_trains():
    """G=1 degenerates to 'master + one group': batch_ranges over two
    members (root compute + the aggregate group) must tile, not 0-div."""
    x, w1, w2, g = _data(batch=6)
    want = _single_device_grads(x, w1, w2, g)
    c = HierarchicalCluster("1x3", microbatches=3)
    try:
        c.probe_times = [1.0, 0.5]  # pinned: group aggregates faster
        plan = c.plan_conv(x.shape, w1, "train")
        plans.check_plan(plan, n_units=6, n_devices=2)
        _assert_grads(_train_chain(c, x, w1, w2, g), want)
    finally:
        c.shutdown()


def test_one_device_groups_plan_and_train():
    """M=1 groups: each inner cluster is MASTER-ONLY (zero slaves) —
    the sub-master computes its rows itself; aggregate Eq. 1 over one
    member is that member.  No empty-list or 0-div hazards anywhere."""
    x, w1, w2, g = _data(batch=6)
    want = _single_device_grads(x, w1, w2, g)
    c = HierarchicalCluster("2x1", microbatches=3)
    try:
        assert [g_.n_slaves for g_ in c.group_clusters] == [0, 0]
        times = c.probe(image_size=8, in_channels=3, kernel_size=3,
                        num_kernels=4, batch=4, repeats=1)
        assert len(times) == 3 and all(t > 0 for t in times)
        plan = c.plan_conv(x.shape, w1, "train")
        plans.check_plan(plan, n_units=6, n_devices=3)
        _assert_grads(_train_chain(c, x, w1, w2, g), want)
    finally:
        c.shutdown()


def test_zero_row_group_short_circuits():
    """A group priced so slow it draws ZERO batch rows must neither
    divide by zero at the root nor crash the sub-master: its zero-row
    conv/bwd short-circuit (``scheduler.group_forward``) and the other
    members carry the exact gradient."""
    x, w1, w2, g = _data(batch=6)
    want = _single_device_grads(x, w1, w2, g)
    c = HierarchicalCluster("2x2", microbatches=2)
    try:
        c.probe_times = [1.0, 1.0, 1e9]  # group 2: ~0 of the Eq. 1 share
        plan = c.plan_conv(x.shape, w1, "train")
        plans.check_plan(plan, n_units=6, n_devices=3)
        assert any(n == 0 for n in plan.counts)  # the starved group
        _assert_grads(_train_chain(c, x, w1, w2, g), want)
    finally:
        c.shutdown()


def test_group_bandwidth_folds_into_root_planning():
    """A group's internal bottleneck (min member link) rides the hello
    meta and CAPS the root's planning bandwidth for that slot — rows
    must not be priced faster than the group can redistribute them."""
    c = HierarchicalCluster(
        [GroupSpec(slowdowns=[1.0, 1.0], bandwidth_mbps=50.0),
         GroupSpec(slowdowns=[1.0, 1.0])],
        bandwidth_mbps=1000.0,
    )
    try:
        metas = [c.hello_meta[d]["group"] for d in c.slave_ids]
        assert metas[0]["bandwidth_mbps"] == 50.0
        assert metas[1]["bandwidth_mbps"] is None
        assert c.bandwidths[0] == 50.0  # min(1000, 50)
        assert c.bandwidths[1] == 1000.0  # unmetered group: uplink rules
    finally:
        c.shutdown()


# --------------------------------------------- elasticity at both tiers


def test_admit_group_and_evict_roundtrip():
    """Root-tier elasticity over WHOLE groups: admit_group grows the
    root by one sub-master (numerics stay exact over the wider plan),
    evict of that sub-master drains its group; the inner clusters ride
    along.  Exercised on inproc where the inner handles are visible."""
    x, w1, w2, g = _data(batch=6)
    want = _single_device_grads(x, w1, w2, g)
    c = HierarchicalCluster("1x2", microbatches=3)
    try:
        c.probe(image_size=8, in_channels=3, kernel_size=3,
                num_kernels=4, batch=4, repeats=1)
        _assert_grads(_train_chain(c, x, w1, w2, g), want)

        dev = c.admit_group(GroupSpec(slowdowns=[1.0, 1.0]))
        assert c.n_slaves == 2
        assert c.hello_meta[dev]["group"]["size"] == 2
        assert len(c.group_clusters) == 2
        plan = c.plan_conv(x.shape, w1, "train")
        plans.check_plan(plan, n_units=6, n_devices=3)
        _assert_grads(_train_chain(c, x, w1, w2, g), want)

        c.evict(dev)
        assert c.n_slaves == 1
        plan = c.plan_conv(x.shape, w1, "train")
        plans.check_plan(plan, n_units=6, n_devices=2)
        _assert_grads(_train_chain(c, x, w1, w2, g), want)
    finally:
        c.shutdown()


def test_inner_tier_admit_evict_reprices_group():
    """Leaf churn INSIDE a group is invisible to the root's membership:
    evicting a leaf only changes the group's aggregate capacity, which
    ``refresh_capacity`` re-prices (slower group, longer aggregate
    time) — and numerics stay exact throughout."""
    x, w1, w2, g = _data(batch=6)
    want = _single_device_grads(x, w1, w2, g)
    # each group's sub-master 3x slower than its leaf, so evicting the
    # leaf makes the group 4x slower; with medians of 5 probe timings a
    # scheduler stall under a parallel test run cannot close that gap
    c = HierarchicalCluster(
        parse_groups("2x2", slowdowns=[3.0, 1.0, 3.0, 1.0]), microbatches=3
    )
    try:
        t_before = c.probe(image_size=16, in_channels=3, kernel_size=3,
                           num_kernels=16, batch=8, repeats=5)
        inner = c.group_clusters[0]
        root_ids_before = list(c.slave_ids)
        _assert_grads(_train_chain(c, x, w1, w2, g), want)

        inner.evict(inner.slave_ids[0])  # a leaf leaves its group
        assert inner.n_slaves == 0
        t_after = c.refresh_capacity()
        assert list(c.slave_ids) == root_ids_before  # root membership: same
        # the shrunk group aggregates SLOWER than with both members
        assert t_after[1] > t_before[1] * 1.2
        _assert_grads(_train_chain(c, x, w1, w2, g), want)

        dev = inner.admit(1.0, "numpy")  # and a leaf joins back
        assert inner.n_slaves == 1 and dev in inner.slave_ids
        c.refresh_capacity()
        _assert_grads(_train_chain(c, x, w1, w2, g), want)
    finally:
        c.shutdown()


# ------------------------------------------------ composed chaos domains


def test_leaf_sigkill_recovers_inside_group_invisible_to_root():
    """ISSUE chaos acceptance 1: SIGKILL a LEAF slave mid-step.  Its
    group's sub-master evicts it and recomputes its in-flight rows; the
    step's gradients stay VJP-exact, and the ROOT sees no failure at
    all — only the capacity drop the next refresh_capacity re-plans
    on.  Root inproc (the sub-master is a thread we can reach), group
    on tcp (leaves are real processes a SIGKILL can take)."""
    x, w1, w2, g = _data(batch=8)
    want = _single_device_grads(x, w1, w2, g)
    c = HierarchicalCluster(
        [GroupSpec(slowdowns=[1.0, 1.0, 1.0], transport="tcp",
                   heartbeat_s=2.0, microbatches=2),
         GroupSpec(slowdowns=[1.0, 1.0, 1.0], transport="tcp",
                   heartbeat_s=2.0, microbatches=2)],
        microbatches=2,
    )
    try:
        c.probe(image_size=8, in_channels=3, kernel_size=3,
                num_kernels=4, batch=4, repeats=1)
        inner = c.group_clusters[0]
        victim_proc = inner.procs[0]
        victim_dev = inner.slave_ids[0]
        fired = {}

        def between(y):
            if not fired:
                fired["t"] = True
                victim_proc.kill()
            mask = (y > 0).astype(np.float32)
            return np.maximum(y, 0.0), lambda gz: gz * mask

        slices = c.microbatch_slices(x.shape[0])

        def head(z, i):
            return None, g[slices[i]]

        res = c.conv_train_chain(x, [w1, w2], [between, None], head)
        _assert_grads(res, want)
        # the failure lives one tier DOWN: group evicted its leaf...
        assert len(inner.failures) == 1
        assert inner.failures[0]["device"] == victim_dev
        assert inner.n_slaves == 1
        # ...and the root never saw a topology event
        assert c.failures == []
        assert c.n_slaves == 2
        # re-price the shrunk group; the next step is still exact
        c.refresh_capacity()
        _assert_grads(_train_chain(c, x, w1, w2, g), want)
    finally:
        c.shutdown()


def test_submaster_sigkill_root_recovers_whole_group():
    """ISSUE chaos acceptance 2: SIGKILL a whole SUB-MASTER mid-step.
    To the root that is ONE dead batch member; the stock batch-axis
    recovery recomputes the group's rows on the root, the dW all-reduce
    still sums every row exactly once, and the next step re-plans over
    the surviving group.  Root on tcp — sub-masters are real processes."""
    x, w1, w2, g = _data(batch=6)
    want = _single_device_grads(x, w1, w2, g)
    c = HierarchicalCluster(
        "2x2", transport="tcp", microbatches=3, heartbeat_s=2.0,
    )
    try:
        c.probe_times = [1.0, 1.0, 1.0]
        victim_proc = c.procs[0]
        victim_dev = c.slave_ids[0]
        fired = {}

        def between(y):
            if not fired:
                fired["t"] = True
                victim_proc.kill()
            mask = (y > 0).astype(np.float32)
            return np.maximum(y, 0.0), lambda gz: gz * mask

        slices = c.microbatch_slices(x.shape[0])

        def head(z, i):
            return None, g[slices[i]]

        res = c.conv_train_chain(x, [w1, w2], [between, None], head)
        _assert_grads(res, want)
        assert len(c.failures) == 1
        assert c.failures[0]["device"] == victim_dev
        assert c.n_slaves == 1
        assert c.timing.recompute_s > 0.0
        plan = c.plan_conv(x.shape, w1, "train")
        plans.check_plan(plan, n_units=6, n_devices=2)
        _assert_grads(_train_chain(c, x, w1, w2, g), want)
    finally:
        c.shutdown()


def test_group_hello_meta_shape():
    """The upward-facing group summary: size counts the sub-master's
    own compute, bandwidth is the min FINITE member link (None when
    every inner link is unmetered)."""
    from repro.core.cluster.hierarchy import build_group_cluster

    inner = build_group_cluster(GroupSpec(slowdowns=[1.0, 1.0, 1.0]))
    try:
        meta = group_hello_meta(inner)
        assert meta == {"size": 3, "bandwidth_mbps": None}
    finally:
        inner.shutdown()
    inner = build_group_cluster(
        GroupSpec(slowdowns=[1.0, 1.0], bandwidth_mbps=25.0)
    )
    try:
        assert group_hello_meta(inner)["bandwidth_mbps"] == 25.0
    finally:
        inner.shutdown()

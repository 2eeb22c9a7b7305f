"""Property-based tests (hypothesis) for the MXDAG calculus & simulator."""
import math
import re

import pytest

hypothesis = pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed (pip install -e .[test])")
from hypothesis import given, settings, strategies as st

from repro.core import (
    AltruisticMultiScheduler, Cluster, MXDAG, MXDAGScheduler, compute, flow,
    simulate,
)
from repro.core import builders

sizes = st.floats(min_value=0.1, max_value=8.0, allow_nan=False,
                  allow_infinity=False)
unit_counts = st.integers(min_value=2, max_value=6)


def pipelined_chain(unit_times, n_units):
    """Alternating compute/flow chain; task i has n_units units of u_i."""
    tasks = []
    for i, u in enumerate(unit_times):
        size = u * n_units
        if i % 2 == 0:
            tasks.append(compute(f"t{i}", size, f"H{i}", unit=u))
        else:
            tasks.append(flow(f"t{i}", size, f"H{i-1}", f"H{i+1}", unit=u))
    g = MXDAG()
    g.chain(*tasks, pipelined=True)
    return g, tasks


class TestEq2Property:
    @given(us=st.lists(sizes, min_size=2, max_size=5), n=unit_counts)
    @settings(max_examples=40, deadline=None)
    def test_eq2_exact_for_equal_unit_counts(self, us, n):
        """Paper Eq.(2) == DES == analytic recursion on pipelined chains
        with a common unit count (each host/NIC private: no contention)."""
        g, tasks = pipelined_chain(us, n)
        expected = MXDAG.len_pipelined(tasks)
        assert g.makespan() == pytest.approx(expected, rel=1e-6)
        assert simulate(g).makespan == pytest.approx(expected, rel=1e-6)

    @given(us=st.lists(sizes, min_size=2, max_size=4),
           ns=st.lists(unit_counts, min_size=2, max_size=4))
    @settings(max_examples=40, deadline=None)
    def test_des_at_least_analytic_for_unequal_unit_counts(self, us, ns):
        """With heterogeneous unit counts the analytic recursion is an
        optimistic (first-unit-latency) bound; the DES's unit-granular
        gating can only be slower."""
        k = min(len(us), len(ns))
        us, ns = us[:k], ns[:k]
        tasks = []
        for i, (u, n) in enumerate(zip(us, ns)):
            tasks.append(compute(f"t{i}", u * n, f"H{i}", unit=u))
        g = MXDAG()
        g.chain(*tasks, pipelined=True)
        assert simulate(g).makespan >= g.makespan() - 1e-6

    @given(us=st.lists(sizes, min_size=2, max_size=5), n=unit_counts)
    @settings(max_examples=25, deadline=None)
    def test_pipelining_never_slower_than_sequential_chain(self, us, n):
        g, tasks = pipelined_chain(us, n)
        seq = MXDAG.len_sequential(tasks)
        assert simulate(g).makespan <= seq + 1e-6


class TestSchedulerProperties:
    @given(bp=st.lists(sizes, min_size=2, max_size=5),
           comm=st.lists(sizes, min_size=2, max_size=5))
    @settings(max_examples=25, deadline=None)
    def test_principle1_never_worse_than_fair_on_ddl(self, bp, comm):
        """Critical-path-priority scheduling of the Fig. 6 family is never
        worse than fair sharing (flows are preemptible; single GPU chain
        fixes the compute order)."""
        k = min(len(bp), len(comm))
        g = builders.ddl(k, bp=bp[:k], fp=bp[:k],
                         push=comm[:k], pull=comm[:k])
        fair = simulate(g, policy="fair")
        s = MXDAGScheduler(try_pipelining=False).schedule(g)
        mx = s.simulate()
        assert mx.makespan <= fair.makespan + 1e-6

    @given(bp=st.lists(sizes, min_size=3, max_size=4), seed=st.integers(0, 3))
    @settings(max_examples=20, deadline=None)
    def test_greedy_pipelining_monotone(self, bp, seed):
        """try_pipelining=True only keeps strictly-improving edges, so it is
        never worse than no pipelining at all."""
        k = len(bp)
        g = builders.ddl(k, bp=bp, fp=bp, push=2.0, pull=2.0,
                         unit_frac=0.25)
        off = MXDAGScheduler(try_pipelining=False).schedule(g).simulate()
        on = MXDAGScheduler(try_pipelining=True).schedule(g).simulate()
        assert on.makespan <= off.makespan + 1e-6

    @given(a=sizes, b=sizes, d=sizes)
    @settings(max_examples=25, deadline=None)
    def test_altruism_never_hurts_own_jct(self, a, b, d):
        """Principle 2's bound: job1's JCT under altruistic demotion equals
        its JCT when scheduled with strict self-priority."""
        j1 = MXDAG("job1")
        ta = j1.add(compute("a", a + b + 0.5, "Ha", job="job1"))
        tb = j1.add(compute("b", b, "Hb", job="job1"))
        f1 = j1.add(flow("f1", 1.0, "Ha", "Hr", job="job1"))
        f2 = j1.add(flow("f2", 1.0, "Hb", "Hr", job="job1"))
        r1 = j1.add(compute("r1", 1.0, "Hr", job="job1"))
        j1.add_edge(ta, f1); j1.add_edge(tb, f2)
        j1.add_edge(f1, r1); j1.add_edge(f2, r1)
        j2 = MXDAG("job2")
        td = j2.add(compute("d", d, "Hb", job="job2"))
        f3 = j2.add(flow("f3", 1.0, "Hb", "Hr2", job="job2"))
        r2 = j2.add(compute("r2", 1.0, "Hr2", job="job2"))
        j2.add_edge(td, f3); j2.add_edge(f3, r2)

        alt = AltruisticMultiScheduler().schedule([j1, j2]).simulate()
        solo = simulate(j1)
        # own JCT must not exceed the isolated JCT by more than the foreign
        # critical work its demoted tasks' slack was checked against
        assert alt.jct("job1") <= solo.jct("job1") + d + 1.0 + 1e-6

    @given(n=st.integers(2, 4), m=st.integers(2, 4), shuffle=sizes)
    @settings(max_examples=15, deadline=None)
    def test_mapreduce_conservation(self, n, m, shuffle):
        """Every task finishes; makespan bounded below by critical path and
        above by the fully-serialized sum."""
        g = builders.mapreduce("mr", n, m, shuffle_time=shuffle)
        r = simulate(g)
        assert all(f is not None for f in r.finish.values())
        assert r.makespan >= g.makespan() - 1e-9
        total = sum(t.size for t in g)
        assert r.makespan <= total + 1e-6


@st.composite
def equivalence_case(draw):
    """Random DAG + topology + policy for the event-calendar oracle."""
    from repro.core import Cluster, Topology

    n_hosts = draw(st.integers(min_value=2, max_value=5))
    hosts = [f"h{i}" for i in range(n_hosts)]
    topo_kind = draw(st.sampled_from(["none", "two_tier", "leaf_spine"]))
    if topo_kind == "none":
        cluster = None
    else:
        half = max(1, n_hosts // 2)
        racks = [hosts[:half], hosts[half:]]
        if topo_kind == "two_tier":
            topo = Topology.two_tier(
                racks,
                oversubscription=draw(st.sampled_from([1.0, 2.0, 4.0])))
        else:
            topo = Topology.leaf_spine(racks, n_spines=2)
        cluster = Cluster.from_topology(topo)

    size_st = st.sampled_from([0.0, 0.25, 0.5, 1.0, 1.75, 3.0])
    n_tasks = draw(st.integers(min_value=2, max_value=10))
    g = MXDAG("rand")
    names = []
    for i in range(n_tasks):
        size = draw(size_st)
        unit = None
        if size > 0 and draw(st.booleans()):
            unit = size * draw(st.sampled_from([0.25, 0.5, 1.0]))
        if draw(st.booleans()):
            t = compute(f"t{i}", size, draw(st.sampled_from(hosts)),
                        unit=unit)
        else:
            src = draw(st.sampled_from(hosts))
            dst = draw(st.sampled_from([h for h in hosts if h != src]))
            t = flow(f"t{i}", size, src, dst, unit=unit)
        g.add(t)
        names.append(t.name)
    for i in range(1, n_tasks):
        for j in draw(st.lists(st.integers(0, i - 1), max_size=2,
                               unique=True)):
            if (names[j], names[i]) not in g.edges:
                g.add_edge(names[j], names[i],
                           pipelined=draw(st.booleans()))
    policy = draw(st.sampled_from(["fair", "priority"]))
    prio = {n: draw(st.integers(0, 2)) for n in names
            if draw(st.booleans())}
    rel = {n: draw(st.sampled_from([0.5, 1.0, 2.0])) for n in names
           if not g.preds(n) and draw(st.booleans())}
    flows = [t.name for t in g.network_tasks() if t.size > 0]
    coflows = None
    if len(flows) >= 2 and draw(st.booleans()):
        coflows = [set(flows[:2])]
    return g, cluster, policy, prio, rel, coflows


class TestEventCalendarEquivalence:
    """The incremental event-calendar core is a pure optimisation: on any
    random DAG, topology and policy it must reproduce the retained
    reference slow path's per-task trajectory."""

    @given(case=equivalence_case())
    @settings(max_examples=120, deadline=None)
    def test_matches_reference_on_random_dags(self, case):
        from repro.core.simulator import Simulator

        g, cluster, policy, prio, rel, coflows = case
        kw = dict(policy=policy, priorities=prio, releases=rel,
                  coflows=coflows)
        try:
            ref = Simulator(g, cluster, **kw)._reference_run()
        except RuntimeError as e:
            # e.g. a coflow whose members depend on each other can never
            # start: the event-calendar core must report the same
            with pytest.raises(RuntimeError, match=re.escape(str(e))):
                Simulator(g, cluster, **kw).run()
            return
        new = Simulator(g, cluster, **kw).run()
        for n in g.tasks:
            assert new.start[n] == pytest.approx(ref.start[n],
                                                 abs=1e-6), n
            assert new.finish[n] == pytest.approx(ref.finish[n],
                                                  abs=1e-6), n


class TestCalculusProperties:
    @given(us=st.lists(sizes, min_size=1, max_size=6))
    @settings(max_examples=40, deadline=None)
    def test_eq1_additivity(self, us):
        ts = [compute(f"t{i}", u, "H") for i, u in enumerate(us)]
        assert MXDAG.len_sequential(ts) == pytest.approx(sum(us))

    @given(us=st.lists(sizes, min_size=1, max_size=6), n=unit_counts)
    @settings(max_examples=40, deadline=None)
    def test_eq2_dominated_by_slowest_stage(self, us, n):
        """Eq.(2): the pipelined length is within one fill latency of the
        slowest stage's total time (Fig. 5)."""
        ts = [compute(f"t{i}", u * n, f"H{i}", unit=u)
              for i, u in enumerate(us)]
        ln = MXDAG.len_pipelined(ts)
        slowest = max(u * n for u in us)
        assert ln >= slowest - 1e-9
        assert ln <= slowest + sum(us) + 1e-9

    @given(us=st.lists(sizes, min_size=2, max_size=6), n=unit_counts,
           r=st.floats(min_value=0.2, max_value=1.0))
    @settings(max_examples=30, deadline=None)
    def test_resource_scaling_linear(self, us, n, r):
        """Halving every task's resource doubles both Eq.(1) and Eq.(2)."""
        ts = [compute(f"t{i}", u * n, f"H{i}", unit=u)
              for i, u in enumerate(us)]
        rs = {t.name: r for t in ts}
        assert MXDAG.len_sequential(ts, rs) == pytest.approx(
            MXDAG.len_sequential(ts) / r, rel=1e-9)
        assert MXDAG.len_pipelined(ts, rs) == pytest.approx(
            MXDAG.len_pipelined(ts) / r, rel=1e-9)

"""Unit tests: discrete-event simulator semantics."""
import pytest

from repro.core import Cluster, Host, MXDAG, compute, flow, simulate
from repro.core import builders


def two_flow_graph(s1=1.0, s2=1.0):
    """Two flows leaving the same host A, no dependencies."""
    g = MXDAG()
    g.add(flow("f1", s1, "A", "B"))
    g.add(flow("f2", s2, "A", "C"))
    return g


class TestBasics:
    def test_single_compute(self):
        g = MXDAG()
        g.add(compute("a", 2.0, "A"))
        r = simulate(g)
        assert r.finish["a"] == pytest.approx(2.0)

    def test_chain(self):
        g = MXDAG()
        g.chain(compute("a", 1.0, "A"), flow("f", 2.0, "A", "B"),
                compute("b", 1.0, "B"))
        r = simulate(g)
        assert r.makespan == pytest.approx(4.0)

    def test_zero_size_task(self):
        g = MXDAG()
        g.chain(compute("a", 0.0, "A"), compute("b", 1.0, "A"))
        assert simulate(g).makespan == pytest.approx(1.0)

    def test_release_time(self):
        g = MXDAG()
        g.add(compute("a", 1.0, "A"))
        r = simulate(g, releases={"a": 3.0})
        assert r.start["a"] == pytest.approx(3.0)
        assert r.makespan == pytest.approx(4.0)


class TestNICSharing:
    def test_fair_share_halves_rate(self):
        r = simulate(two_flow_graph())
        assert r.finish["f1"] == pytest.approx(2.0)
        assert r.finish["f2"] == pytest.approx(2.0)

    def test_priority_serializes(self):
        r = simulate(two_flow_graph(), policy="priority",
                     priorities={"f1": 0, "f2": 1})
        assert r.finish["f1"] == pytest.approx(1.0)
        assert r.finish["f2"] == pytest.approx(2.0)

    def test_priority_is_preemptive_for_flows(self):
        # f2 starts alone, f1 (higher prio) arrives later and takes the NIC
        g = MXDAG()
        g.add(flow("f2", 2.0, "A", "C"))
        g.add(compute("gate", 1.0, "A"))
        g.add(flow("f1", 1.0, "A", "B"))
        g.add_edge("gate", "f1")
        r = simulate(g, policy="priority", priorities={"f1": 0, "f2": 1})
        assert r.finish["f1"] == pytest.approx(2.0)
        assert r.finish["f2"] == pytest.approx(3.0)   # preempted 1s

    def test_heterogeneous_nic(self):
        g = MXDAG()
        g.add(flow("f", 1.0, "A", "B"))
        cl = Cluster([Host("A", nic_out=0.5), Host("B")])
        assert simulate(g, cl).makespan == pytest.approx(2.0)

    def test_different_nics_dont_contend(self):
        g = MXDAG()
        g.add(flow("f1", 1.0, "A", "B"))
        g.add(flow("f2", 1.0, "C", "D"))
        r = simulate(g)
        assert r.makespan == pytest.approx(1.0)

    def test_ingress_contention(self):
        g = MXDAG()
        g.add(flow("f1", 1.0, "A", "C"))
        g.add(flow("f2", 1.0, "B", "C"))
        r = simulate(g)
        assert r.makespan == pytest.approx(2.0)


class TestComputeSlots:
    def test_exclusive_slot_serializes(self):
        g = MXDAG()
        g.add(compute("a", 1.0, "H"))
        g.add(compute("b", 1.0, "H"))
        r = simulate(g)
        assert r.makespan == pytest.approx(2.0)

    def test_two_slots_parallel(self):
        g = MXDAG()
        g.add(compute("a", 1.0, "H"))
        g.add(compute("b", 1.0, "H"))
        cl = Cluster([Host("H", procs={"cpu": 2})])
        assert simulate(g, cl).makespan == pytest.approx(1.0)

    def test_dispatch_by_priority(self):
        g = MXDAG()
        g.add(compute("a", 1.0, "H"))
        g.add(compute("b", 1.0, "H"))
        r = simulate(g, policy="priority", priorities={"b": 0, "a": 1})
        assert r.start["b"] == pytest.approx(0.0)
        assert r.start["a"] == pytest.approx(1.0)

    def test_nonpreemptive_compute(self):
        # low-prio a starts first (alone), high-prio b arrives later but
        # must wait: compute is non-preemptive
        g = MXDAG()
        g.add(compute("a", 2.0, "H"))
        g.add(compute("gate", 1.0, "G"))
        g.add(compute("b", 1.0, "H"))
        g.add_edge("gate", "b")
        r = simulate(g, policy="priority", priorities={"b": 0, "a": 1})
        assert r.start["b"] == pytest.approx(2.0)


class TestPipelining:
    def test_pipelined_chain_matches_eq2(self):
        from repro.core.graph import MXDAG as G
        a = compute("a", 4.0, "A", unit=1.0)
        f = flow("f", 8.0, "A", "B", unit=2.0)
        g = MXDAG()
        g.chain(a, f, pipelined=True)
        r = simulate(g)
        assert r.makespan == pytest.approx(G.len_pipelined([a, f]))

    def test_unit_boundary_landing_on_a_completion_is_not_lost(self):
        # t2's first unit ends (within EPS) when t1 finishes; t2 is then
        # rescheduled before its own unit event fires, and t3 must still
        # start at that boundary rather than when t2 finishes
        from repro.core.graph import MXDAG as G
        from repro.core.simulator import Simulator
        u = 7.67498057627927
        ts = [compute("t0", 2 * u, "H0", unit=u),
              flow("t1", 2.0, "H0", "H2", unit=1.0),
              compute("t2", 2 * u, "H2", unit=u),
              flow("t3", 2.0, "H2", "H4", unit=1.0)]
        g = MXDAG()
        g.chain(*ts, pipelined=True)
        for batch in (True, False):
            r = Simulator(g).run(batch=batch)
            assert r.start["t3"] == pytest.approx(r.finish["t1"])
            assert r.makespan == pytest.approx(G.len_pipelined(ts))

    def test_unpipelined_chain_matches_eq1(self):
        a = compute("a", 4.0, "A", unit=1.0)
        f = flow("f", 8.0, "A", "B", unit=2.0)
        g = MXDAG()
        g.chain(a, f, pipelined=False)
        assert simulate(g).makespan == pytest.approx(12.0)

    def test_consumer_gated_by_producer_units(self):
        # producer slower than consumer: consumer starves between units
        a = compute("a", 4.0, "A", unit=1.0)
        b = compute("b", 2.0, "B", unit=0.5)
        g = MXDAG()
        g.chain(a, b, pipelined=True)
        r = simulate(g)
        # b's last quarter needs a fully delivered: finish = 4 + 0.5
        assert r.makespan == pytest.approx(4.5)

    def test_pipelined_flow_occupies_nic_eagerly(self):
        # paper §4.1: streaming flows contend in the top class
        g = MXDAG()
        a = compute("a", 1.0, "A", unit=0.25)
        g.add(a)
        g.add(flow("fcrit", 1.0, "A", "B"))
        g.add(flow("fpipe", 1.0, "A", "C", unit=0.25))
        g.add_edge("a", "fpipe", pipelined=True)
        r = simulate(g, policy="priority",
                     priorities={"fcrit": 0, "fpipe": 5})
        # fpipe streams from t=0.25 sharing with fcrit despite low priority
        assert r.finish["fcrit"] > 1.0 + 1e-6


class TestCoflow:
    def test_synchronized_start_and_fair_coupling(self):
        # f2 ready at t=0, f1 gated by a 1s compute; coflow syncs both to t=1
        g = MXDAG()
        g.add(compute("a", 1.0, "A"))
        g.add(flow("f1", 1.0, "A", "B"))
        g.add(flow("f2", 1.0, "A", "C"))
        g.add_edge("a", "f1")
        r = simulate(g, coflows=[{"f1", "f2"}])
        assert r.start["f2"] == pytest.approx(1.0)
        # share A egress: both finish at 3 (MADD: equal sizes, equal rates)
        assert r.finish["f1"] == pytest.approx(3.0)
        assert r.finish["f2"] == pytest.approx(3.0)

    def test_madd_finish_together_unequal_sizes(self):
        g = MXDAG()
        g.add(flow("f1", 1.0, "A", "B"))
        g.add(flow("f2", 3.0, "A", "C"))
        r = simulate(g, coflows=[{"f1", "f2"}])
        assert r.finish["f1"] == pytest.approx(r.finish["f2"], rel=1e-6)
        assert r.finish["f2"] == pytest.approx(4.0)

    def test_all_or_nothing_gates_successor(self):
        g = MXDAG()
        g.add(flow("f1", 1.0, "A", "B"))
        g.add(flow("f2", 3.0, "A", "C"))
        g.add(compute("b", 1.0, "B"))
        g.add_edge("f1", "b")
        r = simulate(g, coflows=[{"f1", "f2"}])
        # b waits for the whole coflow (4.0), not just f1
        assert r.start["b"] == pytest.approx(4.0)

    def test_coflow_member_must_be_flow(self):
        g = MXDAG()
        g.add(compute("a", 1.0, "A"))
        with pytest.raises(ValueError):
            simulate(g, coflows=[{"a"}])


class TestInvariants:
    def test_des_never_beats_contention_free_bound(self):
        for builder in (builders.fig1_jobs, builders.fig2a, builders.fig2b,
                        builders.fig3, lambda: builders.ddl(3)):
            g = builder()
            assert simulate(g).makespan >= g.makespan() - 1e-9

    def test_job_completion_tracked(self):
        j1, j2 = builders.mapreduce_pair()
        m = MXDAG("m")
        for t in list(j1) + list(j2):
            m.add(t)
        for e in list(j1.edges.values()) + list(j2.edges.values()):
            m.add_edge(e.src, e.dst)
        r = simulate(m)
        assert set(r.job_completion) == {"job1", "job2"}
        assert r.makespan == pytest.approx(max(r.job_completion.values()))

"""Mega-batch event loop differentials.

The array engine's batched loop (``batch=True``, the default) pops
every event sharing the next timestamp and runs vectorized
integration/completion/start passes over the batch; ``batch=False`` is
the per-event loop kept verbatim as the differential oracle.  These
tests pin the contract:

1. batched == per-event **exactly** (per-task start/finish, makespan,
   job completion) on every builder scenario family, and both agree
   with the event-calendar core to EPS;
2. :class:`ResumableSim` pause / checkpoint / restore-fork at batch
   boundaries is bit-exact under the batched loop, including with
   nemesis mutators applied mid-run (same mutations under both loops
   ⇒ same results);
3. a hypothesis sweep over random layered DAGs (skipped when
   hypothesis isn't installed).

Without numpy the batched passes degrade to the scalar loop, so the
equalities hold trivially — the file stays meaningful in the
numpy-free core CI lane via the calendar-core comparisons.
"""
import math

import pytest

from repro.core import Cluster, builders
from repro.core.arraysim import ResumableSim, array_run
from repro.core.schedule import MXDAGScheduler
from repro.core.simulator import Simulator


def scenarios():
    """(name, Simulator factory) covering every builder family —
    coflows, pipelining, priorities, releases, fabrics, routing."""
    def fanin():
        g, cl = builders.oversubscribed_fanin(8, oversubscription=4.0)
        return Simulator(g, cl)

    def fanin_prio():
        g, cl = builders.oversubscribed_fanin(6, oversubscription=6.0)
        s = MXDAGScheduler(try_pipelining=False).schedule(g, cl)
        return Simulator(s.graph, cl, policy=s.policy,
                         priorities=s.priorities, releases=s.releases)

    def shuffle():
        g, cl = builders.fat_tree_shuffle(8, stride=2)
        return Simulator(g, cl)

    def ddl():
        g = builders.ddl(8, push=2.0, pull=2.0, unit_frac=0.25)
        return Simulator(g, Cluster.for_graph(g))

    def layered():
        g = builders.random_layered(300, n_hosts=16, min_width=4,
                                    max_width=16, seed=5)
        return Simulator(g, Cluster.for_graph(g))

    def coflows():
        g = builders.fig2a()
        return Simulator(g, coflows=builders.fig2a_coflows())

    def mapreduce():
        return Simulator(builders.mapreduce("mr", 8, 8, unit_frac=0.125))

    return [("fanin", fanin), ("fanin_prio", fanin_prio),
            ("shuffle", shuffle), ("ddl_pipelined", ddl),
            ("layered", layered), ("coflows", coflows),
            ("mapreduce_piped", mapreduce)]


def assert_bitexact(a, b):
    assert a.start == b.start
    assert a.finish == b.finish
    assert a.makespan == b.makespan
    assert a.job_completion == b.job_completion


@pytest.mark.parametrize("name,mk", scenarios())
class TestBatchedEqualsPerEvent:
    def test_batch_vs_perevent_vs_calendar(self, name, mk):
        batched = mk().run(batch=True)
        perevent = mk().run(batch=False)
        assert_bitexact(batched, perevent)
        cal = mk().calendar_run()
        for n in cal.finish:
            assert batched.finish[n] == pytest.approx(cal.finish[n],
                                                      abs=1e-9), n
        assert batched.makespan == pytest.approx(cal.makespan, abs=1e-9)

    def test_array_run_batch_flag(self, name, mk):
        assert_bitexact(array_run(mk(), batch=True),
                        array_run(mk(), batch=False))


@pytest.mark.parametrize("name,mk", scenarios())
class TestResumableBatchBoundaries:
    """Pausing cuts between batches, never through one — so a paused,
    checkpointed or forked batched session must replay bit-exactly."""

    def test_paused_run_bitexact(self, name, mk):
        ref = array_run(mk(), batch=True)
        rs = ResumableSim(mk(), batch=True)
        t, status = 0.0, "paused"
        while status == "paused":
            status = rs.run_until(t)
            t += 0.5
        assert status == "done"
        assert_bitexact(rs.result(), ref)

    def test_checkpoint_fork_bitexact(self, name, mk):
        ref = array_run(mk(), batch=True)
        rs = ResumableSim(mk(), batch=True)
        rs.run_until(ref.makespan * 0.4)
        snap = rs.checkpoint()
        assert rs.run_until(math.inf) == "done"
        assert_bitexact(rs.result(), ref)
        rs.restore(snap)
        assert rs.run_until(math.inf) == "done"
        assert_bitexact(rs.result(), ref)

    def test_mutators_agree_across_loops(self, name, mk):
        """The same nemesis mutations applied at the same pause point
        must produce identical runs under both loops — faults don't
        re-introduce a batched/per-event divergence."""
        ref = array_run(mk(), batch=True)
        sample = mk()
        victims = sorted(sample.g.tasks)[: 2]

        def faulted(batch):
            rs = ResumableSim(mk(), batch=batch)
            rs.run_until(ref.makespan * 0.3)
            for v in victims:
                rs.set_speed(v, 0.5)
            assert rs.run_until(math.inf) == "done"
            return rs.result()

        assert_bitexact(faulted(True), faulted(False))


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # the numpy-free core lane installs it, but
    HAVE_HYPOTHESIS = False  # a bare checkout may not


if HAVE_HYPOTHESIS:
    class TestBatchedProperty:
        @given(seed=st.integers(0, 10_000),
               n=st.integers(40, 220))
        @settings(max_examples=15, deadline=None)
        def test_random_layered_bitexact(self, seed, n):
            g = builders.random_layered(n, n_hosts=8, min_width=2,
                                        max_width=8, seed=seed)
            cl = Cluster.for_graph(g)
            assert_bitexact(Simulator(g, cl).run(batch=True),
                            Simulator(g, cl).run(batch=False))

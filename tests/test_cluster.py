"""Multi-node ClusterModel: hierarchy invariants, flat ≡ 1-node parity
(byte-identical traces, equal reports, every registered policy),
multi-node placement/migration/locality guards, and the byte-exact
multi-node sim→sim replay round trip."""

import itertools

import pytest
from hypothesis import given, settings, strategies as st

import repro.runtime.task as task_mod
from repro.core import (EventBus, GovernorSpec, ResourceBroker,
                        jain_fairness)
from repro.core.arbiter import ClusterArbiter
from repro.core.governor import registered_policies
from repro.core.topology import CoreTopology, CoreType
from repro.runtime import (DVFS2, HYBRID_PE, ClusterModel, MachineModel,
                           SimCluster, SimJobSpec, predicted_demand,
                           run_multi_node)
from repro.trace import TraceRecorder, TraceReplayer
from repro.workloads import build_gauss_seidel, build_stream

M8 = MachineModel(name="M8", n_cores=8)

GS_KW = dict(steps=3, bi=4, bj=4, block_elems=300_000, seed=0)
ST_KW = dict(rounds=2, blocks=40, block_elems=40_000, seed=1)


def _fresh_graphs():
    """Deterministic task ids: byte-identical traces require identical
    ids, so every build resets the global counter first."""
    task_mod._ids = itertools.count()
    return build_gauss_seidel(**GS_KW), build_stream(**ST_KW)


# ---------------------------------------------------------------------------
# ClusterModel invariants


class TestClusterModel:
    def test_validation(self):
        with pytest.raises(ValueError, match="at least one node"):
            ClusterModel(nodes=())
        with pytest.raises(ValueError, match="must be 2x2"):
            ClusterModel(nodes=(M8, M8), distance=((0.0,),))
        with pytest.raises(ValueError, match="must be 0"):
            ClusterModel(nodes=(M8, M8),
                         distance=((1.0, 1.0), (1.0, 0.0)))
        with pytest.raises(ValueError, match="symmetric"):
            ClusterModel(nodes=(M8, M8),
                         distance=((0.0, 1.0), (2.0, 0.0)))
        with pytest.raises(ValueError, match=">= 0"):
            ClusterModel(nodes=(M8, M8),
                         distance=((0.0, -1.0), (-1.0, 0.0)))

    def test_global_id_space(self):
        cm = ClusterModel(nodes=(M8, HYBRID_PE, M8))
        assert cm.n_nodes == 3
        assert cm.n_cores == 8 + 24 + 8
        seen = []
        for node in range(cm.n_nodes):
            for c in cm.cores_of(node):
                assert cm.node_of(c) == node
                assert cm.base_of(node) + cm.local_id(c) == c
                assert cm.machine_of(c) is cm.nodes[node]
                seen.append(c)
        assert seen == list(range(cm.n_cores))   # exact partition
        with pytest.raises(IndexError):
            cm.node_of(cm.n_cores)
        with pytest.raises(IndexError):
            cm.node_of(-1)

    def test_locality_costs(self):
        cm = ClusterModel(nodes=(M8, M8, M8),
                          distance=((0.0, 1.0, 2.0),
                                    (1.0, 0.0, 1.0),
                                    (2.0, 1.0, 0.0)),
                          remote_penalty=0.25, transfer_latency=10e-6)
        assert cm.penalty(0, 0) == 1.0
        assert cm.penalty(0, 2) == pytest.approx(1.5)
        assert cm.penalty(2, 0) == cm.penalty(0, 2)
        assert cm.transfer_time(0, 1) == pytest.approx(10e-6)
        assert cm.transfer_time(0, 2) == pytest.approx(20e-6)
        assert cm.transfer_time(1, 1) == 0.0

    def test_type_and_speed_cross_node(self):
        cm = ClusterModel(nodes=(M8, HYBRID_PE))
        assert cm.type_of(0) == "core"
        assert cm.type_of(8) == "P"           # first HYBRID_PE core
        assert cm.type_of(8 + 23) == "E"
        assert cm.speed_of(8 + 23) == pytest.approx(0.55)
        assert cm.socket_of(0) == 0

    def test_round_trip(self):
        cm = ClusterModel(nodes=(M8, HYBRID_PE),
                          distance=((0.0, 2.0), (2.0, 0.0)),
                          transfer_latency=5e-6, remote_penalty=0.3,
                          migration_latency=1e-4, name="mix")
        assert ClusterModel.from_dict(cm.to_dict()) == cm

    @settings(max_examples=30, deadline=None)
    @given(st.lists(st.integers(min_value=1, max_value=16),
                    min_size=1, max_size=5),
           st.floats(min_value=0.0, max_value=4.0))
    def test_partition_property(self, core_counts, d):
        nodes = tuple(MachineModel(name=f"n{i}", n_cores=k)
                      for i, k in enumerate(core_counts))
        n = len(nodes)
        dist = tuple(tuple(0.0 if i == j else d for j in range(n))
                     for i in range(n))
        cm = ClusterModel(nodes=nodes, distance=dist)
        # every global core id maps to exactly one node, and the
        # per-node ranges partition [0, n_cores)
        owners = [cm.node_of(c) for c in range(cm.n_cores)]
        for node in range(n):
            assert [c for c in range(cm.n_cores)
                    if owners[c] == node] == list(cm.cores_of(node))
            assert cm.penalty(node, node) == 1.0
        for i in range(n):
            for j in range(n):
                assert cm.penalty(i, j) == cm.penalty(j, i)
                assert cm.transfer_time(i, j) == cm.transfer_time(j, i)


# ---------------------------------------------------------------------------
# flat MachineModel ≡ 1-node ClusterModel, byte-for-byte


def _run_solo(machine, graph, gov, cpus, tmp_path, tag):
    cluster = SimCluster(machine)
    job = cluster.add_job(SimJobSpec(name="app", graph=graph,
                                     governor=gov, cpus=list(cpus)))
    rec = TraceRecorder()
    rec.attach(job.bus)
    report = cluster.run()["app"]
    path = tmp_path / f"{tag}.jsonl"
    rec.to_jsonl(path)
    return report, path.read_bytes()


def _run_pair(machine, gov, tmp_path, tag):
    """Two co-tenant apps through one broker (sharing policies need a
    co-tenant to trade CPUs with)."""
    task_mod._ids = itertools.count()
    g1 = build_gauss_seidel(**GS_KW)
    g2 = build_stream(**ST_KW)
    broker = ResourceBroker()
    cluster = SimCluster(machine, broker=broker)
    n = (machine.n_cores if isinstance(machine, MachineModel)
         else machine.n_cores)
    half = n // 2
    ja = cluster.add_job(SimJobSpec(name="a", graph=g1, governor=gov,
                                    cpus=list(range(half))))
    jb = cluster.add_job(SimJobSpec(name="b", graph=g2, governor=gov,
                                    cpus=list(range(half, n))))
    rec = TraceRecorder()
    rec.attach(ja.bus)
    rec.attach(jb.bus)
    reports = cluster.run()
    path = tmp_path / f"{tag}.jsonl"
    rec.to_jsonl(path)
    return reports, path.read_bytes()


class TestSingleNodeParity:
    """``ClusterModel.single(m)`` is byte-identical to the flat ``m``
    for every registered policy: same trace JSONL, equal reports."""

    @pytest.mark.parametrize("policy", registered_policies())
    def test_parity_m8(self, policy, tmp_path):
        machine = HYBRID_PE if policy == "hetero-prediction" else M8
        gov = GovernorSpec(resources=machine.n_cores, policy=policy)
        if policy in ("dlb-lewi", "dlb-hybrid", "dlb-prediction"):
            flat_rep, flat_bytes = _run_pair(machine, gov, tmp_path, "f")
            cl_rep, cl_bytes = _run_pair(
                ClusterModel.single(machine), gov, tmp_path, "c")
            assert flat_rep == cl_rep
        else:
            task_mod._ids = itertools.count()
            g = build_gauss_seidel(**GS_KW)
            flat_rep, flat_bytes = _run_solo(
                machine, g, gov, range(machine.n_cores), tmp_path, "f")
            task_mod._ids = itertools.count()
            g = build_gauss_seidel(**GS_KW)
            cl_rep, cl_bytes = _run_solo(
                ClusterModel.single(machine), g, gov,
                range(machine.n_cores), tmp_path, "c")
            assert flat_rep == cl_rep
        assert flat_bytes == cl_bytes
        assert len(flat_bytes) > 0

    def test_parity_dvfs2(self, tmp_path):
        """Frequency-planning machine: the per-socket DVFS path is also
        byte-identical through the 1-node cluster."""
        gov = GovernorSpec(resources=DVFS2.n_cores, policy="prediction")
        task_mod._ids = itertools.count()
        g = build_gauss_seidel(**GS_KW)
        flat_rep, flat_bytes = _run_solo(
            DVFS2, g, gov, range(DVFS2.n_cores), tmp_path, "f")
        task_mod._ids = itertools.count()
        g = build_gauss_seidel(**GS_KW)
        cl_rep, cl_bytes = _run_solo(
            ClusterModel.single(DVFS2), g, gov,
            range(DVFS2.n_cores), tmp_path, "c")
        assert flat_rep == cl_rep
        assert flat_bytes == cl_bytes

    def test_single_node_report_has_no_node_stamp(self):
        task_mod._ids = itertools.count()
        g = build_gauss_seidel(**GS_KW)
        cluster = SimCluster(ClusterModel.single(M8))
        cluster.add_job(SimJobSpec(name="app", graph=g,
                                   governor=GovernorSpec(
                                       resources=8, policy="busy")))
        rep = cluster.run()["app"]
        assert rep.node is None
        assert rep.transfers == 0


# ---------------------------------------------------------------------------
# multi-node runs: placement, locality guards, transfers


def _specs(gov):
    g1, g2 = _fresh_graphs()
    return [SimJobSpec(name="a", graph=g1, governor=gov),
            SimJobSpec(name="b", graph=g2, governor=gov)]


class TestPlacement:
    def test_round_robin(self):
        homes = ClusterArbiter.place({"a": 9.0, "b": 1.0, "c": 5.0},
                                     [8, 8], policy="round-robin")
        assert homes == {"a": 0, "b": 1, "c": 0}

    def test_predicted_is_best_fit_decreasing(self):
        homes = ClusterArbiter.place({"a": 10.0, "b": 9.0, "c": 1.0},
                                     [16, 16], policy="predicted")
        # heaviest to node 0, next to the now-emptier node 1, then the
        # light app back onto node 0 (most remaining: 6 vs 7 → node 1)
        assert homes["a"] == 0
        assert homes["b"] == 1
        assert homes["c"] == 1
    def test_unknown_policy(self):
        with pytest.raises(ValueError, match="unknown placement"):
            ClusterArbiter.place({"a": 1.0}, [8], policy="nope")

    def test_predicted_demand_orders_apps(self):
        g1, g2 = _fresh_graphs()
        d_gs = predicted_demand(SimJobSpec(name="a", graph=g1,
                                           policy="busy"))
        d_st = predicted_demand(SimJobSpec(name="b", graph=g2,
                                           policy="busy"))
        # stream is embarrassingly parallel, gauss-seidel wavefronted
        assert d_st > d_gs > 0.0

    def test_predicted_demand_empty_graph(self):
        from repro.runtime.task import TaskGraph

        assert predicted_demand(
            SimJobSpec(name="a", graph=TaskGraph(), policy="busy")) == 0.0

    def test_run_multi_node_places_heavy_apart(self):
        cm = ClusterModel.symmetric(M8, 2)
        gov = GovernorSpec(resources=8, policy="dlb-prediction",
                           min_borrow_speed=0.0)
        rep = run_multi_node(cm, _specs(gov), placement="predicted")
        assert set(rep.placement.values()) == {0, 1}   # one app per node
        assert rep.apps["a"].node == rep.placement["a"]
        assert rep.apps["b"].node == rep.placement["b"]

    def test_explicit_placement_mapping(self):
        cm = ClusterModel.symmetric(M8, 2)
        gov = GovernorSpec(resources=8, policy="busy")
        rep = run_multi_node(cm, _specs(gov),
                             placement={"a": 1, "b": 1})
        assert rep.placement == {"a": 1, "b": 1}
        # both apps split node 1's eight cores
        assert rep.apps["a"].makespan > 0
        assert rep.apps["b"].makespan > 0


class TestLocalityGuards:
    CM = ClusterModel.symmetric(M8, 2)

    def test_default_guard_refuses_remote_borrows(self):
        # min_borrow_speed defaults to 1.0: a remote core runs at
        # 1/penalty < 1.0 of an own core, so every remote borrow is a
        # losing borrow and must be refused — and counted.
        gov = GovernorSpec(resources=8, policy="dlb-prediction")
        rep = run_multi_node(self.CM, _specs(gov), placement="predicted")
        total_refusals = sum(r.sharing.get("guard_refusals", 0)
                             for r in rep.apps.values())
        assert total_refusals >= 1
        assert all(r.transfers == 0 for r in rep.apps.values())

    def test_relaxed_guard_allows_remote_borrows(self):
        gov = GovernorSpec(resources=8, policy="dlb-prediction",
                           min_borrow_speed=0.0)
        rep = run_multi_node(self.CM, _specs(gov), placement="predicted")
        assert sum(r.transfers for r in rep.apps.values()) > 0
        assert sum(r.transfer_seconds for r in rep.apps.values()) > 0

    def test_max_borrow_distance_refuses_far_nodes(self):
        # speed guard disabled, distance guard alone: unit distance
        # exceeds 0.5, so remote borrowing is still refused.
        gov = GovernorSpec(resources=8, policy="dlb-prediction",
                           min_borrow_speed=0.0, max_borrow_distance=0.5)
        rep = run_multi_node(self.CM, _specs(gov), placement="predicted")
        assert sum(r.sharing.get("guard_refusals", 0)
                   for r in rep.apps.values()) >= 1
        assert all(r.transfers == 0 for r in rep.apps.values())


# ---------------------------------------------------------------------------
# migration


class TestMigration:
    def test_flat_cluster_rejects_migration(self):
        cluster = SimCluster(M8)
        with pytest.raises(ValueError, match="multi-node"):
            cluster.migrate_job("app", 1)

    def test_migrate_before_run(self):
        cm = ClusterModel.symmetric(M8, 2)
        task_mod._ids = itertools.count()
        g = build_gauss_seidel(**GS_KW)
        cluster = SimCluster(cm)
        cluster.add_job(SimJobSpec(name="a", graph=g,
                                   governor=GovernorSpec(
                                       resources=8, policy="busy"),
                                   node=0))
        cluster.migrate_job("a", 1)
        rep = cluster.run()["a"]
        assert rep.node == 1
        assert rep.migrations == 1
        assert rep.makespan > 0

    def test_migrate_same_node_is_noop(self):
        cm = ClusterModel.symmetric(M8, 2)
        task_mod._ids = itertools.count()
        g = build_gauss_seidel(**GS_KW)
        cluster = SimCluster(cm)
        cluster.add_job(SimJobSpec(name="a", graph=g,
                                   governor=GovernorSpec(
                                       resources=8, policy="busy"),
                                   node=0))
        cluster.migrate_job("a", 0)
        rep = cluster.run()["a"]
        assert rep.node == 0
        assert rep.migrations == 0

    def test_migrate_rejects_full_destination(self):
        cm = ClusterModel.symmetric(M8, 2)
        g1, g2 = _fresh_graphs()
        gov = GovernorSpec(resources=8, policy="busy")
        cluster = SimCluster(cm, broker=ResourceBroker())
        cluster.add_job(SimJobSpec(name="a", graph=g1, governor=gov,
                                   node=0))
        cluster.add_job(SimJobSpec(name="b", graph=g2, governor=gov,
                                   node=1))
        with pytest.raises(ValueError, match="free core"):
            cluster.migrate_job("a", 1)
        with pytest.raises(ValueError, match="out of range"):
            cluster.migrate_job("a", 2)


# ---------------------------------------------------------------------------
# multi-node sim→sim replay: byte-exact round trip


class TestMultiNodeReplay:
    def _record(self, cm, g1, g2, tmp_path, tag):
        gov = GovernorSpec(resources=8, policy="dlb-prediction",
                           min_borrow_speed=0.0)
        broker = ResourceBroker()
        cluster = SimCluster(cm, broker=broker)
        ja = cluster.add_job(SimJobSpec(name="a", graph=g1,
                                        governor=gov, node=0))
        jb = cluster.add_job(SimJobSpec(name="b", graph=g2,
                                        governor=gov, node=1))
        rec = TraceRecorder()
        rec.attach(ja.bus)
        rec.attach(jb.bus)
        reports = cluster.run()
        path = tmp_path / f"{tag}.jsonl"
        rec.to_jsonl(path)
        return reports, path

    def test_round_trip_is_byte_exact(self, tmp_path):
        cm = ClusterModel.symmetric(M8, 2)
        task_mod._ids = itertools.count()
        g1 = build_gauss_seidel(**GS_KW)
        g2 = build_stream(**ST_KW)
        live_reports, live_path = self._record(cm, g1, g2, tmp_path,
                                               "live")
        # the scenario must actually exercise cross-node locality
        assert sum(r.transfers for r in live_reports.values()) > 0

        replayer = TraceReplayer(live_path)
        task_mod._ids = itertools.count()
        ga, _ = replayer.for_app("a").build()
        gb, _ = replayer.for_app("b").build()
        replay_reports, replay_path = self._record(
            cm.replay_model(), ga, gb, tmp_path, "replay")

        assert live_path.read_bytes() == replay_path.read_bytes()
        for app in ("a", "b"):
            assert (replay_reports[app].makespan
                    == live_reports[app].makespan)
            assert (replay_reports[app].transfers
                    == live_reports[app].transfers)

    def test_for_app_unknown_raises_keyerror(self, tmp_path):
        cm = ClusterModel.symmetric(M8, 2)
        task_mod._ids = itertools.count()
        g1 = build_gauss_seidel(**GS_KW)
        g2 = build_stream(**ST_KW)
        _, path = self._record(cm, g1, g2, tmp_path, "t")
        replayer = TraceReplayer(path)
        with pytest.raises(KeyError) as exc:
            replayer.for_app("nope")
        assert "'a'" in str(exc.value) and "'b'" in str(exc.value)


# ---------------------------------------------------------------------------
# satellites: fairness, sockets, spec round trips


class TestJainFairness:
    def test_empty_is_perfectly_fair(self):
        assert jain_fairness({}) == 1.0

    def test_all_zero_is_perfectly_fair(self):
        assert jain_fairness({"a": 0.0, "b": 0.0}) == 1.0

    def test_unequal_is_below_one(self):
        assert jain_fairness({"a": 1.0, "b": 3.0}) < 1.0


class TestSocketTier:
    S2 = MachineModel(
        name="S2", n_cores=8,
        core_types=(CoreType(name="L", count=4, socket=0),
                    CoreType(name="R", count=4, socket=1)),
        remote_socket_penalty=1.5)

    def test_topology_socket_accessors(self):
        topo = self.S2.topology()
        assert topo.n_sockets == 2
        assert [topo.socket_of(i) for i in range(8)] == [0] * 4 + [1] * 4
        assert topo.fastest_first()[0].socket == 0

    def test_cross_socket_penalty_stretches_makespan(self):
        from dataclasses import replace

        from repro.runtime.task import Task, TaskGraph

        def makespan(machine):
            # a root fanning out to one task per core: half the
            # children consume the root's output from the other socket
            task_mod._ids = itertools.count()
            g = TaskGraph()
            root = g.add(Task(type_name="t", cost=1.0,
                              service_time=1e-3))
            for _ in range(8):
                g.add(Task(type_name="t", cost=1.0, service_time=1e-3,
                           deps=[root]))
            cluster = SimCluster(machine)
            cluster.add_job(SimJobSpec(
                name="a", graph=g,
                governor=GovernorSpec(resources=8, policy="busy")))
            return cluster.run()["a"].makespan

        no_penalty = replace(self.S2, remote_socket_penalty=1.0)
        assert makespan(self.S2) > makespan(no_penalty)

    def test_core_type_socket_round_trip(self):
        ct = CoreType(name="R", count=4, socket=1)
        d = ct.to_dict()
        assert d["socket"] == 1
        assert CoreType.from_dict(d) == ct
        # socket 0 stays implicit: pre-hierarchy dicts parse unchanged
        assert "socket" not in CoreType(name="L", count=4).to_dict()

    def test_topology_round_trip(self):
        topo = self.S2.topology()
        assert CoreTopology.from_dict(topo.to_dict()) == topo

    def test_machine_round_trip(self):
        d = self.S2.to_dict()
        assert d["remote_socket_penalty"] == 1.5
        assert MachineModel.from_dict(d) == self.S2
        assert "remote_socket_penalty" not in M8.to_dict()

    def test_governor_spec_round_trip(self):
        spec = GovernorSpec(resources=8, policy="busy",
                            max_borrow_distance=1.5)
        d = spec.to_dict()
        assert d["max_borrow_distance"] == 1.5
        assert GovernorSpec.from_dict(d) == spec
        assert "max_borrow_distance" not in GovernorSpec(
            resources=8, policy="busy").to_dict()

    def test_invalid_socket_rejected(self):
        with pytest.raises(ValueError, match="socket"):
            CoreType(name="X", count=1, socket=-1)
        with pytest.raises(ValueError, match="remote_socket_penalty"):
            MachineModel(name="bad", n_cores=2,
                         remote_socket_penalty=0.5)

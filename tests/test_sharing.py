"""DLB broker + sharing policies (paper §3.3, Table 3)."""

from hypothesis import given, settings, strategies as st

from repro.core.monitoring import TaskMonitor
from repro.core.prediction import CPUPredictor, PredictionConfig
from repro.core.sharing import (DLBHybridPolicy, DLBPredictionPolicy,
                                LeWIPolicy, ResourceBroker)
from repro.core.policies import PollDecision


def _broker2() -> ResourceBroker:
    b = ResourceBroker()
    b.register_job("a", [0, 1, 2, 3])
    b.register_job("b", [4, 5, 6, 7])
    return b


def _broker_n(n_jobs: int, cpus_per_job: int = 2) -> ResourceBroker:
    b = ResourceBroker()
    for i in range(n_jobs):
        base = i * cpus_per_job
        b.register_job(f"j{i}", list(range(base, base + cpus_per_job)))
    return b


class TestBroker:
    def test_lend_acquire_roundtrip(self):
        b = _broker2()
        b.lend("a", 0)
        assert b.pool_size() == 1
        got = b.acquire("b", 2)
        assert got == [0]
        assert b.holder(0) == "b"
        # returning it gives it back to the pool (a has no reclaim flag)
        b.lend("b", 0)
        assert b.holder(0) == ""
        got = b.acquire("a", 1)              # owner prefers its own cpu
        assert got == [0] and b.holder(0) == "a"

    def test_reclaim_flags_borrowed(self):
        b = _broker2()
        b.lend("a", 1)
        assert b.acquire("b", 1) == [1]
        back = b.reclaim("a")
        assert back == []                    # borrowed: comes back later
        assert b.cpu_must_return(1)
        owner = b.return_cpu("b", 1)
        assert owner == "a" and b.holder(1) == "a"

    def test_call_counting(self):
        b = _broker2()
        b.lend("a", 0)
        b.acquire("b", 1)
        b.acquire("b", 1)                    # failed acquire still counts
        assert b.job_calls("a") == 1
        assert b.job_calls("b") == 2
        assert b.total_calls == 3

    def test_noop_acquire_is_not_a_dlb_call(self):
        """Regression: ``acquire(max_n <= 0)`` never reaches the DLB
        library, so it must not inflate the Table-3 call-cost metric —
        ``dlb-prediction`` computes ``acquire_target`` every tick and a
        zero target used to be billed as a real call."""
        b = _broker2()
        assert b.acquire("b", 0) == []
        assert b.acquire("b", -3) == []
        assert b.job_calls("b") == 0
        assert b.total_calls == 0
        # a real (even unsuccessful) request still counts
        assert b.acquire("b", 1) == []
        assert b.job_calls("b") == 1 and b.total_calls == 1

    def test_return_cpu_keeps_pending_reclaim_wanted(self):
        """Regression: returning ONE of several flagged CPUs must not
        clear the owner's reclaim_wanted while other lent CPUs still
        carry return flags — that silently dropped multi-CPU reclaims."""
        b = _broker2()
        b.lend("a", 0)
        b.lend("a", 1)
        assert sorted(b.acquire("b", 2)) == [0, 1]
        assert b.reclaim("a") == []          # both borrowed: flagged
        assert b.cpu_must_return(0) and b.cpu_must_return(1)
        assert b.return_cpu("b", 0) == "a"
        # cpu 1 is still flagged ⇒ the reclaim must stay wanted
        assert b._jobs["a"].reclaim_wanted
        # ...so b's next lend of cpu 1 hands it straight to the owner
        assert b.lend("b", 1) == "a"
        assert b.holder(1) == "a"
        # nothing pending anymore
        assert not b._jobs["a"].reclaim_wanted

    def test_return_last_flagged_cpu_clears_reclaim_wanted(self):
        b = _broker2()
        b.lend("a", 0)
        assert b.acquire("b", 1) == [0]
        b.reclaim("a")
        b.return_cpu("b", 0)
        assert not b._jobs["a"].reclaim_wanted

    @given(st.lists(st.tuples(st.sampled_from(["lend_a", "lend_b",
                                               "acq_a", "acq_b"]),
                              st.integers(0, 7)),
                    max_size=60))
    @settings(max_examples=100, deadline=None)
    def test_conservation(self, ops):
        """Property: every CPU always has exactly one holder ∈ {a, b,
        pool}; pool+held == 8 after any op sequence."""
        b = _broker2()
        for op, cpu in ops:
            if op == "lend_a":
                b.lend("a", cpu)
            elif op == "lend_b":
                b.lend("b", cpu)
            elif op == "acq_a":
                b.acquire("a", 1)
            else:
                b.acquire("b", 1)
            holders = [b.holder(c) for c in range(8)]
            assert all(h in ("a", "b", "") for h in holders)
            assert b.pool_size() == sum(1 for h in holders if h == "")


def _check_invariants(b: ResourceBroker) -> None:
    """Full-state broker invariants (the property tests' oracle):

    * every CPU has exactly one holder — a registered job or the pool;
    * a CPU is in the pool iff its holder is "";
    * ``lent``/``borrowed`` stay disjoint and mutually consistent:
      ``cpu ∈ owner.lent``  ⟺ someone else (or the pool) holds it,
      ``cpu ∈ job.borrowed`` ⟺ job holds a CPU it does not own.
    """
    jobs = b._jobs
    for cpu, owner in b._owner.items():
        holder = b.holder(cpu)
        assert holder == "" or holder in jobs
        assert (holder == "") == (cpu in b._pool)
        assert (cpu in jobs[owner].lent) == (holder != owner)
        for name, acct in jobs.items():
            assert not (acct.owned & acct.borrowed)
            assert (cpu in acct.borrowed) == \
                (holder == name and owner != name)
    assert len(b._pool) == len(set(b._pool))      # no duplicates


class TestBrokerInvariants:
    """Property-style interleavings over all four broker verbs."""

    OPS = ["lend_a", "lend_b", "acq_a", "acq_b", "reclaim_a", "reclaim_b",
           "ret_a", "ret_b"]

    @staticmethod
    def _apply(b: ResourceBroker, op: str, cpu: int) -> None:
        job = "a" if op.endswith("_a") else "b"
        if op.startswith("lend"):
            # lending is only legal for a CPU the job actually runs on
            if b.holder(cpu) == job:
                b.lend(job, cpu)
        elif op.startswith("acq"):
            b.acquire(job, 1 + cpu % 3)
        elif op.startswith("reclaim"):
            b.reclaim(job)
        else:   # cooperative return at a task boundary
            if cpu in b._jobs[job].borrowed and b.cpu_must_return(cpu):
                b.return_cpu(job, cpu)

    @given(st.lists(st.tuples(st.sampled_from(OPS), st.integers(0, 7)),
                    max_size=80))
    @settings(max_examples=200, deadline=None)
    def test_random_interleavings_hold_invariants(self, ops):
        b = _broker2()
        for op, cpu in ops:
            self._apply(b, op, cpu)
            _check_invariants(b)

    def test_deterministic_interleaving(self):
        """A fixed dense sequence: the invariants on one known
        interleaving."""
        b = _broker2()
        seq = [("lend_a", 0), ("lend_a", 1), ("acq_b", 0), ("reclaim_a", 0),
               ("ret_b", 0), ("lend_b", 1), ("lend_b", 4), ("acq_a", 2),
               ("reclaim_b", 0), ("ret_a", 4), ("lend_a", 2), ("acq_b", 1),
               ("reclaim_a", 0), ("ret_b", 2), ("ret_b", 1), ("acq_a", 1)]
        for op, cpu in seq:
            self._apply(b, op, cpu)
            _check_invariants(b)


class TestBrokerInvariantsNJobs:
    """The same partition invariants under random N ∈ [2, 5] jobs —
    multiprogramming is exactly where holder/lent/borrowed bookkeeping
    has historically gone wrong (one borrower's return touching another
    owner's flags, the fairness reservation skewing the pool, …)."""

    VERBS = ["lend", "acq", "reclaim", "ret"]

    @staticmethod
    def _apply(b: ResourceBroker, verb: str, job: str, cpu: int) -> None:
        if verb == "lend":
            # lending is only legal for a CPU the job actually runs on
            if b.holder(cpu) == job:
                b.lend(job, cpu)
        elif verb == "acq":
            b.acquire(job, 1 + cpu % 3)
        elif verb == "reclaim":
            b.reclaim(job)
        else:   # cooperative return at a task boundary
            if cpu in b._jobs[job].borrowed and b.cpu_must_return(cpu):
                b.return_cpu(job, cpu)

    @given(st.integers(2, 5),
           st.lists(st.tuples(st.sampled_from(VERBS), st.integers(0, 4),
                              st.integers(0, 9)),
                    max_size=100))
    @settings(max_examples=150, deadline=None)
    def test_random_n_job_interleavings(self, n_jobs, ops):
        b = _broker_n(n_jobs)
        n_cpus = n_jobs * 2
        for verb, job_i, cpu in ops:
            self._apply(b, verb, f"j{job_i % n_jobs}", cpu % n_cpus)
            _check_invariants(b)

    def test_deterministic_interleaving_5_jobs(self):
        """Dense 5-job sequence."""
        b = _broker_n(5)
        seq = [("lend", "j0", 0), ("lend", "j0", 1), ("lend", "j3", 6),
               ("acq", "j1", 2), ("acq", "j2", 1), ("reclaim", "j0", 0),
               ("ret", "j1", 0), ("ret", "j1", 1), ("ret", "j2", 6),
               ("lend", "j4", 8), ("acq", "j2", 0), ("acq", "j3", 2),
               ("reclaim", "j4", 0), ("ret", "j2", 8), ("lend", "j1", 2),
               ("acq", "j0", 1), ("reclaim", "j3", 0), ("ret", "j0", 6),
               ("acq", "j4", 2), ("lend", "j2", 4)]
        for verb, job, cpu in seq:
            self._apply(b, verb, job, cpu)
            _check_invariants(b)


class TestForeignClaimantFairness:
    """Regression: with ≥3 jobs, own-first-then-FIFO draining let the
    borrower whose tick fired first take the whole pool every round,
    starving a third job indefinitely.  The broker now reserves foreign
    CPUs for less-recently-served claimants with registered unmet
    demand (round-robin via least-recently-served)."""

    @staticmethod
    def _broker3() -> ResourceBroker:
        b = ResourceBroker()
        b.register_job("a", [0, 1])
        b.register_job("b", [2, 3])
        b.register_job("c", [4, 5])
        return b

    def test_three_job_starvation_round_robin(self):
        b = self._broker3()
        b.lend("a", 0)
        b.lend("a", 1)
        # b's tick always fires first: without fairness it would win the
        # whole pool on every round.
        assert b.acquire("b", 2) == [0, 1]
        # c asks, comes up short -> its unmet demand is registered
        assert b.acquire("c", 2) == []
        # the CPUs come back to the pool...
        b.lend("b", 0)
        b.lend("b", 1)
        # ...and b (served more recently than the waiting c) must now
        # leave them for c, even though it asks first again.
        assert b.acquire("b", 2) == []
        assert b.acquire("c", 2) == [0, 1]
        # roles flip: b is now the least recently served waiter
        b.lend("c", 0)
        b.lend("c", 1)
        assert b.acquire("c", 2) == []
        assert b.acquire("b", 2) == [0, 1]

    def test_own_cpus_never_reserved_away(self):
        """The reservation applies to *foreign* claims only: an owner
        reclaiming its own lent silicon always wins."""
        b = self._broker3()
        b.lend("a", 0)
        assert b.acquire("b", 2) == [0]      # b borrows, is "served"
        assert b.acquire("c", 1) == []       # c registers unmet demand
        b.lend("b", 0)                       # back to the pool
        # a's own CPU: c's reservation must not block the owner
        assert b.acquire("a", 1) == [0]

    def test_lending_clears_stale_demand(self):
        b = self._broker3()
        b.lend("a", 0)
        assert b.acquire("b", 1) == [0]
        assert b.acquire("c", 1) == []       # c waiting
        b.lend("b", 0)
        b.lend("c", 4)                       # c lends ⇒ surplus ⇒ no claim
        assert b.acquire("b", 1) == [0]      # reservation gone


class TestTypedBroker:
    """Per-core-type accounting: a P-core lent is not an E-core grant."""

    @staticmethod
    def _typed() -> ResourceBroker:
        b = ResourceBroker(core_type_of=lambda c: "P" if c < 4 else "E")
        b.register_job("a", [0, 1, 4, 5])    # 2 P + 2 E
        b.register_job("b", [2, 3, 6, 7])    # 2 P + 2 E
        return b

    def test_pool_by_type(self):
        b = self._typed()
        b.lend("a", 0)
        b.lend("a", 4)
        b.lend("a", 5)
        assert b.pool_by_type() == {"P": 1, "E": 2}
        assert b.pool_size("P") == 1 and b.pool_size("E") == 2
        assert b.pool_size() == 3

    def test_typed_acquire_filters(self):
        b = self._typed()
        b.lend("a", 0)                       # P into the pool
        b.lend("a", 4)                       # E into the pool
        got = b.acquire("b", 2, core_type="E")
        assert got == [4]                    # never the P core
        assert b.pool_by_type() == {"P": 1}
        assert b.acquire("b", 1, core_type="P") == [0]

    def test_untyped_broker_reports_blank_type(self):
        b = _broker2()
        b.lend("a", 0)
        assert b.pool_by_type() == {"": 1}
        assert not b.typed


class TestSharingPolicies:
    def test_lewi_lends_first_poll(self):
        assert LeWIPolicy().on_poll_empty(0, 4, 1) is PollDecision.LEND

    def test_hybrid_spins_first(self):
        p = DLBHybridPolicy(spin_budget=100)
        assert p.on_poll_empty(0, 4, 99) is PollDecision.SPIN
        assert p.on_poll_empty(0, 4, 100) is PollDecision.LEND

    def test_prediction_lends_only_surplus(self):
        m = TaskMonitor(min_samples=1)
        for i in range(3):
            m.on_task_ready(i, "t", 1.0)
            m.on_task_execute(i, "t", 1.0)
            m.on_task_completed(i, "t", 1.0, 50e-6)
        m.on_task_ready(100, "t", 1.0)       # one window of work
        pred = CPUPredictor(m, n_cpus=4, config=PredictionConfig(
            rate_s=50e-6, min_samples=1, allow_oversubscription=True))
        pred.tick()
        p = DLBPredictionPolicy(pred)
        assert p.on_poll_empty(0, active=4, spin_count=1) \
            is PollDecision.LEND             # δ=4 > Δ=1
        assert p.on_poll_empty(0, active=1, spin_count=1) \
            is PollDecision.SPIN
        assert not p.eager_acquire           # single call per tick
        assert p.acquire_target(active=0, ready_tasks=10) == 1  # Δ−δ

"""Differential shard-equivalence harness for the sharded fleet engine.

The central contract of :mod:`repro.core.sharding`'s partitioned fast
path is *shard-count independence*: for any two shard counts (and any
process count) the same configuration must produce byte-identical
traces, telemetry, queue areas and busy-seconds — sharding is an
execution strategy, never a model change.  A seeded config generator
sweeps fleet shape x arrival process x hedge / tier / fault / timeout
toggles and asserts exactly that; runs that route through the classic
per-shard event loop (faults / tiering / deadlines) are additionally
checked for conservation and consistent merged bookkeeping.  The
``n_shards=1`` path must replay the committed golden traces
byte-for-byte, and on an uncongested fleet the partitioned math must be
bit-equal to the classic engine column-for-column.
"""
import json
import math
import pathlib

import numpy as np
import pytest

from repro.core.arrivals import BurstyOnOff, DiurnalProcess, PoissonProcess
from repro.core.engine import ClusterEngine
from repro.core.faults import ExponentialBackoff, FaultPlan, RepairModel
from repro.core.function import standard_pipeline
from repro.core.scheduler import ClusterSim
from repro.core.sharding import (MailboxOverflow, ShardPlan, cpu_affinity,
                                 run_partitioned)
from repro.core.tiering import TierConfig

GOLDEN = pathlib.Path(__file__).parent / "golden"
PIPES = [standard_pipeline(n) for n in ("asset_damage", "content_moderation")]
MIXED = PIPES + [standard_pipeline("asset_damage", accelerate=False)]
COLUMNS = ("arrival", "finish", "winner", "drive", "start", "service",
           "hedged", "dscs_finish", "cpu_finish")


def make_config(seed: int) -> dict:
    """Seeded config generator: fleet shape x arrival process x
    hedge / tier / fault / timeout toggles."""
    rng = np.random.default_rng(seed)
    n_dscs = int(rng.choice([4, 8, 12, 16]))
    n_cpu = int(rng.choice([n_dscs, n_dscs // 2 + 2, 2 * n_dscs]))
    rate = float(rng.uniform(80.0, 400.0))
    kind = rng.choice(["poisson", "bursty", "diurnal"])
    if kind == "poisson":
        arrivals = PoissonProcess(rate=rate)
    elif kind == "bursty":
        arrivals = BurstyOnOff(rate=rate, burst_factor=3.0)
    else:
        arrivals = DiurnalProcess(rate=rate, amplitude=0.6, period_s=4.0)
    return {
        "n_dscs": n_dscs, "n_cpu": n_cpu, "arrivals": arrivals,
        "duration_s": float(rng.uniform(2.0, 5.0)),
        "hedge": (None if rng.random() < 0.3
                  else float(rng.uniform(0.02, 0.15))),
        "pipes": MIXED if rng.random() < 0.5 else PIPES,
        "tier": (TierConfig(replication_k=2, n_objects=64)
                 if rng.random() < 0.35 else None),
        "faults": (FaultPlan(drive_mtbf_s=4.0, drive_mttr_s=1.5,
                             retry=ExponentialBackoff(base_s=0.05),
                             repair=RepairModel())
                   if rng.random() < 0.35 else None),
        "timeout_s": float(rng.uniform(1.0, 3.0)) if rng.random() < 0.3
                     else None,
        "seed": int(rng.integers(1 << 16)),
    }


def run_cfg(cfg: dict, n_shards: int, processes: int = 1,
            backend: str = "segmented"):
    eng = ClusterEngine(n_dscs=cfg["n_dscs"], n_cpu=cfg["n_cpu"],
                        hedge_budget_s=cfg["hedge"], seed=cfg["seed"],
                        tier=cfg["tier"], faults=cfg["faults"])
    tr = eng.run_sharded(cfg["pipes"], arrivals=cfg["arrivals"],
                         duration_s=cfg["duration_s"], n_shards=n_shards,
                         processes=processes, timeout_s=cfg["timeout_s"],
                         backend=backend)
    return eng, tr


def assert_traces_identical(a, b) -> None:
    for col in COLUMNS:
        assert getattr(a, col).tobytes() == getattr(b, col).tobytes(), col
    assert a.events == b.events


# --------------------------------------------------------------------------
# the differential harness: shard-count / process-count independence
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(12))
def test_sharded_runs_are_shard_count_independent(seed):
    """n_shards=2 and n_shards=4 must agree on every per-request column
    and every aggregate (completions, busy-seconds, queue-depth areas,
    fault/tier counters) — byte-for-byte on the partitioned path,
    aggregate-exact on the shard-isolated fallback."""
    cfg = make_config(seed)
    if cfg["n_dscs"] < 4 or cfg["n_cpu"] < 4:
        pytest.skip("fleet too small for 4 shards")
    e2, t2 = run_cfg(cfg, 2)
    e4, t4 = run_cfg(cfg, 4)
    pure = e2.last_shard_stats["path"] == "partitioned"
    assert pure == (e4.last_shard_stats["path"] == "partitioned")
    if pure:
        # partitioned semantics: the shard count can never change a bit
        assert_traces_identical(t2, t4)
        assert e2._qstate == e4._qstate
        assert e2._pstate == e4._pstate
        assert dict(e2.telemetry.counters) == dict(e4.telemetry.counters)
    else:
        # shard-isolated classic loops: per-request streams are defined
        # by the k-partition, but conservation and the merged books must
        # agree with the per-request columns under every k
        for eng, tr in ((e2, t2), (e4, t4)):
            completed = int(tr.completed.sum())
            abandoned = int((tr.winner == -1).sum())
            assert completed + abandoned == tr.n
            fs = eng.fault_stats()
            if fs is not None:
                assert fs["goodput"]["offered"] == tr.n
                assert fs["goodput"]["completed"] == completed
        assert t2.n == t4.n
        assert np.array_equal(t2.arrival, t4.arrival)


@pytest.mark.parametrize("seed", range(6))
def test_lindley_backends_are_bit_identical(seed):
    """The dense (legacy padded) and segmented (bucketed) Lindley
    solvers must produce byte-identical traces, queue state and
    telemetry on the partitioned fast path — backend choice is an
    execution strategy, never a model change."""
    cfg = {**make_config(seed), "tier": None, "faults": None,
           "timeout_s": None}
    es, ts = run_cfg(cfg, 2, backend="segmented")
    assert es.last_shard_stats["path"] == "partitioned"
    ed, td = run_cfg(cfg, 2, backend="dense")
    assert_traces_identical(ts, td)
    assert es._qstate == ed._qstate
    assert es._pstate == ed._pstate
    assert dict(es.telemetry.counters) == dict(ed.telemetry.counters)


def test_pallas_backend_is_bit_identical():
    """Interpret-mode Pallas solve of a whole sharded run agrees with the
    segmented numpy backend within the kernel's float32 error bound,
    queue by queue (small config: interpret mode is slow).  It is no
    longer bit-identical: the device carries waiting times in float32.
    Where no hedge decision flips, both sides see the same CPU queues and
    every CPU copy lies within its own bound too."""
    from repro.core.engine import _placement
    from repro.core.lindley import fcfs_queues, segment_error_bound

    cfg = {**make_config(1), "tier": None, "faults": None,
           "timeout_s": None, "duration_s": 1.0}
    es, ts = run_cfg(cfg, 2, backend="segmented")
    assert es.last_shard_stats["path"] == "partitioned"
    ep, tp = run_cfg(cfg, 2, backend="pallas")
    assert np.array_equal(ts.arrival, tp.arrival)
    accel = ~np.isnan(ts.dscs_finish)
    assert np.array_equal(accel, ~np.isnan(tp.dscs_finish))
    rids, seg, t, s, start = fcfs_queues(
        _placement(cfg["n_dscs"], ts.n), ts.arrival, ts.dscs_finish,
        cfg["n_dscs"])
    bound = segment_error_bound(seg, t, s)
    assert np.all(np.abs(tp.dscs_finish[rids] - ts.dscs_finish[rids])
                  <= bound)
    flipped = (ts.hedged != tp.hedged)[rids]
    assert np.all(np.abs(start - t - cfg["hedge"])[flipped]
                  <= bound[flipped])
    if flipped.any():
        return
    disp = ts.arrival + np.where(accel, cfg["hedge"] or 0.0, 0.0)
    rids, seg, t, s, _ = fcfs_queues(
        cpu_affinity(cfg["n_dscs"], cfg["n_cpu"], ts.n), disp,
        ts.cpu_finish, cfg["n_cpu"])
    assert np.array_equal(np.isnan(ts.cpu_finish), np.isnan(tp.cpu_finish))
    assert np.all(np.abs(tp.cpu_finish[rids] - ts.cpu_finish[rids])
                  <= segment_error_bound(seg, t, s))


def test_unknown_backend_is_rejected():
    cfg = {**make_config(0), "tier": None, "faults": None,
           "timeout_s": None}
    with pytest.raises(ValueError, match="backend"):
        run_cfg(cfg, 2, backend="flat")


@pytest.mark.parametrize("seed", [0, 3, 5, 8])
def test_sharded_runs_are_process_count_independent(seed):
    """Serial in-process execution and a forked worker pool must produce
    byte-identical traces and identical merged stats."""
    cfg = make_config(seed)
    e1, t1 = run_cfg(cfg, 2, processes=1)
    e2, t2 = run_cfg(cfg, 2, processes=2)
    assert_traces_identical(t1, t2)
    assert e1._qstate == e2._qstate
    assert e1._pstate == e2._pstate
    assert e1._fstate == e2._fstate
    assert e1._tierstate == e2._tierstate
    assert dict(e1.telemetry.counters) == dict(e2.telemetry.counters)


def test_sharded_rerun_is_deterministic():
    cfg = make_config(2)
    _, a = run_cfg(cfg, 2)
    _, b = run_cfg(cfg, 2)
    assert_traces_identical(a, b)


# --------------------------------------------------------------------------
# n_shards=1: the classic loop, golden byte-for-byte
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [13, 21])
def test_single_shard_replays_golden_trace(seed):
    """run_sharded(n_shards=1) IS the classic engine: it must replay the
    committed golden traces field-for-field (float equality, all
    columns)."""
    golden = json.loads((GOLDEN / f"engine_trace_seed{seed}.json").read_text())
    cfg = golden["config"]
    eng = ClusterEngine(n_dscs=cfg["n_dscs"], n_cpu=cfg["n_cpu"],
                        hedge_budget_s=cfg["hedge_budget_s"],
                        seed=cfg["seed"])
    tr = eng.run_sharded([standard_pipeline(n) for n in cfg["pipelines"]],
                         arrivals=PoissonProcess(rate=cfg["rate"]),
                         duration_s=cfg["duration_s"], n_shards=1)
    assert tr.n == golden["n"]
    for i, (r, row) in enumerate(zip(tr.to_results(), golden["results"])):
        got = [r.arrival, r.finish, r.accelerated, r.hedged, r.winner,
               r.drive, r.start, r.service, r.dscs_finish, r.cpu_finish]
        assert got == row, f"request {i} deviates from the pinned trace"


def test_single_shard_matches_run_soa_exactly():
    ea = ClusterEngine(n_dscs=4, n_cpu=8, hedge_budget_s=0.05, seed=9)
    a = ea.run_soa(PIPES, arrivals=PoissonProcess(rate=90.0), duration_s=6.0)
    eb = ClusterEngine(n_dscs=4, n_cpu=8, hedge_budget_s=0.05, seed=9)
    b = eb.run_sharded(PIPES, arrivals=PoissonProcess(rate=90.0),
                       duration_s=6.0, n_shards=1)
    assert_traces_identical(a, b)
    assert ea._qstate == eb._qstate


# --------------------------------------------------------------------------
# partitioned math vs the classic event loop
# --------------------------------------------------------------------------

def test_uncongested_fleet_is_bit_equal_to_classic():
    """With arrivals spaced far apart no queueing ever happens, so the
    classic engine consumes its service draws in request order and both
    models start every copy at its arrival: all columns bit-equal."""
    times = np.arange(200, dtype=np.float64) * 10.0
    e1 = ClusterEngine(n_dscs=4, n_cpu=4, hedge_budget_s=None, seed=3)
    t1 = e1.run_soa(PIPES, times=times)
    e2 = ClusterEngine(n_dscs=4, n_cpu=4, hedge_budget_s=None, seed=3)
    t2 = e2.run_sharded(PIPES, times=times, n_shards=2)
    assert_traces_identical(t1, t2)


def test_sharded_run_simulates_the_same_workload_as_classic():
    """Sharded runs draw the same arrival stream and pipeline picks as
    the classic engine (SeedSequence children 0/1), and route on the
    same placement hash — only queueing dynamics may differ."""
    e1 = ClusterEngine(n_dscs=8, n_cpu=8, hedge_budget_s=0.05, seed=5)
    t1 = e1.run_soa(MIXED, arrivals=PoissonProcess(rate=300.0),
                    duration_s=4.0)
    e2 = ClusterEngine(n_dscs=8, n_cpu=8, hedge_budget_s=0.05, seed=5)
    t2 = e2.run_sharded(MIXED, arrivals=PoissonProcess(rate=300.0),
                        duration_s=4.0, n_shards=2)
    assert np.array_equal(t1.arrival, t2.arrival)
    # accelerated requests carry a dscs_finish in both models; their
    # drive assignment is the same placement hash whenever DSCS wins
    assert np.array_equal(np.isnan(t1.dscs_finish), np.isnan(t2.dscs_finish))
    both_dscs = (t1.winner == 0) & (t2.winner == 0)
    assert np.array_equal(t1.drive[both_dscs], t2.drive[both_dscs])
    assert int(t1.completed.sum()) == int(t2.completed.sum()) == t1.n


# --------------------------------------------------------------------------
# partition plan and mailbox semantics
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_dscs,n_cpu,k", [(8, 8, 2), (12, 7, 3), (9, 4, 4),
                                            (16, 33, 5), (5, 5, 5)])
def test_shard_plan_partitions_the_fleet(n_dscs, n_cpu, k):
    plan = ShardPlan.build(n_dscs, n_cpu, k, seed=1)
    assert plan.drive_bounds[0] == 0 and plan.drive_bounds[-1] == n_dscs
    assert plan.cpu_bounds[0] == 0 and plan.cpu_bounds[-1] == n_cpu
    for s in range(k):
        assert plan.drive_bounds[s + 1] > plan.drive_bounds[s]
        assert plan.cpu_bounds[s + 1] > plan.cpu_bounds[s]
    assert len(set(plan.shard_seeds)) == k
    # stable: rebuilding with more shards never changes earlier seeds
    if k > 2:
        sub = ShardPlan.build(n_dscs, n_cpu, 2, seed=1)
        assert sub.shard_seeds == plan.shard_seeds[:2]
    drives = np.arange(n_dscs)
    owner = plan.shard_of_drive(drives)
    assert owner.min() == 0 and owner.max() == k - 1


def test_shard_plan_rejects_oversharding():
    with pytest.raises(ValueError):
        ShardPlan.build(2, 8, 3, seed=0)
    with pytest.raises(ValueError):
        ShardPlan.build(8, 2, 3, seed=0)


def test_matched_fleet_has_no_cross_shard_traffic():
    """With n_cpu == n_dscs every drive's CPU block is its own shard's
    slice, so all hedge/CPU traffic stays shard-local."""
    eng, _ = run_cfg({"n_dscs": 8, "n_cpu": 8,
                      "arrivals": PoissonProcess(rate=300.0),
                      "duration_s": 4.0, "hedge": 0.05, "pipes": MIXED,
                      "tier": None, "faults": None, "timeout_s": None,
                      "seed": 4}, 4)
    mb = eng.last_shard_stats["mailbox"]
    assert mb["posted"] > 0
    assert mb["cross_shard"] == 0
    assert eng.last_shard_stats["cross_shard_hedges"] == 0


def test_mismatched_fleet_counts_cpu_spillover():
    """Drive blocks that straddle a CPU fencepost produce genuine
    cross-shard mailbox traffic."""
    eng, _ = run_cfg({"n_dscs": 12, "n_cpu": 5,
                      "arrivals": PoissonProcess(rate=300.0),
                      "duration_s": 4.0, "hedge": 0.03, "pipes": MIXED,
                      "tier": None, "faults": None, "timeout_s": None,
                      "seed": 4}, 3)
    assert eng.last_shard_stats["mailbox"]["cross_shard"] > 0


def test_mailbox_capacity_bounds_outstanding_messages():
    eng = ClusterEngine(n_dscs=8, n_cpu=8, hedge_budget_s=0.02, seed=4)
    with pytest.raises(MailboxOverflow):
        eng.run_sharded(MIXED, arrivals=PoissonProcess(rate=400.0),
                        duration_s=4.0, n_shards=2, mailbox_capacity=3)


def test_cpu_affinity_is_fleet_shape_pure():
    a = cpu_affinity(8, 8, 500)
    b = cpu_affinity(8, 8, 500)
    assert np.array_equal(a, b)
    assert a.min() >= 0 and a.max() < 8
    # more drives than CPU nodes: still a valid node for every request
    c = cpu_affinity(16, 3, 500)
    assert c.min() >= 0 and c.max() < 3


# --------------------------------------------------------------------------
# shard-isolated fallback bookkeeping
# --------------------------------------------------------------------------

def test_fallback_merges_fault_and_tier_books():
    cfg = {"n_dscs": 8, "n_cpu": 8, "arrivals": PoissonProcess(rate=250.0),
           "duration_s": 4.0, "hedge": 0.05, "pipes": PIPES,
           "tier": TierConfig(replication_k=2, n_objects=64),
           "faults": FaultPlan(drive_mtbf_s=3.0, drive_mttr_s=1.0,
                               retry=ExponentialBackoff(base_s=0.05),
                               repair=RepairModel()),
           "timeout_s": 2.5, "seed": 17}
    eng, tr = run_cfg(cfg, 2)
    assert eng.last_shard_stats["path"] == "shard-isolated"
    fs = eng.fault_stats()
    assert fs["enabled"]
    assert fs["goodput"]["offered"] == tr.n
    assert len(fs["unavailability"]["per_drive_s"]) == 8
    ts = eng.tier_stats()
    assert ts["replication_k"] == 2
    assert len(ts["cache"]["per_drive"]) in (0, 8)
    completed = int(tr.completed.sum())
    abandoned = int((tr.winner == -1).sum())
    assert completed + abandoned == tr.n
    # drive indices were remapped into the global fleet range
    served = tr.drive[tr.drive >= 0]
    assert served.size and served.max() < 8
    ps = eng.power_stats()
    horizon = eng._qstate["horizon"]
    assert ps["dscs"]["busy_s"] <= 8 * horizon + 1e-9
    assert ps["cpu"]["busy_s"] <= 8 * horizon + 1e-9


def test_fallback_timeout_only_goodput():
    cfg = {"n_dscs": 4, "n_cpu": 4, "arrivals": PoissonProcess(rate=500.0),
           "duration_s": 3.0, "hedge": None, "pipes": PIPES, "tier": None,
           "faults": None, "timeout_s": 0.4, "seed": 6}
    eng, tr = run_cfg(cfg, 2)
    fs = eng.fault_stats()
    assert fs is not None and not fs["enabled"]
    assert fs["deadline_abandoned"] == int((tr.winner == -1).sum())
    assert fs["goodput"]["completed"] == int(tr.completed.sum())


def test_fallback_warns_when_backend_is_ignored():
    """Fallback runs (faults/tiering/deadline/overload) never reach the
    Lindley fast path, so ``backend='pallas'`` there raises instead of
    quietly running on the host; the default backend runs.  So does a
    single-shard run (the classic loop), and ``pallas`` with more than
    one worker process (a forked child cannot use its parent's chip)."""
    eng = ClusterEngine(n_dscs=4, n_cpu=4, seed=2,
                        faults=FaultPlan(drive_mtbf_s=5.0, drive_mttr_s=1.0))
    with pytest.raises(ValueError, match="backend='pallas' cannot run"):
        eng.run_sharded(PIPES, arrivals=PoissonProcess(rate=50.0),
                        duration_s=2.0, n_shards=2, backend="pallas")
    tr = eng.run_sharded(PIPES, arrivals=PoissonProcess(rate=50.0),
                         duration_s=2.0, n_shards=2, backend="segmented")
    assert tr.n > 0
    plain = ClusterEngine(n_dscs=4, n_cpu=4, seed=2)
    with pytest.raises(ValueError, match="n_shards=1"):
        plain.run_sharded(PIPES, arrivals=PoissonProcess(rate=50.0),
                          duration_s=2.0, n_shards=1, backend="pallas")
    with pytest.raises(ValueError, match="processes=1"):
        plain.run_sharded(PIPES, arrivals=PoissonProcess(rate=50.0),
                          duration_s=2.0, n_shards=2, processes=2,
                          backend="pallas")


def test_tiny_run_with_empty_shards():
    """A shard that owns zero requests must not break the merge."""
    times = np.array([0.0, 0.01, 0.02])
    eng = ClusterEngine(n_dscs=8, n_cpu=8, hedge_budget_s=0.05, seed=1,
                        faults=FaultPlan(drive_mtbf_s=50.0, drive_mttr_s=1.0))
    tr = eng.run_sharded(PIPES, times=times, n_shards=4, timeout_s=5.0)
    assert tr.n == 3
    assert int(tr.completed.sum()) + int((tr.winner == -1).sum()) == 3


def test_empty_arrival_stream():
    eng = ClusterEngine(n_dscs=4, n_cpu=4, hedge_budget_s=0.05, seed=1)
    tr = eng.run_sharded(PIPES, times=np.empty(0), n_shards=2)
    assert tr.n == 0


# --------------------------------------------------------------------------
# guard rails
# --------------------------------------------------------------------------

def test_sharded_requires_pipelines():
    eng = ClusterEngine(n_dscs=4, n_cpu=4, seed=0)
    with pytest.raises(ValueError):
        eng.run_sharded(None, arrivals=PoissonProcess(rate=10.0),
                        duration_s=1.0, n_shards=2)


def test_facade_run_sharded_matches_engine():
    sim = ClusterSim(n_dscs=8, n_cpu=8, hedge_budget_s=0.05, seed=7)
    tr = sim.run_sharded(PIPES, rps=200.0, duration_s=3.0, n_shards=2)
    eng = ClusterEngine(n_dscs=8, n_cpu=8, hedge_budget_s=0.05, seed=7)
    tr2 = eng.run_sharded(PIPES, arrivals=PoissonProcess(rate=200.0),
                          duration_s=3.0, n_shards=2)
    assert_traces_identical(tr, tr2)
    assert sim.queue_stats()["dscs"]["max_depth"] >= 1.0

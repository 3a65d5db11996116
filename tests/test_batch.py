"""Batched runtime-parameter engine tests.

Two contracts pinned here:

  * parity — ``price_batch`` agrees with the per-task oracle on every
    Table 1 category, for both the jnp and pallas-interpret backends, to
    float32 reduction tolerance (the batched engine draws the identical
    Threefry stream per (task, path, step));
  * compile count — a multi-task characterise traces O(#families)
    computations, not O(#platforms x #tasks x #rungs), which is the whole
    point of making task parameters runtime operands.
"""
import numpy as np
import pytest

from repro.kernels import ref
from repro.pricing import (
    LocalJaxPlatform,
    SimulatedPlatform,
    TABLE2_SPECS,
    TaskBatch,
    group_by_family,
    group_by_launch,
    price,
    price_batch,
)
from repro.pricing import mc
from repro.pricing.platforms import _TaskMoments
from repro.pricing.solver import PricingSolver
from repro.pricing.workload import TABLE1_CATEGORIES, table1_workload

#: One task from every Table 1 category (mixed BS/Heston mini-workload).
ALL_CATS = [(c, 1) for c, _ in TABLE1_CATEGORIES]


def _ref_price(task, n, seed):
    s, s2 = ref.mc_moments_ref(task, n, seed=seed)
    return mc._finalize(task, s, s2, n)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_price_batch_matches_per_task_all_categories(backend):
    tasks = table1_workload(seed=21, n_steps=8, categories=ALL_CATS)
    n = 2048
    results = price_batch(tasks, n, seed=5, backend=backend)
    for t, r in zip(tasks, results):
        want = _ref_price(t, n, seed=5)
        np.testing.assert_allclose(float(r.price), float(want.price),
                                   rtol=1e-4, atol=1e-5, err_msg=t.category)
        np.testing.assert_allclose(float(r.ci95), float(want.ci95),
                                   rtol=1e-3, atol=1e-6, err_msg=t.category)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_price_batch_ragged_path_counts(backend):
    """Padded/masked batching: each task uses exactly its own first n draws."""
    tasks = table1_workload(seed=22, n_steps=8,
                            categories=[("BS-A", 2), ("H-B", 2)])
    ns = [2048, 100, 4096, 64]
    results = price_batch(tasks, ns, seed=2, backend=backend)
    for t, n, r in zip(tasks, ns, results):
        want = _ref_price(t, n, seed=2)
        np.testing.assert_allclose(float(r.price), float(want.price),
                                   rtol=1e-4, atol=1e-5)
        assert int(r.n_paths) == n


def test_ragged_buckets_bound_padding_waste():
    """Extreme per-task path spreads split into bounded-ratio buckets, so a
    64-path shard never simulates a co-batched task's 100k paths; uniform
    counts (the ladder/calibration hot path) stay a single launch."""
    assert mc._ragged_buckets([1024, 1024, 1024]) == [[0, 1, 2]]
    buckets = mc._ragged_buckets([100_000, 64, 90_000, 80])
    assert sorted(sum(buckets, [])) == [0, 1, 2, 3]
    for b in buckets:
        lo = min(max(1, [100_000, 64, 90_000, 80][k]) for k in b)
        hi = max([100_000, 64, 90_000, 80][k] for k in b)
        assert hi <= lo * mc._RAGGED_RATIO
    # and parity survives the split
    tasks = table1_workload(seed=26, n_steps=8, categories=[("BS-A", 3)])
    ns = [50_000, 128, 200]
    for r, t, n in zip(price_batch(tasks, ns, seed=3), tasks, ns):
        want = _ref_price(t, n, seed=3)
        np.testing.assert_allclose(float(r.price), float(want.price),
                                   rtol=1e-4, atol=1e-5)


def test_price_is_thin_wrapper_over_batch_of_one():
    task = table1_workload(seed=23, n_steps=8, categories=[("H-DB", 1)])[0]
    a = price(task, 1024, seed=7)
    (b,) = price_batch([task], 1024, seed=7)
    assert float(a.price) == float(b.price)
    assert float(a.ci95) == float(b.ci95)


def test_task_batch_requires_family_uniformity():
    bs, heston = table1_workload(seed=24, n_steps=8,
                                 categories=[("BS-A", 1), ("H-A", 1)])
    with pytest.raises(ValueError):
        TaskBatch.from_tasks([bs, heston])
    with pytest.raises(ValueError):
        TaskBatch.from_tasks([])


def test_task_batch_rejects_unknown_payoff_kind():
    """Inside jit the coded-payoff where-chain cannot raise, so bad codes
    must be caught at packing time (the legacy path raised ValueError)."""
    import dataclasses

    from repro.pricing import Option

    (bs,) = table1_workload(seed=24, n_steps=8, categories=[("BS-A", 1)])
    bad = dataclasses.replace(bs, option=Option(payoff=7, strike=100.0))
    with pytest.raises(ValueError, match="unknown payoff"):
        TaskBatch.from_tasks([bad])


def test_group_by_family_partitions_table1():
    tasks = table1_workload(seed=25, n_steps=8)
    groups = group_by_family(tasks)
    assert len(groups) == 9  # the 9 Table 1 families
    seen = sorted(i for _, g in groups for i, _ in g)
    assert seen == list(range(len(tasks)))


def test_characterise_compile_count_is_per_family():
    """2 platforms x 16 tasks (3 families) x 2 rungs: O(#families) traces.

    The per-task scheme traces (and compiles) every (platform, task, rung)
    plus one calibration per task: >= 48 here.  The batched engine is
    bounded above by one trace per (platform, family, ladder shape) plus
    one calibration launch per family; in practice it is tighter still —
    payoff kind is a runtime code and the path count a runtime chunk-loop
    bound, so the whole run needs one trace per (model kind, batch size),
    and every platform shares the jit cache because task parameters are
    runtime operands.
    """
    tasks = table1_workload(seed=11, n_steps=8,
                            categories=[("BS-A", 6), ("BS-DB", 5), ("H-A", 5)])
    assert len(tasks) == 16 and len(group_by_family(tasks)) == 3
    platforms = [
        SimulatedPlatform(TABLE2_SPECS[0], moments=_TaskMoments(calib_paths=4096)),
        LocalJaxPlatform(),
    ]
    ladder = (256, 1024)
    mc.reset_trace_counts()
    solver = PricingSolver(tasks, platforms)
    solver.characterise(path_ladder=ladder, seed=1)
    counts = mc.trace_counts()
    traces = sum(counts.values())
    n_families, n_rungs = 3, len(ladder)
    # The acceptance-level bound: one compile per (family, ladder shape)
    # (+1 per family for the calibration launch shape) ...
    assert 0 < traces <= n_families * (n_rungs + 1), counts
    # ... and the runtime-chunked engine's actual bound: one per launch
    # group (model kind x n_steps x batch size), ladder shapes free.
    assert traces <= len(group_by_launch(tasks)), counts
    assert traces < len(tasks) * n_rungs, counts  # beats per-task compile

    # The fitted models must still be per-(platform, task) and sane.
    assert len(solver.models) == len(platforms) * len(tasks)
    for m in solver.models.values():
        assert m.latency.beta > 0 and m.accuracy.alpha > 0


def test_execute_batches_per_platform_family():
    """The solver's execute path prices every task via batched launches."""
    tasks = table1_workload(seed=12, n_steps=8,
                            categories=[("BS-A", 3), ("H-A", 3)])
    platforms = [
        SimulatedPlatform(TABLE2_SPECS[0], moments=_TaskMoments(calib_paths=4096)),
        SimulatedPlatform(TABLE2_SPECS[9], moments=_TaskMoments(calib_paths=4096)),
    ]
    solver = PricingSolver(tasks, platforms)
    solver.characterise(path_ladder=(512, 2048), seed=1)
    alloc = solver.allocate(accuracy=0.5, method="heuristic")
    report = solver.execute(alloc, accuracy=0.5)
    assert set(report.prices) == {t.task_id for t in tasks}
    assert report.measured_makespan > 0
    assert all(np.isfinite(list(report.prices.values())))


def _tap_launches(monkeypatch) -> list[int]:
    """Tap the platform's launch function, ``mc.price_batch``: one entry a
    launch, the engine traces (jit cache misses) the launch added."""
    grew: list[int] = []
    launch = mc.price_batch

    def tapped(*a, **kw):
        before = sum(mc.trace_counts().values())
        out = launch(*a, **kw)
        grew.append(sum(mc.trace_counts().values()) - before)
        return out

    monkeypatch.setattr(mc, "price_batch", tapped)
    return grew


def _records(recs):
    return [(r.task_id, r.n_paths, r.price, r.ci95) for r in recs]


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_local_platform_warms_a_launch_shape_once(backend, monkeypatch):
    """The first call of a launch shape runs a discarded warm launch and
    then the timed one; a repeat runs the timed launch alone, compiles
    nothing inside it, and returns the same records to the bit."""
    tasks = table1_workload(seed=27, n_steps=8,
                            categories=[("BS-A", 2), ("H-A", 1)])
    ns = [256, 512, 256]
    grew = _tap_launches(monkeypatch)
    plat = LocalJaxPlatform(backend=backend)
    warmed = plat.run_batch(tasks, ns, seed=4)
    assert len(grew) == 2 and grew[1] == 0  # warm, then a cached timed launch
    assert (plat.warm_launches, plat.warm_skips) == (1, 0)

    skipped = plat.run_batch(tasks, ns, seed=4)
    assert grew[2:] == [0]  # the timed launch alone, nothing compiled in it
    assert (plat.warm_launches, plat.warm_skips) == (1, 1)
    assert _records(skipped) == _records(warmed)
    assert all(r.latency > 0 for r in skipped)

    fresh = LocalJaxPlatform(backend=backend)
    assert _records(fresh.run_batch(tasks, ns, seed=4)) == _records(warmed)
    assert len(grew) == 5 and (fresh.warm_launches, fresh.warm_skips) == (1, 0)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("change,warms", [
    ("paths", True),    # another path count: a key not yet warmed
    ("model", True),    # another launch group: another executable
    ("payoff", False),  # same launch key: payoff is a runtime code
])
def test_local_platform_warms_again_for_a_new_key(backend, change, warms,
                                                 monkeypatch):
    first = table1_workload(seed=28, n_steps=8, categories=[("BS-A", 2)])
    ns = [256, 256]
    plat = LocalJaxPlatform(backend=backend)
    plat.run_batch(first, ns, seed=6)
    tasks = {"paths": first,
             "model": table1_workload(seed=28, n_steps=8,
                                      categories=[("H-A", 2)]),
             "payoff": table1_workload(seed=28, n_steps=8,
                                       categories=[("BS-B", 2)])}[change]
    if change == "paths":
        ns = [256, 4096]
    grew = _tap_launches(monkeypatch)
    recs = plat.run_batch(tasks, ns, seed=6)
    assert len(grew) == 1 + warms and grew[-1] == 0
    assert (plat.warm_launches, plat.warm_skips) == (1 + warms, 1 - warms)
    assert [r.n_paths for r in recs] == ns

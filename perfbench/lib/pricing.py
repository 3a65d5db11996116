"""The runner for Monte Carlo pricing: a closed loop of whole workloads.

Set-up characterises every task on the fleet with a fixed ladder of path
counts and solves the allocation (both deterministic from their seeds, so
every run prices the same path counts); the window then runs
``Scheduler.execute`` of that allocation back to back, each workload with
a fresh stream seed. The runner taps the kernel wrapper to count, per
launch, the paths and steps the kernel was asked for.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from . import counts, mc_reference, traffic as traffic_gen, xtrace

__all__ = ["PricingRun"]

#: A CI under this share of the target is float32 rounding: every path of
#: the task paid the same (a digital no-touch whose few paths all
#: survived), and both sides' CI is noise of about a hundredth of the
#: target (PERF.md).
ROUNDING_CI = 0.05


class PricingRun:
    kind = "pricing"

    def __init__(self, config: dict, traffic: dict, seed: int, traced: bool):
        self.config, self.traffic = config, traffic
        self.seed, self.traced = seed, traced
        self.reports: list = []
        self.seeds: list = []
        #: (model kind, n_steps, sum of active paths) per kernel launch
        self.launches: list = []
        self.t0 = self.t1 = 0.0

    def setup(self) -> None:
        from repro.domains.pricing import PricingDomain
        from repro.kernels import ops
        from repro.pricing.platforms import LocalJaxPlatform
        from repro.pricing.workload import table1_workload
        from repro.runtime.scheduler import Scheduler

        c = self.config
        t0 = time.perf_counter()
        self.tasks = table1_workload(
            seed=int(c["table1_seed"]), n_steps=int(c["n_steps"]),
            categories=[tuple(x) for x in c["categories"]])
        self.platform = LocalJaxPlatform(backend=c["backend"])
        self.sched = Scheduler(PricingDomain(self.tasks, [self.platform]))
        self.sched.characterise(seed=int(c["characterise_seed"]),
                                path_ladder=list(c["characterise_ladder"]))
        t1 = time.perf_counter()
        self.alloc = self.sched.allocate(self.ci, method=c["solver"])
        t2 = time.perf_counter()

        orig = ops.mc_moments_batch
        launches = self.launches

        def mc_moments_batch(batch, n_active, *a, **kw):
            launches.append((batch.model_kind, int(batch.n_steps),
                             int(np.sum(n_active))))
            return orig(batch, n_active, *a, **kw)

        ops.mc_moments_batch = mc_moments_batch
        self._restore = lambda: setattr(ops, "mc_moments_batch", orig)
        # the window's launch shapes, once
        self.sched.execute(self.alloc, self.ci,
                           seed=traffic_gen.stream_seed(self.seed, 0))
        self.launches.clear()
        self.phases = {"characterise": t1 - t0, "allocate": t2 - t1,
                       "warm execute": time.perf_counter() - t2}

    @property
    def ci(self) -> float:
        return float(self.traffic["ci"])

    def window(self, seconds: float) -> None:
        import jax

        t0 = time.perf_counter()
        k = 0
        with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
            while time.perf_counter() - t0 < seconds:
                s = traffic_gen.stream_seed(self.seed, 1, k)
                with jax.profiler.TraceAnnotation("bench.execute"):
                    self.reports.append(self.sched.execute(self.alloc,
                                                           self.ci, seed=s))
                self.seeds.append(s)
                k += 1
        self.t1 = time.perf_counter()
        self.t0 = t0

    def _priced(self, report) -> dict:
        """task id -> (price, ci95, n_paths) of its one record."""
        out: dict = {}
        for r in report.records:
            if r.task_id in out:
                raise ValueError(f"task {r.task_id} has two records on a "
                                 "one-platform fleet")
            out[r.task_id] = (float(r.price), float(r.ci95), int(r.n_paths))
        return out

    def unpriced(self) -> int:
        n = 0
        for rep in self.reports:
            got = self._priced(rep)
            n += sum(1 for t in self.tasks if t.task_id not in got
                     or not np.isfinite(got[t.task_id][:2]).all())
        return n

    def end_to_end(self) -> dict:
        return {"pricing_makespan_s": ((self.t1 - self.t0) / len(self.reports),
                                       "s")}

    def counts(self) -> dict:
        return {"attempted": len(self.reports) * len(self.tasks),
                "failed": self.unpriced()}

    def describe(self) -> str:
        """Each workload of the window: its execute's wall time."""
        return "workloads (execute s): " + ", ".join(
            f"{rep.wall_s:.3f}" for rep in self.reports)

    # -- facts the per-layer readers use ---------------------------------
    def needed_ops(self) -> float:
        """Operations the window's workloads need: each task's paths once."""
        kinds = {t.task_id: t.underlying.kind for t in self.tasks}
        steps = {t.task_id: t.n_steps for t in self.tasks}
        return sum(counts.mc_ops(kinds[r.task_id], r.n_paths,
                                 steps[r.task_id])
                   for rep in self.reports for r in rep.records)

    def launched_ops(self) -> float:
        """Operations the kernel launches of the window were asked for."""
        return sum(counts.mc_ops(kind, paths, steps)
                   for kind, steps, paths in self.launches)

    # -- the check --------------------------------------------------------
    def release(self) -> None:
        self._restore()
        self.sched = self.platform = None
        gc.collect()

    def check(self, control: bool = False) -> dict:
        """Against the reference at the same (task, paths, stream seed),
        for tasks drawn from the seed in one workload drawn from the seed:
        the widest price gap in units of the reference's CI, and the
        widest relative CI gap (a CI under ``ROUNDING_CI`` of the target
        counts as that floor). Over every task of every workload: the
        tasks left unpriced, and the widest CI that its path count gives,
        over the target, less one (``ci_expected_over_target``). With
        ``control``, under ``control``, the same numbers had the bfloat16
        reference priced the sampled tasks."""
        chk = self.traffic["check"]
        w = traffic_gen.sample(self.seed, len(self.reports), 1, 3)[0]
        got = self._priced(self.reports[w])
        picked = traffic_gen.sample(self.seed, len(self.tasks),
                                    int(chk["tasks"]), 4)
        out = {"price_gap_ci": 0.0, "ci_gap_rel": 0.0}
        low = {"price_gap_ci": 0.0, "ci_gap_rel": 0.0}
        floor = ROUNDING_CI * self.ci

        def gaps(into, p, c, ref_p, ref_ci):
            scale = max(ref_ci, floor)
            into["price_gap_ci"] = max(into["price_gap_ci"],
                                       abs(p - ref_p) / scale)
            # two CIs under the floor agree: both paid the same on every path
            if max(c, ref_ci) >= floor:
                into["ci_gap_rel"] = max(into["ci_gap_rel"],
                                         abs(c - ref_ci) / scale)

        for i in picked:
            task = self.tasks[i]
            price, ci, n = got[task.task_id]
            row = mc_reference.task_row(task)
            ref_p, ref_ci = mc_reference.price(row, n, self.seeds[w])
            gaps(out, price, ci, ref_p, ref_ci)
            if control:
                gaps(low, *mc_reference.price(row, n, self.seeds[w],
                                              dtype="bfloat16"),
                     ref_p, ref_ci)
        out["ci_expected_over_target"] = self.ci_expected_over_target(
            int(chk["ref_paths"]))
        realised = [got_ci / self.ci - 1.0 for rep in self.reports
                    for _, got_ci, _ in self._priced(rep).values()
                    if math.isfinite(got_ci)]
        out["ci_max_over_target"] = max(realised)
        out["unpriced"] = self.unpriced()
        if control:
            # the control prices at the program's path counts
            low.update(ci_expected_over_target=out["ci_expected_over_target"],
                       unpriced=0)
            out["control"] = low
        return out

    def ci_expected_over_target(self, ref_paths: int) -> float:
        """Widest, over every (task, path count) that the window's
        workloads priced, of the CI those paths give, over the target,
        less one. The CI is the reference's at ``ref_paths`` paths, on a
        stream drawn from the seed, scaled by sqrt(ref_paths / paths): a
        steady reading of what a task's measured CI scatters around, which
        at a few hundred paths of a rare-event payoff is 0 on most streams
        and several times the target on some (PERF.md)."""
        used = {(r.task_id, int(r.n_paths)) for rep in self.reports
                for r in rep.records}
        by_id = {t.task_id: t for t in self.tasks}
        spread = {}
        s = traffic_gen.stream_seed(self.seed, 5)
        worst = -1.0
        for tid, n in sorted(used):
            if tid not in spread:
                row = mc_reference.task_row(by_id[tid])
                spread[tid] = mc_reference.price(row, ref_paths, s)[1]
            ci = spread[tid] * math.sqrt(ref_paths / max(n, 1))
            worst = max(worst, ci / self.ci - 1.0)
        return worst

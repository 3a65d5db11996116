"""Bring-up phases: each drives one main path once, through the entry points
a user calls, and checks what comes out.

``chip_smoke.py`` runs them on a TPU at full size; the tests run them on
the CPU at smoke sizes. Every check raises :class:`SmokeCheckError`; a
phase never catches a failure of the path it drives. The numbers a phase
returns are smoke output from a single run, not measurements.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Sequence

import numpy as np

__all__ = ["SmokeCheckError", "pricing_phase", "serving_phase", "tp_phase",
           "KERNEL_PRICE_TOL", "KERNEL_CI_TOL", "BF16_LOGIT_TOL",
           "TP_F32_REL_TOL"]

#: Kernel vs jnp oracle price at the same (task, n_paths, seed), as a
#: fraction of |price| + CI. Both draw one Threefry stream, so only float32
#: summation order and transcendental implementations differ (~1e-7); a
#: barrier crossing flipped by that rounding moves one path of 65536, a few
#: 1e-5 of a digital's price. A wrong stream or payoff is O(CI) or more.
KERNEL_PRICE_TOL = 1e-3
#: The same comparison for the CI half-width, relative to the oracle's.
KERNEL_CI_TOL = 1e-2

#: Decode-through-cache vs prefill logits in bfloat16, as a fraction of the
#: largest reference logit. bf16 keeps 8 significant bits, so one logit
#: rounds at 2^-8 of its binade; 2^-5 allows a few ulps of drift where the
#: two programs round intermediates differently. A wrong cache position or
#: mask moves logits by O(1), and a computation carried in fp8 (2^-4 per
#: operation) would land above it.
BF16_LOGIT_TOL = 2.0 ** -5

#: tp vs tp=1 logits in float32 (true float32 matmuls), as the largest
#: per-row relative L2 error, on a two-layer cut. float32 reassociation
#: starts near 1e-7; random-weight transformers amplify it with depth
#: (CPU forced host devices, width 256: 3e-5 at 2 layers, 1e-3 at 8,
#: 1e-2 at 16), while a sharding error (wrong head, shard order or missing
#: gather) is O(1) from the first layer.
TP_F32_REL_TOL = 1e-3


class SmokeCheckError(RuntimeError):
    """A phase's output failed its check."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeCheckError(what)


# --------------------------------------------------------------------------
# Pricing: the paper's path, with the Pallas kernel as a platform
# --------------------------------------------------------------------------

def _kernel_vs_oracle(tasks, n_paths: int, per_family: int, seed: int) -> dict:
    """Price a few tasks of each family with the kernel and with the jnp
    oracle at the same (task, n_paths, seed): one Threefry stream, so the
    two agree to float tolerance."""
    from repro.pricing import mc
    from repro.pricing.contracts import group_by_launch

    worst_price = worst_ci = 0.0
    compile_s = {}
    for key, group in group_by_launch(tasks):
        sub = [t for _, t in group[:per_family]]
        t0 = time.perf_counter()
        kern = mc.price_batch(sub, n_paths, seed=seed, backend="pallas")
        kern[-1].price.block_until_ready()
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        kern = mc.price_batch(sub, n_paths, seed=seed, backend="pallas")
        kern[-1].price.block_until_ready()
        compile_s[key[0]] = cold - (time.perf_counter() - t0)
        oracle = mc.price_batch(sub, n_paths, seed=seed, backend="jnp")
        for t, k, o in zip(sub, kern, oracle):
            kp, kc, op, oc = (float(k.price), float(k.ci95),
                              float(o.price), float(o.ci95))
            _check(np.isfinite([kp, kc]).all(), f"task {t.task_id}: kernel "
                   f"returned price {kp}, ci {kc}")
            dp = abs(kp - op) / (abs(op) + oc)
            dc = abs(kc - oc) / max(oc, 1e-12)
            _check(dp <= KERNEL_PRICE_TOL and dc <= KERNEL_CI_TOL,
                   f"task {t.task_id} ({t.category}): kernel {kp}±{kc} vs "
                   f"oracle {op}±{oc}")
            worst_price, worst_ci = max(worst_price, dp), max(worst_ci, dc)
    return {"kernel_compile_s": compile_s,
            "kernel_vs_oracle_price_rel": worst_price,
            "kernel_vs_oracle_ci_rel": worst_ci}


def pricing_phase(tasks: Sequence, accuracy: float = 0.05,
                  oracle_paths: int = 65_536, per_family: int = 4,
                  seed: int = 7) -> dict:
    """The Table 2 fleet plus the Pallas kernel on this process's device,
    through ``Scheduler(PricingDomain)``: characterise, then allocate with
    the MILP and execute at CI ``accuracy``."""
    from repro.domains.pricing import PricingDomain
    from repro.kernels.ops import default_interpret
    from repro.pricing.platforms import LocalJaxPlatform, build_cluster
    from repro.runtime.scheduler import Scheduler

    out = {"compiled_kernel": not default_interpret()}
    out.update(_kernel_vs_oracle(tasks, oracle_paths, per_family, seed))

    local = LocalJaxPlatform(backend="pallas")
    fleet = build_cluster(include_local=False) + [local]
    sched = Scheduler(PricingDomain(tasks, fleet))
    t0 = time.perf_counter()
    sched.characterise()
    t1 = time.perf_counter()
    report = sched.run(quality=accuracy, method="milp")
    out["characterise_s"] = t1 - t0
    out["allocate_execute_s"] = time.perf_counter() - t1
    out["milp_solve_s"] = report.allocation.solve_time

    row = fleet.index(local)
    A = report.allocation.A
    out["chip_share"] = float(A[row].sum() / A.sum())
    chip_paths = sum(r.n_paths for r in report.records
                     if r.platform == local.spec.name)
    out["chip_paths"] = int(chip_paths)
    _check(out["chip_share"] > 0 and chip_paths > 0,
           f"{local.spec.name} was given no work: share "
           f"{out['chip_share']}, {chip_paths} paths")
    priced: dict[int, int] = {}
    for r in report.records:
        priced[r.task_id] = priced.get(r.task_id, 0) + r.n_paths
    missing = [t.task_id for t in tasks if priced.get(t.task_id, 0) <= 0]
    _check(not missing, f"tasks never priced: {missing}")
    prices = np.asarray([report.summary["prices"][t.task_id] for t in tasks])
    _check(np.isfinite(prices).all(), "non-finite pooled price")
    out["tasks_priced"] = len(tasks)
    out["device"] = f"{local.spec.category}/{local.spec.device}"
    out["measured_makespan_s"] = report.measured_makespan
    out["predicted_makespan_s"] = report.predicted_makespan
    return out


# --------------------------------------------------------------------------
# LM serving
# --------------------------------------------------------------------------

def _requests(arch: str, smoke: bool, batch: int, prompt_len: int,
              gens: Sequence[int], max_new_tokens: int):
    from repro.domains.lm_serving import LMRequest

    return [LMRequest(arch, prompt_len=prompt_len, gen_tokens=g, batch=batch,
                      max_new_tokens=max_new_tokens, task_id=i, smoke=smoke)
            for i, g in enumerate(gens)]


def _serve(platform, reqs) -> dict:
    """Characterise + run through ``Scheduler(LMServingDomain)``; check
    every request's tokens were served on ``platform``."""
    from repro.domains.lm_serving import LMServingDomain
    from repro.runtime.scheduler import Scheduler

    sched = Scheduler(LMServingDomain(reqs, [platform]))
    t0 = time.perf_counter()
    sched.characterise()
    report = sched.run(method="milp")
    wall = time.perf_counter() - t0
    served = report.summary["tokens"]
    short = {r.task_id: served[r.task_id] for r in reqs
             if served[r.task_id] < r.gen_tokens}
    _check(not short, f"requests served short of their target: {short}")
    return {"wall_s": wall, "tokens": int(sum(served.values())),
            "device": f"{platform.spec.category}/{platform.spec.device}"}


def _decode_vs_prefill(engine, steps: int) -> dict:
    """Every decode step's logits against the extended prefill's."""
    dec, ref = engine.decode_consistency(steps)
    _check(np.isfinite(dec).all() and np.isfinite(ref).all(),
           f"non-finite logits ({engine.cfg.n_layers} layers)")
    return {"max_abs": float(np.abs(dec - ref).max()),
            "scale": float(np.abs(ref).max()), "steps": steps}


def _ulp_sensitivity(engine) -> float:
    """How far the prefill logits move when every embedding entry moves by
    one or two ulps of the parameter dtype: the rounding noise floor a
    comparison between two programs at this depth cannot go below."""
    import jax.numpy as jnp

    ulp = 2.0 ** -jnp.finfo(engine.params["embed"].dtype).nmant
    base, _ = engine.probe_logits()
    params = engine.params
    engine.params = dict(params, embed=params["embed"] * (1 + ulp))
    try:
        nudged, _ = engine.probe_logits()
    finally:
        engine.params = params
    return float(np.abs(nudged - base).max())


def serving_phase(arch: str = "qwen25_3b", smoke: bool = False,
                  batch: int = 8, prompt_len: int = 512,
                  gens: Sequence[int] = (16, 32, 64),
                  max_new_tokens: int = 64, consistency_steps: int = 8,
                  consistency_depth: int = 2) -> dict:
    """One model served by a ``LocalLMPlatform`` through the scheduler; the
    logits of each of ``consistency_steps`` decode steps are checked finite
    and compared with a prefill over the extended prompt.

    The comparison is gated on the configuration cut to
    ``consistency_depth`` layers (full width, same prompt and cache):
    random-weight transformers amplify a rounding difference chaotically
    with depth, so at full depth two correct bf16 programs disagree by
    O(1) and the difference there is reported, not gated, beside the
    model's own noise floor (:func:`_ulp_sensitivity`). Two layers
    exercise every cache mechanism, per-layer cache indexing included,
    because the layers share one scanned body."""
    from repro.domains.lm_serving import LocalLMPlatform
    from repro.launch.serve import ServeEngine

    reqs = _requests(arch, smoke, batch, prompt_len, gens, max_new_tokens)
    local = LocalLMPlatform()
    t0 = time.perf_counter()
    engine = local.engine(reqs[0])  # build + compile, outside the run
    out = {"build_compile_s": time.perf_counter() - t0}
    out.update(_serve(local, reqs))
    depth = engine.cfg.n_layers
    out[f"decode_vs_prefill_{depth}_layers"] = _decode_vs_prefill(
        engine, consistency_steps)
    out[f"ulp_embed_nudge_{depth}_layers"] = _ulp_sensitivity(engine)

    cut = ServeEngine(dataclasses.replace(engine.cfg,
                                          n_layers=consistency_depth),
                      batch=batch, prompt_len=prompt_len,
                      max_seq=engine.max_seq)
    gate = _decode_vs_prefill(cut, consistency_steps)
    out[f"decode_vs_prefill_{consistency_depth}_layers"] = gate
    limit = BF16_LOGIT_TOL * gate["scale"]
    _check(gate["max_abs"] <= limit,
           f"{consistency_depth}-layer decode logits differ from the "
           f"extended prefill by {gate['max_abs']} (limit {limit})")
    return out


# --------------------------------------------------------------------------
# Tensor parallelism across the chips of one host
# --------------------------------------------------------------------------

def _compare_tp(cfg, tp: int, batch: int, prompt_len: int, max_seq: int,
                steps: int) -> dict:
    """The same configuration served at tp=1 and at tp=``tp`` (one engine
    alive at a time), compared on one teacher-forced token sequence."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import ServeEngine

    probes = []
    for mesh in (None, make_host_mesh(data=1, model=tp)):
        eng = ServeEngine(cfg, batch=batch, prompt_len=prompt_len,
                          max_seq=max_seq, mesh=mesh)
        forced = (None if not probes else
                  np.concatenate([probes[0][0], probes[0][1][:, :-1]],
                                 axis=1).argmax(-1))
        probes.append(eng.probe_logits(steps=steps, tokens=forced))
        del eng
        gc.collect()
    ref = np.concatenate(probes[0], axis=1)
    got = np.concatenate(probes[1], axis=1)
    _check(np.isfinite(got).all(), "non-finite tp logits")
    rel = float((np.linalg.norm(got - ref, axis=-1)
                 / np.linalg.norm(ref, axis=-1)).max())
    top2 = np.sort(ref, axis=-1)[..., -2:]
    decided = (top2[..., 1] - top2[..., 0]) > 2 * np.abs(got - ref).max(-1)
    agree = ref.argmax(-1) == got.argmax(-1)
    return {"rel_l2": rel, "greedy_positions": int(agree.size),
            "greedy_decided": int(decided.sum()),
            "greedy_disagree_where_decided": int((decided & ~agree).sum())}


def tp_phase(arch: str = "yi_9b", smoke: bool = False, tp: int = 4,
             depth: int = 2, batch: int = 8, prompt_len: int = 512,
             gens: Sequence[int] = (16, 32), max_new_tokens: int = 64,
             steps: int = 4) -> dict:
    """Full depth served at tp=``tp`` through ``LocalLMPlatform(tp=...)``;
    then the configuration cut to ``depth`` layers compared at tp=1 and
    tp=``tp``: gated in float32 (true float32 matmuls), reported in
    bfloat16, where rounding differences between the two programs are
    amplified past any useful tolerance (see :func:`serving_phase`)."""
    import jax

    from repro.configs import get_config
    from repro.domains.lm_serving import LocalLMPlatform

    reqs = _requests(arch, smoke, batch, prompt_len, gens, max_new_tokens)
    local = LocalLMPlatform(tp=tp)
    t0 = time.perf_counter()
    engine = local.engine(reqs[0])
    out = {"build_compile_s": time.perf_counter() - t0}
    shard = engine.params["blocks/mlp/w_in"].addressable_shards[0].data.shape
    _check(shard[-1] == engine.cfg.d_ff // tp,
           f"w_in shard {shard} is not 1/{tp} of d_ff {engine.cfg.d_ff}")
    out.update(_serve(local, reqs))
    first, decoded = engine.probe_logits(steps=steps)
    _check(np.isfinite(first).all() and np.isfinite(decoded).all(),
           "non-finite logits at full depth")
    del engine, local
    gc.collect()

    base = get_config(arch)
    base = base.smoke() if smoke else base
    max_seq = prompt_len + steps + 8
    for dtype in ("float32", "bfloat16"):
        cfg = dataclasses.replace(base, n_layers=depth, param_dtype=dtype,
                                  compute_dtype=dtype)
        with jax.default_matmul_precision("float32"):
            cmp = _compare_tp(cfg, tp, batch, prompt_len, max_seq, steps)
        out[f"depth{depth}_{dtype}"] = cmp
    f32 = out[f"depth{depth}_float32"]
    _check(f32["rel_l2"] <= TP_F32_REL_TOL,
           f"tp={tp} logits differ from tp=1 by {f32['rel_l2']} "
           f"(limit {TP_F32_REL_TOL})")
    _check(f32["greedy_disagree_where_decided"] == 0,
           f"tp={tp} greedy tokens differ from tp=1: {f32}")
    return out

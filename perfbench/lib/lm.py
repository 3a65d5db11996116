"""The runner for served language models: one closed loop of batch jobs.

Each job goes through the system's own dispatch path,
``LMServingDomain.dispatch_batch`` -> ``LocalLMPlatform.run_batch`` ->
``ServeEngine``, one job per dispatch, so the device decodes exactly the
rows and steps that the job asks for. The runner taps the engine's
``generate_many`` to keep what it returns (the served tokens, the prefill
time and each decode step's time, all ending in ``block_until_ready``) and,
in a traced run, wraps the engine's prefill and decode calls in host spans.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from . import lm_model, traffic as traffic_gen, xtrace

__all__ = ["LMRun", "check_config"]

#: Configuration-file key -> (system's config attribute, conversion).
_KEYS = {
    "hidden_size": ("d_model", int), "intermediate_size": ("d_ff", int),
    "num_hidden_layers": ("n_layers", int),
    "num_attention_heads": ("n_heads", int),
    "num_key_value_heads": ("n_kv_heads", int), "head_dim": ("hd", int),
    "vocab_size": ("vocab", int), "rms_norm_eps": ("eps", float),
    "rope_theta": ("rope_theta", float),
    "attention_bias": ("qkv_bias", bool),
}


def check_config(cfg, config: dict) -> None:
    """Refuse to run a system configuration other than the file's."""
    bad = {k: (config[k], getattr(cfg, attr)) for k, (attr, conv) in
           _KEYS.items() if k in config and conv(getattr(cfg, attr)) !=
           conv(config[k])}
    dtype = config.get("torch_dtype")
    if dtype and (cfg.param_dtype, cfg.compute_dtype) != (dtype, dtype):
        bad["torch_dtype"] = (dtype, (cfg.param_dtype, cfg.compute_dtype))
    if config.get("hidden_act", "silu") != "silu" or cfg.mlp_variant != "swiglu":
        bad["hidden_act"] = (config.get("hidden_act"), cfg.mlp_variant)
    if bad:
        raise ValueError(f"the system runs another configuration than "
                         f"{config['name']}: file vs system {bad}")


@dataclasses.dataclass
class Job:
    gen: int
    seed: int
    t_dispatch: float
    t_enter: float
    t_end: float
    prefill_s: float
    decode_s: list
    tokens: np.ndarray

    @property
    def ttft_s(self) -> float:
        """Dispatch to first token: the host time before the engine took
        the job, then the engine's prefill (ending in the device sync)."""
        return (self.t_enter - self.t_dispatch) + self.prefill_s


class LMRun:
    kind = "lm"

    def __init__(self, config: dict, traffic: dict, seed: int, traced: bool):
        self.config, self.traffic = config, traffic
        self.seed, self.traced = seed, traced
        self.jobs: list = []
        self._taken: list = []
        self.t0 = self.t1 = 0.0

    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        import jax

        from repro.domains.lm_serving import (LMRequest, LMServingDomain,
                                              LocalLMPlatform)

        t0 = time.perf_counter()
        t = self.traffic
        self.req = LMRequest(self.config["arch"], prompt_len=t["prompt_len"],
                             gen_tokens=1, batch=t["batch"],
                             max_new_tokens=t["max_new_tokens"], task_id=0,
                             smoke=bool(self.config.get("smoke", False)))
        self.platform = LocalLMPlatform(tp=int(self.config["tp"]))
        self.domain = LMServingDomain([self.req], [self.platform])
        engine = self.platform.engine(self.req)
        check_config(engine.cfg, self.config)
        t1 = time.perf_counter()
        shapes = jax.eval_shape(lambda: engine.params)
        shardings = {k: v.sharding for k, v in engine.params.items()}
        engine.params = None
        self.weights = lm_model.make_weights(shapes, self.seed, shardings)
        jax.block_until_ready(self.weights)
        t2 = time.perf_counter()
        engine.params = self.weights
        self._tap(engine)
        # the cell's one shape family, once, with the new weights
        self._run_job(-1, {"gen": 1, "seed": traffic_gen.stream_seed(
            self.seed, 0)})
        self.phases = {"engine build": t1 - t0, "weights": t2 - t1,
                       "warm job": time.perf_counter() - t2}

    def _tap(self, engine) -> None:
        import jax

        orig = engine.generate_many
        taken = self._taken

        def generate_many(gens, seed=0):
            t_enter = time.perf_counter()
            out = orig(gens, seed=seed)
            taken.append((t_enter, out))
            return out

        engine.generate_many = generate_many
        if self.traced:
            for attr, span in (("_prefill", "bench.prefill"),
                               ("_decode", "bench.decode")):
                fn = getattr(engine, attr)

                def wrapped(*a, _fn=fn, _span=span):
                    with jax.profiler.TraceAnnotation(_span):
                        return _fn(*a)

                setattr(engine, attr, wrapped)

    def _run_job(self, index: int, job: dict) -> Job:
        import jax

        req = dataclasses.replace(self.req, gen_tokens=job["gen"],
                                  task_id=max(index, 0))
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            self.domain.dispatch_batch(self.platform, [req], [job["gen"]],
                                       seed=job["seed"])
        t1 = time.perf_counter()
        (t_enter, results), = self._taken
        self._taken.clear()
        res, = results
        return Job(job["gen"], job["seed"], t0, t_enter, t1,
                   res.prefill_latency, list(res.decode_latencies),
                   np.asarray(res.tokens))

    # -- the window -------------------------------------------------------
    def window(self, seconds: float) -> None:
        import jax

        t0 = time.perf_counter()
        c = 0
        with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
            # whole cycles, so that every window holds every stratum; a
            # traced run takes one, since the device trace of two drops
            # seconds of events (PERF.md)
            while True:
                for job in traffic_gen.lm_cycle(self.traffic, self.seed, c):
                    self.jobs.append(self._run_job(len(self.jobs), job))
                c += 1
                if self.traced or time.perf_counter() - t0 >= seconds:
                    break
        self.t0, self.t1 = t0, self.jobs[-1].t_end

    def bad_jobs(self) -> list:
        b = self.traffic["batch"]
        vocab = self.config["vocab_size"]
        return [i for i, job in enumerate(self.jobs)
                if job.tokens.shape != (b, job.gen + 1)
                or job.tokens.min() < 0 or job.tokens.max() >= vocab]

    def end_to_end(self) -> dict:
        tokens = sum(int(job.tokens.size) for job in self.jobs)
        steps = np.concatenate([job.decode_s for job in self.jobs])
        return {
            "tokens_per_s": (tokens / (self.t1 - self.t0), "tokens/s"),
            "itl_p95_ms": (float(np.percentile(steps, 95)) * 1e3, "ms"),
            "ttft_p50_ms": (float(np.median([job.ttft_s for job in self.jobs]))
                            * 1e3, "ms"),
        }

    def counts(self) -> dict:
        return {"attempted": len(self.jobs), "failed": len(self.bad_jobs())}

    def describe(self) -> str:
        """Each job of the window: G, start (s into the window), time to
        first token, whole job and slowest decode step."""
        return "jobs (G @ start: ttft / job s, slowest step ms): " + "; ".join(
            f"{j.gen} @ {j.t_dispatch - self.t0:.2f}: {j.ttft_s:.3f} / "
            f"{j.t_end - j.t_dispatch:.3f}, {1e3 * max(j.decode_s):.2f}"
            for j in self.jobs)

    # -- facts the per-layer readers use ---------------------------------
    def decode_positions(self) -> list:
        """The position of the token each decode step of the window wrote."""
        p = self.traffic["prompt_len"]
        return [p + s for job in self.jobs for s in range(job.gen)]

    def held_weight_bytes(self) -> float:
        """Bytes of weights one device holds, less the embedding table, of
        which a decode step reads only its tokens' rows."""
        total = 0.0
        for name, w in self.weights.items():
            if name == "embed":
                continue
            shard = w.addressable_shards[0].data
            total += shard.size * shard.dtype.itemsize
        return total

    # -- the check --------------------------------------------------------
    def release(self) -> None:
        """Free the system's state (engine, cache, compiled steps); keep the
        weights, which the benchmark made and the reference reads."""
        self.platform._engines.clear()
        self.platform = self.domain = None
        gc.collect()

    def check(self, control: bool = False) -> dict:
        """Widest and mean gap, in logits, by which a served token lies
        below the reference's best, over the longest job of the window and
        others drawn from the seed, on rows drawn from the seed. With
        ``control``,
        the result holds under ``control`` the numbers the check would
        compare had the control (the reference one precision lower) served
        the tokens: the gap of the token it puts first at each position."""
        chk = self.traffic["check"]
        order = sorted(range(len(self.jobs)), key=lambda i: -self.jobs[i].gen)
        picked = [order[0]] + [order[1:][k] for k in traffic_gen.sample(
            self.seed, len(order) - 1, int(chk["jobs"]) - 1, 1)]
        rows = traffic_gen.sample(self.seed, self.traffic["batch"],
                                  int(chk["rows"]), 2)
        out = {"gap_max": 0.0, "gap_mean": 0.0, "served_checked": 0}
        low = {"gap_max": 0.0, "gap_mean": 0.0, "bad_jobs": 0}
        for i in picked:
            job = self.jobs[i]
            prompt = traffic_gen.prompt_tokens(
                self.config["vocab_size"], self.traffic["batch"],
                self.traffic["prompt_len"], job.seed)[rows]
            served = job.tokens[rows]
            gaps = lm_model.served_gaps(self.weights, self.config, prompt,
                                        served)
            out["gap_max"] = max(out["gap_max"], float(gaps.max()))
            out["gap_mean"] += float(gaps.sum())
            out["served_checked"] += int(served.size)
            if control:
                tokens = np.concatenate([prompt, served[:, :-1]], axis=1)
                gaps = lm_model.ref_gaps(self.weights, self.config, tokens,
                                         served.shape[1], None, control=True)
                low["gap_max"] = max(low["gap_max"], float(gaps.max()))
                low["gap_mean"] += float(gaps.sum())
        out["gap_mean"] /= max(out["served_checked"], 1)
        low["gap_mean"] /= max(out["served_checked"], 1)
        out["bad_jobs"] = len(self.bad_jobs())
        if control:
            out["control"] = low
        return out

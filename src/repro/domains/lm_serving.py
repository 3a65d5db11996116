"""LM token serving as a runtime :class:`Domain`.

The second metric-modelled domain (paper §3/§7: the workflow generalises
beyond pricing). A task is a batched generation request against one of the
repo's model configurations (:mod:`repro.configs` + :mod:`repro.models`);
the domain *variable* is the number of decoded tokens, and serving latency
follows exactly the paper's eq. 7:

    f_L(tokens) = beta * tokens + gamma

with beta the per-token decode cost and gamma the constant part (prefill +
dispatch for a local engine, network RTT for a remote one). The quality
metric is the *generation length*: unlike the MC domain there is no
estimator noise, so the quality->work reduction is linear (W = beta o c)
rather than inverse-square — supplied to the solvers via
:func:`repro.core.allocation.linear_work_reduction`. Requests are divisible
the same way MC tasks are: a 64-token generation can be served as chunks
on different platforms (speculative / segmented serving), which is what
lets the same MILP/annealing/heuristic solvers allocate a mixed fleet.

Two platform kinds mirror the pricing domain: ``LocalLMPlatform`` runs the
real JAX engine (:class:`repro.launch.serve.ServeEngine`) with wall-clock
latency; ``SimulatedLMPlatform`` replays a fleet spec from its two
characteristics (application GFLOPS, network RTT) using the model's
analytic FLOPs-per-token.

Both platforms serve with **continuous batching**: the requests of a
dispatch share one running decode batch — joining when their KV pages fit
the platform's memory budget, leaving the step their generation target is
reached — rather than each paying a solo decode pass. Each request's
record carries its *attributed* share of the shared steps, so per-platform
record sums remain the platform's busy time and eq. 7 fits stay linear in
the token count. The KV pages a request pins while resident
(:func:`kv_bytes_per_token` x tokens, from the model shapes in
:mod:`repro.configs`) are also what the domain reports to the allocator as
the resource/capacity dimension: ``resource[p, t] = kv_bytes_per_token``
per decoded token vs ``capacity[p] = spec.mem_bytes`` (HBM), so the
solvers see memory, not just eq. 7 latency.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import threading
import time
import zlib
from collections import deque
from typing import Sequence

import numpy as np

from repro.core.allocation import CapacityError, linear_work_reduction
from repro.core.metrics import CombinedModel, LatencyModel, fit_latency_model
from repro.obs.trace import default_tracer
from repro.runtime.domain import (Domain, MeshPlatformSpec, PlatformSpec,
                                  local_device_spec, seed_for)
from repro.runtime.scenario import Scenario, apply_scenario, salvage_runs

__all__ = [
    "LMRequest", "ServeRecord", "LMServingModel",
    "LocalLMPlatform", "SimulatedLMPlatform",
    "LM_FLEET_SPECS", "LM_MESH_FLEET_SPECS", "build_lm_fleet",
    "smoke_requests", "LMServingDomain", "flops_per_token",
    "kv_bytes_per_token", "request_kv_bytes",
]


# --------------------------------------------------------------------------
# Tasks
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LMRequest:
    """One batched generation request (divisible by generated tokens).

    ``gen_tokens`` is the request's quality target — the domain's default
    quality vector — and ``max_new_tokens`` bounds the KV cache so every
    request family shares one compiled (prefill, decode) executable pair.
    """

    arch: str                 # repro.configs name, e.g. "qwen25_3b"
    prompt_len: int
    gen_tokens: int           # quality target: tokens to generate
    batch: int = 1
    max_new_tokens: int = 64
    task_id: int = 0
    smoke: bool = True        # reduced same-family config (CPU-friendly)

    def __post_init__(self):
        if not 1 <= self.gen_tokens <= self.max_new_tokens:
            raise ValueError(
                f"gen_tokens={self.gen_tokens} must be in "
                f"[1, max_new_tokens={self.max_new_tokens}] — the KV cache "
                "is sized for max_new_tokens and platforms cannot serve past it")

    def config(self):
        from repro.configs import get_config

        cfg = get_config(self.arch)
        return cfg.smoke() if self.smoke else cfg

    @property
    def max_seq(self) -> int:
        return self.prompt_len + self.max_new_tokens + 8


@dataclasses.dataclass(frozen=True)
class ServeRecord:
    """One executed generation shard.

    ``queue_delay`` is time the request spent *waiting* inside its
    dispatch for KV pages to free before joining the decode batch — it
    is not part of ``latency`` (record latencies sum to platform busy
    time, and waiting is not work), but TTFT accounting adds it back.
    """

    platform: str
    task_id: int
    n_tokens: int
    latency: float            # seconds, prefill included
    prefill_latency: float = 0.0
    queue_delay: float = 0.0

    @property
    def tokens_per_s(self) -> float:
        return self.n_tokens / max(self.latency, 1e-12)


@dataclasses.dataclass(frozen=True)
class LMServingModel:
    """Fitted per-(platform, request) metric model: eq. 7 on tokens.

    The combined model is the latency model itself — quality *is* the
    token count, so delta = beta and the work reduction is linear."""

    latency: LatencyModel

    @property
    def combined(self) -> CombinedModel:
        return CombinedModel(delta=self.latency.beta, gamma=self.latency.gamma)


# --------------------------------------------------------------------------
# FLOPs model (for simulated platforms)
# --------------------------------------------------------------------------

def flops_per_token(cfg, batch: int = 1) -> float:
    """Decode FLOPs per generated token: the 2*N_active convention, per
    batch element (a decode step advances the whole batch together)."""
    _, active = cfg.param_count()
    return 2.0 * active * batch


# --------------------------------------------------------------------------
# KV-cache memory model (the capacity dimension)
# --------------------------------------------------------------------------

def kv_bytes_per_token(cfg, batch: int = 1) -> float:
    """Bytes of KV cache one decoded token pins, per request.

    From the model shapes: 2 (K and V) x attention layers x n_kv_heads x
    head_dim x cache dtype x the request's internal batch. Recurrent
    families hold constant-size state (no per-token growth); hybrids pay
    only their attention layers.
    """
    if not cfg.has_decoder or cfg.family == "rwkv":
        return 0.0
    layers = cfg.n_layers
    if cfg.family == "hybrid" and cfg.hybrid_pattern:
        pat = cfg.hybrid_pattern
        layers = sum(1 for i in range(cfg.n_layers) if pat[i % len(pat)] != "rec")
    itemsize = np.dtype(cfg.param_dtype).itemsize
    return float(2 * layers * cfg.n_kv_heads * cfg.hd * itemsize * batch)


@functools.lru_cache(maxsize=1024)
def _kv_per_token(arch: str, smoke: bool, batch: int) -> float:
    from repro.configs import get_config

    cfg = get_config(arch)
    return kv_bytes_per_token(cfg.smoke() if smoke else cfg, batch)


def request_kv_bytes(req: "LMRequest", n_tokens: int | None = None) -> float:
    """KV pages the request holds while resident in a decode batch:
    prompt pages plus one page per decoded token (``max_new_tokens``
    when ``n_tokens`` is not given — the reservation the engine makes)."""
    n = req.max_new_tokens if n_tokens is None else int(n_tokens)
    return _kv_per_token(req.arch, req.smoke, req.batch) * (req.prompt_len + n)


# --------------------------------------------------------------------------
# Platforms
# --------------------------------------------------------------------------

#: A small heterogeneous serving fleet, same schema as the paper's Table 2:
#: application performance (GFLOPS, smoke-model scale) + network RTT +
#: device memory (KV-cache budget, smoke-model scale so workloads of a few
#: hundred KB of pages genuinely contend). The spread is chosen so the
#: constant term matters — the regime where the MILP/annealing solvers
#: beat the proportional heuristic (§6.3).
LM_FLEET_SPECS: list[PlatformSpec] = [
    PlatformSpec("Edge Accelerator", "CPU", "embedded NPU", "on-prem",     2.0,   0.200, mem_bytes=128 * 1024),
    PlatformSpec("Rack GPU",         "GPU", "rack server",  "on-prem",    50.0,   4.000, mem_bytes=512 * 1024),
    PlatformSpec("Cloud GPU",        "GPU", "cloud vm",     "us-east",   200.0,  60.000, mem_bytes=2 * 1024 ** 2),
    PlatformSpec("Cloud Pod",        "GPU", "accelerator pod", "us-west", 800.0, 120.000, mem_bytes=8 * 1024 ** 2),
]

#: The mesh-shaped fleet: the *same* device kind quoted at several
#: tensor-parallel widths, so the solvers genuinely trade one wide mesh
#: (lowest beta, pooled KV, collective-inflated gamma) against many
#: narrow ones (cheap gamma, per-device KV, request-level parallelism).
#: ``gflops``/``rtt_ms``/``mem_bytes`` stay the Rack GPU datasheet row;
#: only the shape varies.
def _rack_mesh(model: int) -> MeshPlatformSpec:
    return MeshPlatformSpec(
        f"Rack GPU 1x{model}", "GPU", "rack server", "on-prem",
        50.0, 4.000, mem_bytes=512 * 1024, mesh_shape=(1, model),
        tp_efficiency=0.85, collective_ms=2.0)


LM_MESH_FLEET_SPECS: list[MeshPlatformSpec] = [
    _rack_mesh(1), _rack_mesh(2), _rack_mesh(4), _rack_mesh(8),
]


class _LMPlatformBase:
    """Shared platform plumbing: the token clamp and batched dispatch."""

    spec: PlatformSpec

    def _clamp(self, req: LMRequest, n_tokens: int) -> int:
        # the KV cache is sized for max_new_tokens; never generate past it
        return min(max(int(n_tokens), 1), req.max_new_tokens)

    def _admission_guard(self, reqs: Sequence[LMRequest],
                         tokens: Sequence[int]) -> None:
        # KV pools across every device of a mesh platform; a single
        # device is the trivial (1, 1) mesh, so total == mem_bytes there
        cap = self.spec.total_mem_bytes
        for req, n in zip(reqs, tokens):
            if request_kv_bytes(req, n) > cap:
                raise CapacityError(
                    f"request {req.task_id}: {request_kv_bytes(req, n):.0f} "
                    f"KV bytes exceed {self.spec.name}'s {cap:.0f}-byte budget "
                    "on its own — no batch schedule can serve it")

    def run(self, req: LMRequest, n_tokens: int, seed: int = 0) -> ServeRecord:
        raise NotImplementedError

    def run_batch(self, reqs: Sequence[LMRequest], n_tokens,
                  seed: int = 0) -> list[ServeRecord]:
        # fallback for third-party platforms: solo serves back-to-back. An
        # outage striking mid-batch re-raises with the completed records
        # attached (see scenario.salvage_runs) so dispatchers keep them
        return salvage_runs(lambda rn: self.run(rn[0], rn[1], seed=seed),
                            list(zip(reqs, _as_token_list(reqs, n_tokens))))


class LocalLMPlatform(_LMPlatformBase):
    """Real platform: serves with the JAX engine, wall-clock latency.

    Engines are cached per request family ((config, batch, prompt_len,
    max_seq) — the compile unit), and warmed outside the timed region, so
    gamma measures prefill + dispatch, not compilation."""

    def __init__(self, name: str = "Local JAX LM", rtt_ms: float = 0.05,
                 tp: int = 1):
        self.spec = local_device_spec(name, rtt_ms, tp)
        self.tp = int(tp)
        self._mesh = None
        self._engines: dict[tuple, object] = {}
        # characterisation threads for different launch groups share this
        # platform; double-checked locking keeps build+warm once per family
        self._engines_lock = threading.Lock()

    def _host_mesh(self):
        if self._mesh is None and self.tp > 1:
            from repro.launch.mesh import make_host_mesh

            self._mesh = make_host_mesh(data=1, model=self.tp)
        return self._mesh

    def engine(self, req: LMRequest):
        """The (built, warmed) engine serving ``req``'s family."""
        key = (req.arch, req.smoke, req.batch, req.prompt_len, req.max_seq)
        eng = self._engines.get(key)
        if eng is None:
            with self._engines_lock:
                eng = self._engines.get(key)
                if eng is None:
                    from repro.launch.serve import ServeEngine

                    eng = ServeEngine(req.config(), batch=req.batch,
                                      prompt_len=req.prompt_len,
                                      max_seq=req.max_seq,
                                      mesh=self._host_mesh())
                    eng.warm()
                    self._engines[key] = eng
        return eng

    def run(self, req: LMRequest, n_tokens: int, seed: int = 0) -> ServeRecord:
        n = self._clamp(req, n_tokens)
        result, = self.engine(req).generate_many([n], seed=seed)
        return ServeRecord(self.spec.name, req.task_id, n,
                           result.total_latency, result.prefill_latency)

    def run_batch(self, reqs: Sequence[LMRequest], n_tokens,
                  seed: int = 0) -> list[ServeRecord]:
        """Continuous batching on the real engine.

        Same-family requests (one dispatch group shares a launch key by
        construction) ride one running decode loop
        (:meth:`repro.launch.serve.ServeEngine.generate_many`) in KV-gated
        admission waves: a wave joins when its pages fit ``mem_bytes``,
        each request leaves the step its target is reached. Mixed-family
        calls fall back to solo serves. The call is one ``lm.run_batch``
        span on the process tracer."""
        with default_tracer().span("lm.run_batch", track="serve",
                                   requests=len(reqs)):
            tokens = [self._clamp(r, n) for r, n in
                      zip(reqs, _as_token_list(reqs, n_tokens))]
            if len({(r.arch, r.smoke, r.batch, r.prompt_len, r.max_seq)
                    for r in reqs}) > 1:
                return super().run_batch(reqs, tokens, seed=seed)
            self._admission_guard(reqs, tokens)
            engine = self.engine(reqs[0])
            out: list[ServeRecord] = []
            wave: list[int] = []
            held = 0.0
            cap = self.spec.total_mem_bytes

            def flush():
                if not wave:
                    return
                results = engine.generate_many([tokens[i] for i in wave],
                                               seed=seed)
                for i, res in zip(wave, results):
                    out.append(ServeRecord(self.spec.name, reqs[i].task_id,
                                           tokens[i], res.total_latency,
                                           res.prefill_latency))

            for i, (req, n) in enumerate(zip(reqs, tokens)):
                need = request_kv_bytes(req, n)
                if wave and held + need > cap:
                    flush()
                    wave, held = [], 0.0
                wave.append(i)
                held += need
            flush()
            return out


class SimulatedLMPlatform(_LMPlatformBase):
    """Replays a fleet spec row from (GFLOPS, RTT, HBM) — the published
    characteristics that determine beta, gamma and the KV budget (§5.1.2):

        latency(tokens) = (prefill + tokens) * flops_tok / GFLOPS
                          + RTT + lognormal jitter

    A dispatch's requests share a continuous decode batch: they join in
    submission order as their KV pages (prompt + decoded tokens) fit
    ``spec.mem_bytes``, decode in lockstep, and leave at their token
    target, freeing pages for the queue. A shared step over ``k`` residents
    costs ``(1 + batch_alpha * (k - 1))`` solo steps (decode is
    memory-bound, so batching is sub-linear) attributed equally — each
    record carries its request's share, so per-platform record sums stay
    the platform's busy time and a solo serve reproduces the formula above
    exactly.
    """

    #: marginal cost of one extra resident per decode step, as a fraction
    #: of a solo step; 0 = perfectly memory-bound, 1 = no batching win.
    batch_alpha: float = 0.6

    def __init__(self, spec: PlatformSpec, jitter: float = 0.02, seed: int = 0,
                 realtime: float = 0.0, scenario: Scenario | None = None):
        self.spec = spec
        self.jitter = jitter
        self._seed = seed
        #: sleep(latency * realtime) per run: occupy host wall clock so
        #: overlap benchmarks see true concurrency; records are unchanged.
        self.realtime = realtime
        #: optional drift scenario, consulted at the platform's virtual
        #: clock (cumulative replayed latency) — same hook as the pricing
        #: simulator's.
        self.scenario = scenario
        self.clock = 0.0

    def attach_scenario(self, scenario: Scenario | None) -> None:
        """Attach (or clear) a scenario and rewind the virtual clock."""
        self.scenario = scenario
        self.clock = 0.0

    def _continuous_plan(self, reqs: Sequence[LMRequest],
                         tokens: Sequence[int]) -> tuple[
                             list[float], list[float], list[float]]:
        """Clean (jitter-free) per-request (prefill, attributed decode,
        queue wait) seconds under KV-gated lockstep continuous batching.

        ``wait[i]`` is the in-dispatch time request ``i`` spent queued for
        KV pages before joining the decode batch — zero for everything
        admitted in the first wave, and the TTFT-visible queueing delay
        for requests gated behind a full cache.
        """
        # mesh platforms: beta falls with the (efficiency-discounted)
        # tensor-parallel width, KV pools across every device
        cap = self.spec.total_mem_bytes
        gps = self.spec.effective_gflops * 1e9
        d = [flops_per_token(r.config(), r.batch) / gps for r in reqs]
        prefill = [r.prompt_len * di for r, di in zip(reqs, d)]
        need = [request_kv_bytes(r, n) for r, n in zip(reqs, tokens)]
        decode = [0.0] * len(reqs)
        wait = [0.0] * len(reqs)
        remaining = [int(n) for n in tokens]
        queue = deque(range(len(reqs)))
        active: list[int] = []
        held = 0.0
        t_clock = 0.0  # wall time inside this dispatch's shared batch
        while queue or active:
            while queue and held + need[queue[0]] <= cap:
                i = queue.popleft()
                active.append(i)
                held += need[i]
                wait[i] = t_clock
            k = len(active)
            share = (1.0 + self.batch_alpha * (k - 1)) / k
            step = min(remaining[i] for i in active)
            for i in active:
                decode[i] += d[i] * share * step
                remaining[i] -= step
            t_clock += share * step * sum(d[i] for i in active)
            for i in [i for i in active if remaining[i] <= 0]:
                active.remove(i)
                held -= need[i]
        return prefill, decode, wait

    def run(self, req: LMRequest, n_tokens: int, seed: int = 0) -> ServeRecord:
        return self.run_batch([req], n_tokens, seed=seed)[0]

    def run_batch(self, reqs: Sequence[LMRequest], n_tokens,
                  seed: int = 0) -> list[ServeRecord]:
        tokens = [self._clamp(r, n) for r, n in
                  zip(reqs, _as_token_list(reqs, n_tokens))]
        self._admission_guard(reqs, tokens)
        prefill, decode, wait = self._continuous_plan(reqs, tokens)

        def finish(item) -> ServeRecord:
            req, n, pre_s, dec_s, wait_s = item
            # stable across processes (unlike hash(): PYTHONHASHSEED
            # randomises str hashing), so seeded runs reproduce exactly
            key = zlib.crc32(f"{self.spec.name}/{req.task_id}/{n}/{seed}".encode())
            rng = np.random.default_rng(key + self._seed)
            jitter = rng.lognormal(0.0, self.jitter)
            pre = pre_s * jitter
            qd = wait_s * jitter
            # gamma picks up the per-hop collective cost on mesh platforms
            latency = (pre_s + dec_s + self.spec.effective_rtt_ms * 1e-3) * jitter
            if self.scenario is not None:
                stretched = apply_scenario(self, latency)
                scale = stretched / max(latency, 1e-300)
                pre *= scale
                qd *= abs(scale)  # waiting stretches with the slowdown too
                latency = stretched
            if self.realtime:
                # corrupt-window runs report a negated latency; the real
                # work still took |latency| of wall clock
                time.sleep(abs(latency) * self.realtime)
            return ServeRecord(self.spec.name, req.task_id, n, latency,
                               prefill_latency=pre, queue_delay=qd)

        # an outage striking mid-batch re-raises with the completed records
        # attached (see scenario.salvage_runs) so dispatchers keep them
        return salvage_runs(finish,
                            list(zip(reqs, tokens, prefill, decode, wait)))


def _as_token_list(reqs: Sequence[LMRequest], n_tokens) -> list[int]:
    return [int(n) for n in
            np.broadcast_to(np.asarray(n_tokens, dtype=np.int64), (len(reqs),))]


def build_lm_fleet(include_local: bool = True,
                   specs: Sequence[PlatformSpec] | None = None,
                   mesh: bool = False) -> list:
    """The evaluation fleet (optionally + the real local engine).

    ``mesh=True`` swaps in :data:`LM_MESH_FLEET_SPECS` — the same device
    kind at several tensor-parallel widths — so the solvers choose between
    one wide mesh and many narrow ones."""
    if specs is None:
        specs = LM_MESH_FLEET_SPECS if mesh else LM_FLEET_SPECS
    fleet: list = [SimulatedLMPlatform(s) for s in specs]
    if include_local:
        fleet.append(LocalLMPlatform())
    return fleet


def smoke_requests(n: int = 4, arch: str = "qwen25_3b", batch: int = 2,
                   prompt_len: int = 8, seed: int = 0) -> list[LMRequest]:
    """A small single-family request workload (one compile unit)."""
    rng = np.random.default_rng(seed)
    return [LMRequest(arch=arch, prompt_len=prompt_len,
                      gen_tokens=int(rng.integers(8, 25)), batch=batch,
                      max_new_tokens=32, task_id=i)
            for i in range(n)]


# --------------------------------------------------------------------------
# The domain
# --------------------------------------------------------------------------

class LMServingDomain(Domain):
    """LM token serving: decode tokens for a generation-length target."""

    name = "lm_serving"
    reduction = staticmethod(linear_work_reduction)
    min_chunk = 1

    #: default online-benchmarking ladder (token counts per rung).
    TOKEN_LADDER: tuple[int, ...] = (2, 4, 8, 16)

    # -- identity ----------------------------------------------------------

    def launch_key(self, req: LMRequest):
        # one compiled (prefill, decode) executable pair per family
        return (req.arch, req.smoke, req.batch, req.prompt_len, req.max_seq)

    def default_quality(self) -> np.ndarray:
        return np.asarray([r.gen_tokens for r in self.tasks], dtype=np.float64)

    # -- capacity: KV-cache memory vs HBM ----------------------------------

    def resource_per_unit(self, platform, req: LMRequest) -> float:
        """Each decoded token pins one KV page on the serving platform for
        the request's residency (continuous batching holds the cache until
        the request leaves). Prompt pages are the per-dispatch analogue of
        gamma — constant, not per-unit — so the linear dimension the
        solvers see is tokens x bytes/token."""
        return _kv_per_token(req.arch, req.smoke, req.batch)

    def platform_capacity(self, platform) -> float:
        """The KV budget the allocator sees: pooled across every device of
        a mesh platform (``total_mem_bytes``; a bare spec's 1x1 mesh makes
        this its plain ``mem_bytes``)."""
        spec = platform.spec
        return float(getattr(spec, "total_mem_bytes",
                             getattr(spec, "mem_bytes", math.inf)))

    # -- characterisation ---------------------------------------------------

    def characterise_batch(self, platform, reqs: Sequence[LMRequest],
                           seed: int = 1, token_ladder=None) -> list[list[ServeRecord]]:
        # launch_key includes max_seq, so max_new_tokens is uniform within a
        # group; clamp the ladder once and dedupe — repeated rungs at the cap
        # would make the (beta, gamma) fit rank-deficient.
        cap = min(r.max_new_tokens for r in reqs)
        ladder = sorted({min(int(n), cap) for n in (token_ladder or self.TOKEN_LADDER)})
        if len(ladder) < 2 and cap > 1:  # need 2 distinct points for eq. 7
            ladder = sorted({max(1, cap // 2), cap})
        # seeds are a stable hash of (platform, launch group, rung), not the
        # loop position, so records are independent of dispatch interleaving
        pname = self.platform_name(platform)
        key = self.launch_key(reqs[0])
        return [platform.run_batch(reqs, n, seed=seed_for(seed, pname, key, i))
                for i, n in enumerate(ladder)]

    def fit_models(self, records: Sequence[ServeRecord]) -> LMServingModel:
        lat = fit_latency_model([r.n_tokens for r in records],
                                [r.latency for r in records])
        return LMServingModel(latency=lat)

    # -- execution ----------------------------------------------------------

    def work_units(self, model: LMServingModel, quality: float) -> float:
        return float(quality)  # quality is measured in work units (tokens)

    def degrade_quality(self, quality: float, step: float) -> float:
        """Shorten the generation target by ``step`` (never below one
        token): the latency win is linear in tokens dropped."""
        return max(float(np.floor(quality * (1.0 - step))), 1.0)

    def record_units(self, record: ServeRecord) -> int:
        return int(record.n_tokens)

    # -- SLO / overload control --------------------------------------------

    def record_ttft(self, record: ServeRecord, end_t: float) -> float:
        """First-token time for a serve record: the record's span starts
        at ``end_t - |latency|``; the first token lands after the
        in-dispatch queue wait plus prefill (clamped into the record's
        span so corrupt/stretched records stay well-ordered)."""
        span = abs(record.latency)
        first = record.queue_delay + abs(record.prefill_latency)
        return end_t - span + min(first, span)

    def task_quality(self, req: LMRequest) -> float:
        return float(req.gen_tokens)

    def dispatch_batch(self, platform, reqs: Sequence[LMRequest],
                       units: Sequence[int], seed: int = 0) -> list[ServeRecord]:
        return platform.run_batch(reqs, units, seed=seed)

    def summarise(self, records: Sequence[ServeRecord], problem) -> dict:
        tokens = {r.task_id: 0 for r in self.tasks}
        latency = {r.task_id: 0.0 for r in self.tasks}
        for rec in records:
            tokens[rec.task_id] += rec.n_tokens
            latency[rec.task_id] += rec.latency
        throughput = {tid: tokens[tid] / latency[tid] if latency[tid] > 0 else math.inf
                      for tid in tokens}
        requested = {t.task_id: float(problem.c[j])
                     for j, t in enumerate(self.tasks)}
        return {"tokens": tokens, "requested_tokens": requested,
                "throughput_tok_s": throughput}

"""Batched serving engine + CLI driver: prefill + decode with a KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen25_3b --smoke \
        --batch 4 --prompt-len 32 --gen 16

Serving latency decomposes exactly like the paper's eq. 7: a constant
prefill cost (gamma) plus a per-token decode cost (beta x tokens). The
reusable :class:`ServeEngine` is what the LM-serving domain
(:mod:`repro.domains.lm_serving`) drives as its local execution platform;
the CLI fits the latency model online from its own measurements and logs
the coefficients, which is what the fleet allocator consumes.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any

from repro.obs.log import get_logger
from repro.obs.trace import default_tracer

log = get_logger("launch.serve")


@dataclasses.dataclass
class GenerationResult:
    """One batched generation: wall-clock split + greedy tokens."""

    prefill_latency: float          # seconds, one prefill of the whole batch
    decode_latencies: list[float]   # seconds per decode step (len == gen)
    tokens: Any                     # (batch, gen + 1) int32 greedy samples

    @property
    def total_latency(self) -> float:
        return self.prefill_latency + sum(self.decode_latencies)


class ServeEngine:
    """Prefill + KV-cache decode engine for one model configuration.

    Owns the params and the jitted prefill/decode executables. ``max_seq``
    is fixed at construction so every ``generate_many`` call with
    ``prompt_len + max(gens) <= max_seq`` reuses the same two executables —
    the engine analogue of the pricing engine's runtime-parameter batching
    (the compile unit is the (config, batch, max_seq) family, not the
    individual request). The decode step donates its cache argument: a
    cache passed to ``_decode`` is consumed, and only the one it returns
    lives on.
    """

    def __init__(self, cfg, batch: int, prompt_len: int, max_seq: int | None = None,
                 seed: int = 0, mesh=None):
        import jax

        from repro.models import build_model

        if not cfg.has_decoder:
            raise ValueError(f"{cfg.name} has no decoder; nothing to serve")
        self.cfg = cfg
        self.batch = batch
        self.prompt_len = prompt_len
        self.max_seq = max_seq or (prompt_len + 64)
        self.model = build_model(cfg)
        self.mesh = mesh
        tp = mesh.shape.get("model", 1) if mesh is not None else 1
        if mesh is not None and mesh.shape.get("data", 1) > 1:
            # a data axis > 1 would reach XLA with mixed manual/auto
            # shardings and abort the process inside the SPMD partitioner
            # — refuse it here with a catchable error instead
            raise ValueError(
                f"ServeEngine only shards the model axis; got a mesh with "
                f"data axis {mesh.shape['data']} — batch-parallel serving "
                f"is not supported yet, pass make_host_mesh(data=1, "
                f"model={tp})")
        key = jax.random.PRNGKey(seed)
        if tp > 1:
            from jax.sharding import NamedSharding

            from repro.launch.tp import (build_tp_step_fns, exchange_counts,
                                         tp_param_specs, validate_tp)

            validate_tp(cfg, tp)
            specs = tp_param_specs(jax.eval_shape(self.model.init, key),
                                   self.model.block_key)
            # placed sharded once, by the init itself: no parameter is ever
            # whole on one device, and the step functions' in_specs match
            # these shardings, so no step reshards them
            self.params = jax.jit(self.model.init, out_shardings={
                k: NamedSharding(mesh, spec) for k, spec in specs.items()})(key)
            prefill, decode = build_tp_step_fns(self.model, specs, mesh,
                                                self.max_seq)
            pre, dec = exchange_counts(prefill, decode, self.params,
                                       self._batch_inputs(seed))
        else:
            self.params = jax.jit(self.model.init)(key)

            # a named function, so the profile shows ``jit_prefill``
            def prefill(params, batch):
                return self.model.prefill(params, batch, self.max_seq)

            decode = self.model.decode_step
            pre = dec = {"collectives": 0, "exchange_bytes": 0}
        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=(1,))
        self.tp = tp
        #: the attributes of every ``serve.prefill`` / ``serve.decode``
        #: span: the mesh width and the exchange one call makes
        #: (:func:`repro.launch.tp.exchange_counts`); ``warm`` adds
        #: ``cache_in_place`` to the decode step's
        self._prefill_args = {"tp": tp, **pre}
        self._decode_args = {"tp": tp, **dec}
        self._warm = False

    def probe_logits(self, seed: int = 0, steps: int = 1, tokens=None):
        """(prefill logits [B, 1, V], logits of ``steps`` decode steps
        [B, steps, V]) as numpy — the probe that compares engines serving
        one configuration on different meshes. Decode reads the greedy
        token of the logits before it, or ``tokens[:, j]`` at step ``j``
        when ``tokens`` [B, steps] is given (teacher forcing, so that two
        engines stay on one token sequence)."""
        import jax.numpy as jnp
        import numpy as np

        self.warm(seed)
        cache, logits = self._prefill(self.params, self._batch_inputs(seed))
        first = np.asarray(logits)
        decoded = []
        for j in range(steps):
            toks = (jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
                    if tokens is None else
                    jnp.asarray(tokens[:, j:j + 1], jnp.int32))
            cache, logits = self._decode(self.params, cache, toks)
            decoded.append(np.asarray(logits))
        return first, np.concatenate(decoded, axis=1)

    def decode_consistency(self, steps: int, seed: int = 0):
        """(logits of ``steps`` greedy decode steps through the KV cache,
        logits at the same positions of one prefill over the prompt
        extended by the tokens those steps read), both [B, steps, V] numpy.
        The two compute the same function; they differ only by the compute
        dtype's rounding. Dense decoder families only (the prefill returns
        the logits of its last ``steps`` positions)."""
        import jax
        import numpy as np

        if not 1 <= steps <= self.max_seq - self.prompt_len:
            raise ValueError(
                f"steps={steps} must be in [1, max_seq - prompt_len = "
                f"{self.max_seq - self.prompt_len}]")
        batch = self._batch_inputs(seed)
        first, decoded = self.probe_logits(seed, steps)
        # decode step j read the greedy token of the logits before it
        read = np.concatenate([first, decoded[:, :-1]], axis=1).argmax(-1)
        tokens = np.concatenate([batch["tokens"], read.astype(np.int32)], axis=1)
        _, ref = jax.jit(lambda p, b: self.model.prefill(
            p, b, self.max_seq, n_logits=steps))(
                self.params, {**batch, "tokens": tokens})
        return decoded, np.asarray(ref)

    def _batch_inputs(self, seed: int):
        from repro.data.pipeline import batch_for

        return batch_for(self.cfg, self.batch, self.prompt_len, seed=seed)

    def warm(self, seed: int = 0) -> None:
        """Compile prefill + decode outside any timed region (the paper's
        gamma measures dispatch, not code generation). Records on the
        decode spans' attributes whether the step consumed every buffer
        of the cache it was given (``cache_in_place`` 1: the donated
        cache was taken, so the step updates it in place)."""
        if self._warm:
            return
        import jax
        import jax.numpy as jnp

        batch = self._batch_inputs(seed)
        cache, logits = self._prefill(self.params, batch)
        toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        _, logits = self._decode(self.params, cache, toks)
        logits.block_until_ready()
        self._decode_args["cache_in_place"] = int(all(
            leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(cache)))
        self._warm = True

    def generate_many(self, gens: list[int], seed: int = 0) -> list[GenerationResult]:
        """Continuous batching: ``len(gens)`` streams share one running
        decode loop and leave it individually.

        All streams join at one joint prefill (its wall clock split
        evenly); the loop then decodes until the *longest* stream's target,
        and each measured step is attributed in equal shares to the streams
        still active at that step — a stream "leaves the batch" the moment
        its own target is reached, so late steps get cheaper per resident
        exactly as on a continuous-batching server. Per-stream sums
        therefore add up to the engine's true busy time, which is what the
        allocator's records must reflect.

        Spans (on the process tracer): ``serve.generate`` around the call,
        ``serve.prefill``, one ``serve.decode`` a step, and inside them
        ``serve.wait`` (the device sync) and ``serve.fetch`` (the token's
        copy to the host). ``serve.generate`` carries the mesh width
        ``tp``; ``serve.prefill`` and ``serve.decode`` carry ``tp`` and
        the call's ``collectives`` and ``exchange_bytes`` (0 at tp=1);
        ``serve.decode`` also ``cache_in_place`` (see :meth:`warm`).
        """
        import jax.numpy as jnp
        import numpy as np

        gens = [int(g) for g in gens]
        if not gens:
            return []
        if min(gens) < 1:
            raise ValueError(f"every stream must decode >= 1 token: {gens}")
        if self.prompt_len + max(gens) > self.max_seq:
            raise ValueError(
                f"prompt {self.prompt_len} + gen {max(gens)} exceeds "
                f"max_seq {self.max_seq}")
        self.warm(seed)
        batch = self._batch_inputs(seed)
        span = default_tracer().span

        with span("serve.generate", track="serve", streams=len(gens),
                  steps=max(gens), tp=self.tp):
            with span("serve.prefill", track="serve", rows=self.batch,
                      **self._prefill_args):
                t0 = time.perf_counter()
                cache, logits = self._prefill(self.params, batch)
                with span("serve.wait", track="serve"):
                    logits.block_until_ready()
                t_prefill = (time.perf_counter() - t0) / len(gens)

            toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            generated = [np.asarray(toks)]
            per_stream: list[list[float]] = [[] for _ in gens]
            for step in range(max(gens)):
                with span("serve.decode", track="serve", **self._decode_args):
                    t0 = time.perf_counter()
                    cache, logits = self._decode(self.params, cache, toks)
                    toks = jnp.argmax(logits[:, -1:],
                                      axis=-1).astype(jnp.int32)
                    with span("serve.wait", track="serve"):
                        toks.block_until_ready()
                    step_lat = time.perf_counter() - t0
                    with span("serve.fetch", track="serve"):
                        generated.append(np.asarray(toks))
                active = [i for i, g in enumerate(gens) if g > step]
                for i in active:
                    per_stream[i].append(step_lat / len(active))
        tokens = np.concatenate(generated, axis=1)
        return [GenerationResult(prefill_latency=t_prefill,
                                 decode_latencies=per_stream[i],
                                 tokens=tokens[:, :g + 1])
                for i, g in enumerate(gens)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen25_3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queue", default="",
                    help="comma-separated per-stream token targets served "
                         "with continuous batching (e.g. 4,16,8); streams "
                         "share one decode loop and leave at their target")
    args = ap.parse_args(argv)

    import numpy as np
    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config
    from repro.core.metrics import fit_latency_model

    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if not cfg.has_decoder:
        log.info(f"{args.arch} has no decoder; nothing to serve")
        return 0

    gens = [int(g) for g in args.queue.split(",") if g] if args.queue else []
    engine = ServeEngine(cfg, batch=args.batch, prompt_len=args.prompt_len,
                         max_seq=args.max_seq or
                         (args.prompt_len + max([args.gen, *gens]) + 8),
                         seed=args.seed)
    if gens:
        results = engine.generate_many(gens, seed=args.seed)
        busy = sum(r.total_latency for r in results)
        for i, (g, r) in enumerate(zip(gens, results)):
            log.info(f"stream {i}: {g} tokens in {r.total_latency*1e3:.1f} ms "
                     f"(attributed share of the running batch)")
        # solo baseline: every stream paying its own prefill + decode pass
        step = busy / max(sum(gens), 1)
        solo = sum(results[0].prefill_latency * len(gens) + step * g for g in gens)
        log.info(f"continuous batch: {sum(gens)} tokens, engine busy "
                 f"{busy*1e3:.1f} ms (solo serves ~{solo*1e3:.1f} ms)")
        return 0
    result, = engine.generate_many([args.gen], seed=args.seed)

    n = np.arange(1, len(result.decode_latencies) + 1)
    cum = np.cumsum(result.decode_latencies)
    lm = fit_latency_model(n, cum)
    log.info(f"prefill: {result.prefill_latency*1e3:.1f} ms "
             f"for {args.batch}x{args.prompt_len}")
    log.info(f"decode:  beta={lm.beta*1e3:.3f} ms/token-step, gamma={lm.gamma*1e3:.3f} ms")
    log.info(f"sample output tokens[0]: {list(map(int, result.tokens[0, :8]))}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

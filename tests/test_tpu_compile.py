"""Compiles of the main path's programs for a described TPU v5e.

The TPU compiler is installed wherever JAX is, and compiles for a chip that
is described, not attached. Nothing runs: these tests catch what the chip's
compiler refuses (kernel tiling, unsupported casts, programs that do not
fit HBM) at no chip time. The topology is described inside a fixture, never
at import: only one process at a time may load the TPU library, and test
workers import every test file.
"""
from __future__ import annotations

import numpy as np
import pytest

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _like(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("family", ["black-scholes", "heston"])
def test_pricing_kernel_compiles_for_v5e(one_chip, family):
    """The batched kernel at Table 1 launch shapes (35 BS tasks, 93 Heston
    tasks, n_steps=256) compiles to a Mosaic custom call."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.pricing import TaskBatch, group_by_launch, table1_workload

    groups = dict(group_by_launch(table1_workload(n_steps=256)))
    batch = TaskBatch.from_tasks([t for _, t in groups[(family, 256)]])
    n = batch.n_tasks
    compiled = ops._mc_moments_batch_jit.lower(
        _like(batch, one_chip),
        jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((1,), jnp.uint32, sharding=one_chip),
        n_paths_max=1 << 20, block_paths=1024, interpret=False).compile()
    assert n == {"black-scholes": 35, "heston": 93}[family]
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen25_3b_decode_fits_one_v5e_chip(one_chip):
    """Full-width Qwen2.5-3B (bf16) decode step, batch 8, 584-slot cache."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model

    model = build_model(get_config("qwen25_3b"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(8, 584))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_step).lower(
        _like(params, one_chip), _like(cache, one_chip), tokens).compile()
    used = _device_bytes(compiled)
    assert 6e9 < used < V5E_HBM_BYTES, used


def _whole_cache_ops(compiled, shape) -> list:
    """(name, op of the fused computation's ROOT, or None for a copy) of
    each fusion or copy whose output has the cache's full ``shape``."""
    import re

    want = f"[{','.join(map(str, shape))}]"
    instr = re.compile(r"^\s*(?:ROOT )?%(\S+) = [a-z0-9]+(\[[\d,]*\])\S* "
                       r"([\w\-]+)\((.*)$")
    roots, ops, comp = {}, [], None
    for line in compiled.as_text().splitlines():
        head = re.match(r"^(?:ENTRY )?(%\S+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = instr.match(line)
        if not m:
            continue
        name, shp, op, rest = m.groups()
        if line.lstrip().startswith("ROOT "):
            roots[comp] = op
        if shp == want and op in ("fusion", "copy"):
            called = re.search(r"calls=(%[\w.\-]+)", rest)
            ops.append((name, called.group(1) if called else None))
    return [(name, roots.get(called)) for name, called in ops]


def _assert_cache_updated_in_place(compiled, cache):
    """The decode step takes the donated cache as its output (aliased: the
    k/v bytes a device holds, plus the position scalar's one tile), needs
    almost no scratch, and writes the cache only with in-place
    dynamic-update-slices: no whole-cache copy."""
    shape = compiled.output_shardings[0]["k"].shard_shape(cache["k"].shape)
    kv = 2 * int(np.prod(shape)) * cache["k"].dtype.itemsize
    m = compiled.memory_analysis()
    assert 0 <= m.alias_size_in_bytes - kv < 1024, (m.alias_size_in_bytes, kv)
    assert m.temp_size_in_bytes < 0.01 * kv, m.temp_size_in_bytes
    ops = _whole_cache_ops(compiled, shape)
    assert ops, "no whole-cache update found"
    assert all(root == "dynamic-update-slice" for _, root in ops), ops


def test_qwen25_3b_decode_step_updates_its_cache_in_place(one_chip):
    """The benchmark cell's decode step (batch 32, cache 161 + 1,024 + 8),
    jitted with the cache donated as ``ServeEngine`` jits it, writes only
    the new position of each layer into the cache it was given."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model

    model = build_model(get_config("qwen25_3b"))
    params = _like(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                   one_chip)
    cache = _like(jax.eval_shape(lambda: model.init_cache(32, 1193)),
                  one_chip)
    tokens = jax.ShapeDtypeStruct((32, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, tokens).compile()
    _assert_cache_updated_in_place(compiled, cache)


def test_qwen25_3b_prefill_writes_its_cache_once(one_chip):
    """The cell's prefill (batch 32, prompt 161) attends over the prompt's
    own keys and writes the 1,193-slot cache once, by padding them: no
    whole-cache copy or fusion, and under a third of the 0.45 GB of
    scratch a prefill through a zero cache took."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model

    model = build_model(get_config("qwen25_3b"))
    params = _like(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                   one_chip)
    tokens = jax.ShapeDtypeStruct((32, 161), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, b: model.prefill(p, b, 1193)).lower(
        params, {"tokens": tokens}).compile()
    shape = model.cache_shape(32, 1193)["k"][0]
    assert not _whole_cache_ops(compiled, shape), \
        _whole_cache_ops(compiled, shape)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.15e9


def test_yi_9b_tp4_init_and_prefill_fit_four_v5e_chips(topo):
    """Full-depth Yi-9B at tp=4: the sharded init places each parameter
    once, and the prefill (batch 8, prompt 512) fits each device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.launch.tp import build_tp_step_fns, tp_param_specs
    from repro.models import build_model

    model = build_model(get_config("yi_9b"))
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = tp_param_specs(shapes, model.block_key)
    shardings = {k: NamedSharding(mesh, s) for k, s in specs.items()}
    replicated = NamedSharding(mesh, P())

    init = jax.jit(model.init, out_shardings=shardings).lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated)).compile()
    placed = _device_bytes(init)
    whole = sum(np.prod(s.shape) * s.dtype.itemsize for s in shapes.values())
    assert whole > V5E_HBM_BYTES > placed, (whole, placed)

    prefill, _ = build_tp_step_fns(model, specs, mesh, max_seq=584)
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shardings[k])
              for k, v in shapes.items()}
    tokens = jax.ShapeDtypeStruct((8, 512), jnp.int32, sharding=replicated)
    compiled = jax.jit(prefill).lower(params, {"tokens": tokens}).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    assert "all-gather" in compiled.as_text()


def test_yi_9b_tp4_decode_step_compiles_with_its_exchange(topo):
    """The Yi-9B tp=4 decode step at the benchmark cell's shapes (batch 32,
    cache 161 + 1,024 + 8), jitted with the cache donated as
    ``ServeEngine`` jits it, compiles as ``jit_decode_step``, fits each
    device, keeps its all-gathers and updates each device's shard of the
    cache in place; the engine's counters for it are
    97 all-gathers (two a layer, one at the logits) and 3/4 of each
    gathered tensor received."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.launch.tp import (build_tp_step_fns, exchange_counts,
                                 tp_param_specs)
    from repro.models import build_model

    cfg = get_config("yi_9b")
    model = build_model(cfg)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = tp_param_specs(shapes, model.block_key)
    params = {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype, sharding=NamedSharding(mesh, specs[k]))
        for k, v in shapes.items()}
    replicated = NamedSharding(mesh, P())
    batch = {"tokens": jax.ShapeDtypeStruct((32, 161), jnp.int32,
                                            sharding=replicated)}
    prefill, decode = build_tp_step_fns(model, specs, mesh, max_seq=1193)
    cache, _ = jax.eval_shape(prefill, params, batch)
    tokens = jax.ShapeDtypeStruct((32, 1), jnp.int32, sharding=replicated)
    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, tokens).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_decode_step")
    assert "all-gather" in text
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    _assert_cache_updated_in_place(compiled, cache)

    _, dec = exchange_counts(prefill, decode, params, batch)
    gathered = cfg.n_layers * 32 * (cfg.n_heads * cfg.hd + cfg.d_ff) \
        + 32 * cfg.vocab
    assert dec == {"collectives": 2 * cfg.n_layers + 1,
                   "exchange_bytes": gathered * 2 * 3 // 4}

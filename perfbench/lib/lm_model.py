"""Weights and the plain reference for the dense decoder configurations.

The benchmark makes the weights itself, from the seed, in one jitted call
on the device and in the dtype they are served in, and hands the same
arrays to the system under test. The reference below reads them by name
and computes the published architecture (pre-norm RMSNorm, rotary
embeddings on half-split dimensions, grouped-query causal attention with
optional q/k/v bias, SwiGLU MLP, untied output head) in float32 with
``highest`` matmul precision. It imports nothing of the system under test.

Weight layout, by name (L layers stacked on the leading axis):
``embed`` [V, d], ``unembed`` [d, V], ``ln_f`` [d],
``blocks/ln1``, ``blocks/ln2`` [L, d],
``blocks/attn/wq`` [L, d, H, hd], ``blocks/attn/wk|wv`` [L, d, KV, hd],
``blocks/attn/bq`` [L, H, hd], ``blocks/attn/bk|bv`` [L, KV, hd],
``blocks/attn/wo`` [L, H, hd, d],
``blocks/mlp/w_gate|w_in`` [L, d, F], ``blocks/mlp/w_out`` [L, F, d].
"""
from __future__ import annotations

import functools
import math
import zlib

import numpy as np

__all__ = ["BRANCH_SCALE", "LOWER", "weight_scale", "make_weights",
           "ref_gaps", "served_gaps"]

#: Multiplier on the residual branches' output projections (``wo``,
#: ``w_out``) over the 1/sqrt(fan_in) scale of every other matrix. A
#: random-weight decoder of this depth at 1/sqrt(fan_in) throughout
#: amplifies rounding with depth; with the branches scaled down every layer
#: still adds a sizeable part of the residual stream, and the served
#: tokens' gap below the reference's best stays nearer rounding (CPU,
#: width 1024, 36 layers: 0.021 against 0.032).
BRANCH_SCALE = 0.25
#: Scale of the q/k/v biases (the program's own init leaves them zero,
#: which would leave the bias path untested).
BIAS_SCALE = 0.1


def weight_scale(name: str, shape) -> float:
    """Standard deviation of the normal draw for one weight, by name; 0
    marks a norm gain, which is set to one."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf.startswith("ln"):
        return 0.0
    if leaf == "embed":
        return 1.0
    if leaf in ("bq", "bk", "bv"):
        return BIAS_SCALE
    if leaf == "wo":
        return BRANCH_SCALE / math.sqrt(shape[-3] * shape[-2])
    if leaf == "w_out":
        return BRANCH_SCALE / math.sqrt(shape[-2])
    if leaf in ("wq", "wk", "wv"):
        return 1.0 / math.sqrt(shape[-3])
    return 1.0 / math.sqrt(shape[-2])


def _leaf_key(seed, name: str):
    import jax

    return jax.random.fold_in(jax.random.key(seed),
                              zlib.crc32(name.encode()) & 0x7FFFFFFF)


def make_weights(shapes: dict, seed: int, shardings: dict | None = None):
    """Every weight of ``shapes`` (name -> ShapeDtypeStruct) drawn from
    ``seed`` in one jitted call, in each leaf's own dtype, placed as
    ``shardings`` says (name -> Sharding) when given."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)

    def build(seed_arr):
        out = {}
        for name in names:
            s = shapes[name]
            scale = weight_scale(name, s.shape)
            if scale == 0.0:
                out[name] = jnp.ones(s.shape, s.dtype)
            else:
                z = jax.random.normal(_leaf_key(seed_arr, name), s.shape,
                                      jnp.float32)
                out[name] = (z * scale).astype(s.dtype)
        return out

    # the seed is an argument, so every seed runs the one cached program
    fn = jax.jit(build, out_shardings=(None if shardings is None else
                                       {n: shardings[n] for n in names}))
    return fn(jnp.uint32(seed % (1 << 32)))


# --------------------------------------------------------------------------
# The reference forward pass
# --------------------------------------------------------------------------

#: The control's precision for each stated one: the nearest below it.
LOWER = {"bfloat16": "float8_e4m3fn", "float16": "float8_e4m3fn",
         "float32": "bfloat16"}


def _round(x, dtype: str):
    """Round trip through ``dtype``; float8 (e4m3) with a per-tensor
    scale, as a float8 program would quantise a product's operand."""
    import jax.numpy as jnp

    if not dtype.startswith("float8"):
        return x.astype(dtype).astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _mm(low: str | None):
    """A matrix product in float32, its operands first rounded to
    ``low`` where given."""
    import jax.numpy as jnp

    def mm(spec, a, b):
        if low:
            a, b = _round(a, low), _round(b, low)
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)

    return mm


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * g


def _rope(x, theta):
    """x [B, S, H, D]: rotate the two halves of each head by position."""
    import jax.numpy as jnp

    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(eps: float, theta: float, low: str | None, dtype: str):
    """One decoder layer in float32; with ``low``, the control's: every
    product's operands rounded to ``low``, and the residual stream kept
    in the stated ``dtype`` between sublayers, as a program that runs its
    products one precision lower keeps it."""
    import jax
    import jax.numpy as jnp

    mm = _mm(low)
    keep = (lambda v: _round(v, dtype)) if low else (lambda v: v)

    @jax.jit
    def layer(x, w):
        f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        h = _rms(x, f32["ln1"], eps)
        q = mm("bsd,dhk->bshk", h, f32["attn/wq"])
        k = mm("bsd,dhk->bshk", h, f32["attn/wk"])
        v = mm("bsd,dhk->bshk", h, f32["attn/wv"])
        if "attn/bq" in f32:
            q, k, v = q + f32["attn/bq"], k + f32["attn/bk"], v + f32["attn/bv"]
        q, k = _rope(q, theta), _rope(k, theta)
        b, s, nh, hd = q.shape
        kvh = k.shape[2]
        q = q.reshape(b, s, kvh, nh // kvh, hd)
        att = mm("bskgd,btkd->bkgst", q, k) / math.sqrt(hd)
        causal = np.tril(np.ones((s, s), bool))
        att = jnp.where(causal, att, -jnp.inf)
        att = jax.nn.softmax(att, axis=-1)
        o = mm("bkgst,btkd->bskgd", att, v).reshape(b, s, nh, hd)
        x = keep(x + mm("bshk,hkd->bsd", o, f32["attn/wo"]))
        h = _rms(x, f32["ln2"], eps)
        g = mm("bsd,df->bsf", h, f32["mlp/w_gate"])
        u = mm("bsd,df->bsf", h, f32["mlp/w_in"])
        return keep(x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, f32["mlp/w_out"]))

    return layer


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, low: str | None):
    import jax
    import jax.numpy as jnp

    mm = _mm(low)

    @jax.jit
    def head(x, ln_f, unembed):
        h = _rms(x, ln_f.astype(jnp.float32), eps)
        return mm("bsd,dv->bsv", h, unembed.astype(jnp.float32))

    return head


def _gaps(ref, pick_from):
    """Gap below the reference's best of the token that ``pick_from`` puts
    first ([B, S, V] each -> [B, S])."""
    import jax.numpy as jnp

    tok = jnp.argmax(pick_from, -1)
    got = jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]
    return ref.max(-1) - got


def ref_gaps(weights: dict, cfg: dict, tokens: np.ndarray, n_out: int,
             served: np.ndarray | None, control: bool = False,
             pos_chunk: int = 128) -> np.ndarray:
    """Gaps, in logits, at the last ``n_out`` positions of ``tokens``
    ([B, S] int, prompt followed by the served tokens that were read back).

    With ``served`` ([B, n_out], the token served at each of those
    positions), the gap is the reference's best logit less the logit of
    the served token. With ``control`` the same is read for the token that
    the control (``_layer_fn``, one precision below the configuration's:
    float8 products for bfloat16) puts first, at the same positions. Runs layer by
    layer on the first device, with the weights gathered there one layer
    at a time.
    """
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    n_layers = weights["blocks/ln1"].shape[0]
    blocks = {k[len("blocks/"):]: v for k, v in weights.items()
              if k.startswith("blocks/")}

    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    dtype = cfg["torch_dtype"]

    def run(low: str | None):
        layer = _layer_fn(eps, theta, low, dtype)
        x = jax.device_put(weights["embed"], dev)[
            jax.device_put(jnp.asarray(tokens), dev)].astype(jnp.float32)
        for li in range(n_layers):
            w = {k: jax.device_put(v[li], dev) for k, v in blocks.items()}
            x = layer(x, w)
        return x[:, -n_out:]

    head_ref = _head_fn(eps, None)
    ln_f = jax.device_put(weights["ln_f"], dev)
    unembed = jax.device_put(weights["unembed"], dev)
    gaps = jax.jit(_gaps)
    with jax.default_matmul_precision("highest"):
        x_ref = run(None)
        if control:
            x_low = run(LOWER[dtype])
            head_low = _head_fn(eps, LOWER[dtype])
        out = []
        for c0 in range(0, n_out, pos_chunk):
            sl = slice(c0, min(c0 + pos_chunk, n_out))
            ref = head_ref(x_ref[:, sl], ln_f, unembed)
            if control:
                pick = head_low(x_low[:, sl], ln_f, unembed)
            else:
                pick = jax.nn.one_hot(jnp.asarray(served[:, sl]),
                                      ref.shape[-1], dtype=jnp.float32)
            out.append(np.asarray(gaps(ref, pick)))
    return np.concatenate(out, axis=1)


def served_gaps(weights: dict, cfg: dict, prompt: np.ndarray,
                served: np.ndarray, **kw) -> np.ndarray:
    """Gaps of the served tokens ([B, G+1]: the first from the prompt's
    logits, each later one from the decode step that read the one before)
    against the reference run over the prompt and the served tokens."""
    tokens = np.concatenate([prompt, served[:, :-1]], axis=1)
    return ref_gaps(weights, cfg, tokens, served.shape[1], served, **kw)

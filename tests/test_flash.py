"""The causal flash-attention kernel (``kernels/flash.py``) and the calls of
``layers.attention`` that take it.

Kernel: in interpret mode, at S of two kernel blocks (so the block above
the diagonal is skipped), the forward and dq/dk/dv against the chunked
online softmax (``_chunked_sdpa``) and the dense ``_sdpa`` path, for query
heads per KV head 3 and 5 and head_dim 64 and 128.

Dispatch: ``attention()`` lowered for a TPU (``lowering_platforms``: no
chip and no TPU library needed) holds a Mosaic call only for plain causal
self-attention in training at S > 1024; every other caller keeps its path.
A multi-device mesh is covered by ``tests/test_tpu_compile.py`` (described
v5e:2x2) and ``tests/dist_check.py`` (8 CPU devices).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash
from repro.models import layers
from repro.runtime.elastic import make_mesh

B, KV = 2, 2
S = 2 * flash.BLOCK
# worst |kernel - reference| over the reference's largest |value|, per leaf;
# both sides compute in float32 on the CPU (errors read 2e-7 to 1.1e-6)
TOL = {"out": 1e-5, "dq": 1e-5, "dk": 1e-5, "dv": 1e-5}


def _inputs(rep, hd):
    ks = jax.random.split(jax.random.PRNGKey(rep * 1000 + hd), 4)
    return (jax.random.normal(ks[0], (B, S, KV * rep, hd)),
            jax.random.normal(ks[1], (B, S, KV, hd)),
            jax.random.normal(ks[2], (B, S, KV, hd)),
            jax.random.normal(ks[3], (B, S, KV * rep, hd)))


def _out_and_grads(attend, q, k, v, ct):
    out = attend(q, k, v)
    grads = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) * ct),
                     argnums=(0, 1, 2))(q, k, v)
    return dict(zip(("out", "dq", "dk", "dv"), (out,) + grads))


@functools.lru_cache(maxsize=None)
def _kernel(rep, hd):
    q, k, v, ct = _inputs(rep, hd)
    attend = jax.jit(functools.partial(flash.causal_attention,
                                       scale=1 / np.sqrt(hd)))
    return _out_and_grads(attend, q, k, v, ct)


@pytest.mark.parametrize("reference", ["chunked", "dense"])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("rep", [3, 5])
def test_kernel_matches_the_sdpa_paths(monkeypatch, rep, hd, reference):
    if reference == "dense":          # S within one chunk: no online softmax
        monkeypatch.setattr(layers, "_Q_CHUNK", S)
    else:                             # 2 x 2 chunks of the online softmax
        monkeypatch.setattr(layers, "_Q_CHUNK", S // 2)
        monkeypatch.setattr(layers, "_KV_CHUNK", S // 2)
    q, k, v, ct = _inputs(rep, hd)
    want = _out_and_grads(
        lambda q, k, v: layers._sdpa(q, k, v, scale=1 / np.sqrt(hd),
                                     causal=True), q, k, v, ct)
    got = _kernel(rep, hd)
    for leaf, tol in TOL.items():
        err = float(jnp.max(jnp.abs(got[leaf] - want[leaf]))
                    / jnp.max(jnp.abs(want[leaf])))
        assert err <= tol, (leaf, err)


# ------------------------------------------------------------------ dispatch

CFG = layers.AttnConfig(d_model=64, n_heads=6, n_kv_heads=2, head_dim=64)


def _mosaic_calls(fn, *args) -> int:
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    return text.count("tpu_custom_call")


def _attend(cfg, seq=2048, **kw):
    p = layers.attention_init(jax.random.PRNGKey(0), cfg)
    x = jax.ShapeDtypeStruct((B, seq, cfg.d_model), jnp.float32)
    extra = {k: v for k, v in kw.items() if k != "xk"}

    def loss(p, x, *xk):
        out, _ = layers.attention(p, cfg, x, xk=xk[0] if xk else None,
                                  **extra)
        return jnp.sum(out)
    args = (p, x) + ((kw["xk"],) if "xk" in kw else ())
    return _mosaic_calls(jax.grad(loss), *args)


def test_causal_training_attention_takes_the_kernel():
    # the forward saving its residuals, dq and dk/dv
    assert _attend(CFG) == 3


def _cache():
    return (jnp.zeros((B, 2048, CFG.n_kv_heads, CFG.head_dim)),) * 2


@pytest.mark.parametrize("case", [
    "short", "ragged", "cache", "pos", "sliding_window", "non_causal",
    "cross", "seq_pinned"])
def test_other_callers_keep_their_path(case):
    cfg, seq, kw = CFG, 2048, {}
    if case == "short":                 # S <= 1024: the dense path
        seq = 1024
    elif case == "ragged":              # not a multiple of the kernel block
        seq = 1152
    elif case == "cache":               # serving prefill into a cache
        kw = {"cache": _cache(), "pos": jnp.int32(0)}
    elif case == "pos":                 # positions offset by a chunk
        kw = {"pos": jnp.int32(0)}
    elif case == "sliding_window":
        cfg = dataclasses.replace(CFG, sliding_window=256)
    elif case == "non_causal":
        cfg = dataclasses.replace(CFG, causal=False)
    elif case == "cross":
        kw = {"xk": jax.ShapeDtypeStruct((B, 2048, CFG.d_model),
                                         jnp.float32)}
    elif case == "seq_pinned":          # alone on a one-device mesh
        cfg = dataclasses.replace(CFG, batch_axes=("data",),
                                  seq_axis="model")
        with jax.sharding.set_mesh(make_mesh((1, 1), ("data", "model"))):
            assert _attend(cfg, seq) == 0
            assert _attend(CFG, seq) == 3
        return
    assert _attend(cfg, seq, **kw) == 0

"""Plain float32 reference of a dense decoder LM and its first training steps.

Llama/Qwen2 block (as in the published ``LlamaForCausalLM`` and
``Qwen2ForCausalLM``): pre-RMSNorm, grouped-query attention with rotary
embeddings (rotate-half convention), optional q/k/v biases, SwiGLU MLP, final
RMSNorm, tied or untied output head.  Loss: mean next-token cross entropy
over every position.  Every product runs at ``Precision.HIGHEST`` in float32.

Optimizer, as the DMuon paper states it: Muon with Nesterov momentum 0.95,
five Polar Express Newton-Schulz steps on the Frobenius-normalized update,
scale ``0.2 * sqrt(max(m, n))`` and learning rate 0.02, on every hidden
weight matrix; AdamW (lr 3e-4, betas 0.9 / 0.95, eps 1e-8, no weight decay,
bias-corrected) on embeddings, the head, norms and biases.  ``mode="adamw"``
takes AdamW for every leaf.  Newton-Schulz is written in its plain form
``X <- aX + b(XX^T)X + c(XX^T)^2 X`` (the program iterates in Gram space;
the two agree in exact arithmetic).

Weights come from the seed by the model zoo's published init convention:
normal(0, 1/sqrt(d_in)) projections, normal(0, 0.02) embedding and head,
zero biases, unit norm scales, with the PRNG keys split in the same tree
order.  Departure from the published models: random weights, and the vocab
slice of a reduced configuration.

Nothing here imports the program.  Work runs row by row (one batch row per
gradient call, summed) and layer by layer (scan with remat), so it fits one
chip at the benchmark's sizes; given several devices, each leaf is split
over them along its longest axis that they divide, and the compiler places
the rest.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST

POLAR_EXPRESS = (   # arXiv:2505.16932, safety-factored, first five steps
    (8.28721201814563, -23.595886519098837, 17.300387312530933),
    (4.107059111542203, -2.9478499167379106, 0.5448431082926601),
    (3.9486908534822946, -2.908902115962949, 0.5518191394370137),
    (3.3184196573706015, -2.488488024314874, 0.51004894012372),
    (2.300652019954817, -1.6689039845747493, 0.4188073119525673),
)
MUON = dict(lr=0.02, momentum=0.95, ns_eps=1e-7)
ADAMW = dict(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8)

_PROJ = ("q_proj", "k_proj", "v_proj", "o_proj")


def _dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d, h, cfg["num_key_value_heads"], cfg.get("head_dim") or d // h,
            cfg["intermediate_size"])


def split_over(tree, devices):
    """Shardings that split each leaf of ``tree`` over ``devices`` along its
    longest axis that their count divides (replicated where none does)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devices), ("d",))

    def one(x):
        axes = [None] * len(x.shape)
        for i in sorted(range(len(x.shape)), key=lambda i: -x.shape[i]):
            if x.shape[i] % len(devices) == 0:
                axes[i] = "d"
                break
        return NamedSharding(mesh, P(*axes))
    return jax.tree.map(one, tree)


def init_params(cfg: dict, seed: int, devices=None) -> dict:
    """Flat ``{path: array}`` of float32 weights made from ``seed``, split
    over ``devices`` where there are several."""
    d, h, kv, hd, ff = _dims(cfg)
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    bias = bool(cfg.get("attention_bias"))

    def normal(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    def layer(key):
        bk = jax.random.split(key, 8)
        ak = jax.random.split(bk[0], 4)
        mk = jax.random.split(bk[3], 3)
        out = {}
        for name, k, (din, dout) in zip(
                _PROJ, ak, ((d, h * hd), (d, kv * hd), (d, kv * hd),
                            (h * hd, d))):
            out[f"attn/{name}/w"] = normal(k, (din, dout), 1 / math.sqrt(din))
            if bias and name != "o_proj":
                out[f"attn/{name}/b"] = jnp.zeros((dout,), jnp.float32)
        out["mlp/up_proj/w"] = normal(mk[0], (d, ff), 1 / math.sqrt(d))
        out["mlp/down_proj/w"] = normal(mk[1], (ff, d), 1 / math.sqrt(ff))
        out["mlp/gate_proj/w"] = normal(mk[2], (d, ff), 1 / math.sqrt(d))
        out["attn_norm/norm_scale"] = jnp.ones((d,), jnp.float32)
        out["mlp_norm/norm_scale"] = jnp.ones((d,), jnp.float32)
        return out

    def build(key):
        ks = jax.random.split(key, 6)
        p = {"embed/embedding": normal(ks[0], (V, d), 0.02),
             "final_norm/norm_scale": jnp.ones((d,), jnp.float32)}
        if not cfg["tie_word_embeddings"]:
            p["lm_head/embedding"] = normal(ks[1], (V, d), 0.02)
        blocks = jax.vmap(layer)(jax.random.split(ks[2], L))
        p.update({f"blocks/{k}": v for k, v in blocks.items()})
        return p

    key = jax.random.PRNGKey(seed)
    out = None if devices is None or len(devices) < 2 else split_over(
        jax.eval_shape(build, key), devices)
    return jax.jit(build, out_shardings=out)(key)


def is_matrix(path: str) -> bool:
    """Hidden weight matrices: Muon's leaves."""
    return path.startswith("blocks/") and path.endswith("/w")


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (b, s, heads, hd); rotate-half rotary embedding at 0..s-1."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@jax.custom_vjp
def _fp8(x):
    """``x`` rounded to float8 e4m3 with a per-tensor scale (its largest
    magnitude maps to 448, the format's largest finite value), in float32;
    the gradient passes through rounded the same way."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_fp8(g),))


def _einsum(eq, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _loss_sum(cfg, p, tokens, labels, fp8=False):
    """Summed next-token cross entropy of a block of rows.  ``fp8`` rounds
    both operands of every product (and their gradients) to float8: the
    control of the comparison, one precision step below the program's
    one-pass bfloat16 products."""
    d, h, kv, hd, _ = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, s = tokens.shape
    mm = lambda a, w: _einsum("bsi,io->bso", a, w, fp8)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, lp):
        y = _rmsnorm(x, lp["attn_norm/norm_scale"], eps)
        qkv = []
        for name, heads in (("q_proj", h), ("k_proj", kv), ("v_proj", kv)):
            t = mm(y, lp[f"attn/{name}/w"])
            if f"attn/{name}/b" in lp:
                t = t + lp[f"attn/{name}/b"]
            qkv.append(t.reshape(b, s, heads, hd))
        q, k, v = qkv
        q, k = _rope(q, theta), _rope(k, theta)
        k, v = jnp.repeat(k, h // kv, 2), jnp.repeat(v, h // kv, 2)
        sc = _einsum("bqhd,bkhd->bhqk", q, k, fp8) / math.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        a = _einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v, fp8)
        x = x + mm(a.reshape(b, s, h * hd), lp["attn/o_proj/w"])
        y = _rmsnorm(x, lp["mlp_norm/norm_scale"], eps)
        g = jax.nn.silu(mm(y, lp["mlp/gate_proj/w"])) * mm(
            y, lp["mlp/up_proj/w"])
        return x + mm(g, lp["mlp/down_proj/w"]), None

    layers = {k[len("blocks/"):]: v for k, v in p.items()
              if k.startswith("blocks/")}
    x = p["embed/embedding"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(block), x, layers)
    x = _rmsnorm(x, p["final_norm/norm_scale"], eps)
    head = p.get("lm_head/embedding", p["embed/embedding"])
    logits = _einsum("bsd,vd->bsv", x, head, fp8)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)


def _newton_schulz(x):
    """Polar factor of each matrix of a (count, m, n) stack, m <= n."""
    x = x / (jnp.sqrt(jnp.sum(x * x, (-2, -1), keepdims=True))
             + MUON["ns_eps"])
    for a, b, c in POLAR_EXPRESS:
        g = jnp.einsum("cij,ckj->cik", x, x, precision=HI)
        poly = b * g + c * jnp.einsum("cij,cjk->cik", g, g, precision=HI)
        x = a * x + jnp.einsum("cij,cjk->cik", poly, x, precision=HI)
    return x


def _muon_update(g, mom):
    mom = MUON["momentum"] * mom + g
    eff = g + MUON["momentum"] * mom
    m0, n0 = g.shape[-2:]
    x = eff.reshape((-1, m0, n0))
    if m0 > n0:
        x = jnp.swapaxes(x, -1, -2)
    o = _newton_schulz(x)
    if m0 > n0:
        o = jnp.swapaxes(o, -1, -2)
    scale = 0.2 * math.sqrt(max(m0, n0))
    return -MUON["lr"] * scale * o.reshape(g.shape), mom


def _adamw_update(g, mu, nu, t):
    mu = ADAMW["b1"] * mu + (1 - ADAMW["b1"]) * g
    nu = ADAMW["b2"] * nu + (1 - ADAMW["b2"]) * g * g
    mhat = mu / (1 - ADAMW["b1"] ** t)
    vhat = nu / (1 - ADAMW["b2"] ** t)
    return -ADAMW["lr"] * mhat / (jnp.sqrt(vhat) + ADAMW["eps"]), mu, nu


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x)))


def train_steps(cfg: dict, batches, seed: int, mode: str,
                fp8: bool = False, devices=None) -> dict:
    """Run ``len(batches)`` reference steps from the seed's weights.

    Returns the loss of each step, the norm of each leaf's first gradient,
    and each leaf's change after the last step (on the device) with its
    norm.  ``fp8``: the control (see ``_loss_sum``).  ``devices``: where
    the weights are split (see ``init_params``)."""
    with jax.default_matmul_precision("highest"):
        return _train_steps(cfg, batches, seed, mode, fp8, devices)


def _train_steps(cfg, batches, seed, mode, fp8, devices):
    params = init_params(cfg, seed, devices)
    p0 = jax.tree.map(jnp.copy, params)
    muon = {k for k in params if mode != "adamw" and is_matrix(k)}
    state = {k: (jnp.zeros_like(v),) if k in muon else
             (jnp.zeros_like(v), jnp.zeros_like(v)) for k, v in params.items()}
    grad_row = jax.jit(jax.value_and_grad(
        lambda p, t, l: _loss_sum(cfg, p, t, l, fp8)))

    @jax.jit
    def update(params, grads, state, t):
        new_p, new_s = {}, {}
        for k, g in grads.items():
            if k in muon:
                u, mom = _muon_update(g, state[k][0])
                new_s[k] = (mom,)
            else:
                u, mu, nu = _adamw_update(g, *state[k], t)
                new_s[k] = (mu, nu)
            new_p[k] = params[k] + u
        return new_p, new_s

    norms = jax.jit(lambda tree: {k: _norm(v) for k, v in tree.items()})
    losses, grad_norms = [], None
    for step, batch in enumerate(batches):
        tokens, labels = batch["tokens"], batch["labels"]
        total, grads = 0.0, None
        for r in range(tokens.shape[0]):
            loss, g = grad_row(params, jnp.asarray(tokens[r:r + 1]),
                               jnp.asarray(labels[r:r + 1]))
            total += float(loss)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        n = tokens.size
        grads = jax.tree.map(lambda x: x / n, grads)
        losses.append(total / n)
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in norms(grads).items()}
        params, state = update(params, grads, state, float(step + 1))
        del grads
    del state
    delta = jax.tree.map(jnp.subtract, params, p0)
    del params, p0
    return {"losses": losses, "grad_norms": grad_norms, "deltas": delta,
            "delta_norms": {k: float(v) for k, v in norms(delta).items()}}
